"""Compile-only checks: the main-path Pallas kernels lower through Mosaic.

Every kernel is compiled for a described (not attached) TPU v5e chip at the
widths of ``chip_smoke.py`` — HIGGS's 28 features, 64 bins, depth-5 trees —
and the compiled program must hold a Mosaic kernel (``tpu_custom_call``);
the whole tree build must also hold no ``scatter``. The split scan and the
sparse tree build are compiled at real-sim's geometry too (72,309 rows x
20,958 features, 51 stored entries a row, depth 7), where a level holds
up to 64 nodes.
Nothing runs: these tests catch what only the TPU compiler refuses (block
shapes off the (8, 128) tiling, primitives Mosaic cannot lower, VMEM
overflow) without a chip. The topology is described inside a fixture, so
only the test process that runs this file loads the TPU compiler.
"""
import math
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import autotune
from repro.kernels.forest_traversal import forest_traverse_pallas
from repro.kernels.histogram import histogram_pallas
from repro.kernels.histogram_sparse import histogram_sparse_pallas
from repro.kernels.split_scan import split_gain_pallas
from repro.trees.learner import LearnerConfig, build_tree

N, F, B, DEPTH = 65_536, 28, 64, 5
L = 1 << (DEPTH - 1)  # nodes of the deepest split level
SLOTS = 1024  # a 1000-tree forest padded to whole 512-tree blocks
ENTRIES = 4_096  # stored entries per feature for the sparse kernel
# real-sim (arXiv:1804.04659 sec. VI.B): rows, features, stored entries a
# row, and a feature-major width above its most-filled column.
RS_N, RS_F, RS_NNZ, RS_WIDTH, RS_DEPTH = 72_309, 20_958, 51, 256, 7


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "no TPU compiler"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    # A compile for a described chip can be written to the persistent
    # cache but never read back here; keep the cache out of it.
    from jax.experimental.compilation_cache import compilation_cache

    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", enabled)


def _shapes(sharding, *specs):
    return [jax.ShapeDtypeStruct(shape, dtype, sharding=sharding) for shape, dtype in specs]


def _kernels(fn, args) -> int:
    return jax.jit(fn).lower(*args).compile().as_text().count("tpu_custom_call")


def _tiling():
    f_pad, fb = autotune.feature_tiling(F, B)
    return f_pad, fb, autotune.default_sample_block(fb, B)


def _kernel_dots(fn, args):
    """The operand dtypes and precision of every ``dot_general`` inside the
    Pallas kernels ``fn`` traces to."""
    found = []

    def walk(jaxpr, in_kernel):
        for eqn in jaxpr.eqns:
            if in_kernel and eqn.primitive.name == "dot_general":
                found.append((tuple(v.aval.dtype for v in eqn.invars),
                              eqn.params["precision"]))
            for v in eqn.params.values():
                for sub in v if isinstance(v, (tuple, list)) else (v,):  # cond branches
                    inner = getattr(sub, "jaxpr", sub)
                    if hasattr(inner, "eqns"):
                        walk(inner, in_kernel or eqn.primitive.name == "pallas_call")

    walk(jax.make_jaxpr(fn)(*args).jaxpr, False)
    return found


BF16_DOT = ((jnp.bfloat16, jnp.bfloat16), None)


# (level nodes, nodes built): HIGGS's subtract-mode levels with 2 and 16 GH
# rows, the full 16-node level, and a full 64-node level (128 rows, 384
# stacked: past one MXU tile).
@pytest.mark.parametrize("l,l_sub", [(L, L), (L, L // 2), (2, 1), (64, 64)],
                         ids=["full_level", "node_subset", "rows2", "full_level64"])
def test_histogram_compiles(one_chip, l, l_sub):
    """The dense histogram compiles for v5e at HIGGS's geometry (28
    features, 64 bins, 512-sample blocks), and its one dot takes bf16
    operands at default precision."""
    f_pad, fb, sb = _tiling()
    assert sb == 512
    args = _shapes(
        one_chip, ((N, f_pad), jnp.int32), ((N,), jnp.int32),
        ((N,), jnp.float32), ((N,), jnp.float32), ((l_sub,), jnp.int32),
    )

    def fn(bins, node, g, h, active):
        return histogram_pallas(
            bins, node, g, h, l, B, sample_block=sb, feature_block=fb,
            interpret=False, active_nodes=active if l_sub < l else None,
        )

    assert _kernels(fn, args) >= 1
    assert _kernel_dots(fn, args) == [BF16_DOT]


def test_split_scan_compiles(one_chip):
    f_pad, fb, _ = _tiling()
    args = _shapes(
        one_chip, ((2, L, f_pad, B), jnp.float32), ((), jnp.float32), ((), jnp.float32)
    )

    def fn(hist, lam, min_h):
        return split_gain_pallas(hist, lam, min_h, feature_block=fb, interpret=False)

    assert _kernels(fn, args) >= 1


@pytest.mark.parametrize("l,f", [(16, F), (64, RS_F), (256, RS_F), (256, F)])
def test_split_scan_fits_half_vmem(one_chip, monkeypatch, l, f):
    """At its chosen blocks the split scan compiles under a scoped VMEM
    limit of ``SPLIT_VMEM_BUDGET`` (half of v5e's 16 MiB), from HIGGS's
    16 nodes to the 64 of real-sim's depth 7 and the 256 of depth 9."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    call = pl.pallas_call

    def limited(*args, **kwargs):
        kwargs["compiler_params"] = pltpu.CompilerParams(
            vmem_limit_bytes=autotune.SPLIT_VMEM_BUDGET)
        return call(*args, **kwargs)

    monkeypatch.setattr(pl, "pallas_call", limited)
    jax.clear_caches()  # trace anew, under the limit
    l_pad, nb, f_pad, fb = autotune.split_tiling(l, f, B)
    args = _shapes(
        one_chip, ((2, l_pad, f_pad, B), jnp.float32), ((), jnp.float32),
        ((), jnp.float32),
    )

    def fn(hist, lam, min_h):
        return split_gain_pallas(hist, lam, min_h, node_block=nb, feature_block=fb,
                                 interpret=False)

    try:
        assert _kernels(fn, args) >= 1
    finally:
        jax.clear_caches()


def test_histogram_sparse_compiles(one_chip):
    args = _shapes(
        one_chip, ((F, ENTRIES), jnp.int32), ((F, ENTRIES), jnp.int32),
        ((N,), jnp.int32), ((N,), jnp.float32), ((N,), jnp.float32),
    )

    def fn(rows, codes, node, g, h):
        return histogram_sparse_pallas(rows, codes, node, g, h, L, B, interpret=False)

    assert _kernels(fn, args) >= 1


# (threshold, leaf) dtypes of the layouts serving installs: the f32 forest
# and the two ``Forest.quantize`` modes.
TRAVERSAL_LAYOUTS = {
    "f32": (jnp.int32, jnp.float32),
    "int8": (jnp.int8, jnp.int8),
    "fp16": (jnp.int16, jnp.float16),
}


@pytest.mark.parametrize("leaves", list(TRAVERSAL_LAYOUTS))
def test_forest_traversal_compiles(one_chip, leaves):
    n_int, n_leaf = (1 << DEPTH) - 1, 1 << DEPTH
    thr_dtype, leaf_dtype = TRAVERSAL_LAYOUTS[leaves]
    args = _shapes(
        one_chip, ((256, F), jnp.int32), ((SLOTS, n_int), jnp.int32),
        ((SLOTS, n_int), thr_dtype), ((SLOTS, n_leaf), leaf_dtype), ((), jnp.int32),
        ((SLOTS,), jnp.float32),
    )

    def fn(bins, feat, thr, leaf, n_trees, scale):
        return forest_traverse_pallas(
            bins, feat, thr, leaf, n_trees, DEPTH, sample_block=256,
            tree_block=512, interpret=False,
            leaf_scale=scale if leaves == "int8" else None,
        )

    assert _kernels(fn, args) >= 1


@pytest.mark.parametrize("hist_mode", ["subtract", "rebuild"])
def test_build_tree_has_no_scatter(one_chip, monkeypatch, hist_mode):
    """The whole tree build for the chip: its per-node row sums (child
    counts, leaf sums) are reductions, not scatters. A TPU runs a
    scatter-add of N rows into a few node slots almost serially."""
    # The kernels pick Mosaic over the interpreter by the default backend:
    # steer them to the chip's path, and keep these traces out of the
    # caches the CPU tests share.
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    jax.clear_caches()
    cfg = LearnerConfig(depth=DEPTH, n_bins=B, feature_fraction=0.8,
                        backend="pallas", hist_mode=hist_mode)
    args = _shapes(
        one_chip, ((N, F), jnp.int32), ((N,), jnp.float32), ((N,), jnp.float32),
        ((2,), jnp.uint32),
    )
    try:
        text = jax.jit(lambda b, g, h, k: build_tree(cfg, b, g, h, k)).lower(
            *args).compile().as_text()
    finally:
        jax.clear_caches()
    assert text.count("tpu_custom_call") >= 1
    assert "scatter(" not in text


def test_sparse_build_tree_depth7_has_no_scatter(one_chip, monkeypatch):
    """The sparse tree build at real-sim's geometry and published depth
    compiles for the chip — 64-node split scans over 20,958 features, the
    sparse histogram at every level — and holds no ``scatter``."""
    from repro.trees.binning import SparseBins

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    jax.clear_caches()
    cfg = LearnerConfig(depth=RS_DEPTH, n_bins=B, feature_fraction=0.8,
                        backend="pallas", hist_mode="subtract")
    bins = SparseBins(*_shapes(
        one_chip, ((RS_N, RS_NNZ), jnp.int32), ((RS_N, RS_NNZ), jnp.int32),
        ((RS_F, RS_WIDTH), jnp.int32), ((RS_F, RS_WIDTH), jnp.int32),
        ((RS_F,), jnp.int32),
    ))
    args = _shapes(
        one_chip, ((RS_N,), jnp.float32), ((RS_N,), jnp.float32), ((2,), jnp.uint32),
    )
    try:
        text = jax.jit(lambda b, g, h, k: build_tree(cfg, b, g, h, k)).lower(
            bins, *args).compile().as_text()
    finally:
        jax.clear_caches()
    assert text.count("tpu_custom_call") >= 2  # histogram_sparse, split_scan
    assert "scatter(" not in text


def _indexed_rows(text: str) -> dict[str, int]:
    """Index rows of each gather (output ÷ slice) and updates of each
    scatter in compiled HLO text."""
    shapes = dict(re.findall(r"(%[\w.\-]+) = \w+\[([\d,]*)\]", text))
    size = lambda dims: math.prod(int(d) for d in dims.split(",") if d)  # noqa: E731
    rows = {}
    for name, dims, op, args in re.findall(
            r"(%[\w.\-]+) = \w+\[([\d,]*)\]\S* (gather|scatter)\(([^)]*)\)", text):
        if op == "gather":
            line = text[text.index(name + " = "):].split("\n", 1)[0]
            slice_sizes = re.search(r"slice_sizes=\{([\d,]*)\}", line).group(1)
            rows[name] = size(dims) // size(slice_sizes)
        else:
            rows[name] = size(shapes[args.split(",")[-1].strip()])
    return rows


def test_planned_draw_has_no_full_row_scatter(one_chip):
    """The planned draw at HIGGS's 11,000,000 rows and the bench's Zipf
    profile, compiled for the chip: no gather or scatter indexes more rows
    than the plan's largest set. A compaction inside the program (a prefix
    sum and a scatter of all N rows) would."""
    from bench.data import zipf_multiplicity
    from repro.data.sampling import DrawPlan, bernoulli_weights, draw_plan

    n = 11_000_000
    plan = draw_plan(zipf_multiplicity(n, n), 0.8)
    sets = (plan.inversion_rows.shape[0], plan.btrs_rows.shape[0])
    abstract = DrawPlan(*_shapes(one_chip, ((n,), jnp.float32), ((sets[0],), jnp.int32),
                                 ((sets[1],), jnp.int32)), rate=0.8)
    key = _shapes(one_chip, ((2,), jnp.uint32))[0]
    text = jax.jit(lambda k, p: bernoulli_weights(k, 0.8, p)).lower(
        key, abstract).compile().as_text()
    rows = _indexed_rows(text)
    assert sets == (423_779, 12_972)
    assert sorted(v for k, v in rows.items() if "scatter" in k) == sorted(sets)
    assert max(rows.values()) <= max(sets), rows
