"""Persistent block-shape autotuner for the fused level-build pipeline.

Anghel et al. (PAPERS.md, arXiv:1809.04559) show GBDT kernels win or lose
on block-shape choices, and the right shapes depend on the problem
geometry ``(N, F, B, L)`` — samples, features, bins, level nodes. This
module owns that decision end to end:

  * ``sweep_level_build`` times the fused kernel over the lane-legal
    candidate grid for one geometry and returns the winner;
  * winners persist in ``tuning_table.json`` NEXT TO THIS FILE — a
    committed artifact, regenerated by ``benchmarks/kernel_bench.py``
    (``PYTHONPATH=src python -m benchmarks.kernel_bench``), so dispatch
    never pays a tuning sweep at training time;
  * ``lookup`` is the dispatch-time read: exact geometry hit, else the
    nearest recorded geometry in log-space, else the defaults.

Wall times recorded on a CPU container come from the Pallas interpreter
and do NOT transfer to TPU — the table's ``host`` field records where a
row was measured so a TPU regeneration can overwrite CPU-tuned rows.

Every block shape this module hands out is one Mosaic can lower: the
feature block is the whole (padded) feature width up to 128 features and a
multiple of 128 above it (``feature_tiling``), and the sample block is a
multiple of 128 sized so the one-hot factor fits ``ONEHOT_BYTES``. The
split scan's blocks (``split_tiling``) come from the geometry alone.
"""
from __future__ import annotations

import json
import math
import pathlib
import time
from typing import Iterable

import jax

from repro.kernels.split_scan import LANES, scan_width

TABLE_PATH = pathlib.Path(__file__).resolve().parent / "tuning_table.json"

# The (F_blk * B, S) one-hot factor the histogram dot consumes is the
# largest VMEM value of both histogram programs; the default sample block
# keeps it under this many bytes priced at 4 bytes an entry. The one-hot is
# bf16 (2 bytes), and the block stays at its f32 size on purpose: a larger
# block is a choice of its own, to be timed on its own.
ONEHOT_BYTES = 4 * 2**20
MAX_SAMPLE_BLOCK = 512

# Candidate sample blocks for the sweep. They trade DMA granularity against
# VMEM residency; feature blocks come from ``legal_feature_blocks``.
SAMPLE_BLOCKS = (128, 256, 512, 1024, 2048, 4096)

# The split scan's scoped VMEM, in f32 (node_block, F_blk * B) blocks: its
# three operands double-buffered and the scan's temporaries. v5e's
# compiler reports 6 to 9.5 blocks for tiles of 0.25 to 1 MiB, and has
# asked 14.7 for a whole 64-node tile of 2 MiB; the chooser keeps 15
# blocks within half of v5e's 16 MiB scoped limit.
SPLIT_VMEM_BLOCKS = 15
SPLIT_VMEM_BUDGET = 8 * 2**20

_cache: dict[str, dict] | None = None


def geometry_key(n: int, f: int, b: int, l: int) -> str:
    return f"N{n}_F{f}_B{b}_L{l}"


def _parse_key(key: str) -> tuple[int, int, int, int]:
    parts = dict(
        (seg[0], int(seg[1:])) for seg in key.split("_") if seg and seg[1:].isdigit()
    )
    return parts.get("N", 0), parts.get("F", 0), parts.get("B", 0), parts.get("L", 0)


def load_table(path: pathlib.Path | None = None, refresh: bool = False) -> dict:
    """The committed tuning table as {geometry_key: entry}; cached."""
    global _cache
    if _cache is not None and not refresh and path is None:
        return _cache
    p = path or TABLE_PATH
    table: dict[str, dict] = {}
    if p.exists():
        try:
            raw = json.loads(p.read_text())
            table = raw.get("entries", {})
        except (json.JSONDecodeError, AttributeError):
            table = {}
    if path is None:
        _cache = table
    return table


def feature_tiling(f: int, n_bins: int, feature_block: int | None = None) -> tuple[int, int]:
    """``(padded F, feature block)`` for an F-wide dense bin matrix.

    Mosaic tiles a block's last two dims in (8, 128): the feature-major
    (F_blk, S) bins block needs a multiple of 8 features or the whole padded
    width, and the (rows, F_blk * B) histogram block a multiple of 128
    lanes or the whole row. The default block — the padded width up to 128
    features, 128 above it — satisfies both. F is padded so that F * B
    fills whole 128-lane tiles (``split_scan.scan_width``). An explicit
    ``feature_block`` is kept as given (the interpreter takes any block).
    """
    align = scan_width(n_bins) // n_bins
    if feature_block is None:
        whole = -(-max(f, 1) // align) * align
        feature_block = whole if whole <= LANES else LANES
    unit = math.lcm(feature_block, align)
    return -(-max(f, 1) // unit) * unit, feature_block


def split_vmem_bytes(node_block: int, feature_block: int, n_bins: int) -> int:
    """Modelled scoped VMEM of one split-scan tile: ``SPLIT_VMEM_BLOCKS``
    f32 blocks of ``node_block`` rows (whole 8-row sublane tiles) by
    ``feature_block * n_bins`` lanes."""
    rows = -(-node_block // 8) * 8
    return SPLIT_VMEM_BLOCKS * rows * feature_block * n_bins * 4


def split_tiling(l: int, f: int, n_bins: int) -> tuple[int, int, int, int]:
    """``(padded L, node block, padded F, feature block)`` of the split scan
    over an (L, F, n_bins) level.

    The feature block is ``feature_tiling``'s. The node block is the whole
    level when its tile fits ``SPLIT_VMEM_BUDGET`` (one node block, as at
    HIGGS's L <= 16 x 28 features); else the level splits into the fewest
    blocks of a multiple of 8 nodes that fit, and L pads to whole blocks.
    A geometry whose 8-node tile exceeds the budget (over 136 bins at
    more than 128 features) raises.
    """
    f_pad, fb = feature_tiling(f, n_bins)
    tile8 = split_vmem_bytes(8, fb, n_bins)
    if tile8 > SPLIT_VMEM_BUDGET:
        raise ValueError(
            f"split scan: an 8-node tile of {fb} features x {n_bins} bins "
            f"prices at {tile8} bytes, over the {SPLIT_VMEM_BUDGET}-byte budget")
    if split_vmem_bytes(l, fb, n_bins) <= SPLIT_VMEM_BUDGET:
        return l, l, f_pad, fb
    n_blocks = -(-l // (SPLIT_VMEM_BUDGET // tile8 * 8))
    node_block = -(-l // (8 * n_blocks)) * 8
    return n_blocks * node_block, node_block, f_pad, fb


def default_sample_block(feature_block: int, n_bins: int) -> int:
    """The largest multiple of 128 (at most 512) whose one-hot factor, priced
    at 4 bytes an entry, fits ``ONEHOT_BYTES`` — never below 128, the lane
    width of the per-sample operand rows."""
    rows = ONEHOT_BYTES // (4 * feature_block * n_bins)
    return max(LANES, min(MAX_SAMPLE_BLOCK, rows // LANES * LANES))


def legal_feature_blocks(f: int, n_bins: int) -> list[int]:
    """Every feature block Mosaic can lower for this width: the default
    and each multiple of 128 below the padded width."""
    f_pad, default = feature_tiling(f, n_bins)
    return sorted({default, *range(LANES, f_pad, LANES)})


def lookup(n: int, f: int, b: int, l: int) -> dict:
    """Dispatch-time ``{"sample_block", "feature_block"}`` for a geometry.

    Exact key first; else the nearest recorded geometry by Euclidean
    distance in log-space over (N, F, B, L) — block-shape winners vary
    smoothly with scale, so the nearest measured neighbour beats a blind
    default; else the defaults. A recorded block is used only where it is
    legal for this geometry.
    """
    table = load_table()
    key = geometry_key(n, f, b, l)
    entry = table.get(key)
    if entry is None and table:
        target = [math.log(max(v, 1)) for v in (n, f, b, l)]

        def dist(other_key: str) -> float:
            other = [math.log(max(v, 1)) for v in _parse_key(other_key)]
            return sum((a - c) ** 2 for a, c in zip(target, other))

        entry = table[min(table, key=dist)]
    entry = entry or {}
    fb = entry.get("feature_block")
    if fb not in legal_feature_blocks(f, b):
        fb = feature_tiling(f, b)[1]
    sb = entry.get("sample_block")
    if not sb or sb % LANES:
        sb = default_sample_block(fb, b)
    return {"sample_block": sb, "feature_block": fb}


def save_table(entries: dict[str, dict], path: pathlib.Path | None = None) -> pathlib.Path:
    """Merge ``entries`` into the committed table (sorted, stable diffs)."""
    global _cache
    p = path or TABLE_PATH
    table = load_table(p if path is not None else None, refresh=True)
    table = dict(table)
    table.update(entries)
    payload = {
        "format": 1,
        "comment": "block-shape winners per (N, F, B, L); regenerate with "
        "`PYTHONPATH=src python -m benchmarks.kernel_bench`",
        "entries": {k: table[k] for k in sorted(table)},
    }
    p.write_text(json.dumps(payload, indent=1, sort_keys=True) + "\n")
    if path is None:
        _cache = payload["entries"]
    return p


def _time(fn, reps: int = 3) -> float:
    out = fn()
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(reps):
        out = fn()
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / reps


def sweep_level_build(
    bins: jax.Array,  # (N, F) int32
    node_ids: jax.Array,  # (N,) int32
    grad: jax.Array,
    hess: jax.Array,
    n_nodes: int,
    n_bins: int,
    sample_blocks: Iterable[int] = SAMPLE_BLOCKS,
    reps: int = 3,
) -> tuple[dict, list[dict]]:
    """Sweep the fused kernel over (sample_block, feature_block) for ONE
    geometry, then time the staged split kernel (at its own blocks,
    ``split_tiling``).

    Returns ``(entry, records)``: the winning entry (ready for
    ``save_table``) and every measured point. The fused kernel is timed
    through the public ``ops.level_build`` dispatch (padding included), a
    full-level build — the shape every level of a rebuild-mode tree and
    the root of every subtract-mode tree runs. Only lane-legal feature
    blocks are tried, and sample blocks whose one-hot factor would exceed
    ``ONEHOT_BYTES`` are skipped (the default block is always tried).
    """
    import jax.numpy as jnp

    from repro.kernels import ops

    n, f = bins.shape
    active = jnp.arange(n_nodes, dtype=jnp.int32)
    mask = jnp.ones((f,), jnp.float32)
    records: list[dict] = []
    best: dict | None = None
    for fb in legal_feature_blocks(f, n_bins):
        default_sb = default_sample_block(fb, n_bins)
        for sb in sorted({*sample_blocks, default_sb}):
            too_big = sb * fb * n_bins * 4 > ONEHOT_BYTES and sb != default_sb
            if too_big or sb > max(n, 1):
                continue

            def run(sb=sb, fb=fb):
                return ops.level_build(
                    bins, node_ids, grad, hess, active, None, mask,
                    1.0, 1e-3, n_nodes, n_bins, backend="fused",
                    sample_block=sb, feature_block=fb,
                )

            t = _time(run, reps=reps)
            rec = {"sample_block": sb, "feature_block": fb, "fused_ms": t * 1e3}
            records.append(rec)
            if best is None or t * 1e3 < best["fused_ms"]:
                best = dict(rec)

    hist = ops.build_histogram(
        bins, node_ids, grad, hess, n_nodes, n_bins, backend="pallas",
        sample_block=best["sample_block"], feature_block=best["feature_block"],
    )
    split_s = _time(
        lambda: ops.split_gain(hist, 1.0, 1e-3, backend="pallas"), reps=reps
    )
    entry = {
        "sample_block": best["sample_block"],
        "feature_block": best["feature_block"],
        "fused_ms": best["fused_ms"],
        "split_ms": split_s * 1e3,
        "host": jax.default_backend(),
    }
    return entry, records
