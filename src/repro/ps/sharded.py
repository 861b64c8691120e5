"""Data-parallel tree building: ``shard_map`` workers + ``psum`` merge.

The block-distributed GBT / DimBoost production shape: every shard of the
``'data'`` mesh axis runs the histogram kernel on its local samples only,
and the level histogram is merged with one ``psum`` across the axis — the
server-side aggregation of the paper's parameter server, executed as an
ICI all-reduce instead of a NIC round-trip. Split search then runs
replicated on the merged histograms, so every shard routes its local
samples through the SAME tree.

The ``psum`` hooks live inside the ordinary build path
(``kernels.ops.build_histogram(axis_name=...)`` and the leaf-stat merge in
``trees.learner.build_tree``); this module only wraps that path in
``shard_map`` with the right specs. Sample counts must divide the shard
count (pad the dataset otherwise).

Histogram-subtraction builds (``LearnerConfig.hist_mode='subtract'``)
compose with the same specs: subtraction is linear, so it COMMUTES with
the psum — the learner psums the per-shard smaller-child histograms (and
the per-node sample counts that pick the child) first, then derives the
sibling as ``merged_parent - merged_child`` AFTER the collective. Every
shard therefore subtracts identical merged values and the replicated tree
stays in lockstep; nothing in this module special-cases the mode.
"""
from __future__ import annotations

import functools

import jax
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from repro import collectives
from repro.kernels import ops
from repro.trees.binning import SparseBins
from repro.trees.learner import LearnerConfig, build_tree


def make_sharded_builder(cfg: LearnerConfig, mesh: Mesh, axis_name: str = "data"):
    """A TreeBuilder (bins, g, h, rng) -> Tree running data-parallel.

    Inputs are sharded over ``axis_name`` on their sample dim; the rng is
    replicated (every shard draws the same feature mask). The returned Tree
    is replicated — histograms and leaf stats are psum'd, and split search
    is deterministic on the merged values.

    Subtraction mode stays in lockstep: the sibling is derived AFTER the
    psum (subtraction commutes with it), so every shard's derived rows are
    identical (see trees/learner.py).
    """
    local = functools.partial(build_tree, cfg._replace(axis_name=axis_name))
    fn = shard_map(
        local,
        mesh=mesh,
        in_specs=(P(axis_name), P(axis_name), P(axis_name), P()),
        out_specs=P(),
    )

    def builder(bins, g, h, rng):
        if isinstance(bins, SparseBins):
            raise ValueError(
                "SparseBins cannot shard over a 1D data axis (the "
                "feature-major store holds global sample ids); use "
                "make_sharded_builder_2d on a (1, P_f) mesh"
            )
        return fn(bins, g, h, rng)

    return builder


def make_sharded_builder_2d(
    cfg: LearnerConfig,
    mesh: Mesh,
    data_axis: str = "data",
    feature_axis: str = "feature",
):
    """A TreeBuilder running on the block-distributed 2D (data × feature)
    mesh — rows sharded over ``data_axis``, feature columns over
    ``feature_axis`` (DESIGN.md §16).

    Each shard histograms only its own (rows/P_d, F/P_f) block: row psums
    merge histograms over the data axis FIRST (the subtract-after-psum
    invariant now holds per feature shard), then the split decision merges
    over the feature axis with the (L,)-sized argmax collective — never a
    full (2, L, F, B) histogram psum. The dense partition step reconstructs
    the winning bin column with a one-byte-per-sample owner-masked psum.

    Dense bins shard as ``P(data, feature)``. A ``SparseBins`` dataset
    shards its feature-major store over ``feature_axis`` while the
    row-major store and ``zero_bin`` stay replicated (they route samples
    by GLOBAL feature id, which costs no collective at all) — and is
    restricted to ``data_axis`` size 1: the feature-major entries hold
    global sample ids, which row sharding would invalidate.
    """
    d_size = mesh.shape[data_axis]
    f_size = mesh.shape[feature_axis]
    cfg2 = cfg._replace(
        axis_name=data_axis, feature_axis=feature_axis, feature_shards=f_size
    )
    local = functools.partial(build_tree, cfg2)

    def builder(bins, g, h, rng):
        if isinstance(bins, SparseBins):
            if d_size != 1:
                raise ValueError(
                    "sparse 2D builds need a (1, P_f) mesh: the feature-major "
                    f"store holds global sample ids, but {data_axis!r} has "
                    f"size {d_size}"
                )
            bins_spec = SparseBins(
                indices=P(), codes=P(),
                feat_rows=P(feature_axis), feat_codes=P(feature_axis),
                zero_bin=P(),
            )
        else:
            bins_spec = P(data_axis, feature_axis)
        fn = shard_map(
            local,
            mesh=mesh,
            in_specs=(bins_spec, P(data_axis), P(data_axis), P()),
            out_specs=P(),
        )
        return fn(bins, g, h, rng)

    return builder


def collective_bytes_per_build(
    cfg: LearnerConfig,
    mesh: Mesh,
    bins,  # (N, F) array / ShapeDtypeStruct, or a SparseBins of either
    data_axis: str = "data",
    feature_axis: str | None = None,
) -> dict:
    """MEASURED per-tree-build collective bytes on the given mesh.

    Traces the sharded builder abstractly (``jax.eval_shape`` — nothing
    executes, so roofline-sized geometries account in milliseconds) with a
    ``collectives.ByteRecorder`` active, and returns its summary:
    ``realized_bytes`` counts only collectives whose mesh axis spans more
    than one shard (a psum over a size-1 axis moves nothing on the wire).
    ``jax.clear_caches()`` first — recording happens at trace time, and a
    cache hit would skip the trace.
    """
    import jax.numpy as jnp

    def _sds(x):
        return jax.ShapeDtypeStruct(x.shape, x.dtype)

    bins_in = jax.tree.map(_sds, bins)
    n = bins.shape[0]
    gh = jax.ShapeDtypeStruct((n,), jnp.float32)
    # Tracer-only key: eval_shape never executes, nothing is ever replayed.
    rng = jax.random.PRNGKey(0)  # analysis: ignore[prngkey-outside-ticket]
    if feature_axis is not None:
        builder = make_sharded_builder_2d(
            cfg, mesh, data_axis=data_axis, feature_axis=feature_axis
        )
    else:
        builder = make_sharded_builder(cfg, mesh, axis_name=data_axis)
    rec = collectives.ByteRecorder(axis_sizes=dict(mesh.shape))
    jax.clear_caches()
    with collectives.recording(rec):
        jax.eval_shape(builder, bins_in, gh, gh, rng)
    return rec.summary()


def build_histogram_sharded(
    mesh: Mesh,
    bins: jax.Array,
    node_ids: jax.Array,
    grad: jax.Array,
    hess: jax.Array,
    n_nodes: int,
    n_bins: int,
    backend: str = "auto",
    axis_name: str = "data",
) -> jax.Array:
    """Sharded histogram build: per-shard kernel + psum over ``axis_name``.

    Bit-compatible with the single-device path up to float summation order
    (each (node, feature, bin) cell is a sum over disjoint sample subsets).
    """
    local = functools.partial(
        ops.build_histogram,
        n_nodes=n_nodes,
        n_bins=n_bins,
        backend=backend,
        axis_name=axis_name,
    )
    fn = shard_map(
        local,
        mesh=mesh,
        in_specs=(P(axis_name), P(axis_name), P(axis_name), P(axis_name)),
        out_specs=P(),
    )
    return fn(bins, node_ids, grad, hess)
