"""Level-wise histogram tree learner — fully jittable, fixed shapes.

One tree build = ``depth`` levels; each level builds per-node grad/hess
histograms (Pallas kernel or jnp oracle), scans them for the best split, and
re-routes samples. Matches the paper's worker-side "building the tree
sub-step": the tree fits the (sampled, importance-weighted) gradient target.

Histogram modes (``LearnerConfig.hist_mode``):
  * ``'subtract'`` (default) — the parent-histogram-caching builder: below
    the root, only the SMALLER child of every split is histogrammed
    (per-node hessian mass — the drawn-sample count — picks it) and the
    sibling is derived as ``parent - built``. A level then costs 2^(l-1)
    node-histograms instead of 2^l: a depth-d tree builds 2^(d-1) instead of
    2^d - 1 — ~50% of the rebuild mode's histogram kernel work at depth 7.
    Exact in exact arithmetic (children partition their parent's samples);
    in f32 the derived sibling differs from a rebuilt one by subtraction
    rounding, so the two modes agree to tolerance, not bitwise.
  * ``'rebuild'`` — the historical full-level build: every node of every
    level is histogrammed from its samples. Bitwise-identical to the
    pre-subtraction learner; the exact-parity reference mode.
Either mode is deterministic WITHIN itself: the threaded runtime's
record-and-replay contract (DESIGN.md §11) holds bit-for-bit per mode.

Backends (``LearnerConfig.backend``, resolved once by
``kernels.ops.resolve_backend``): ``'auto'`` is ``'pallas'`` on a TPU, the
staged kernel pipeline, and ``'ref'``, the jnp oracle, elsewhere.

Conventions:
  * Caller supplies per-sample (g_i, h_i). For the paper's plain gradient
    step, g_i = m'_i * l'_i and h_i = m'_i (leaf value = - mean residual).
    For Newton (xgboost-style) steps, g/h are weighted gradient/hessian.
  * Leaf value = -G_leaf / (H_leaf + lam) in both cases.
  * Samples with h_i == 0 (not drawn by the Bernoulli sampler) are inert:
    they contribute to no histogram and no leaf.
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro import collectives
from repro.kernels import ops
from repro.trees.binning import SparseBins, gather_feature_bins
from repro.trees.tree import Tree


class LearnerConfig(NamedTuple):
    depth: int = 7  # 2^depth leaves (paper: 100 -> 128, 400 -> 512)
    n_bins: int = 64
    lam: float = 1.0  # L2 on leaf values
    min_child_hess: float = 1e-3
    feature_fraction: float = 0.8  # paper samples 80% of features per tree
    backend: str = "auto"  # 'auto' | 'ref' | 'pallas'
    # Mesh axis samples are sharded over when building under shard_map
    # (repro.ps.sharded): histograms and leaf stats psum across it; the rng
    # must be replicated so every shard draws the same feature mask.
    axis_name: str | None = None
    # 'subtract' — parent-minus-child histogram derivation (the default
    # fast path); 'rebuild' — full per-level histogram builds (the exact
    # pre-subtraction semantics). See the module docstring.
    hist_mode: str = "subtract"
    # Mesh axis FEATURES are sharded over — the block-distributed 2D mesh
    # (DESIGN.md §16). Each shard histograms and scans only its own
    # (L, F/P_f, B) bin block; split decisions merge with the (L,)-sized
    # argmax all-reduce (pmax gain + pmin global index) instead of
    # psumming full histograms, and the dense partition reconstructs the
    # winning bin column with an owner-masked uint8 psum. None = every
    # shard holds every feature (the 1D path, unchanged).
    feature_axis: str | None = None
    # Static feature-shard count. Consulted only on the DENSE 2D path,
    # where the GLOBAL feature count (the feature-mask draw must be global
    # so 1D and 2D runs consume identical rng) is not recoverable from the
    # local bins shape. SparseBins carries the global width in zero_bin.
    feature_shards: int = 1


def _check_hist_mode(cfg: LearnerConfig) -> None:
    if cfg.hist_mode not in ("subtract", "rebuild"):
        raise ValueError(
            f"unknown hist_mode {cfg.hist_mode!r} (want 'subtract'|'rebuild')"
        )


def _smaller_children(
    cfg: LearnerConfig, node: jax.Array, h: jax.Array, n_nodes: int
) -> jax.Array:
    """The subtraction builder's per-parent smaller child, (n_nodes // 2,).

    "Smaller" is by per-node hessian mass — the drawn-sample count in the
    paper's gradient step (h_i = m'_i) — so inert samples (h == 0) stay
    inert in the builder's control flow too, not just in its sums. Under
    shard_map the counts psum first: every shard must pick the SAME child.
    """
    with jax.named_scope("child_counts"):
        counts = ops.node_sums(node, h, n_nodes)
        if cfg.axis_name is not None:
            counts = collectives.psum(counts, cfg.axis_name)
        parents = jnp.arange(n_nodes // 2, dtype=jnp.int32)
        go_odd = (counts[0::2] > counts[1::2]).astype(jnp.int32)
        return 2 * parents + go_odd


def _level_histogram(
    cfg: LearnerConfig,
    bins: jax.Array,
    node: jax.Array,  # (N,) level-local node ids in [0, 2^level)
    g: jax.Array,
    h: jax.Array,
    level: int,
    parent_hist: jax.Array | None,  # (2, 2^(level-1), F, B) from last level
    backend: str,
) -> jax.Array:
    """The (2, 2^level, F, B) histogram of one level, by the config's mode."""
    n_nodes = 1 << level
    _check_hist_mode(cfg)
    if cfg.hist_mode == "rebuild" or level == 0:
        return ops.build_histogram(
            bins, node, g, h, n_nodes, n_bins=cfg.n_bins,
            backend=backend, axis_name=cfg.axis_name,
        )

    # Subtraction mode: histogram only the smaller child of every parent,
    # derive the sibling from the cached parent histogram. Children
    # partition the parent's samples, so parent = left + right exactly;
    # the derived sibling differs from a rebuilt one only by f32 rounding.
    active = _smaller_children(cfg, node, h, n_nodes)
    built = ops.build_histogram_subset(
        bins, node, g, h, active, n_nodes, cfg.n_bins,
        backend=backend, axis_name=cfg.axis_name,
    )  # (2, 2^(level-1), F, B), already psum'd across shards
    # Expand to the full level by a gather: node n (parent p = n >> 1) is
    # either the built child or the derived sibling. The subtraction runs
    # AFTER the collective — it commutes with the psum (both linear), and
    # subtracting merged values keeps every shard's derived rows identical.
    node_ids = jnp.arange(n_nodes, dtype=jnp.int32)
    par_of = node_ids >> 1
    is_built = node_ids == active[par_of]
    built_rows = built[:, par_of]  # (2, n_nodes, F, B)
    sibling_rows = parent_hist[:, par_of] - built_rows
    return jnp.where(is_built[None, :, None, None], built_rows, sibling_rows)


def _staged_level(
    cfg: LearnerConfig,
    backend: str,
    hist_bins,  # histogram view: dense (N, F_loc) or shard-local SparseBins
    route_bins,  # partition view: dense (N, F_loc) or the row-major store
    node: jax.Array,
    g: jax.Array,
    h: jax.Array,
    feat_mask: jax.Array,  # (F_loc,) — the shard's slice of the global mask
    level: int,
    parent_hist: jax.Array | None,
):
    """One level via the staged pipeline (histogram -> gain -> partition),
    each stage round-tripping HBM. Returns (hist, feat, thr, new_node).

    Under feature sharding (``cfg.feature_axis``) the histogram/gain/argmax
    stages see only the shard's own (L, F_loc, B) block; the split decision
    then merges across the feature axis with two (L,)-sized collectives:
    ``pmax`` of the local best gains, then ``pmin`` of the GLOBAL flat
    (feature * B + bin) index among the shards achieving that max. Because
    shard s owns the contiguous global columns [s*F_loc, (s+1)*F_loc), the
    global flat order equals the 1D path's flat order — so the pmin
    reproduces the first-maximum tie-break BITWISE, with (L,) floats + (L,)
    ints on the wire instead of the full (2, L, F, B) histogram psum.
    ``feat`` is returned in GLOBAL feature ids either way.
    """
    n_nodes, n_bins = 1 << level, cfg.n_bins
    with jax.named_scope("histogram"):
        hist = _level_histogram(cfg, hist_bins, node, g, h, level, parent_hist, backend)
    with jax.named_scope("split"):
        gain = ops.split_gain(hist, cfg.lam, cfg.min_child_hess, backend=backend)
        gain = jnp.where(feat_mask[None, :, None], gain, -jnp.inf)  # (L, F_loc, B)

        f_local = gain.shape[1]
        flat = gain.reshape(n_nodes, -1)
        idx = jnp.argmax(flat, axis=-1)
        best = jnp.take_along_axis(flat, idx[:, None], axis=-1)[:, 0]

        if cfg.feature_axis is not None:
            shard = jax.lax.axis_index(cfg.feature_axis)
            gidx = idx.astype(jnp.int32) + shard * (f_local * n_bins)
            best_g = collectives.pmax(best, cfg.feature_axis)
            # Among shards holding the global max, the lowest global flat index
            # wins — all--inf rows tie at shard 0's index 0, exactly like the
            # 1D argmax, and the pass-left fix below overrides them anyway.
            cand = jnp.where(best == best_g, gidx, jnp.iinfo(jnp.int32).max)
            idx = collectives.pmin(cand, cfg.feature_axis)
            best = best_g

        feat = (idx // n_bins).astype(jnp.int32)
        thr = (idx % n_bins).astype(jnp.int32)

        # Unsplittable node -> pass-through: all samples go left.
        ok = jnp.isfinite(best) & (best > 0.0)
        feat = jnp.where(ok, feat, 0)
        thr = jnp.where(ok, thr, n_bins - 1)
    with jax.named_scope("partition"):
        f_of = jnp.take(feat, node)  # (N,) global winning feature per sample
        if cfg.feature_axis is not None and not isinstance(route_bins, SparseBins):
            # Dense 2D partition: only the winning feature's owner shard holds
            # its column, so each shard contributes its owned values and a
            # one-byte-per-sample psum reconstructs the column everywhere
            # (bin ids < n_bins <= 256 — uint8 is exact).
            lo = jax.lax.axis_index(cfg.feature_axis) * f_local
            owned = (f_of >= lo) & (f_of < lo + f_local)
            col = jnp.clip(f_of - lo, 0, f_local - 1)
            v = gather_feature_bins(route_bins, col)
            v = jnp.where(owned, v, 0).astype(jnp.uint8)
            val = collectives.psum(v, cfg.feature_axis).astype(jnp.int32)
        else:
            # 1D dense gather, or the sparse row-major store (replicated across
            # feature shards: routing needs no collective at all).
            val = gather_feature_bins(route_bins, f_of)
        go_right = (val > jnp.take(thr, node)).astype(jnp.int32)
    return hist, feat, thr, 2 * node + go_right


@functools.partial(jax.jit, static_argnames=("cfg",))
def build_tree(
    cfg: LearnerConfig,
    bins,  # (N, F) int32 dense matrix, or a ``SparseBins``
    g: jax.Array,  # (N,) f32 — weighted gradient target
    h: jax.Array,  # (N,) f32 — weighted hessian / sample weight
    rng: jax.Array,  # feature-subsampling key
) -> Tree:
    feature_sharded = cfg.feature_axis is not None
    if isinstance(bins, SparseBins):
        # Under feature sharding only the feature-major store is sharded;
        # the row-major store + zero_bin stay replicated (they route
        # samples through GLOBAL feature ids). The histogram view gets the
        # zero-bin slice matching its local feature block.
        n = bins.n_samples
        f_local = bins.feat_rows.shape[0]
        f_global = bins.n_features
        hist_bins = bins
        if feature_sharded and f_local != f_global:
            lo = jax.lax.axis_index(cfg.feature_axis) * f_local
            zb = jax.lax.dynamic_slice(bins.zero_bin, (lo,), (f_local,))
            hist_bins = bins._replace(zero_bin=zb)
    else:
        n, f_local = bins.shape
        f_global = f_local * (cfg.feature_shards if feature_sharded else 1)
        hist_bins = bins

    backend = ops.resolve_backend(cfg.backend)

    # The feature mask is drawn over the GLOBAL feature space from the
    # replicated rng — a 2D run consumes the key exactly like its 1D twin
    # — and each shard slices out its own contiguous block.
    feat_mask = (
        jax.random.uniform(rng, (f_global,)) < cfg.feature_fraction
        if cfg.feature_fraction < 1.0
        else jnp.ones((f_global,), bool)
    )
    if feature_sharded and f_local != f_global:
        lo = jax.lax.axis_index(cfg.feature_axis) * f_local
        feat_mask = jax.lax.dynamic_slice(feat_mask, (lo,), (f_local,))

    node = jnp.zeros((n,), jnp.int32)  # heap ids, level-local after offset
    features = []
    thresholds = []
    hist = None  # the previous level's histograms (the subtraction cache)

    for level in range(cfg.depth):
        # Scopes (level, then child_counts / histogram / split / partition
        # inside it, and leaf_sums) name each step's device operations in
        # the compiled program's metadata; they change no value.
        with jax.named_scope(f"level{level}"):
            hist, feat, thr, node = _staged_level(
                cfg, backend, hist_bins, bins, node, g, h, feat_mask, level, hist
            )
        features.append(feat)
        thresholds.append(thr)

    # Leaf statistics.
    n_leaves = 1 << cfg.depth
    with jax.named_scope("leaf_sums"):
        leaf_g, leaf_h = ops.node_sums(node, jnp.stack([g, h]), n_leaves)
        if cfg.axis_name is not None:  # merge leaf stats across data shards
            leaf_g = collectives.psum(leaf_g, cfg.axis_name)
            leaf_h = collectives.psum(leaf_h, cfg.axis_name)
    leaf_value = -leaf_g / (leaf_h + cfg.lam)
    leaf_value = jnp.where(leaf_h > 0, leaf_value, 0.0)

    return Tree(
        feature=jnp.concatenate(features),
        threshold=jnp.concatenate(thresholds),
        leaf_value=leaf_value.astype(jnp.float32),
    )


@functools.partial(jax.jit, static_argnames=("cfg",))
def build_tree_multi(
    cfg: LearnerConfig,
    bins: jax.Array,  # (N, F) int32
    g: jax.Array,  # (N, K) f32 — per-output weighted gradient field
    h: jax.Array,  # (N, K) f32 — per-output weighted hessian / weight
    rng: jax.Array,  # ONE feature-subsampling key shared across outputs
) -> Tree:
    """K trees against the (N, K) gradient field, one vmapped build.

    Returns a stacked ``Tree`` with (K, ...) arrays — the K-output
    boosting round's "one push" payload. Sharing ``rng`` across outputs
    draws one feature mask per round (the multiclass convention: the K
    trees of a round see the same feature subsample). Each lane is
    numerically identical to a standalone ``build_tree`` on its column.
    """
    return jax.vmap(
        lambda gk, hk: build_tree(cfg, bins, gk, hk, rng), in_axes=(1, 1)
    )(g, h)
