"""Jit'd public entry points for the kernels, with backend dispatch.

``backend='ref'`` runs the pure-jnp oracle (always available, and what a CPU
production deployment would use); ``'pallas'`` runs the TPU kernels. On this
CPU container Pallas executes via ``interpret=True``; on a real TPU the same
call sites compile to Mosaic. ``'auto'`` picks pallas on TPU, ref elsewhere.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro import collectives
from repro.kernels import autotune
from repro.kernels import ref as _ref
from repro.kernels.histogram import histogram_pallas
from repro.kernels.histogram_sparse import histogram_sparse_pallas
from repro.kernels.split_scan import split_gain_pallas

BACKENDS = ("auto", "ref", "pallas")


def resolve_backend(backend: str) -> str:
    """The one kernel choice: ``'auto'`` resolves to ``'pallas'`` on a TPU
    and ``'ref'`` elsewhere; an unknown name raises."""
    if backend not in BACKENDS:
        raise ValueError(f"unknown backend {backend!r} (want one of {BACKENDS})")
    if backend == "auto":
        return "pallas" if jax.default_backend() == "tpu" else "ref"
    return backend


def _pad_to(x: jax.Array, multiple: int, axis: int, fill) -> jax.Array:
    size = x.shape[axis]
    pad = (-size) % multiple
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths, constant_values=fill)


@functools.partial(jax.jit, static_argnames=("n_samples",))
def _sparse_local_dense(
    feat_rows: jax.Array,  # (F, C) int32 sample ids, -1 pad
    feat_codes: jax.Array,  # (F, C) int32 stored codes
    zero_bin: jax.Array,  # (F,) int32
    n_samples: int,
) -> jax.Array:
    """Exact dense (N, F) int32 from the feature-major ELL store — the same
    integers as ``binning.to_dense`` (one stored entry per cell, integer
    scatter), but built from the shard-local feature-major view so it works
    on a feature shard where no row-major store exists."""
    f, _ = feat_rows.shape
    valid = feat_rows >= 0
    rows = jnp.where(valid, feat_rows, 0)
    cols = jnp.broadcast_to(jnp.arange(f, dtype=jnp.int32)[:, None], rows.shape)
    delta = jnp.where(valid, feat_codes - zero_bin[:, None], 0)
    base = jnp.broadcast_to(zero_bin[None, :], (n_samples, f)).astype(jnp.int32)
    return base.at[rows.reshape(-1), cols.reshape(-1)].add(delta.reshape(-1))


def node_sums(node: jax.Array, values: jax.Array, n_nodes: int) -> jax.Array:
    """Per-node f32 sums of ``values`` (N,) or (K, N) by ``node`` (N,):
    (n_nodes,) or (K, n_nodes).

    A compare-and-reduce, not a scatter: every row meets every node id and
    adds its value where they match, in one fused pass over N that reads
    each operand once. A TPU lowers a scatter-add of N updates into a few
    slots almost serially; this lowers to a plain reduction. Rows whose id
    is no node in [0, n_nodes) (the sparse path's -1) add nothing.
    """
    ids = jnp.arange(n_nodes, dtype=node.dtype)
    # The compare takes the values' shape, so each value row makes its own
    # and nothing of (.., N, n_nodes) is written out.
    hit = jnp.broadcast_to(node, values.shape)[..., None] == ids
    vals = values.astype(jnp.float32)[..., None]
    return jnp.sum(jnp.where(hit, vals, 0.0), axis=-2)


def _node_totals(
    node_ids: jax.Array,
    grad: jax.Array,
    hess: jax.Array,
    active_nodes: jax.Array,  # (n_sub,) int32
    n_nodes: int,
) -> jax.Array:
    """(2, n_sub) grad/hess mass per active node — the zero-bin complement's
    'what the stored entries are missing' term. Row-local (N work); under
    data sharding it psums alongside the stored histogram."""
    n_sub = active_nodes.shape[0]
    # Each node's slot among the (distinct) active nodes, or -1: a compare
    # of the (n_nodes, n_sub) ids, not a scatter.
    slots = jnp.arange(n_sub, dtype=jnp.int32)
    hit = jnp.arange(n_nodes, dtype=jnp.int32)[:, None] == active_nodes[None, :]
    inv = jnp.max(jnp.where(hit, slots[None, :], -1), axis=1)
    row = jnp.where(node_ids >= 0, inv[jnp.clip(node_ids, 0, n_nodes - 1)], -1)
    return node_sums(row, jnp.stack([grad, hess]), n_sub)


def _zero_bin_complement(
    stored: jax.Array,  # (2, R, F, B) stored-entry histograms
    totals: jax.Array,  # (2, R) per-node grad/hess mass
    zero_bin: jax.Array,  # (F,) int32
) -> jax.Array:
    """Add each node's absent-entry mass at the feature's zero bin.

    ``missing = totals - sum_b stored`` is a SUBTRACTION: on a sharded
    build it must consume the psummed stored/totals, never shard-local
    partials (the subtract-after-psum invariant, now per feature shard —
    the determinism checker's taint pass walks exactly this seam).
    """
    row_sum = stored.sum(axis=-1)  # (2, R, F)
    missing = totals[:, :, None] - row_sum
    b_iota = jnp.arange(stored.shape[-1], dtype=jnp.int32)
    onehot = (zero_bin[:, None] == b_iota[None, :]).astype(stored.dtype)  # (F, B)
    return stored + missing[..., None] * onehot[None, None]


def build_histogram_sparse(
    feat_rows: jax.Array,  # (F_local, C) int32
    feat_codes: jax.Array,  # (F_local, C) int32
    zero_bin: jax.Array,  # (F_local,) int32 — SLICED to the local features
    node_ids: jax.Array,
    grad: jax.Array,
    hess: jax.Array,
    n_nodes: int,
    n_bins: int,
    backend: str = "auto",
    entry_block: int = 512,
    axis_name: str | None = None,
    active_nodes: jax.Array | None = None,
) -> jax.Array:
    """(2, R, F_local, n_bins) histograms from the feature-major sparse store.

    The sparse twin of ``build_histogram``/``build_histogram_subset``:
    operands are the raw feature-major arrays (possibly one feature shard
    of them, with ``zero_bin`` sliced to match). ``backend='ref'``
    densifies the local store exactly and runs the dense oracle — bitwise
    identical to the dense path on the same features. The pallas path runs
    the nnz-scaling stored-entry kernel, psums stored counts AND node
    totals over ``axis_name`` first, and applies the zero-bin complement
    only after the collective (subtract-after-psum, per feature shard).
    """
    backend = resolve_backend(backend)
    n_samples = node_ids.shape[0]
    active = (
        jnp.arange(n_nodes, dtype=jnp.int32)
        if active_nodes is None
        else active_nodes.astype(jnp.int32)
    )
    if backend == "ref":
        dense = _sparse_local_dense(feat_rows, feat_codes, zero_bin, n_samples)
        if active_nodes is None:
            out = _ref.histogram_ref(dense, node_ids, grad, hess, n_nodes, n_bins)
        else:
            out = _ref.histogram_subset_ref(
                dense, node_ids, grad, hess, active, n_nodes, n_bins
            )
        if axis_name is not None:
            out = collectives.psum(out, axis_name)
        return out
    interpret = jax.default_backend() != "tpu"
    # Features ride the sublane axis here: blocks of 8, or all of them.
    fb = min(8, max(feat_rows.shape[0], 1))
    stored = histogram_sparse_pallas(
        feat_rows, feat_codes, node_ids, grad, hess, n_nodes, n_bins,
        entry_block=entry_block, feature_block=fb, interpret=interpret,
        active_nodes=None if active_nodes is None else active,
    )
    with jax.named_scope("histogram_sparse.complement"):
        totals = _node_totals(node_ids, grad, hess, active, n_nodes)
    if axis_name is not None:
        stored = collectives.psum(stored, axis_name)
        totals = collectives.psum(totals, axis_name)
    with jax.named_scope("histogram_sparse.complement"):
        return _zero_bin_complement(stored, totals, zero_bin)


def build_histogram(
    bins,
    node_ids: jax.Array,
    grad: jax.Array,
    hess: jax.Array,
    n_nodes: int,
    n_bins: int,
    backend: str = "auto",
    axis_name: str | None = None,
) -> jax.Array:
    """(2, n_nodes, F, n_bins) grad/hess histograms. See kernels/histogram.py.

    ``bins`` may be the dense (N, F) int32 matrix or a
    ``trees.binning.SparseBins`` — the sparse layout dispatches to the
    nnz-scaling path (``build_histogram_sparse``); on ``backend='ref'``
    the two are bitwise identical.

    ``axis_name``: when running data-parallel under shard_map (samples
    sharded over a mesh axis), each shard builds its local histogram with
    the kernel and the results merge with a psum across the axis — every
    cell is a sum over disjoint sample subsets, so partial sums compose
    exactly (the parameter-server aggregation as an all-reduce).
    """
    return build_histogram_subset(
        bins, node_ids, grad, hess, None, n_nodes, n_bins, backend=backend,
        axis_name=axis_name,
    )


def build_histogram_subset(
    bins,
    node_ids: jax.Array,
    grad: jax.Array,
    hess: jax.Array,
    active_nodes: jax.Array | None,  # (n_sub,) int32 node ids; None = all
    n_nodes: int,
    n_bins: int,
    backend: str = "auto",
    axis_name: str | None = None,
) -> jax.Array:
    """(2, n_sub, F, n_bins) histograms for the ``active_nodes`` subset only.

    The histogram-subtraction builder's entry point: at each level it
    histograms one child per parent and derives the sibling as
    ``parent - built``. It halves the GH rows (2 * n_sub vs 2 * n_nodes),
    which halves the kernel's MXU work once its stacked rows pass one
    128-row tile (``kernels.histogram``).

    ``axis_name``: as in ``build_histogram`` — per-shard subset histograms
    merge with a psum across the data axis. The SUBTRACTION does not live
    here: it commutes with the psum (both are linear), and the learner
    subtracts after the collective so every shard derives the sibling from
    identical merged values and stays in lockstep.

    The kernel's blocks come from the geometry alone: the feature block
    from ``autotune.feature_tiling``, the sample block from
    ``autotune.default_sample_block``.
    """
    from repro.trees.binning import SparseBins  # lazy: trees imports kernels

    if active_nodes is not None:
        active_nodes = active_nodes.astype(jnp.int32)
    if isinstance(bins, SparseBins):
        return build_histogram_sparse(
            bins.feat_rows, bins.feat_codes, bins.zero_bin,
            node_ids, grad, hess, n_nodes, n_bins, backend=backend,
            axis_name=axis_name, active_nodes=active_nodes,
        )
    backend = resolve_backend(backend)
    if backend == "ref" and active_nodes is None:
        out = _ref.histogram_ref(bins, node_ids, grad, hess, n_nodes, n_bins)
    elif backend == "ref":
        out = _ref.histogram_subset_ref(
            bins, node_ids, grad, hess, active_nodes, n_nodes, n_bins
        )
    else:
        n_feat = bins.shape[1]
        f_pad, fb = autotune.feature_tiling(n_feat, n_bins)
        sb = autotune.default_sample_block(fb, n_bins)
        binsp = _pad_to(_pad_to(bins, sb, 0, 0), f_pad, 1, 0)
        nodep = _pad_to(node_ids, sb, 0, -1)  # padded samples inactive
        gradp = _pad_to(grad, sb, 0, 0.0)
        hessp = _pad_to(hess, sb, 0, 0.0)
        out = histogram_pallas(
            binsp, nodep, gradp, hessp, n_nodes, n_bins,
            sample_block=sb, feature_block=fb,
            interpret=jax.default_backend() != "tpu", active_nodes=active_nodes,
        )[:, :, :n_feat, :]
    if axis_name is not None:
        out = collectives.psum(out, axis_name)
    return out


def split_gain(
    hist: jax.Array,
    lam,
    min_child_hess,
    backend: str = "auto",
) -> jax.Array:
    """Gain surface (L, F, B), -inf where invalid. The kernel's node and
    feature blocks come from the geometry (``autotune.split_tiling``);
    padded nodes and features are dropped from the result."""
    backend = resolve_backend(backend)
    lam = jnp.asarray(lam, jnp.float32)
    minh = jnp.asarray(min_child_hess, jnp.float32)
    if backend == "ref":
        return _ref.split_gain_surface_ref(hist, lam, minh)
    _, l, f, b = hist.shape
    l_pad, nb, f_pad, fb = autotune.split_tiling(l, f, b)
    out = split_gain_pallas(
        _pad_to(_pad_to(hist, l_pad, 1, 0.0), f_pad, 2, 0.0), lam, minh,
        node_block=nb, feature_block=fb, interpret=jax.default_backend() != "tpu",
    )
    return out[:l, :f, :]


def best_split(
    hist: jax.Array, lam, min_child_hess, backend: str = "auto"
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """(best_gain (L,), feature (L,), bin (L,)) — argmax over the gain surface."""
    gain = split_gain(hist, lam, min_child_hess, backend=backend)
    nb = gain.shape[-1]
    flat = gain.reshape(gain.shape[0], -1)
    idx = jnp.argmax(flat, axis=-1)
    best = jnp.take_along_axis(flat, idx[:, None], axis=-1)[:, 0]
    return best, (idx // nb).astype(jnp.int32), (idx % nb).astype(jnp.int32)


apply_forest = _ref.apply_forest_ref  # unmasked train-time form (zero-padded slots)


def forest_traverse(
    bins: jax.Array,
    feature: jax.Array,
    threshold: jax.Array,
    leaf_value: jax.Array,
    n_trees,
    depth: int,
    backend: str = "auto",
    sample_block: int = 256,
    tree_block: int = 512,
    n_outputs: int = 1,
    leaf_scale: jax.Array | None = None,
) -> jax.Array:
    """Masked forest sum (N,) f32 — the serving predict. See forest_traversal.py.

    Slots >= ``n_trees`` contribute exactly 0 regardless of their contents,
    so partially-filled and hot-swapped forests serve correctly. The ref
    backend is the O(N)-memory scan (production CPU form); the kernel's
    bitwise oracle is ``ref.forest_traverse_ref``. With ``n_outputs`` =
    K > 1 the result is (N, K): slot t reduces into output column t % K
    (padded tree slots are masked by ``n_trees``, so padding never leaks
    into any output column).

    Quantized layouts (``trees.forest.Forest.quantize``) pass int8/int16
    thresholds and int8/fp16 leaves — int8 with the per-tree ``leaf_scale``.
    Both backends dequantize with identical float ops, and scores stay
    within ``trees.forest.quantization_atol`` of the f32 forest's; with f32
    inputs the dequant converts are no-ops and the path is bitwise-unchanged.
    """
    backend = resolve_backend(backend)
    n_trees = jnp.asarray(n_trees, jnp.int32)
    if backend == "ref":
        return _ref.apply_forest_ref(
            bins, feature, threshold, leaf_value, depth, n_trees,
            n_outputs=n_outputs, leaf_scale=leaf_scale,
        )
    from repro.kernels.forest_traversal import forest_traverse_pallas

    interpret = jax.default_backend() != "tpu"
    n = bins.shape[0]
    t = feature.shape[0]
    sb = min(sample_block, max(n, 1))
    tb = min(tree_block, max(t, 1))
    binsp = _pad_to(bins, sb, 0, 0)
    featp = _pad_to(feature, tb, 0, 0)
    thrp = _pad_to(threshold, tb, 0, 0)
    leafp = _pad_to(leaf_value, tb, 0, 0 if leaf_value.dtype == jnp.int8 else 0.0)
    scalep = None if leaf_scale is None else _pad_to(leaf_scale, tb, 0, 1.0)
    out = forest_traverse_pallas(
        binsp, featp, thrp, leafp, n_trees, depth,
        sample_block=sb, tree_block=tb, interpret=interpret,
        n_outputs=n_outputs, leaf_scale=scalep,
    )
    return out[:n]


def _flash_call(qf, kf, vf, causal, group, block_q, block_k):
    """Pad to blocks, run the forward kernel, return (out, lse) unpadded."""
    from repro.kernels.flash_attention import flash_attention_pallas

    sq, sk = qf.shape[1], kf.shape[1]
    interpret = jax.default_backend() != "tpu"
    bq, bk = min(block_q, sq), min(block_k, sk)
    qp = _pad_to(qf, bq, 1, 0.0)
    kp = _pad_to(kf, bk, 1, 0.0)
    vp = _pad_to(vf, bk, 1, 0.0)
    out, lse = flash_attention_pallas(
        qp, kp, vp, causal=causal, block_q=bq, block_k=bk,
        group=group, interpret=interpret, seq_k=sk,
    )
    return out[:, :sq], lse[:, :sq]


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _flash_fwd_only(qf, kf, vf, causal, group, block_q, block_k):
    out, _ = _flash_call(qf, kf, vf, causal, group, block_q, block_k)
    return out


def _flash_vjp_fwd(qf, kf, vf, causal, group, block_q, block_k):
    out, lse = _flash_call(qf, kf, vf, causal, group, block_q, block_k)
    return out, (qf, kf, vf, out, lse)


def _flash_vjp_bwd(causal, group, block_q, block_k, res, g):
    """Fused Pallas backward (dq / dk+dv kernels) — recomputes P tiles from
    (q, k, lse); nothing quadratic ever hits HBM in either direction."""
    from repro.kernels.flash_attention import flash_attention_bwd_pallas

    qf, kf, vf, out, lse = res
    sq, sk = qf.shape[1], kf.shape[1]
    interpret = jax.default_backend() != "tpu"
    bq, bk = min(block_q, sq), min(block_k, sk)
    qp = _pad_to(qf, bq, 1, 0.0)
    kp = _pad_to(kf, bk, 1, 0.0)
    vp = _pad_to(vf, bk, 1, 0.0)
    op = _pad_to(out, bq, 1, 0.0)
    gp = _pad_to(g, bq, 1, 0.0)
    lp = _pad_to(lse, bq, 1, 0.0)
    dq, dk, dv = flash_attention_bwd_pallas(
        qp, kp, vp, op, lp, gp,
        causal=causal, block_q=bq, block_k=bk, group=group,
        interpret=interpret, seq_k=sk, seq_q=sq,
    )
    return dq[:, :sq], dk[:, :sk], dv[:, :sk]


_flash_fwd_only.defvjp(_flash_vjp_fwd, _flash_vjp_bwd)


def flash_attention(
    q: jax.Array,  # (B, Sq, H, hd)
    k: jax.Array,  # (B, Sk, KV, hd)
    v: jax.Array,
    causal: bool = True,
    backend: str = "auto",
    block_q: int = 128,
    block_k: int = 128,
) -> jax.Array:
    """Fused attention entry point (model-layout in/out). Pads Sq/Sk to the
    block sizes and flattens (B, H) into the kernel's head-grid axis.
    Differentiable: forward is the Pallas kernel (O(S) memory), backward
    recomputes through the jnp oracle (see _flash_vjp_bwd)."""
    backend = resolve_backend(backend)
    b, sq, h, hd = q.shape
    _, sk, kv, _ = k.shape
    group = h // kv
    qf = q.transpose(0, 2, 1, 3).reshape(b * h, sq, hd)
    kf = k.transpose(0, 2, 1, 3).reshape(b * kv, sk, hd)
    vf = v.transpose(0, 2, 1, 3).reshape(b * kv, sk, hd)
    if backend == "ref":
        out = _ref.flash_attention_ref(qf, kf, vf, causal=causal, group=group)
    else:
        out = _flash_fwd_only(qf, kf, vf, causal, group, block_q, block_k)
    return out.reshape(b, h, sq, hd).transpose(0, 2, 1, 3)
