"""Pure-jnp oracles for every Pallas kernel in this package.

These are the semantics of record: kernels must match them to float32
tolerance across the shape/dtype sweeps in tests/test_kernels.py. They are
also the fallback backend on platforms without Pallas lowering.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp


@functools.partial(jax.jit, static_argnames=("n_nodes", "n_bins"))
def histogram_ref(
    bins: jax.Array,  # (N, F) int32 bin ids
    node_ids: jax.Array,  # (N,) int32 current node per sample, -1 = inactive
    grad: jax.Array,  # (N,) f32 weighted gradient  (m'_i * l'_i)
    hess: jax.Array,  # (N,) f32 weighted hessian / count weight
    n_nodes: int,
    n_bins: int,
) -> jax.Array:
    """Gradient/hessian histograms: out[0|1, node, f, b] = sum over samples.

    Scatter-add formulation via segment_sum — the LightGBM semantics.
    Inactive samples (node_id == -1 or sampled out with weight 0) contribute
    nothing.
    """
    n, f = bins.shape
    active = node_ids >= 0
    node = jnp.where(active, node_ids, 0)
    # segment id per (sample, feature): node * F * B + f * B + bin
    seg = (node[:, None] * f + jnp.arange(f)[None, :]) * n_bins + bins
    gmat = jnp.where(active, grad, 0.0)[:, None] * jnp.ones((1, f), grad.dtype)
    hmat = jnp.where(active, hess, 0.0)[:, None] * jnp.ones((1, f), hess.dtype)
    num = n_nodes * f * n_bins
    hg = jax.ops.segment_sum(gmat.reshape(-1), seg.reshape(-1), num_segments=num)
    hh = jax.ops.segment_sum(hmat.reshape(-1), seg.reshape(-1), num_segments=num)
    out = jnp.stack([hg, hh]).reshape(2, n_nodes, f, n_bins)
    return out.astype(jnp.float32)


@functools.partial(jax.jit, static_argnames=("n_nodes", "n_bins"))
def histogram_subset_ref(
    bins: jax.Array,  # (N, F) int32 bin ids
    node_ids: jax.Array,  # (N,) int32 current node per sample, -1 = inactive
    grad: jax.Array,  # (N,) f32 weighted gradient
    hess: jax.Array,  # (N,) f32 weighted hessian / count weight
    active_nodes: jax.Array,  # (n_sub,) int32 — node ids to histogram
    n_nodes: int,  # static bound on node ids (inverse-map size)
    n_bins: int,
) -> jax.Array:
    """Node-subset histograms: out[0|1, r, f, b] sums samples on node
    ``active_nodes[r]`` only — the oracle for the subtraction builder's
    smaller-child build (``trees.learner`` ``hist_mode='subtract'``).

    Samples whose node is not in ``active_nodes`` (or is -1) contribute
    nothing; each active row is bit-identical to the matching row of
    ``histogram_ref`` (same scatter order over the same samples).
    """
    n, f = bins.shape
    n_sub = active_nodes.shape[0]
    # Inverse map node id -> subset row (-1 = not built this level).
    inv = jnp.full((n_nodes,), -1, jnp.int32)
    inv = inv.at[active_nodes].set(jnp.arange(n_sub, dtype=jnp.int32))
    row = jnp.where(node_ids >= 0, inv[jnp.clip(node_ids, 0, n_nodes - 1)], -1)
    active = row >= 0
    rowc = jnp.where(active, row, 0)
    seg = (rowc[:, None] * f + jnp.arange(f)[None, :]) * n_bins + bins
    gmat = jnp.where(active, grad, 0.0)[:, None] * jnp.ones((1, f), grad.dtype)
    hmat = jnp.where(active, hess, 0.0)[:, None] * jnp.ones((1, f), hess.dtype)
    num = n_sub * f * n_bins
    hg = jax.ops.segment_sum(gmat.reshape(-1), seg.reshape(-1), num_segments=num)
    hh = jax.ops.segment_sum(hmat.reshape(-1), seg.reshape(-1), num_segments=num)
    out = jnp.stack([hg, hh]).reshape(2, n_sub, f, n_bins)
    return out.astype(jnp.float32)


@functools.partial(jax.jit, static_argnames=("n_nodes", "n_bins", "derive_sibling"))
def level_build_ref(
    bins: jax.Array,  # (N, F) int32 bin ids
    node_ids: jax.Array,  # (N,) int32 level-local node per sample, -1 inactive
    grad: jax.Array,  # (N,) f32
    hess: jax.Array,  # (N,) f32
    active_nodes: jax.Array,  # (L_sub,) int32 node ids to histogram
    parent_hist: jax.Array | None,  # (2, L_sub, F, B) previous-level cache
    feat_mask: jax.Array,  # (F,) bool/f32 — available features
    lam: jax.Array,
    min_child_hess: jax.Array,
    n_nodes: int,
    n_bins: int,
    derive_sibling: bool = False,
) -> tuple[jax.Array, jax.Array, jax.Array, jax.Array, jax.Array]:
    """The oracle of one staged tree level: (hist (2, L, F, B),
    best_feature (L,), best_bin (L,), best_gain (L,), new_node (N,)).

    ``trees.learner._staged_level`` written independently as one function
    — histogram (subset + sibling derivation in subtract mode), gain scan,
    feature mask, argmax with the first-maximum tie-break, the
    unsplittable pass-left fix (feature 0, threshold ``n_bins - 1``), and
    the ``2 * node + go_right`` re-route. The staged level must match its
    histogram to f32 tolerance and its splits and routing exactly wherever
    the gains are decisively separated.
    """
    built = histogram_subset_ref(
        bins, node_ids, grad, hess, active_nodes, n_nodes, n_bins
    )
    if derive_sibling:
        node_iota = jnp.arange(n_nodes, dtype=jnp.int32)
        par_of = node_iota >> 1
        is_built = node_iota == active_nodes[par_of]
        built_rows = built[:, par_of]
        hist = jnp.where(
            is_built[None, :, None, None],
            built_rows,
            parent_hist[:, par_of] - built_rows,
        )
    else:
        hist = built  # active_nodes must enumerate 0..n_nodes-1 in order

    gain = split_gain_surface_ref(hist, lam, min_child_hess)
    gain = jnp.where(feat_mask[None, :, None] > 0, gain, -jnp.inf)

    flat = gain.reshape(n_nodes, -1)
    idx = jnp.argmax(flat, axis=-1)
    best = jnp.take_along_axis(flat, idx[:, None], axis=-1)[:, 0]
    ok = jnp.isfinite(best) & (best > 0.0)
    feat = jnp.where(ok, idx // n_bins, 0).astype(jnp.int32)
    thr = jnp.where(ok, idx % n_bins, n_bins - 1).astype(jnp.int32)

    node_c = jnp.clip(node_ids, 0, n_nodes - 1)
    val = jnp.take_along_axis(bins, jnp.take(feat, node_c)[:, None], axis=1)[:, 0]
    go_right = (val > jnp.take(thr, node_c)).astype(jnp.int32)
    new_node = jnp.where(node_ids >= 0, 2 * node_ids + go_right, 2 * node_ids)
    return hist, feat, thr, best, new_node


def histogram_sparse_ref(
    sp,  # trees.binning.SparseBins
    node_ids: jax.Array,  # (N,) int32, -1 = inactive
    grad: jax.Array,  # (N,) f32
    hess: jax.Array,  # (N,) f32
    n_nodes: int,
    n_bins: int,
) -> jax.Array:
    """Sparse-layout histogram oracle: densify, then ``histogram_ref``.

    The explicit-zero-bin round trip is exact integers, so this is
    BITWISE-identical to the dense path on the same data — the parity
    contract ``tests/test_sparse.py`` pins. The Pallas sparse kernel
    (nnz-scaling stored-entry contraction + zero-bin complement) must
    match this to f32 tolerance, exactly like the dense kernel vs its
    oracle.
    """
    from repro.trees import binning  # lazy: trees.learner imports kernels

    return histogram_ref(binning.to_dense(sp), node_ids, grad, hess, n_nodes, n_bins)


def histogram_sparse_subset_ref(
    sp,  # trees.binning.SparseBins
    node_ids: jax.Array,
    grad: jax.Array,
    hess: jax.Array,
    active_nodes: jax.Array,  # (n_sub,) int32
    n_nodes: int,
    n_bins: int,
) -> jax.Array:
    """Node-subset sparse oracle — densify + ``histogram_subset_ref``."""
    from repro.trees import binning

    return histogram_subset_ref(
        binning.to_dense(sp), node_ids, grad, hess, active_nodes, n_nodes, n_bins
    )


def bin_prefix_sum(x: jax.Array) -> jax.Array:
    """Inclusive prefix sum over the last (bin) axis as a log-step
    (Hillis-Steele) scan: step k adds the value k bins to the left (zero
    below bin k), for k = 1, 2, 4, ...

    The values are ``cumsum``'s up to rounding; the fixed operand order is
    the one the kernels' lane scan (``split_scan.bin_prefix_sums``) uses, so
    equal histograms scan to bitwise-equal sums on every backend.
    """
    b = x.shape[-1]
    k = 1
    while k < b:
        shifted = jnp.pad(x[..., :-k], [(0, 0)] * (x.ndim - 1) + [(k, 0)])
        x = x + shifted
        k *= 2
    return x


@jax.jit
def split_gain_surface_ref(
    hist: jax.Array,  # (2, L, F, B) f32 grad/hess histograms
    lam: jax.Array,  # scalar L2 regularizer
    min_child_hess: jax.Array,  # scalar: both children need >= this hessian mass
) -> jax.Array:
    """Gain surface (L, F, B), -inf where invalid — the split kernel's oracle.

    gain = GL^2/(HL+lam) + GR^2/(HR+lam) - G^2/(H+lam); splitting at bin b
    sends bins <= b left. The last bin is not a valid split point.
    """
    g, h = hist[0], hist[1]  # (L, F, B)
    gl = bin_prefix_sum(g)  # left sums, inclusive
    hl = bin_prefix_sum(h)
    gt = gl[..., -1:]  # totals (L, F, 1)
    ht = hl[..., -1:]
    gr = gt - gl
    hr = ht - hl
    parent = gt**2 / (ht + lam)
    gain = gl**2 / (hl + lam) + gr**2 / (hr + lam) - parent  # (L, F, B)
    valid = (hl >= min_child_hess) & (hr >= min_child_hess)
    valid = valid.at[..., -1].set(False)
    return jnp.where(valid, gain, -jnp.inf)


@jax.jit
def split_scan_ref(
    hist: jax.Array,  # (2, L, F, B) f32 grad/hess histograms
    lam: jax.Array,
    min_child_hess: jax.Array,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Best split per node from histograms.

    Returns (best_gain (L,), best_feature (L,) int32, best_bin (L,) int32),
    the first maximum of ``split_gain_surface_ref`` in (f * B + b) order.
    """
    gain = split_gain_surface_ref(hist, lam, min_child_hess)
    flat = gain.reshape(gain.shape[0], -1)  # (L, F*B)
    idx = jnp.argmax(flat, axis=-1)
    best_gain = jnp.take_along_axis(flat, idx[:, None], axis=-1)[:, 0]
    nb = hist.shape[-1]
    return best_gain, (idx // nb).astype(jnp.int32), (idx % nb).astype(jnp.int32)


@functools.partial(jax.jit, static_argnames=("causal", "group"))
def flash_attention_ref(
    q: jax.Array,  # (BH, Sq, d)
    k: jax.Array,  # (BKV, Sk, d)
    v: jax.Array,
    causal: bool = True,
    group: int = 1,
) -> jax.Array:
    """Plain softmax attention — the oracle for the flash kernel."""
    bh, sq, d = q.shape
    if group > 1:
        k = jnp.repeat(k, group, axis=0)
        v = jnp.repeat(v, group, axis=0)
    s = jnp.einsum("hqd,hkd->hqk", q.astype(jnp.float32), k.astype(jnp.float32))
    s = s / jnp.sqrt(d)
    if causal:
        mask = jnp.tril(jnp.ones((sq, k.shape[1]), bool))
        s = jnp.where(mask[None], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("hqk,hkd->hqd", p, v.astype(jnp.float32)).astype(q.dtype)


def _tree_leaf_values(
    bins: jax.Array, feat: jax.Array, thr: jax.Array, leaves: jax.Array, depth: int
) -> jax.Array:
    """One tree's leaf value per sample, (N,) — the shared heap descent."""
    node = jnp.zeros((bins.shape[0],), jnp.int32)

    def step(_, node):
        f = jnp.take(feat, node)
        t = jnp.take(thr, node)
        v = jnp.take_along_axis(bins, f[:, None], axis=1)[:, 0]
        return 2 * node + 1 + (v > t).astype(jnp.int32)

    node = jax.lax.fori_loop(0, depth, step, node)
    return jnp.take(leaves, node - ((1 << depth) - 1))


def _dequantize_forest(
    threshold: jax.Array, leaf_value: jax.Array, leaf_scale: jax.Array | None
) -> tuple[jax.Array, jax.Array]:
    """Quantized-layout prologue shared by both traversal oracles.

    The reference semantics of the kernel's dequantize-in-VMEM epilogue:
    int8 leaves scale back through the per-tree f32 ``leaf_scale``, fp16
    leaves cast exactly, quantized thresholds widen to int32. On f32/int32
    inputs both converts are same-dtype no-ops, so the unquantized path
    stays BITWISE-identical to the historical one.
    """
    leaf = leaf_value.astype(jnp.float32)
    if leaf_value.dtype == jnp.int8:
        if leaf_scale is None:
            raise ValueError("int8 leaf_value needs a per-tree leaf_scale")
        leaf = leaf * leaf_scale[:, None]
    return threshold.astype(jnp.int32), leaf


@functools.partial(jax.jit, static_argnames=("depth", "n_outputs"))
def forest_traverse_ref(
    bins: jax.Array,  # (N, F) int32
    feature: jax.Array,  # (T, 2^d - 1) int32
    threshold: jax.Array,  # (T, 2^d - 1) int32 — or int8/int16 quantized
    leaf_value: jax.Array,  # (T, 2^d) f32 — or int8/fp16 quantized
    n_trees: jax.Array,  # () int32 — live slots
    depth: int,
    n_outputs: int = 1,
    leaf_scale: jax.Array | None = None,  # (T,) f32, int8 mode only
) -> jax.Array:
    """Masked forest sum, (N,) f32 — the traversal kernel's oracle.

    Unlike ``apply_forest_ref`` this masks slots >= ``n_trees``, so a
    partially-filled forest predicts correctly even when dead slots hold
    stale (nonzero) trees — the hot-swap serving contract. Reduction shape
    mirrors the kernel (per-tree values, one reduce over the tree axis):
    interpret-mode parity is bitwise. It materializes a transient (T, N)
    buffer; for large train-set evaluation use ``apply_forest_ref`` with
    ``n_trees``, the O(N)-memory scan form of the same sum.

    With ``n_outputs`` = K > 1, slot t belongs to output t % K (the
    forest's round-major/output-minor layout) and the result is (N, K).

    Quantized forests (``Forest.quantize``) pass their packed
    threshold/leaf arrays plus ``leaf_scale``; the oracle dequantizes up
    front (``_dequantize_forest``), which is the reference for the
    kernel's in-VMEM epilogue — interpret-mode parity stays bitwise.
    """
    threshold, leaf_value = _dequantize_forest(threshold, leaf_value, leaf_scale)
    per_tree = jax.vmap(
        lambda feat, thr, leaves: _tree_leaf_values(bins, feat, thr, leaves, depth)
    )(feature, threshold, leaf_value)  # (T, N)
    live = jnp.arange(feature.shape[0])[:, None] < n_trees
    masked = jnp.where(live, per_tree, 0.0)
    if n_outputs == 1:
        return jnp.sum(masked, axis=0).astype(jnp.float32)
    out_k = jnp.arange(feature.shape[0]) % n_outputs
    per_out = jax.ops.segment_sum(masked, out_k, num_segments=n_outputs)
    return per_out.T.astype(jnp.float32)  # (N, K)


@functools.partial(jax.jit, static_argnames=("depth", "n_outputs"))
def apply_forest_ref(
    bins: jax.Array,  # (N, F) int32
    feature: jax.Array,  # (T, 2^d - 1) int32
    threshold: jax.Array,  # (T, 2^d - 1) int32 — or int8/int16 quantized
    leaf_value: jax.Array,  # (T, 2^d) f32 — or int8/fp16 quantized
    depth: int,
    n_trees: jax.Array | None = None,  # () int32; None = all slots live
    n_outputs: int = 1,
    leaf_scale: jax.Array | None = None,  # (T,) f32, int8 mode only
) -> jax.Array:
    """Sum of per-tree predictions, (N,) f32 — the forest F(x) evaluation.

    Scan-accumulated: O(N) live memory regardless of T (the right form for
    full-train-set evaluation). With ``n_trees``, slots past the live count
    contribute exactly 0 (same masking contract as ``forest_traverse_ref``;
    on zero-padded training forests the two agree either way). With
    ``n_outputs`` = K > 1, slot t accumulates into output column t % K
    and the result is (N, K). Quantized forests dequantize up front
    (outside the scan), same as ``forest_traverse_ref``.
    """
    threshold, leaf_value = _dequantize_forest(threshold, leaf_value, leaf_scale)

    def one_tree(carry, tree):
        total, idx = carry
        feat, thr, leaves = tree
        vals = _tree_leaf_values(bins, feat, thr, leaves, depth)
        if n_trees is not None:
            vals = jnp.where(idx < n_trees, vals, 0.0)
        if n_outputs == 1:
            total = total + vals
        else:
            total = total.at[:, idx % n_outputs].add(vals)
        return (total, idx + 1), None

    shape = (bins.shape[0],) if n_outputs == 1 else (bins.shape[0], n_outputs)
    (total, _), _ = jax.lax.scan(
        one_tree,
        (jnp.zeros(shape, jnp.float32), jnp.asarray(0, jnp.int32)),
        (feature, threshold, leaf_value),
    )
    return total
