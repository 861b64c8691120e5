"""Plain references for the benchmark's output check.

Nothing here imports the program or takes an array it made: the training
reference rebuilds trees from the bench's own data, the seed's keys and the
realized fold schedule (which fold read which prediction version, and
which ticket's key it used: the observed order of a race, like the served
tokens of a language model), and the serving reference scores raw request
rows against the forest the bench made.

The training reference is the paper's stochastic GBDT round written out
in ``jax.numpy`` at one precision throughout (``dtype``): Bernoulli
importance weights m' = Binomial(m, R) / R and an 80% feature mask drawn
from the round key, gradient step g = m' * 2 (sigmoid(2F) - y), h = m',
level-wise histograms of g and h over (node, feature, bin), the gain
GL^2/(HL+lam) + GR^2/(HR+lam) - G^2/(H+lam) over bin prefix sums with
first-maximum tie-breaking, pass-through for unsplittable nodes, and
leaves v * -G / (H + lam). Histograms are one-hot contractions in row
blocks (dense) or feature blocks (sparse), so they fit beside the program's
state at the published sizes.
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST


class Learner(NamedTuple):
    depth: int
    n_bins: int
    lam: float
    min_child_hess: float
    feature_fraction: float
    sampling_rate: float
    step_length: float


def learner_from_config(config: dict) -> Learner:
    ln = config["learner"]
    return Learner(
        depth=ln["depth"], n_bins=config["data"]["n_bins"], lam=ln["lam"],
        min_child_hess=ln["min_child_hess"], feature_fraction=ln["feature_fraction"],
        sampling_rate=ln["sampling_rate"], step_length=ln["step_length"],
    )


def draws(key, ln: Learner, multiplicity, n_features: int):
    """(m', feature mask) of one round key."""
    r_sample, r_feat = jax.random.split(key)
    rate = jnp.broadcast_to(jnp.float32(ln.sampling_rate), multiplicity.shape)
    counts = jax.random.binomial(r_sample, multiplicity, rate)
    m = (counts / rate).astype(jnp.float32)
    mask = jax.random.uniform(r_feat, (n_features,)) < ln.feature_fraction
    return m, mask


def init_score(labels, multiplicity):
    ybar = jnp.sum(multiplicity * labels) / jnp.sum(multiplicity)
    ybar = jnp.clip(ybar, 1e-6, 1.0 - 1e-6)
    return 0.5 * jnp.log(ybar / (1.0 - ybar))


def _pad_rows(x, block, fill):
    pad = -x.shape[0] % block
    if not pad:
        return x
    widths = [(0, pad)] + [(0, 0)] * (x.ndim - 1)
    return jnp.pad(x, widths, constant_values=fill)


def node_sums(node, vals, n_nodes: int, dtype, block: int):
    """(n_nodes, K) sums of ``vals`` (N, K) by ``node`` (N,), in row blocks."""
    node = _pad_rows(node, block, -1).reshape(-1, block)
    vals = _pad_rows(vals.astype(dtype), block, 0).reshape(-1, block, vals.shape[1])

    def body(acc, xs):
        nd, v = xs
        onehot = (nd[:, None] == jnp.arange(n_nodes)).astype(dtype)
        return acc + jnp.einsum("nl,nk->lk", onehot, v, precision=HIGHEST,
                                preferred_element_type=dtype), None

    acc0 = jnp.zeros((n_nodes, vals.shape[2]), dtype)
    return jax.lax.scan(body, acc0, (node, vals))[0]


def dense_histogram(bins, node, g, h, n_nodes: int, n_bins: int, dtype, block: int):
    """(2, L, F, B) histograms of a dense (N, F) bin matrix."""
    f = bins.shape[1]
    bins = _pad_rows(bins, block, 0).reshape(-1, block, f)
    node = _pad_rows(node, block, -1).reshape(-1, block)
    gh = _pad_rows(jnp.stack([g, h], axis=1).astype(dtype), block, 0)
    gh = gh.reshape(-1, block, 2)

    def body(acc, xs):
        b, nd, v = xs
        on_node = (nd[:, None] == jnp.arange(n_nodes)).astype(dtype)  # (S, L)
        weights = (on_node[:, None, :] * v[:, :, None]).reshape(block, 2 * n_nodes)
        onehot = (b[:, :, None] == jnp.arange(n_bins)).astype(dtype)  # (S, F, B)
        return acc + jnp.einsum("sk,sfb->kfb", weights, onehot, precision=HIGHEST,
                                preferred_element_type=dtype), None

    acc0 = jnp.zeros((2 * n_nodes, f, n_bins), dtype)
    out = jax.lax.scan(body, acc0, (bins, node, gh))[0]
    return out.reshape(2, n_nodes, f, n_bins)


def sparse_histogram(feat_rows, feat_codes, zero_bin, node, g, h, n_nodes: int,
                     n_bins: int, dtype, block: int):
    """(2, L, F, B) histograms of the explicit-zero-bin layout: stored
    entries in feature blocks, and each node's remaining mass at the
    feature's zero bin."""
    f, c = feat_rows.shape
    rows = _pad_rows(feat_rows, block, -1).reshape(-1, block, c)
    codes = _pad_rows(feat_codes, block, 0).reshape(-1, block, c)
    g = g.astype(dtype)
    h = h.astype(dtype)

    def body(_, xs):
        r, code = xs
        valid = r >= 0
        safe = jnp.where(valid, r, 0)
        nd = jnp.where(valid, node[safe], -1)
        on_node = (nd[..., None] == jnp.arange(n_nodes)).astype(dtype)  # (Fb, C, L)
        w = jnp.concatenate(
            [on_node * g[safe][..., None], on_node * h[safe][..., None]], axis=2
        )  # (Fb, C, 2L)
        onehot = (code[..., None] == jnp.arange(n_bins)).astype(dtype)  # (Fb, C, B)
        out = jnp.einsum("fck,fcb->kfb", w, onehot, precision=HIGHEST,
                         preferred_element_type=dtype)
        return None, out

    stored = jax.lax.scan(body, None, (rows, codes))[1]  # (nb, 2L, Fb, B)
    stored = jnp.moveaxis(stored, 0, 1).reshape(2 * n_nodes, -1, n_bins)[:, :f]
    stored = stored.reshape(2, n_nodes, f, n_bins)
    totals = node_sums(node, jnp.stack([g, h], axis=1), n_nodes, dtype, 1 << 14)
    missing = totals.T[:, :, None] - stored.sum(axis=-1)  # (2, L, F)
    at_zero = (zero_bin[:, None] == jnp.arange(n_bins)).astype(dtype)  # (F, B)
    return stored + missing[..., None] * at_zero[None, None]


def best_splits(hist, feat_mask, ln: Learner):
    """Per-node (feature, threshold) of the largest valid gain."""
    n_nodes, f, b = hist.shape[1:]
    dtype = hist.dtype
    lam = jnp.asarray(ln.lam, dtype)
    gl = jnp.cumsum(hist[0], axis=-1)
    hl = jnp.cumsum(hist[1], axis=-1)
    gt, ht = gl[..., -1:], hl[..., -1:]
    gr, hr = gt - gl, ht - hl
    gain = gl * gl / (hl + lam) + gr * gr / (hr + lam) - gt * gt / (ht + lam)
    min_h = jnp.asarray(ln.min_child_hess, dtype)
    valid = (hl >= min_h) & (hr >= min_h) & (jnp.arange(b) < b - 1)
    valid = valid & feat_mask[None, :, None]
    flat = jnp.where(valid, gain, -jnp.inf).reshape(n_nodes, f * b)
    idx = jnp.argmax(flat, axis=1)
    best = jnp.max(flat, axis=1)
    ok = jnp.isfinite(best) & (best > 0)
    feat = jnp.where(ok, idx // b, 0).astype(jnp.int32)
    thr = jnp.where(ok, idx % b, b - 1).astype(jnp.int32)
    return feat, thr


def lookup(bins, feat):
    """Per-row bin of the feature ``feat`` (N,) picks."""
    if isinstance(bins, dict):
        hit = bins["indices"] == feat[:, None]
        stored = jnp.max(jnp.where(hit, bins["codes"], -1), axis=1)
        return jnp.where(stored >= 0, stored, bins["zero_bin"][feat])
    cols = jnp.arange(bins.shape[1], dtype=jnp.int32)
    return jnp.sum(jnp.where(cols[None, :] == feat[:, None], bins, 0), axis=1)


@functools.partial(jax.jit, static_argnames=("ln", "dtype", "block"))
def build_tree(bins, g, h, feat_mask, ln: Learner, dtype, block: int):
    """One tree: (feature, threshold, leaf_value scaled by v, leaf of each row)."""
    n = g.shape[0]
    node = jnp.zeros((n,), jnp.int32)
    feats, thrs = [], []
    for level in range(ln.depth):
        n_nodes = 1 << level
        if isinstance(bins, dict):
            hist = sparse_histogram(bins["feat_rows"], bins["feat_codes"],
                                    bins["zero_bin"], node, g, h, n_nodes,
                                    ln.n_bins, dtype, block)
        else:
            hist = dense_histogram(bins, node, g, h, n_nodes, ln.n_bins, dtype, block)
        feat, thr = best_splits(hist, feat_mask, ln)
        val = lookup(bins, feat[node])
        node = 2 * node + (val > thr[node]).astype(jnp.int32)
        feats.append(feat)
        thrs.append(thr)
    rows_block = 1 << 14 if isinstance(bins, dict) else block
    sums = node_sums(node, jnp.stack([g, h], axis=1), 1 << ln.depth, dtype, rows_block)
    lam = jnp.asarray(ln.lam, dtype)
    leaf = jnp.where(sums[:, 1] > 0, -sums[:, 0] / (sums[:, 1] + lam), 0.0)
    leaf = (jnp.asarray(ln.step_length, dtype) * leaf.astype(dtype))
    return jnp.concatenate(feats), jnp.concatenate(thrs), leaf, node


@functools.partial(jax.jit, static_argnames=("ln", "n_features", "dtype"))
def round_inputs(key, labels, multiplicity, f, ln: Learner, n_features: int, dtype):
    m, mask = draws(key, ln, multiplicity, n_features)
    f = f.astype(dtype)
    p = jax.nn.sigmoid(2.0 * f)
    g = m.astype(dtype) * (2.0 * (p - labels.astype(dtype)))
    return g, m.astype(dtype), mask


def as_reference_bins(bins):
    """The bench's own arrays as the reference reads them (no program type)."""
    if hasattr(bins, "feat_rows"):
        return {k: getattr(bins, k) for k in
                ("indices", "codes", "feat_rows", "feat_codes", "zero_bin")}
    return bins


def replay(bins, labels, multiplicity, seed_key, n_slots: int, schedule, tickets,
           ln: Learner, dtype=jnp.float32, block: int | None = None,
           row_filter=None):
    """Rebuild the first ``len(schedule)`` folds of a recorded run.

    Fold j builds from the reference's own prediction after ``schedule[j]``
    folds, with round key ``split(seed_key, n_slots)[tickets[j]]``.
    ``row_filter`` (N,) bool, when given, leaves the other rows out of every
    draw: the half-batch fault of the output check's own tests.
    Returns (features (T, 2^d-1), thresholds, leaves (T, 2^d)) as numpy.
    """
    n_features = (bins["zero_bin"].shape[0] if isinstance(bins, dict)
                  else bins.shape[1])
    if block is None:
        block = 256 if isinstance(bins, dict) else 1 << 15
    keys = jax.random.split(seed_key, n_slots)
    base = init_score(labels, multiplicity).astype(dtype)
    versions = [jnp.full(labels.shape, base, dtype)]
    out_f, out_t, out_l = [], [], []
    for j, (k, i) in enumerate(zip(schedule, tickets)):
        g, h, mask = round_inputs(keys[int(i)], labels, multiplicity,
                                  versions[int(k)], ln, n_features, dtype)
        if row_filter is not None:
            g = jnp.where(row_filter, g, 0)
            h = jnp.where(row_filter, h, 0)
        feat, thr, leaf, node = build_tree(bins, g, h, mask, ln, dtype, block)
        versions.append((versions[j] + leaf[node]).astype(dtype))
        out_f.append(np.asarray(feat))
        out_t.append(np.asarray(thr))
        out_l.append(np.asarray(leaf, np.float32))
    return np.stack(out_f), np.stack(out_t), np.stack(out_l)


def compare_trees(feat, thr, leaf, ref_feat, ref_thr, ref_leaf) -> dict:
    """The numbers the training check compares.

    ``split_mismatch``: share of internal nodes whose (feature, threshold)
    differs from the reference's. ``leaf_gap``: the widest leaf gap over
    leaves whose whole root path matches, over the largest reference leaf
    of those trees; 1.0 when no leaf path matches at all.
    """
    feat, thr, leaf = (np.asarray(a) for a in (feat, thr, leaf))
    diff = (feat != ref_feat) | (thr != ref_thr)  # (T, n_int), heap order
    n_int = diff.shape[1]
    # A node's path is bad when it or any ancestor differs (parent of
    # heap node i is (i - 1) // 2; leaf j sits at heap node n_int + j).
    bad = diff.copy()
    for i in range(1, n_int):
        bad[:, i] |= bad[:, (i - 1) // 2]
    leaf_parent = (n_int + np.arange(n_int + 1) - 1) // 2
    ok = ~bad[:, leaf_parent]
    scale = float(np.max(np.abs(ref_leaf)))
    if not ok.any() or scale == 0.0:
        gap = 1.0
    else:
        gap = float(np.max(np.abs(leaf - ref_leaf)[ok])) / scale
    return {"split_mismatch": float(diff.mean()), "leaf_gap": gap}


def score_rows(x: np.ndarray, edges: np.ndarray, feature: np.ndarray,
               threshold: np.ndarray, leaf: np.ndarray, n_trees: int,
               base_score: float) -> np.ndarray:
    """Float64 scores of raw rows: bin each value by the count of edges
    below it, walk every live tree, add its leaf and the base score."""
    x = np.asarray(x, np.float32)
    bins = np.sum(x[:, :, None] > edges[None, :, :], axis=2)  # (n, F)
    n = x.shape[0]
    n_int = feature.shape[1]
    depth = int(n_int + 1).bit_length() - 1
    total = np.full(n, float(base_score), np.float64)
    rows = np.arange(n)
    for t in range(n_trees):
        node = np.zeros(n, np.int64)
        for _ in range(depth):
            f = feature[t, node]
            node = 2 * node + 1 + (bins[rows, f] > threshold[t, node])
        total += leaf[t, node - n_int].astype(np.float64)
    return total
