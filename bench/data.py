"""Datasets, forests and request streams made on the device from ``--seed``.

The generators keep the properties of ``repro.data.synthetic`` (which builds
the same sets on the host, at 33 s and 107 s a process at the published
sizes): HIGGS-like rows are dense standard normals with a linear-threshold
label and a Zipf-like multiplicity profile; real-sim-like rows carry ``nnz``
lognormal values at uniformly drawn columns, a label from a sparse linear
model thresholded at its median with 5% of labels flipped. Binning follows
``repro.trees.binning``: a value's bin is its quantile cell under the
per-feature quantile edges, and sparse sets take the explicit-zero-bin
ELL layouts.

Everything here is a pure function of the seed: the same seed gives the
same arrays, bit for bit.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.scipy.special import ndtri


def program_seed(seed: int) -> int:
    """A 31-bit seed for ``jax.random.PRNGKey`` from any whole number.

    ``PRNGKey`` keeps only the low 32 bits of a Python int, so two bench
    seeds that differ above bit 31 would draw the same stream.
    """
    return int(np.random.SeedSequence(int(seed)).generate_state(1)[0] & 0x7FFFFFFF)


def normal_edges(n_features: int, n_bins: int) -> jax.Array:
    """Quantile edges of a standard normal column, (F, n_bins - 1) f32:
    the edges ``binning.make_bins`` converges to on N(0, 1) features."""
    q = jnp.arange(1, n_bins, dtype=jnp.float32) / n_bins
    return jnp.broadcast_to(ndtri(q), (n_features, n_bins - 1)).astype(jnp.float32)


def zipf_multiplicity(n: int, total_mass: int) -> jax.Array:
    """``make_dense_low_diversity``'s profile: m_i = max(1, round(c / i))."""
    raw = 1.0 / np.arange(1, n + 1, dtype=np.float64)
    m = np.maximum(1.0, np.round(raw / raw.sum() * total_mass))
    return m.astype(np.float32)


@functools.partial(jax.jit, static_argnames=("n", "f", "n_bins"))
def _dense(key, multiplicity, *, n: int, f: int, n_bins: int):
    # x = Phi^-1(u) is a standard normal; its bin under the normal quantile
    # edges is the quantile cell floor(u * n_bins): elementwise, no search.
    ku, kw = jax.random.split(key)
    u = jax.random.uniform(ku, (n, f), jnp.float32)
    x = ndtri(u)
    w = jax.random.normal(kw, (f,), jnp.float32)
    logit = jnp.sum(x * w[None, :], axis=1)  # elementwise: no MXU rounding
    y = (logit > 0).astype(jnp.float32)
    bins = jnp.minimum(jnp.floor(u * n_bins), n_bins - 1).astype(jnp.int32)
    return bins, normal_edges(f, n_bins), y, multiplicity


def dense_dataset(seed: int, n: int, f: int, n_bins: int):
    """HIGGS-like ``BinnedData`` at (n, f), built on the default device."""
    from repro.trees.binning import BinnedData

    key = jax.random.PRNGKey(program_seed(seed))
    m = jnp.asarray(zipf_multiplicity(n, n))
    bins, edges, y, m = _dense(key, m, n=n, f=f, n_bins=n_bins)
    return BinnedData(bins=bins, bin_edges=edges, labels=y, multiplicity=m, n_bins=n_bins)


@functools.partial(jax.jit, static_argnames=("n", "f", "nnz"))
def _sparse_entries(key, *, n: int, f: int, nnz: int):
    kc, kv, kw, km, kn, kf = jax.random.split(key, 6)
    cols = jax.random.randint(kc, (n, nnz), 0, f, jnp.int32)
    vals = jnp.exp(jax.random.normal(kv, (n, nnz), jnp.float32))
    # Sort each row's columns, keeping draw order among equal ones; a column
    # drawn twice keeps its later value, as the host generator's
    # fancy-index assignment does.
    slot = jnp.arange(nnz, dtype=jnp.int32)
    keyed = jnp.sort(cols * nnz + slot[None, :], axis=1)
    cols, order = keyed // nnz, keyed % nnz
    vals = jnp.take_along_axis(vals, order, axis=1)
    last = jnp.concatenate(
        [cols[:, 1:] != cols[:, :-1], jnp.ones((n, 1), bool)], axis=1
    )
    w = jax.random.normal(kw, (f,), jnp.float32) * (
        jax.random.uniform(km, (f,)) < 0.2
    )
    logit = jnp.sum(jnp.where(last, vals * w[cols], 0.0), axis=1)
    logit = logit + 0.1 * jax.random.normal(kn, (n,), jnp.float32)
    y = (logit > jnp.median(logit)).astype(jnp.float32)
    flip = jax.random.uniform(kf, (n,)) < 0.05
    y = jnp.where(flip, 1.0 - y, y)
    # Row-major ELL: kept columns ascending, pads (-1) at the end.
    packed = jnp.sort(jnp.where(last, cols, f), axis=1)
    indices = jnp.where(packed < f, packed, -1)
    # Entries ordered by feature, rows ascending within one (the flat
    # row-major order is already by row, and the sort is stable).
    flat = packed.reshape(-1)
    rows = jnp.repeat(jnp.arange(n, dtype=jnp.int32), nnz)
    by_feat = jnp.argsort(flat, stable=True)
    col_s, row_s = flat[by_feat], rows[by_feat]
    start = jnp.searchsorted(col_s, jnp.arange(f + 1, dtype=jnp.int32), side="left")
    counts = start[1:] - start[:-1]
    return indices, y, counts, start[:-1], row_s


@functools.partial(jax.jit, static_argnames=("width", "code"))
def _feature_ell(counts, start, row_s, *, width: int, code: int):
    pos = jnp.arange(width, dtype=jnp.int32)[None, :]
    take = jnp.minimum(start[:, None] + pos, row_s.shape[0] - 1)
    feat_rows = jnp.where(pos < counts[:, None], row_s[take], -1).astype(jnp.int32)
    feat_codes = jnp.where(feat_rows >= 0, code, 0).astype(jnp.int32)
    return feat_rows, feat_codes


def sparse_dataset(seed: int, n: int, f: int, nnz: int, n_bins: int, width: int):
    """real-sim-like ``BinnedData`` with ``SparseBins``, built on the device.

    Quantile binning puts every value of a feature with fewer than
    n / n_bins nonzeros above all of its edges, which are then 0: the
    absent entries decode to bin 0 and every stored entry to bin
    ``n_bins - 1``. The check below holds the geometry to that case.

    The feature-major store is ``width`` entries wide whatever the seed
    draws (pads are -1), so every seed runs the same compiled programs.
    """
    from repro.trees.binning import BinnedData, SparseBins

    key = jax.random.PRNGKey(program_seed(seed))
    indices, y, counts, start, row_s = _sparse_entries(key, n=n, f=f, nnz=nnz)
    most = int(jnp.max(counts))
    if most * n_bins >= n:
        raise ValueError(
            f"a feature holds {most} of {n} rows: its quantile edges are not "
            "all 0, which this generator does not model"
        )
    if most > width:
        raise ValueError(f"seed {seed}: a feature holds {most} rows, more than "
                         f"the feature-major width {width}")
    code = n_bins - 1
    feat_rows, feat_codes = _feature_ell(counts, start, row_s, width=width, code=code)
    codes = jnp.where(indices >= 0, code, 0).astype(jnp.int32)
    bins = SparseBins(
        indices=indices, codes=codes, feat_rows=feat_rows, feat_codes=feat_codes,
        zero_bin=jnp.zeros((f,), jnp.int32),
    )
    return BinnedData(
        bins=bins, bin_edges=jnp.zeros((f, n_bins - 1), jnp.float32), labels=y,
        multiplicity=jnp.ones((n,), jnp.float32), n_bins=n_bins,
    )


def make_dataset(seed: int, config: dict):
    d = config["data"]
    if d["layout"] == "dense":
        return dense_dataset(seed, d["rows"], d["features"], d["n_bins"])
    if d["layout"] == "sparse":
        return sparse_dataset(seed, d["rows"], d["features"], d["nnz_per_row"],
                              d["n_bins"], d["feature_ell_width"])
    raise ValueError(f"unknown data layout {d['layout']!r}")


@functools.partial(jax.jit, static_argnames=("slots", "depth", "f", "n_bins"))
def _forest(key, *, slots: int, depth: int, f: int, n_bins: int):
    k1, k2, k3 = jax.random.split(key, 3)
    n_int, n_leaf = (1 << depth) - 1, 1 << depth
    feature = jax.random.randint(k1, (slots, n_int), 0, f, jnp.int32)
    threshold = jax.random.randint(k2, (slots, n_int), 0, n_bins - 1, jnp.int32)
    # A trained forest's leaves are v * -G / (H + lam) with v = 0.01.
    leaf = 0.01 * jax.random.normal(k3, (slots, n_leaf), jnp.float32)
    return feature, threshold, leaf


def random_forest(seed: int, slots: int, depth: int, f: int, n_bins: int,
                  base_score: float = 0.0):
    """A fully live ``Forest`` with random splits and leaves. Traversal
    cost does not depend on the values, so a forest from the seed serves
    like a trained one of the same shape."""
    from repro.trees.forest import Forest

    key = jax.random.fold_in(jax.random.PRNGKey(program_seed(seed)), 1)
    feature, threshold, leaf = _forest(key, slots=slots, depth=depth, f=f, n_bins=n_bins)
    return Forest(
        feature=feature, threshold=threshold, leaf_value=leaf,
        n_trees=jnp.asarray(slots, jnp.int32),
        base_score=jnp.asarray(base_score, jnp.float32),
    )
