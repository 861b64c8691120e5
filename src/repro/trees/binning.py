"""Feature quantization into histogram bins.

GBDT histogram algorithms (LightGBM, DimBoost, this paper's workers) never
split on raw feature values: features are pre-quantized into at most
``n_bins`` integer bins, and split search runs over bin boundaries. Binning
happens once per dataset, outside the training loop.
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np


class SparseBins(NamedTuple):
    """A sparse quantized feature matrix — the explicit-zero-bin layout.

    High-dimensional binned datasets (real-sim, E2006) put almost every
    sample of almost every feature into one dominant bin (the feature's
    quantile-degenerate "zero"). Storing only the entries that DIFFER from
    that bin makes histogram cost scale with nnz instead of N*F (the
    block-distributed GBT representation). Two padded fixed-shape layouts
    of the same entry set are kept so every consumer stays jittable:

      * row-major ELL — ``indices``/``codes`` (N, E): per-sample stored
        columns (pad -1) and their bin codes; drives per-sample feature
        lookups (tree routing, serving).
      * feature-major ELL — ``feat_rows``/``feat_codes`` (F, C): per-
        feature stored sample ids (pad -1) and codes; drives the histogram
        kernel, whose contraction length is then C ≈ N * density per
        feature instead of N.

    ``zero_bin`` (F,) int32 is the bin an ABSENT entry decodes to (the
    per-feature majority bin). Stored codes never equal their feature's
    zero bin, so dense↔sparse round-trips are exact (integer scatter).
    Under feature sharding the feature-major fields are sharded over the
    'feature' mesh axis while ``indices``/``codes``/``zero_bin`` stay
    replicated (the global row view routes samples; see DESIGN.md §16).
    """

    indices: jax.Array  # (N, E) int32, -1 = pad
    codes: jax.Array  # (N, E) int32
    feat_rows: jax.Array  # (F, C) int32, -1 = pad
    feat_codes: jax.Array  # (F, C) int32
    zero_bin: jax.Array  # (F,) int32

    @property
    def shape(self) -> tuple[int, int]:
        """(N, F) of the equivalent dense matrix — F is GLOBAL (zero_bin's
        width) even when the feature-major store is a feature shard."""
        return (self.indices.shape[0], self.zero_bin.shape[0])

    @property
    def n_samples(self) -> int:
        return self.indices.shape[0]

    @property
    def n_features(self) -> int:
        return self.zero_bin.shape[0]

    @property
    def max_nnz_row(self) -> int:
        return self.indices.shape[1]

    @property
    def max_nnz_feature(self) -> int:
        return self.feat_rows.shape[1]


class BinnedData(NamedTuple):
    """A quantized dataset.

    Attributes:
      bins: (N, F) int32 — bin index of every sample/feature, in
        [0, n_bins) — or a ``SparseBins`` holding the same matrix in the
        explicit-zero-bin sparse layout (``bin_dataset`` picks it when the
        density falls under the threshold). Either way ``bins.shape`` is
        (N, F), so shape-derived consumers are representation-blind.
      bin_edges: (F, n_bins - 1) float32 — upper edge of each bin (last bin
        is open-ended); used only to map raw inference inputs onto bins.
      labels: (N,) float32 — {0, 1} for binary classification, class ids
        for multiclass, reals for regression, relevance grades for ranking.
      multiplicity: (N,) float32 — the paper's m_i: how many times each
        *distinct* sample occurs in the logical dataset. Controls diversity.
      n_bins: static int.
      qid: (N,) int32 query ids for ranking objectives, else None.
    """

    bins: jax.Array | SparseBins
    bin_edges: jax.Array
    labels: jax.Array
    multiplicity: jax.Array
    n_bins: int
    qid: jax.Array | None = None

    @property
    def n_samples(self) -> int:
        return self.bins.shape[0]

    @property
    def n_features(self) -> int:
        return self.bins.shape[1]


def make_bins(x: np.ndarray, n_bins: int = 256) -> np.ndarray:
    """Compute per-feature quantile bin edges. Host-side, once per dataset.

    Returns (F, n_bins - 1) edges. Degenerate (constant / ultra-sparse)
    features get repeated edges, which is harmless: all samples land in bin 0
    and the split gain there is 0.
    """
    qs = np.linspace(0.0, 1.0, n_bins + 1)[1:-1]
    edges = np.quantile(x, qs, axis=0).T.astype(np.float32)  # (F, n_bins-1)
    return np.ascontiguousarray(edges)


@functools.partial(jax.jit, static_argnames=("nan_bin",))
def apply_bins(x: jax.Array, bin_edges: jax.Array, nan_bin: int = 0) -> jax.Array:
    """Map raw features (N, F) onto bin ids (N, F) int32 via searchsorted.

    Finite-values policy (serving sees raw, possibly malformed floats):
      * ``-inf`` clamps to bin 0, ``+inf`` clamps to the last bin — the
        values really are below/above every edge;
      * ``NaN`` routes deterministically to ``nan_bin`` (default 0).
        ``searchsorted`` on NaN is comparison-order-defined and lands in
        the LAST bin, which silently reads as "very large feature" — a
        malformed request must not get a confident extreme-bin prediction.
    """

    def one_feature(col: jax.Array, edges: jax.Array) -> jax.Array:
        # searchsorted already clamps ±inf (below/above every finite edge
        # -> bin 0 / last bin); only NaN needs explicit routing.
        ids = jnp.searchsorted(edges, col, side="left").astype(jnp.int32)
        return jnp.where(jnp.isnan(col), jnp.int32(nan_bin), ids)

    return jax.vmap(one_feature, in_axes=(1, 0), out_axes=1)(x, bin_edges)


# Densities below this default make the sparse layout the win: histogram
# contraction length drops to ~N * density per feature and the row-ELL
# stays narrow. Above it, padding (E = max row nnz) erodes the saving.
SPARSE_DENSITY_THRESHOLD = 0.25


def _zero_bins(b: np.ndarray) -> np.ndarray:
    """Per-feature majority bin — the sparse layout's implicit bin."""
    return np.stack(
        [np.bincount(b[:, f]).argmax() for f in range(b.shape[1])]
    ).astype(np.int32)


def sparse_density(bins: np.ndarray | jax.Array) -> float:
    """nnz / (N * F) under the per-feature majority-bin complement."""
    b = np.asarray(bins)
    zero = _zero_bins(b)
    return float((b != zero[None, :]).mean())


def _ell_pack(mask: np.ndarray, vals: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
    """Pack ``vals[mask]`` row-major into (rows, max_row_nnz) ELL arrays:
    (indices int32 pad -1, values int32 pad 0)."""
    rows, cols = mask.shape
    nnz = mask.sum(1)
    width = max(int(nnz.max(initial=0)), 1)
    idx = np.full((rows, width), -1, np.int32)
    out = np.zeros((rows, width), np.int32)
    r, c = np.nonzero(mask)
    pos = np.arange(len(r)) - np.repeat(np.cumsum(nnz) - nnz, nnz)
    idx[r, pos] = c
    out[r, pos] = vals[r, c]
    return idx, out


def to_sparse(bins: np.ndarray | jax.Array) -> SparseBins:
    """Dense (N, F) bin matrix -> the explicit-zero-bin sparse layout.

    Host-side, once per dataset (like ``make_bins``). Stored entries are
    exactly the cells that differ from their feature's majority bin, in
    both row-major and feature-major ELL order; ``to_dense`` inverts this
    bitwise (integers — no rounding anywhere).
    """
    b = np.asarray(bins).astype(np.int32)
    zero = _zero_bins(b)
    mask = b != zero[None, :]
    indices, codes = _ell_pack(mask, b)
    feat_rows, feat_codes = _ell_pack(mask.T, b.T)
    return SparseBins(
        indices=jnp.asarray(indices),
        codes=jnp.asarray(codes),
        feat_rows=jnp.asarray(feat_rows),
        feat_codes=jnp.asarray(feat_codes),
        zero_bin=jnp.asarray(zero),
    )


@jax.jit
def to_dense(sp: SparseBins) -> jax.Array:
    """SparseBins -> the exact dense (N, F) int32 matrix (round-trip is
    bitwise: one stored entry per cell, integer scatter)."""
    n, f = sp.shape
    valid = sp.indices >= 0
    col = jnp.where(valid, sp.indices, 0)
    row = jnp.broadcast_to(jnp.arange(n, dtype=jnp.int32)[:, None], col.shape)
    delta = jnp.where(valid, sp.codes - sp.zero_bin[col], 0)
    base = jnp.broadcast_to(sp.zero_bin[None, :], (n, f)).astype(jnp.int32)
    return base.at[row.reshape(-1), col.reshape(-1)].add(delta.reshape(-1))


@jax.jit
def gather_feature_bins(bins: jax.Array | SparseBins, feat: jax.Array) -> jax.Array:
    """Per-sample bin of a chosen feature: (N,) int32 from feat (N,) int32.

    The representation-blind form of ``bins[i, feat[i]]`` — dense selects
    by a one-hot compare over the F columns (an (N, 1) gather index would
    pad to 128 lanes in TPU memory, 0.5 KB per sample); sparse scans the
    row-ELL store (E compares per sample) and falls back to the feature's
    zero bin when the entry is absent. Shared by the tree partition step
    and the heap routing in ``trees.tree`` so training and serving route
    identically on either layout.
    """
    if not isinstance(bins, SparseBins):
        cols = jnp.arange(bins.shape[1], dtype=jnp.int32)
        return jnp.sum(jnp.where(cols[None, :] == feat[:, None], bins, 0), axis=1)
    hit = bins.indices == feat[:, None]  # pads are -1: never match feat >= 0
    stored = jnp.max(jnp.where(hit, bins.codes, -1), axis=1)
    return jnp.where(stored >= 0, stored, jnp.take(bins.zero_bin, feat))


def bin_dataset(
    x: np.ndarray,
    y: np.ndarray,
    n_bins: int = 256,
    multiplicity: np.ndarray | None = None,
    qid: np.ndarray | None = None,
    sparse: bool | str = False,
    density_threshold: float = SPARSE_DENSITY_THRESHOLD,
) -> BinnedData:
    """One-shot host-side dataset quantization.

    ``sparse``: ``True`` forces the ``SparseBins`` layout, ``'auto'`` goes
    sparse when the majority-bin complement density falls below
    ``density_threshold`` — the real-sim / E2006 regime where
    F ≫ N * density. The default stays ``False`` (dense matrix): sparse is
    an opt-in representation, and every dense consumer keeps its exact
    bytes.
    """
    edges = make_bins(x, n_bins)
    bins = apply_bins(jnp.asarray(x, jnp.float32), jnp.asarray(edges))
    if sparse == "auto":
        sparse = sparse_density(bins) < density_threshold
    if sparse:
        bins = to_sparse(bins)
    if multiplicity is None:
        multiplicity = np.ones(x.shape[0], np.float32)
    return BinnedData(
        bins=bins,
        bin_edges=jnp.asarray(edges),
        labels=jnp.asarray(y, jnp.float32),
        multiplicity=jnp.asarray(multiplicity, jnp.float32),
        n_bins=n_bins,
        qid=None if qid is None else jnp.asarray(qid, jnp.int32),
    )
