"""The sparse binned-data layer (DESIGN.md §16).

Contracts under test:
  * dense -> SparseBins -> dense round-trips EXACTLY (integer bin codes,
    explicit zero-bin — no tolerance anywhere);
  * histogram builds dispatch on the representation and the ref paths are
    BITWISE identical dense-vs-sparse (the sparse oracle densifies);
  * the Pallas sparse kernel (interpret mode on CPU) matches the oracle to
    f32 tolerance on full and subset (subtraction-mode) builds;
  * build_tree grows the IDENTICAL forest from either representation;
  * serving-side routing (apply_tree) reads the same values through
    ``gather_feature_bins`` on either layout;
  * ``bin_dataset(sparse='auto')`` picks the layout by measured density;
  * the 1D data-parallel builder REJECTS SparseBins (global sample ids
    cannot shard over rows).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.data as D
from repro.kernels import ops, ref
from repro.kernels.histogram_sparse import histogram_sparse_pallas
from repro.launch.mesh import make_mesh
from repro.trees import binning
from repro.trees.learner import LearnerConfig, build_tree
from repro.trees.tree import apply_tree, leaf_indices


@pytest.fixture(scope="module")
def sparse_pair():
    """(dense bins, SparseBins) views of one high-dim sparse dataset."""
    data = D.make_sparse_classification(256, 24, 4, seed=11, sparse=True)
    sp = data.bins
    assert isinstance(sp, binning.SparseBins)
    return binning.to_dense(sp), sp, data


def _rand_gh(n, seed=0):
    k1, k2 = jax.random.split(jax.random.PRNGKey(seed))
    return jax.random.normal(k1, (n,)), jax.random.uniform(k2, (n,)) + 0.1


# ------------------------------------------------------------- round trip
def test_sparse_roundtrip_exact(sparse_pair):
    dense, sp, _ = sparse_pair
    assert np.array_equal(np.asarray(binning.to_dense(sp)), np.asarray(dense))
    sp2 = binning.to_sparse(dense)
    assert np.array_equal(np.asarray(binning.to_dense(sp2)), np.asarray(dense))


def test_sparse_shape_properties(sparse_pair):
    dense, sp, _ = sparse_pair
    assert sp.shape == dense.shape
    assert sp.n_samples == dense.shape[0]
    assert sp.n_features == dense.shape[1]
    # stored entries never collide with the zero bin (exactness invariant)
    codes = np.asarray(sp.codes)
    idx = np.asarray(sp.indices)
    zb = np.asarray(sp.zero_bin)
    valid = idx >= 0
    assert (codes[valid] != zb[idx[valid]]).all()


def test_gather_feature_bins_matches_dense(sparse_pair):
    dense, sp, _ = sparse_pair
    feat = jax.random.randint(
        jax.random.PRNGKey(4), (sp.n_samples,), 0, sp.n_features
    )
    got = binning.gather_feature_bins(sp, feat)
    want = binning.gather_feature_bins(dense, feat)
    assert np.array_equal(np.asarray(got), np.asarray(want))


# -------------------------------------------------------------- histograms
def test_histogram_ref_bitwise_dense_vs_sparse(sparse_pair):
    dense, sp, _ = sparse_pair
    n = sp.n_samples
    g, h = _rand_gh(n)
    node = jax.random.randint(jax.random.PRNGKey(7), (n,), -1, 4)
    want = ops.build_histogram(dense, node, g, h, 4, n_bins=64, backend="ref")
    got = ops.build_histogram(sp, node, g, h, 4, n_bins=64, backend="ref")
    assert np.array_equal(np.asarray(got), np.asarray(want))


def test_histogram_sparse_pallas_matches_oracle(sparse_pair):
    dense, sp, _ = sparse_pair
    n = sp.n_samples
    g, h = _rand_gh(n, seed=1)
    node = jax.random.randint(jax.random.PRNGKey(8), (n,), -1, 4)
    want = ref.histogram_ref(dense, node, g, h, 4, 64)
    got = ops.build_histogram_sparse(
        sp.feat_rows, sp.feat_codes, sp.zero_bin, node, g, h,
        4, 64, backend="pallas",
    )
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(want), rtol=0, atol=1e-5
    )


def test_histogram_sparse_subset_matches_oracle(sparse_pair):
    dense, sp, _ = sparse_pair
    n = sp.n_samples
    g, h = _rand_gh(n, seed=2)
    node = jax.random.randint(jax.random.PRNGKey(9), (n,), 0, 4)
    active = jnp.asarray([1, 2], jnp.int32)
    want = ref.histogram_subset_ref(dense, node, g, h, active, 4, 64)
    got = ops.build_histogram_sparse(
        sp.feat_rows, sp.feat_codes, sp.zero_bin, node, g, h,
        4, 64, backend="pallas", active_nodes=active,
    )
    # The kernel and the oracle sum the same f32 values in different
    # orders; at cell values near 10 that is a few ulps (~1e-5 absolute),
    # so the bound is relative.
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(want), rtol=1e-6, atol=1e-5
    )


# ------------------------------------------------------------------ forest
@pytest.mark.parametrize("mode", ["rebuild", "subtract"])
def test_build_tree_identical_forest(sparse_pair, mode):
    dense, sp, _ = sparse_pair
    g, h = _rand_gh(sp.n_samples, seed=3)
    cfg = LearnerConfig(depth=4, n_bins=64, hist_mode=mode)
    key = jax.random.PRNGKey(5)
    td = build_tree(cfg, dense, g, h, key)
    ts = build_tree(cfg, sp, g, h, key)
    for a, b in zip(td, ts):
        assert np.array_equal(np.asarray(a), np.asarray(b))


def test_apply_tree_routes_identically(sparse_pair):
    dense, sp, _ = sparse_pair
    g, h = _rand_gh(sp.n_samples, seed=4)
    cfg = LearnerConfig(depth=3, n_bins=64)
    tree = build_tree(cfg, dense, g, h, jax.random.PRNGKey(6))
    assert np.array_equal(
        np.asarray(leaf_indices(tree, sp)), np.asarray(leaf_indices(tree, dense))
    )
    assert np.array_equal(
        np.asarray(apply_tree(tree, sp)), np.asarray(apply_tree(tree, dense))
    )


# ---------------------------------------------------------------- dispatch
def test_bin_dataset_auto_picks_by_density():
    rng = np.random.default_rng(0)
    x_sparse = np.zeros((128, 32), np.float32)
    x_sparse[rng.random((128, 32)) < 0.05] = 1.0
    got = binning.bin_dataset(x_sparse, np.zeros(128, np.float32), sparse="auto")
    assert isinstance(got.bins, binning.SparseBins)
    x_dense = rng.standard_normal((128, 8)).astype(np.float32)
    got = binning.bin_dataset(x_dense, np.zeros(128, np.float32), sparse="auto")
    assert not isinstance(got.bins, binning.SparseBins)
    # default stays dense regardless of density
    got = binning.bin_dataset(x_sparse, np.zeros(128, np.float32))
    assert not isinstance(got.bins, binning.SparseBins)


def test_1d_builder_rejects_sparse(sparse_pair):
    _, sp, _ = sparse_pair
    from repro.ps.sharded import make_sharded_builder

    mesh = make_mesh((1,), ("data",))
    builder = make_sharded_builder(LearnerConfig(depth=2, n_bins=64), mesh)
    g = jnp.zeros((sp.n_samples,), jnp.float32)
    with pytest.raises(ValueError, match="1, P_f"):
        builder(sp, g, g, jax.random.PRNGKey(0))
