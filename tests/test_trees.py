"""Tree substrate: binning, learner, routing."""
import jax
import jax.numpy as jnp
import numpy as np
from hypothesis import given, settings, strategies as st

from repro.trees import (
    LearnerConfig,
    apply_bins,
    bin_dataset,
    build_tree,
    make_bins,
)
from repro.trees.tree import apply_tree, leaf_indices


def test_binning_nonfinite_policy(rng):
    """Serve-time regression: NaN must NOT silently land in the top bin
    (searchsorted's comparison-order artifact); ±inf clamp to the ends."""
    x = rng.standard_normal((100, 3)).astype(np.float32)
    edges = make_bins(x, n_bins=16)
    bad = x.copy()
    bad[0, 0] = np.nan
    bad[1, 1] = np.inf
    bad[2, 2] = -np.inf
    bins = np.asarray(apply_bins(jnp.asarray(bad), jnp.asarray(edges)))
    assert bins[0, 0] == 0  # NaN routes to the designated bin, not bin 15
    assert bins[1, 1] == 15  # +inf really is above every edge
    assert bins[2, 2] == 0  # -inf really is below every edge
    # a non-default NaN bin routes there instead
    bins7 = np.asarray(
        apply_bins(jnp.asarray(bad), jnp.asarray(edges), nan_bin=7)
    )
    assert bins7[0, 0] == 7
    # finite entries are untouched by the policy
    clean = np.asarray(apply_bins(jnp.asarray(x), jnp.asarray(edges)))
    mask = np.isfinite(bad)
    np.testing.assert_array_equal(bins[mask], clean[mask])


def test_binning_monotone_and_bounded(rng):
    x = rng.standard_normal((500, 7)).astype(np.float32)
    edges = make_bins(x, n_bins=16)
    bins = np.asarray(apply_bins(jnp.asarray(x), jnp.asarray(edges)))
    assert bins.min() >= 0 and bins.max() <= 15
    # monotone: larger value -> bin id never decreases (per feature)
    f = 3
    order = np.argsort(x[:, f])
    assert (np.diff(bins[order, f]) >= 0).all()


@settings(max_examples=20, deadline=None)
@given(seed=st.integers(0, 2**31 - 1), n_bins=st.sampled_from([4, 16, 64]))
def test_binning_quantile_balance(seed, n_bins):
    """Property: quantile bins get roughly equal mass on continuous data."""
    rng = np.random.default_rng(seed)
    x = rng.standard_normal((2000, 1)).astype(np.float32)
    data = bin_dataset(x, np.zeros(2000, np.float32), n_bins=n_bins)
    counts = np.bincount(np.asarray(data.bins[:, 0]), minlength=n_bins)
    assert counts.max() <= 3 * 2000 / n_bins  # no bin grossly overloaded


def test_tree_fits_axis_aligned_step(key):
    """A depth-1-expressible target must be fit exactly."""
    bins = jax.random.randint(key, (400, 5), 0, 32, dtype=jnp.int32)
    target = jnp.where(bins[:, 2] > 13, 2.0, -1.0)
    tree = build_tree(
        LearnerConfig(depth=3, n_bins=32, lam=0.0, feature_fraction=1.0),
        bins, -target, jnp.ones(400), key,  # g = -target => leaf = mean target
    )
    pred = apply_tree(tree, bins)
    np.testing.assert_allclose(np.asarray(pred), np.asarray(target), atol=1e-5)


def test_tree_reduces_residual(key):
    bins = jax.random.randint(key, (500, 10), 0, 64, dtype=jnp.int32)
    g = jax.random.normal(key, (500,))
    tree = build_tree(
        LearnerConfig(depth=5, n_bins=64, feature_fraction=1.0),
        bins, g, jnp.ones(500), key,
    )
    pred = apply_tree(tree, bins)
    before = float(jnp.sum(g**2))
    after = float(jnp.sum((g + pred) ** 2))  # tree predicts -g direction
    assert after < before


def test_leaf_routing_partition(key):
    """Every sample lands in exactly one leaf; siblings partition parents."""
    bins = jax.random.randint(key, (300, 4), 0, 16, dtype=jnp.int32)
    g = jax.random.normal(key, (300,))
    tree = build_tree(
        LearnerConfig(depth=4, n_bins=16, feature_fraction=1.0),
        bins, g, jnp.ones(300), key,
    )
    leaf = np.asarray(leaf_indices(tree, bins))
    assert leaf.min() >= 0 and leaf.max() < 16
    # deterministic: same input -> same leaf
    leaf2 = np.asarray(leaf_indices(tree, bins))
    assert (leaf == leaf2).all()


def test_unsplittable_node_passthrough(key):
    """Constant gradients -> no split gain -> all samples route left and the
    single active leaf predicts the regularized mean."""
    bins = jnp.zeros((100, 3), jnp.int32)  # all samples identical
    g = jnp.ones(100)
    h = jnp.ones(100)
    tree = build_tree(
        LearnerConfig(depth=3, n_bins=8, lam=1.0, feature_fraction=1.0),
        bins, g, h, key,
    )
    pred = np.asarray(apply_tree(tree, bins))
    expected = -100.0 / (100.0 + 1.0)
    np.testing.assert_allclose(pred, expected, rtol=1e-5)
