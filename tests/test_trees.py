"""Tree substrate: binning, learner, routing."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.kernels.ops import node_sums
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


def _node_sum_case(n_nodes: int, rows: str, seed: int, n: int = 20_000):
    """Node ids and (g, h) rows: h integer multiplicities, g = h * noise."""
    r = np.random.default_rng(seed)
    node = r.integers(0, n_nodes, n).astype(np.int32)
    if rows == "empty_nodes":
        node -= node % 2  # every odd node id is empty
    elif rows == "no_node":
        node[r.random(n) < 0.3] = -1  # the sparse path's rows outside every node
    h = np.minimum(r.zipf(2.0, n), 50).astype(np.float32) * (r.random(n) < 0.8)
    g = (r.standard_normal(n) * h).astype(np.float32)
    return node, np.stack([g, h])


def _float64_sums(node, values, n_nodes):
    keep = node >= 0
    return np.stack([np.bincount(node[keep], v[keep].astype(np.float64), minlength=n_nodes)
                     for v in values])


@pytest.mark.parametrize("mapped", [False, True], ids=["plain", "vmap"])
@pytest.mark.parametrize("k", [1, 2])
@pytest.mark.parametrize("rows", ["all", "empty_nodes", "no_node"])
@pytest.mark.parametrize("n_nodes", [2, 32, 128])
def test_node_sums_match_float64(n_nodes, rows, k, mapped):
    """The learner's per-node sums equal float64 sums to f32 tolerance, and
    integer-valued h (the multiplicities) sums exactly; empty nodes and rows
    with no node sum to 0. (N,) values give (n_nodes,), (K, N) give (K,
    n_nodes), also under vmap as in ``build_tree_multi``."""
    cases = [_node_sum_case(n_nodes, rows, seed) for seed in (0, 1)]
    nodes = np.stack([c[0] for c in cases])
    values = np.stack([c[1][2 - k:] for c in cases])  # (2, K, N): h last
    if k == 1:
        values = values[:, 0]  # (2, N)
    if mapped:
        out = jax.vmap(lambda nd, v: node_sums(nd, v, n_nodes))(nodes, values)
    else:
        out = jnp.stack([node_sums(jnp.asarray(nd), jnp.asarray(v), n_nodes)
                         for nd, v in zip(nodes, values)])
    out = np.asarray(out)
    assert out.dtype == np.float32
    assert out.shape == values.shape[:-1] + (n_nodes,)
    for nd, v, got in zip(nodes, values, out):
        v2, got2 = v.reshape(-1, v.shape[-1]), got.reshape(-1, n_nodes)
        want = _float64_sums(nd, v2, n_nodes)
        scale = _float64_sums(nd, np.abs(v2), n_nodes)
        assert np.all(np.abs(got2 - want) <= 1e-6 * scale + 1e-30)
        np.testing.assert_array_equal(got2[-1], want[-1])  # h: exact
        if rows == "empty_nodes":
            assert not got2[:, 1::2].any()
