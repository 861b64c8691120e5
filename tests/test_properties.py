"""Property-based invariants of the histogram/tree substrate.

Hypothesis draws over the algebraic contracts the subtraction builder
leans on:

  * parent histogram == left child + right child (the subtraction identity);
  * histogram totals == masked ``segment_sum`` (no mass invented or lost);
  * inert samples (h == 0, g == 0 — the Bernoulli-sampled-out invariant)
    contribute to no bucket and no leaf;
  * unsplittable nodes pass every sample left;
  * ``build_tree_multi`` lane k == a standalone ``build_tree`` on column k.
"""
import jax
import jax.numpy as jnp
import numpy as np

from hypothesis import given, settings, strategies as st

from repro.kernels import ref
from repro.trees.learner import LearnerConfig, build_tree, build_tree_multi
from repro.trees.tree import leaf_indices


def _draw_case(seed: int, n: int, f: int, n_bins: int, n_nodes: int):
    key = jax.random.PRNGKey(seed)
    k1, k2, k3, k4 = jax.random.split(key, 4)
    bins = jax.random.randint(k1, (n, f), 0, n_bins, dtype=jnp.int32)
    node = jax.random.randint(k2, (n,), 0, n_nodes, dtype=jnp.int32)
    g = jax.random.normal(k3, (n,))
    h = jax.random.uniform(k4, (n,))
    return bins, node, g, h


@settings(max_examples=20, deadline=None)
@given(
    seed=st.integers(0, 2**31 - 1),
    n=st.integers(16, 300),
    f=st.integers(1, 10),
    n_bins=st.sampled_from([4, 8, 16]),
    level=st.integers(1, 4),
)
def test_parent_histogram_equals_child_sum(seed, n, f, n_bins, level):
    """The subtraction identity: children partition their parent's samples,
    so hist(parent p) == hist(child 2p) + hist(child 2p+1)."""
    n_children = 1 << level
    bins, child, g, h = _draw_case(seed, n, f, n_bins, n_children)
    child_hist = ref.histogram_ref(bins, child, g, h, n_children, n_bins)
    parent_hist = ref.histogram_ref(bins, child >> 1, g, h, n_children // 2, n_bins)
    recomposed = child_hist[:, 0::2] + child_hist[:, 1::2]
    np.testing.assert_allclose(
        np.asarray(parent_hist), np.asarray(recomposed), rtol=1e-5, atol=1e-4
    )


@settings(max_examples=20, deadline=None)
@given(
    seed=st.integers(0, 2**31 - 1),
    n=st.integers(16, 300),
    f=st.integers(1, 8),
    n_bins=st.sampled_from([4, 8, 16]),
    n_nodes=st.sampled_from([1, 2, 4, 8]),
)
def test_histogram_totals_match_segment_sum(seed, n, f, n_bins, n_nodes):
    """Summing a histogram over bins recovers the per-node masked
    segment_sum of g and h, for every feature column."""
    bins, node, g, h = _draw_case(seed, n, f, n_bins, n_nodes)
    hist = ref.histogram_ref(bins, node, g, h, n_nodes, n_bins)
    per_node_g = jax.ops.segment_sum(g, node, num_segments=n_nodes)
    per_node_h = jax.ops.segment_sum(h, node, num_segments=n_nodes)
    for feat in range(f):
        np.testing.assert_allclose(
            np.asarray(hist[0, :, feat].sum(-1)), np.asarray(per_node_g),
            rtol=1e-4, atol=1e-4,
        )
        np.testing.assert_allclose(
            np.asarray(hist[1, :, feat].sum(-1)), np.asarray(per_node_h),
            rtol=1e-4, atol=1e-4,
        )


@settings(max_examples=15, deadline=None)
@given(
    seed=st.integers(0, 2**31 - 1),
    depth=st.sampled_from([2, 3, 4]),
    hist_mode=st.sampled_from(["subtract", "rebuild"]),
)
def test_inert_samples_touch_no_bucket_or_leaf(seed, depth, hist_mode):
    """Samples the Bernoulli sampler zeroed out (h == 0 implies g == 0 in
    the trainer) are inert: perturbing their FEATURE ROWS changes neither
    any histogram nor the built tree — structure and leaves are bitwise
    unchanged, because the inert rows of the GH factor are exactly zero."""
    key = jax.random.PRNGKey(seed)
    k1, k2, k3, k4 = jax.random.split(key, 4)
    n, f, n_bins = 200, 6, 16
    bins = jax.random.randint(k1, (n, f), 0, n_bins, dtype=jnp.int32)
    m = (jax.random.uniform(k2, (n,)) < 0.7).astype(jnp.float32)
    g = m * jax.random.normal(k3, (n,))
    h = m  # the paper's gradient step: hessian weight = sample weight
    cfg = LearnerConfig(
        depth=depth, n_bins=n_bins, feature_fraction=1.0, hist_mode=hist_mode
    )
    tree = build_tree(cfg, bins, g, h, key)
    # rebin every inert sample to garbage
    scrambled = jnp.where(
        (m == 0.0)[:, None],
        jax.random.randint(k4, (n, f), 0, n_bins, dtype=jnp.int32),
        bins,
    )
    tree2 = build_tree(cfg, scrambled, g, h, key)
    np.testing.assert_array_equal(np.asarray(tree.feature), np.asarray(tree2.feature))
    np.testing.assert_array_equal(
        np.asarray(tree.threshold), np.asarray(tree2.threshold)
    )
    np.testing.assert_array_equal(
        np.asarray(tree.leaf_value), np.asarray(tree2.leaf_value)
    )
    # and at the histogram layer: node 0, both moved and unmoved bins agree
    hist = ref.histogram_ref(bins, jnp.zeros((n,), jnp.int32), g, h, 1, n_bins)
    hist2 = ref.histogram_ref(
        scrambled, jnp.zeros((n,), jnp.int32), g, h, 1, n_bins
    )
    np.testing.assert_array_equal(np.asarray(hist), np.asarray(hist2))


@settings(max_examples=10, deadline=None)
@given(
    seed=st.integers(0, 2**31 - 1),
    depth=st.sampled_from([2, 3]),
    hist_mode=st.sampled_from(["subtract", "rebuild"]),
)
def test_unsplittable_nodes_pass_all_samples_left(seed, depth, hist_mode):
    """With min_child_hess above the total hessian mass no split is valid:
    every node degrades to the pass-through split (feature 0, threshold
    n_bins - 1) and every sample routes to leaf 0."""
    key = jax.random.PRNGKey(seed)
    n, f, n_bins = 120, 5, 8
    bins = jax.random.randint(key, (n, f), 0, n_bins, dtype=jnp.int32)
    g = jax.random.normal(jax.random.fold_in(key, 1), (n,))
    h = jnp.ones((n,))
    cfg = LearnerConfig(
        depth=depth, n_bins=n_bins, feature_fraction=1.0,
        min_child_hess=float(n + 1), hist_mode=hist_mode,
    )
    tree = build_tree(cfg, bins, g, h, key)
    np.testing.assert_array_equal(np.asarray(tree.feature), 0)
    np.testing.assert_array_equal(np.asarray(tree.threshold), n_bins - 1)
    assert (np.asarray(leaf_indices(tree, bins)) == 0).all()


@settings(max_examples=8, deadline=None)
@given(
    seed=st.integers(0, 2**31 - 1),
    k=st.sampled_from([2, 3]),
    hist_mode=st.sampled_from(["subtract", "rebuild"]),
)
def test_build_tree_multi_lane_equals_standalone(seed, k, hist_mode):
    """Lane k of the vmapped K-output build is identical to a standalone
    build on column k (vmap batches, it does not reassociate)."""
    key = jax.random.PRNGKey(seed)
    k1, k2, k3 = jax.random.split(key, 3)
    n, f, n_bins = 150, 6, 16
    bins = jax.random.randint(k1, (n, f), 0, n_bins, dtype=jnp.int32)
    g = jax.random.normal(k2, (n, k))
    h = jnp.broadcast_to(
        (jax.random.uniform(k3, (n,)) < 0.8).astype(jnp.float32)[:, None], (n, k)
    )
    g = jnp.where(h > 0, g, 0.0)
    cfg = LearnerConfig(
        depth=3, n_bins=n_bins, feature_fraction=0.8, hist_mode=hist_mode
    )
    stacked = build_tree_multi(cfg, bins, g, h, key)
    for lane in range(k):
        single = build_tree(cfg, bins, g[:, lane], h[:, lane], key)
        np.testing.assert_array_equal(
            np.asarray(stacked.feature[lane]), np.asarray(single.feature)
        )
        np.testing.assert_array_equal(
            np.asarray(stacked.threshold[lane]), np.asarray(single.threshold)
        )
        np.testing.assert_allclose(
            np.asarray(stacked.leaf_value[lane]), np.asarray(single.leaf_value),
            rtol=1e-6, atol=1e-7,
        )
