"""Differential parity: ``hist_mode='subtract'`` vs ``'rebuild'``.

The subtraction builder (trees/learner.py) is exact in exact arithmetic —
children partition their parent's samples — so the two modes must agree:

  * bitwise on tree STRUCTURE whenever split gains are decisively
    separated (continuous random data; a derived sibling differs from a
    rebuilt one only by f32 subtraction rounding, which can flip argmax
    only on near-ties);
  * to f32 tolerance on histograms, leaves, and losses. Documented
    tolerances: one level of subtraction costs ~1 ulp per cell
    (atol 1e-4 on O(1..100) sums); across a depth-7 build and a
    multi-round training run the drift stays within rtol ~1e-3 on losses.

WITHIN a mode, determinism is bitwise: the threaded runtime's
record-and-replay contract (DESIGN.md §11) must keep holding under the
new 'subtract' default, which this file pins for both modes.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.sgbdt import SGBDTConfig, init_state
from repro.kernels import ops
from repro.ps.engine import get_trainer, propose_tree
from repro.ps.runtime import AsyncRuntime
from repro.trees.learner import LearnerConfig, build_tree
from repro.trees.tree import apply_tree

DEPTHS = (1, 3, 7)
BACKENDS = ("ref", "pallas")


def _case(seed, n=700, f=9, n_bins=32):
    key = jax.random.PRNGKey(seed)
    k1, k2, k3 = jax.random.split(key, 3)
    bins = jax.random.randint(k1, (n, f), 0, n_bins, dtype=jnp.int32)
    g = jax.random.normal(k2, (n,))
    h = (jax.random.uniform(k3, (n,)) < 0.8).astype(jnp.float32)
    return bins, jnp.where(h > 0, g, 0.0), h


@pytest.mark.parametrize("backend", BACKENDS)
@pytest.mark.parametrize("level", [1, 2, 4])
def test_level_reconstruction_parity(key, backend, level):
    """One level in isolation: build the even children + derive the odd
    ones from the parent, compare against the full rebuild. Tolerance-only
    (no argmax involved): this is the core f32 subtraction error bound."""
    n, f, n_bins = 640, 8, 16
    n_nodes = 1 << level
    bins, g, h = _case(11, n=n, f=f, n_bins=n_bins)
    child = jax.random.randint(jax.random.fold_in(key, 9), (n,), 0, n_nodes,
                               dtype=jnp.int32)
    full = ops.build_histogram(bins, child, g, h, n_nodes, n_bins, backend=backend)
    parent = ops.build_histogram(
        bins, child >> 1, g, h, n_nodes // 2, n_bins, backend=backend
    )
    active = 2 * jnp.arange(n_nodes // 2, dtype=jnp.int32)  # even children
    built = ops.build_histogram_subset(
        bins, child, g, h, active, n_nodes, n_bins, backend=backend
    )
    derived = parent - built  # the odd siblings
    np.testing.assert_allclose(
        np.asarray(built), np.asarray(full[:, 0::2]), rtol=1e-5, atol=1e-4
    )
    np.testing.assert_allclose(
        np.asarray(derived), np.asarray(full[:, 1::2]), rtol=1e-4, atol=1e-4
    )


@pytest.mark.parametrize("backend", BACKENDS)
@pytest.mark.parametrize("depth", DEPTHS)
def test_build_tree_mode_parity(key, backend, depth):
    """Whole-tree parity across depths and backends.

    Top levels (well-populated nodes, decisively separated gains) must
    match BITWISE in structure. Deep levels of a depth-7 tree hold a
    handful of samples each; their gains are tiny and near-tied, so one
    ulp of subtraction rounding may flip an argmax — the documented f32
    contract is therefore quantitative below level 4: >= 97% of nodes
    identical and RMS prediction drift <= 1% of the prediction scale.
    """
    bins, g, h = _case(23)
    sub = LearnerConfig(
        depth=depth, n_bins=32, feature_fraction=1.0, backend=backend,
        hist_mode="subtract",
    )
    t_sub = build_tree(sub, bins, g, h, key)
    t_reb = build_tree(sub._replace(hist_mode="rebuild"), bins, g, h, key)
    exact_nodes = (1 << min(depth, 4)) - 1  # heap prefix: levels 0..3
    for name in ("feature", "threshold"):
        a = np.asarray(getattr(t_sub, name))
        b = np.asarray(getattr(t_reb, name))
        np.testing.assert_array_equal(a[:exact_nodes], b[:exact_nodes])
        assert np.mean(a == b) >= 0.97, f"{name}: too many deep-node flips"
    if depth <= 4:
        np.testing.assert_allclose(
            np.asarray(t_sub.leaf_value), np.asarray(t_reb.leaf_value),
            rtol=1e-4, atol=1e-5,
        )
    pred_sub = np.asarray(apply_tree(t_sub, bins))
    pred_reb = np.asarray(apply_tree(t_reb, bins))
    scale = np.sqrt(np.mean(pred_reb**2)) + 1e-12
    drift = np.sqrt(np.mean((pred_sub - pred_reb) ** 2))
    assert drift <= 0.01 * scale, f"prediction drift {drift:.3e} vs scale {scale:.3e}"


def _train_cfg(objective, hist_mode, depth=3, n_trees=15):
    return SGBDTConfig(
        n_trees=n_trees, step_length=0.3, sampling_rate=0.8,
        objective=objective,
        learner=LearnerConfig(depth=depth, n_bins=64, hist_mode=hist_mode),
    )


@pytest.mark.parametrize("objective", ["logistic", "multiclass:3", "quantile:0.5"])
def test_training_mode_parity(objective, sparse_data):
    """End-to-end scan training per objective: the two modes' loss curves
    stay within f32 drift of each other and both converge."""
    data = sparse_data
    if objective == "multiclass:3":
        data = data._replace(
            labels=jnp.asarray(np.asarray(data.labels) % 3, jnp.float32)
        )
    losses = {}
    for mode in ("subtract", "rebuild"):
        _, losses[mode] = get_trainer(_train_cfg(objective, mode)).train_scan(
            data, ("round_robin", 2), seed=0
        )
    sub, reb = (np.asarray(losses[m]) for m in ("subtract", "rebuild"))
    assert np.isfinite(sub).all() and np.isfinite(reb).all()
    np.testing.assert_allclose(sub, reb, rtol=5e-3, atol=5e-4)
    assert sub[-1] < sub[0] and reb[-1] < reb[0]


@pytest.mark.parametrize("objective", ["logistic", "multiclass:3", "quantile:0.5"])
def test_propose_round_mode_parity(objective, sparse_data, key):
    """One worker round per objective: the pushed (tree, delta) payloads of
    the two modes agree to f32 tolerance (K-output shapes included).

    The bitwise structure assertions need a draw whose deep-node gains are
    decisively separated (the file-docstring contract: subtraction rounding
    may flip near-tied argmaxes). The shard-invariant PRNG flag (PR 9,
    ``jax_threefry_partitionable``) re-rolled the stream and PRNGKey(0) now
    lands two level-2 near-ties under multiclass:3 — fold to a decisive
    draw instead of weakening the assertions.
    """
    key = jax.random.fold_in(key, 1)
    data = sparse_data
    if objective == "multiclass:3":
        data = data._replace(
            labels=jnp.asarray(np.asarray(data.labels) % 3, jnp.float32)
        )
    out = {}
    for mode in ("subtract", "rebuild"):
        cfg = _train_cfg(objective, mode)
        state = init_state(cfg, data)
        out[mode] = propose_tree(cfg, data, state.f, key)
    (tree_s, delta_s), (tree_r, delta_r) = out["subtract"], out["rebuild"]
    np.testing.assert_array_equal(
        np.asarray(tree_s.feature), np.asarray(tree_r.feature)
    )
    np.testing.assert_array_equal(
        np.asarray(tree_s.threshold), np.asarray(tree_r.threshold)
    )
    np.testing.assert_allclose(
        np.asarray(tree_s.leaf_value), np.asarray(tree_r.leaf_value),
        rtol=1e-4, atol=1e-6,
    )
    np.testing.assert_allclose(
        np.asarray(delta_s), np.asarray(delta_r), rtol=1e-4, atol=1e-6
    )


@pytest.mark.parametrize("hist_mode", ["subtract", "rebuild"])
def test_threaded_replay_bitwise_per_mode(hist_mode, sparse_data):
    """The PR-4 replay contract under the new default: threaded record ->
    ``Trainer.scan_with`` replay reproduces the forest BIT FOR BIT in
    either histogram mode (modes only differ from each other, never from
    themselves)."""
    cfg = SGBDTConfig(
        n_trees=10, step_length=0.3, sampling_rate=0.8,
        learner=LearnerConfig(depth=3, n_bins=64, hist_mode=hist_mode),
    )
    rt = AsyncRuntime(cfg, sparse_data, n_workers=3)
    state, trace = rt.run(seed=1)
    replayed, _ = rt.replay(trace)
    np.testing.assert_array_equal(np.asarray(state.f), np.asarray(replayed.f))
    for name in ("feature", "threshold", "leaf_value"):
        np.testing.assert_array_equal(
            np.asarray(getattr(state.forest, name)),
            np.asarray(getattr(replayed.forest, name)),
        )
