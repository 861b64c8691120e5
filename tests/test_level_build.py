"""One tree level on the chip's path: the staged ``pallas`` pipeline
(histogram kernel -> ``split_gain_pallas`` -> partition) against the jnp
oracle, from a single level up to whole training runs.

Contracts, pinned here:

  * one level (``trees.learner._staged_level``) vs ``ref.level_build_ref``,
    on the dense bins and on the ``SparseBins`` of the same data: split
    structure and row map exactly equal on continuous random data (gains
    decisively separated), histograms to f32 tolerance — rtol 1e-5 /
    atol 1e-4, one ulp per accumulated O(1..100) cell (the kernels' dot
    and entry orders differ from segment_sum's);
  * whole trees, worker rounds and training runs, ``pallas`` vs ``ref``:
    the contract the hist modes carry (different f32 accumulation orders
    can flip an argmax only on near-ties) — a tree's well-populated heap
    prefix exact, >= 97% of its nodes identical and <= 1% RMS prediction
    drift; a round's root split exact, >= 90% of nodes identical and its
    loss within rel 1e-3, across logistic / multiclass:3 / quantile:0.5
    and both hist modes (multiclass lanes are the near-tie-prone ones:
    softmax splits each node's gradient mass K ways);
  * the determinism contracts hold under ``backend='pallas'``: threaded
    record -> replay is bit-identical, and the committed golden trace
    replays to the committed forest (structure exact, leaves atol 1e-6 —
    the corpus was recorded on the ref backend, so this calibrates
    pallas-vs-ref drift end to end).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.sgbdt import SGBDTConfig, init_state
from repro.kernels import ops
from repro.kernels.ref import level_build_ref
from repro.ps.engine import get_trainer, propose_tree
from repro.ps.runtime import AsyncRuntime
from repro.trees import binning
from repro.trees.learner import (
    LearnerConfig,
    _smaller_children,
    _staged_level,
    build_tree,
)

DEPTHS = (1, 3, 7)
OBJECTIVES = ("logistic", "multiclass:3", "quantile:0.5")


def _case(seed, n=700, f=9, n_bins=32):
    key = jax.random.PRNGKey(seed)
    k1, k2, k3 = jax.random.split(key, 3)
    bins = jax.random.randint(k1, (n, f), 0, n_bins, dtype=jnp.int32)
    g = jax.random.normal(k2, (n,))
    h = (jax.random.uniform(k3, (n,)) < 0.8).astype(jnp.float32)
    return bins, jnp.where(h > 0, g, 0.0), h


def _level_inputs(seed, n, f, n_bins, n_nodes):
    bins, g, h = _case(seed, n=n, f=f, n_bins=n_bins)
    node = jax.random.randint(
        jax.random.PRNGKey(seed + 1), (n,), 0, n_nodes, dtype=jnp.int32
    )
    return bins, node, g, h


def _staged(layout, bins, node, g, h, mask, level, parent, hist_mode):
    """One staged ``pallas`` level on ``layout``'s view of ``bins``, and the
    dense bins the oracle reads (``binning.to_dense`` of the sparse view).
    Returns ((hist, feat, thr, new_node), oracle bins, config)."""
    cfg = LearnerConfig(n_bins=16, backend="pallas", hist_mode=hist_mode)
    view = binning.to_sparse(bins) if layout == "sparse" else bins
    oracle_bins = binning.to_dense(view) if layout == "sparse" else bins
    out = _staged_level(
        cfg, "pallas", view, view, node, g, h, mask > 0, level, parent
    )
    return out, oracle_bins, cfg


def _assert_level_matches(got, want):
    h_s, f_s, t_s, n_s = got
    h_r, f_r, t_r, _, n_r = want
    np.testing.assert_array_equal(np.asarray(f_s), np.asarray(f_r))
    np.testing.assert_array_equal(np.asarray(t_s), np.asarray(t_r))
    np.testing.assert_array_equal(np.asarray(n_s), np.asarray(n_r))
    np.testing.assert_allclose(
        np.asarray(h_s), np.asarray(h_r), rtol=1e-5, atol=1e-4
    )


# ------------------------------------------------------ kernel-level parity
@pytest.mark.parametrize("layout", ["dense", "sparse"])
@pytest.mark.parametrize("n_nodes", [1, 2, 4, 8])
@pytest.mark.parametrize("n,f", [(640, 8), (700, 9), (515, 3)])
def test_staged_matches_ref_full_level(n, f, n_nodes, layout):
    """Full-level builds (``hist_mode='rebuild'``) across ragged geometries
    (515/700 exercise sample padding, 9/3 feature padding)."""
    n_bins = 16
    bins, node, g, h = _level_inputs(5, n, f, n_bins, n_nodes)
    level = n_nodes.bit_length() - 1
    mask = jnp.ones((f,), jnp.float32)
    got, dense, _ = _staged(layout, bins, node, g, h, mask, level, None, "rebuild")
    active = jnp.arange(n_nodes, dtype=jnp.int32)
    want = level_build_ref(
        dense, node, g, h, active, None, mask, 1.0, 1e-3, n_nodes, n_bins
    )
    _assert_level_matches(got, want)


@pytest.mark.parametrize("layout", ["dense", "sparse"])
@pytest.mark.parametrize("level", [1, 2, 3])
def test_staged_matches_ref_subtract_level(level, layout):
    """Subtraction levels: only the smaller child of every parent is
    histogrammed, the sibling is derived from the parent cache."""
    n, f, n_bins = 640, 8, 16
    n_nodes = 1 << level
    bins, node, g, h = _level_inputs(7, n, f, n_bins, n_nodes)
    parent = ops.build_histogram(
        bins, node >> 1, g, h, n_nodes // 2, n_bins, backend="ref"
    )
    mask = jnp.ones((f,), jnp.float32)
    got, dense, cfg = _staged(
        layout, bins, node, g, h, mask, level, parent, "subtract"
    )
    active = _smaller_children(cfg, node, h, n_nodes)
    want = level_build_ref(
        dense, node, g, h, active, parent, mask, 1.0, 1e-3, n_nodes, n_bins,
        derive_sibling=True,
    )
    _assert_level_matches(got, want)


@pytest.mark.parametrize("layout", ["dense", "sparse"])
def test_feature_mask_respected(layout):
    """Masked features never win a split, matching the oracle's argmax."""
    n, f, n_bins, n_nodes = 512, 8, 16, 2
    bins, node, g, h = _level_inputs(13, n, f, n_bins, n_nodes)
    mask = jnp.asarray([1, 0, 1, 0, 1, 0, 1, 0], jnp.float32)
    (_, f_s, t_s, _), dense, _ = _staged(
        layout, bins, node, g, h, mask, 1, None, "rebuild"
    )
    active = jnp.arange(n_nodes, dtype=jnp.int32)
    _, f_r, t_r, _, _ = level_build_ref(
        dense, node, g, h, active, None, mask, 1.0, 1e-3, n_nodes, n_bins
    )
    np.testing.assert_array_equal(np.asarray(f_s), np.asarray(f_r))
    np.testing.assert_array_equal(np.asarray(t_s), np.asarray(t_r))
    assert np.all(np.asarray(f_s) % 2 == 0), "a masked feature won a split"


# ------------------------------------------------- learner-level differential
@pytest.mark.parametrize("hist_mode", ["subtract", "rebuild"])
@pytest.mark.parametrize("depth", DEPTHS)
def test_build_tree_backend_parity(key, depth, hist_mode):
    """Whole trees, both hist modes, depths 1/3/7, pallas vs ref.

    The kernels' dot and entry orders differ from the oracle's
    segment_sum, so the contract is the hist-mode one: bitwise structure
    on the well-populated heap prefix (levels 0..3; decisively separated
    gains on continuous random data), >= 97% of nodes identical overall,
    and <= 1% RMS prediction drift."""
    from repro.trees.tree import apply_tree

    bins, g, h = _case(23)
    trees = {}
    for backend in ("ref", "pallas"):
        cfg = LearnerConfig(
            depth=depth, n_bins=32, feature_fraction=1.0, backend=backend,
            hist_mode=hist_mode,
        )
        trees[backend] = build_tree(cfg, bins, g, h, key)
    exact_nodes = (1 << min(depth, 4)) - 1  # heap prefix: levels 0..3
    pred = {b: np.asarray(apply_tree(t, bins)) for b, t in trees.items()}
    for name in ("feature", "threshold"):
        a = np.asarray(getattr(trees["pallas"], name))
        b = np.asarray(getattr(trees["ref"], name))
        np.testing.assert_array_equal(
            a[:exact_nodes], b[:exact_nodes], err_msg=f"{name} prefix"
        )
        assert np.mean(a == b) >= 0.97, f"{name} flips"
    scale = np.sqrt(np.mean(pred["ref"] ** 2)) + 1e-12
    drift = np.sqrt(np.mean((pred["pallas"] - pred["ref"]) ** 2))
    assert drift <= 0.01 * scale, f"drift {drift:.3e}"


def _objective_cfg(objective, backend, hist_mode="subtract"):
    return SGBDTConfig(
        n_trees=8, step_length=0.3, sampling_rate=0.8, objective=objective,
        learner=LearnerConfig(depth=3, n_bins=64, backend=backend,
                              hist_mode=hist_mode),
    )


def _objective_data(objective, sparse_data):
    if objective == "multiclass:3":
        return sparse_data._replace(
            labels=jnp.asarray(np.asarray(sparse_data.labels) % 3, jnp.float32)
        )
    return sparse_data


@pytest.mark.parametrize("hist_mode", ["subtract", "rebuild"])
@pytest.mark.parametrize("objective", OBJECTIVES)
def test_propose_round_backend_parity(objective, hist_mode, sparse_data, key):
    """One worker round per objective x hist mode: the pallas backend's
    pushed (tree, delta) payload vs ref (K-output shapes included).

    Multiclass lanes split each node's gradient mass K ways, so deep
    splits near-tie and one ulp of cross-backend accumulation drift can
    flip an argmax. A flipped near-tie re-routes real
    samples, so the PAYLOAD may differ — what cannot differ is its
    QUALITY: the contract is exact root split per lane, >= 90% of nodes
    identical, and the post-update objective loss within rel 1e-3
    (measured ~5e-5). When structures happen to agree everywhere, the
    floats must too (rtol 1e-5).

    The >=90% bar needs a draw without EXACT gain ties near the root:
    the sparse synthetic data has duplicated columns, and an exactly
    tied split re-routes a whole subtree when the backends break the
    tie in different orders. The shard-invariant PRNG flag (PR 9,
    ``jax_threefry_partitionable``) re-rolled the stream and PRNGKey(0)
    now lands two exact ties at levels 1-2 (verified numerically: equal
    gains to 10 decimals) — fold to a decisive draw instead of
    weakening the assertions."""
    from repro.objectives import get_objective

    key = jax.random.fold_in(key, 1)
    data = _objective_data(objective, sparse_data)
    obj = get_objective(objective)
    out = {}
    for backend in ("ref", "pallas"):
        cfg = _objective_cfg(objective, backend, hist_mode)
        state = init_state(cfg, data)
        out[backend] = (state.f, propose_tree(cfg, data, state.f, key))
    (f0, (tree_r, delta_r)), (_, (tree_p, delta_p)) = out["ref"], out["pallas"]
    feat_r, feat_p = (np.asarray(t.feature) for t in (tree_r, tree_p))
    thr_r, thr_p = (np.asarray(t.threshold) for t in (tree_r, tree_p))
    # Root split of every output lane is decisively separated.
    np.testing.assert_array_equal(feat_p[..., 0], feat_r[..., 0])
    np.testing.assert_array_equal(thr_p[..., 0], thr_r[..., 0])
    agree = np.mean((feat_p == feat_r) & (thr_p == thr_r))
    assert agree >= 0.90, f"only {agree:.0%} of split nodes identical"
    loss_r = float(obj.loss(data.labels, f0 + delta_r))
    loss_p = float(obj.loss(data.labels, f0 + delta_p))
    assert abs(loss_p - loss_r) <= 1e-3 * abs(loss_r), (
        f"update quality diverged: {loss_p:.6f} vs {loss_r:.6f}"
    )
    if agree == 1.0:
        np.testing.assert_allclose(
            np.asarray(tree_p.leaf_value), np.asarray(tree_r.leaf_value),
            rtol=1e-5, atol=1e-6,
        )
        np.testing.assert_allclose(
            np.asarray(delta_p), np.asarray(delta_r), rtol=1e-5, atol=1e-6
        )


@pytest.mark.parametrize("objective", OBJECTIVES)
def test_training_backend_parity(objective, sparse_data):
    """Multi-round scan training per objective: pallas and ref loss curves
    agree to accumulated-f32 tolerance and both converge."""
    data = _objective_data(objective, sparse_data)
    losses = {}
    for backend in ("ref", "pallas"):
        _, losses[backend] = get_trainer(
            _objective_cfg(objective, backend)
        ).train_scan(data, ("round_robin", 2), seed=0)
    ref_l, pal_l = (np.asarray(losses[b]) for b in ("ref", "pallas"))
    assert np.isfinite(ref_l).all() and np.isfinite(pal_l).all()
    np.testing.assert_allclose(pal_l, ref_l, rtol=5e-3, atol=5e-4)
    assert pal_l[-1] < pal_l[0]


# -------------------------------------------------- determinism + golden
def test_threaded_replay_bitwise_pallas(sparse_data):
    """The record-and-replay contract holds under backend='pallas':
    threaded record -> deterministic replay, bit for bit."""
    cfg = SGBDTConfig(
        n_trees=10, step_length=0.3, sampling_rate=0.8,
        learner=LearnerConfig(depth=3, n_bins=64, backend="pallas"),
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


def test_golden_trace_replays_under_pallas():
    """The committed golden trace replays to the committed forest with
    backend='pallas': structure exact, leaves atol 1e-6. The corpus was
    recorded on the ref backend, so this pins pallas-vs-ref drift through
    a full threaded schedule, not just one tree."""
    import importlib.util
    import pathlib

    from repro import checkpoint
    from repro.ps.runtime import RunTrace, replay_trace

    golden = pathlib.Path(__file__).resolve().parent / "golden"
    spec = importlib.util.spec_from_file_location(
        "golden_regen_pallas", golden / "regen.py"
    )
    regen = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(regen)

    cfg, data = regen.golden_config(), regen.golden_data()
    pallas_cfg = cfg._replace(
        learner=cfg.learner._replace(backend="pallas")
    )
    like = init_state(cfg, data)
    forest = checkpoint.restore_pytree(
        golden / "ckpt", regen.GOLDEN_STEP, like, check_crc=True
    ).forest
    trace = RunTrace.load(golden / "run_trace.json")
    state, _ = replay_trace(pallas_cfg, data, trace)
    np.testing.assert_array_equal(
        np.asarray(state.forest.feature), np.asarray(forest.feature)
    )
    np.testing.assert_array_equal(
        np.asarray(state.forest.threshold), np.asarray(forest.threshold)
    )
    np.testing.assert_allclose(
        np.asarray(state.forest.leaf_value), np.asarray(forest.leaf_value),
        rtol=0, atol=1e-6,
    )
