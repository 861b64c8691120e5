"""The PS execution layer: trainer equivalences, schedules, sharded builds.

The contracts under test:
  * serial training IS the W=1 round-robin schedule — bitwise;
  * the engine's loop and scan forms produce identical forests;
  * the vmapped worker pool executes the same schedule semantics as the
    per-round loop (exact when split gains are decisive);
  * the shard_map+psum histogram path matches the single-device kernel
    (subprocess with a forced multi-device CPU platform).
"""
import json
import os
import subprocess
import sys
import textwrap

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.sgbdt import SGBDTConfig, train_loss, train_serial
from repro.core.simulator import ClusterSpec
from repro.ps import (
    Trainer,
    resolve_schedule,
    train_worker_parallel,
    worker_round_robin,
)
from repro.ps.schedules import constant_delay, max_staleness
from repro.trees.binning import BinnedData
from repro.trees.learner import LearnerConfig


def _forests_identical(a, b) -> bool:
    return (
        np.array_equal(np.asarray(a.feature), np.asarray(b.feature))
        and np.array_equal(np.asarray(a.threshold), np.asarray(b.threshold))
        and np.allclose(
            np.asarray(a.leaf_value), np.asarray(b.leaf_value), atol=1e-6
        )
    )


# ------------------------------------------------------------ equivalences
def test_round_robin_w1_bitmatches_serial(fast_cfg, sparse_data):
    """The serial trainer is the zero-staleness schedule, same program."""
    st_serial = train_serial(fast_cfg, sparse_data, seed=0)
    st_w1 = Trainer(fast_cfg).train(sparse_data, ("round_robin", 1), seed=0)
    assert np.array_equal(np.asarray(st_serial.f), np.asarray(st_w1.f))
    assert _forests_identical(st_serial.forest, st_w1.forest)


def test_loop_and_scan_identical_forests(fast_cfg, sparse_data):
    """Same schedule + seeds -> the two execution forms agree exactly."""
    tr = Trainer(fast_cfg)
    sched = worker_round_robin(fast_cfg.n_trees, 8)
    st_loop = tr.train(sparse_data, sched, seed=0)
    st_scan, losses = tr.train_scan(sparse_data, sched, seed=0)
    assert np.array_equal(np.asarray(st_loop.f), np.asarray(st_scan.f))
    assert _forests_identical(st_loop.forest, st_scan.forest)
    assert losses.shape == (fast_cfg.n_trees,)
    assert float(losses[-1]) < float(losses[0])


def _decisive_data(n=256):
    """A dataset whose split gains are decisively separated, so tree choice
    cannot flip on ulp-level differences between compiled programs."""
    rng = np.random.default_rng(0)
    bins = rng.integers(0, 16, size=(n, 4)).astype(np.int32)
    y = 10.0 * (bins[:, 0] > 8) + 3.0 * (bins[:, 1] > 4)
    return BinnedData(
        bins=jnp.asarray(bins),
        bin_edges=jnp.zeros((4, 15), jnp.float32),
        labels=jnp.asarray(y, jnp.float32),
        multiplicity=jnp.ones((n,), jnp.float32),
        n_bins=16,
    )


def test_worker_parallel_exact_on_decisive_splits():
    """Batched worker-pool == per-round loop, tree for tree, when gains are
    decisive (deterministic sampling, full features)."""
    data = _decisive_data()
    cfg = SGBDTConfig(
        n_trees=12, step_length=0.5, sampling_rate=1.0, loss="mse",
        learner=LearnerConfig(depth=2, n_bins=16, feature_fraction=1.0),
    )
    st_loop = Trainer(cfg).train(data, ("round_robin", 4), seed=0)
    st_pool = train_worker_parallel(cfg, data, 4, seed=0)
    assert _forests_identical(st_loop.forest, st_pool.forest)
    np.testing.assert_allclose(
        np.asarray(st_loop.f), np.asarray(st_pool.f), atol=1e-5
    )


def test_worker_parallel_loss_equivalence(fast_cfg, sparse_data):
    """On realistic data, near-tied splits may resolve differently between
    the batched and per-round programs; the trained models must agree at
    the loss level."""
    st_loop = Trainer(fast_cfg).train(sparse_data, ("round_robin", 8), seed=0)
    st_pool = train_worker_parallel(fast_cfg, sparse_data, 8, seed=0)
    l_loop = float(train_loss(fast_cfg, sparse_data, st_loop))
    l_pool = float(train_loss(fast_cfg, sparse_data, st_pool))
    assert abs(l_loop - l_pool) < 0.02, (l_loop, l_pool)


def test_simulator_schedule_provider(fast_cfg, sparse_data):
    """A ClusterSpec is a schedule provider: the engine simulates it and
    trains on the realized k(j)."""
    spec = ClusterSpec(n_workers=8, t_build=0.1, t_comm=0.01, t_server=0.01)
    st = Trainer(fast_cfg).train(sparse_data, spec, seed=0)
    from repro.core.sgbdt import init_state

    l0 = float(train_loss(fast_cfg, sparse_data, init_state(fast_cfg, sparse_data)))
    l1 = float(train_loss(fast_cfg, sparse_data, st))
    assert l1 < 0.85 * l0


# --------------------------------------------------------------- schedules
def test_resolve_schedule_specs():
    np.testing.assert_array_equal(
        resolve_schedule(("constant", 3), 10), constant_delay(10, 3)
    )
    np.testing.assert_array_equal(
        resolve_schedule(("round_robin", 4), 10), worker_round_robin(10, 4)
    )
    np.testing.assert_array_equal(
        resolve_schedule(4, 10), worker_round_robin(10, 4)
    )
    np.testing.assert_array_equal(
        resolve_schedule(lambda n: constant_delay(n, 2), 10),
        constant_delay(10, 2),
    )
    explicit = worker_round_robin(10, 2)
    np.testing.assert_array_equal(resolve_schedule(explicit, 10), explicit)


def test_resolve_schedule_rejects_bad():
    with pytest.raises(ValueError):
        resolve_schedule(np.arange(5), 10)  # wrong length
    with pytest.raises(ValueError):
        resolve_schedule(np.arange(10) + 1, 10)  # k(j) > j
    with pytest.raises(ValueError):
        resolve_schedule(np.full(10, -1), 10)  # negative version
    with pytest.raises(ValueError):
        resolve_schedule(("warp", 3), 10)  # unknown closed form
    assert max_staleness(worker_round_robin(16, 4)) == 3


# ------------------------------------------------------- sharded histograms
_SHARD_SCRIPT = textwrap.dedent(
    """
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import json

    import jax
    import jax.numpy as jnp
    import numpy as np

    import repro.data as D
    from repro.kernels import ref
    from repro.launch.mesh import make_mesh
    from repro.ps.sharded import build_histogram_sharded, make_sharded_builder
    from repro.trees.learner import LearnerConfig, build_tree

    assert jax.device_count() == 8
    mesh = make_mesh((4, 2), ("data", "model"))

    key = jax.random.PRNGKey(0)
    k1, k2, k3, k4 = jax.random.split(key, 4)
    n, f, n_bins, n_nodes = 512, 16, 16, 4
    bins = jax.random.randint(k1, (n, f), 0, n_bins, dtype=jnp.int32)
    node = jax.random.randint(k2, (n,), -1, n_nodes, dtype=jnp.int32)
    grad = jax.random.normal(k3, (n,))
    hess = jax.random.uniform(k4, (n,))
    h_ref = ref.histogram_ref(bins, node, grad, hess, n_nodes, n_bins)
    h_sh = build_histogram_sharded(
        mesh, bins, node, grad, hess, n_nodes, n_bins, backend="ref"
    )
    hist_max_diff = float(jnp.max(jnp.abs(h_ref - h_sh)))

    cfg = LearnerConfig(depth=3, n_bins=64, feature_fraction=1.0)
    data = D.make_sparse_classification(512, 64, 8, seed=3)
    g = jax.random.normal(key, (512,))
    h = jnp.abs(jax.random.normal(k2, (512,))) + 0.1
    t0 = build_tree(cfg, data.bins, g, h, key)
    t1 = make_sharded_builder(cfg, mesh)(data.bins, g, h, key)
    results = {
        "hist_max_diff": hist_max_diff,
        "tree_feature_equal": bool(
            np.array_equal(np.asarray(t0.feature), np.asarray(t1.feature))
        ),
        "tree_threshold_equal": bool(
            np.array_equal(np.asarray(t0.threshold), np.asarray(t1.threshold))
        ),
        "leaf_max_diff": float(
            jnp.max(jnp.abs(t0.leaf_value - t1.leaf_value))
        ),
    }
    print("RESULTS_JSON=" + json.dumps(results))
    """
)


@pytest.fixture(scope="module")
def shard_results():
    proc = subprocess.run(
        [sys.executable, "-c", _SHARD_SCRIPT],
        capture_output=True, text=True, timeout=600,
        env={**os.environ, "PYTHONPATH": "src"},
    )
    for line in proc.stdout.splitlines():
        if line.startswith("RESULTS_JSON="):
            return json.loads(line.split("=", 1)[1])
    raise RuntimeError(f"subprocess failed:\n{proc.stderr[-3000:]}")


def test_sharded_histogram_matches_single_device(shard_results):
    """shard_map over a 4-shard 'data' axis + psum == the one-device kernel
    (disjoint sample subsets per cell, so partial sums compose exactly)."""
    assert shard_results["hist_max_diff"] < 1e-4, shard_results


def test_sharded_tree_build_matches_single_device(shard_results):
    assert shard_results["tree_feature_equal"], shard_results
    assert shard_results["tree_threshold_equal"], shard_results
    assert shard_results["leaf_max_diff"] < 1e-5, shard_results


# ------------------------------------------------------------ trainer cache
def test_trainer_cache_lru_bounded():
    """get_trainer must not leak one Trainer (plus its jit caches) per
    config forever across sweeps; the cache is LRU-bounded and clearable."""
    from repro.ps import clear_trainers, get_trainer
    from repro.ps.engine import _TRAINERS, _TRAINERS_MAX

    clear_trainers()
    cfgs = [
        SGBDTConfig(
            n_trees=5 + i, step_length=0.1, sampling_rate=0.8,
            learner=LearnerConfig(depth=2, n_bins=16),
        )
        for i in range(_TRAINERS_MAX + 4)
    ]
    trainers = [get_trainer(c) for c in cfgs]
    assert len(_TRAINERS) == _TRAINERS_MAX
    # most-recent configs hit the same instance; the oldest were evicted
    assert get_trainer(cfgs[-1]) is trainers[-1]
    assert get_trainer(cfgs[0]) is not trainers[0]
    # LRU recency: re-touching an entry protects it from the next eviction
    get_trainer(cfgs[-2])
    extra = SGBDTConfig(
        n_trees=99, step_length=0.1, sampling_rate=0.8,
        learner=LearnerConfig(depth=2, n_bins=16),
    )
    get_trainer(extra)
    assert cfgs[-2] in _TRAINERS
    clear_trainers()
    assert len(_TRAINERS) == 0


# ------------------------------------------------------- staleness-adaptive
def test_adaptive_step_serial_is_bitwise_fixed(fast_cfg, sparse_data):
    """tau = 0 everywhere => scale = 1/(1+6*rho*0) = exactly 1.0f, so the
    adaptive trainer on a serial schedule must reproduce the fixed-step
    forest bit for bit (the flag is free when there is no asynchrony)."""
    fixed = Trainer(fast_cfg).train_scan(sparse_data, ("round_robin", 1), seed=0)[0]
    adaptive = Trainer(fast_cfg._replace(adaptive_step=0.25)).train_scan(
        sparse_data, ("round_robin", 1), seed=0
    )[0]
    assert _forests_identical(fixed.forest, adaptive.forest)
    np.testing.assert_array_equal(np.asarray(fixed.f), np.asarray(adaptive.f))


def test_adaptive_step_rescues_aggressive_step_under_staleness(sparse_data):
    """The point of the 1/(1+6*rho*tau) rule: with an aggressive step and
    deep staleness, fixed-step async diverges toward garbage while the
    deflated step still converges. (At mild step lengths fixed wins — the
    rule is a safety valve, not a free lunch — so the test pins the regime
    the paper's Prop. 1 actually covers: step ~1, tau >> 1.)"""
    cfg = SGBDTConfig(
        n_trees=40, step_length=0.9, sampling_rate=0.8,
        learner=LearnerConfig(depth=4, n_bins=64),
    )
    schedule = ("constant", 12)
    fixed_state = Trainer(cfg).train_scan(sparse_data, schedule, seed=0)[0]
    adaptive_state = Trainer(cfg._replace(adaptive_step=0.1)).train_scan(
        sparse_data, schedule, seed=0
    )[0]
    fixed_loss = float(train_loss(cfg, sparse_data, fixed_state))
    adaptive_loss = float(train_loss(cfg, sparse_data, adaptive_state))
    assert adaptive_loss < fixed_loss * 0.75, (fixed_loss, adaptive_loss)
    # and the deflated run is actually good, not just "less bad"
    assert adaptive_loss < 0.45, adaptive_loss


# --------------------------------------------- 2D (data x feature) sharding
_SHARD2D_SCRIPT = textwrap.dedent(
    """
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import importlib.util
    import json
    import pathlib

    import jax
    import jax.numpy as jnp
    import numpy as np

    import repro.data as D
    from repro.core.sgbdt import init_state
    from repro import checkpoint
    from repro.launch.mesh import make_gbdt_mesh, make_mesh
    from repro.ps.engine import Trainer
    from repro.ps.runtime import RunTrace, replay_trace
    from repro.ps.sharded import (
        collective_bytes_per_build,
        make_sharded_builder,
        make_sharded_builder_2d,
    )
    from repro.trees import binning
    from repro.trees.learner import LearnerConfig, build_tree

    assert jax.device_count() == 8
    results = {}

    def same(a, b):
        return all(
            bool(np.array_equal(np.asarray(x), np.asarray(y)))
            for x, y in zip(a, b)
        )

    cfg = LearnerConfig(depth=3, n_bins=64)
    data = D.make_sparse_classification(512, 64, 8, seed=3)
    sp = binning.to_sparse(data.bins)
    key = jax.random.PRNGKey(0)
    k1, k2 = jax.random.split(key)
    g = jax.random.normal(k1, (512,))
    h = jnp.abs(jax.random.normal(k2, (512,))) + 0.1

    # (1, 4): feature-only sharding is BITWISE vs single-device (the data
    # psum is a size-1 identity; the argmax merge preserves first-max).
    t0 = build_tree(cfg, data.bins, g, h, key)
    mesh_14 = make_gbdt_mesh(1, 4)
    b14 = make_sharded_builder_2d(cfg, mesh_14)
    results["dense_2d_bitwise"] = same(t0, b14(data.bins, g, h, key))
    results["sparse_2d_bitwise"] = same(t0, b14(sp, g, h, key))

    # (2, 4) vs a plain 2-shard 1D mesh: identical data-psum structure,
    # so adding the feature axis changes NOTHING — bitwise incl. leaves.
    mesh_1d = make_mesh((2,), ("data",))
    t_1d = make_sharded_builder(cfg, mesh_1d)(data.bins, g, h, key)
    mesh_24 = make_gbdt_mesh(2, 4)
    t_24 = make_sharded_builder_2d(cfg, mesh_24)(data.bins, g, h, key)
    results["mesh_2x4_matches_1d_x2"] = same(t_1d, t_24)

    # 2x2 (data, feature) smoke through the Trainer
    cfg_t = __import__("repro.core.sgbdt", fromlist=["SGBDTConfig"]).SGBDTConfig(
        n_trees=4, loss="logistic",
        learner=LearnerConfig(depth=3, n_bins=64),
    )
    mesh_22 = make_gbdt_mesh(2, 2)
    st_22 = Trainer(cfg_t, mesh=mesh_22).train(data, ("round_robin", 1), seed=3)
    st_1d = Trainer(cfg_t, mesh=mesh_1d).train(data, ("round_robin", 1), seed=3)
    results["trainer_2x2_matches_1d_x2"] = same(
        jax.tree.leaves(st_22.forest), jax.tree.leaves(st_1d.forest)
    )
    results["trainer_2x2_finite"] = bool(np.isfinite(np.asarray(st_22.f)).all())

    # Realized collective bytes: argmax merge beats the dense-histogram
    # psum, sparse beats dense (trace-time accounting, nothing executes).
    results["bytes_1d"] = collective_bytes_per_build(
        cfg, mesh_1d, data.bins
    )["realized_bytes"]
    results["bytes_2d_dense"] = collective_bytes_per_build(
        cfg, mesh_14, data.bins, feature_axis="feature"
    )["realized_bytes"]
    results["bytes_2d_sparse"] = collective_bytes_per_build(
        cfg, mesh_14, sp, feature_axis="feature"
    )["realized_bytes"]

    # Golden-trace replay under the 2D mesh: the single-device replay of
    # the committed trace must reproduce bit-for-bit on dense AND sparse
    # representations, and match the committed forest as test_golden
    # holds it (split integers exact, leaves to 1e-6).
    golden = pathlib.Path("tests/golden")
    spec = importlib.util.spec_from_file_location("golden_regen", golden / "regen.py")
    regen = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(regen)
    gcfg, gdata = regen.golden_config(), regen.golden_data()
    gforest = checkpoint.restore_pytree(
        golden / "ckpt", regen.GOLDEN_STEP, init_state(gcfg, gdata), check_crc=True
    ).forest
    trace = RunTrace.load(golden / "run_trace.json")
    st_1, _ = replay_trace(gcfg, gdata, trace)
    results["golden_replay_matches_committed"] = bool(
        same((st_1.forest.feature, st_1.forest.threshold),
             (gforest.feature, gforest.threshold))
        and np.allclose(np.asarray(st_1.forest.leaf_value),
                        np.asarray(gforest.leaf_value), rtol=0, atol=1e-6)
    )
    st_g, _ = replay_trace(
        gcfg, gdata, trace, trainer=Trainer(gcfg, mesh=make_gbdt_mesh(1, 4))
    )
    results["golden_replay_2d_bitwise"] = same(
        jax.tree.leaves(st_g.forest), jax.tree.leaves(st_1.forest)
    )
    gdata_sp = gdata._replace(bins=binning.to_sparse(gdata.bins))
    st_gs, _ = replay_trace(
        gcfg, gdata_sp, trace, trainer=Trainer(gcfg, mesh=make_gbdt_mesh(1, 4))
    )
    results["golden_replay_2d_sparse_bitwise"] = same(
        jax.tree.leaves(st_gs.forest), jax.tree.leaves(st_1.forest)
    )

    print("RESULTS_JSON=" + json.dumps(results))
    """
)


@pytest.fixture(scope="module")
def shard2d_results():
    proc = subprocess.run(
        [sys.executable, "-c", _SHARD2D_SCRIPT],
        capture_output=True, text=True, timeout=900,
        env={**os.environ, "PYTHONPATH": "src"},
    )
    for line in proc.stdout.splitlines():
        if line.startswith("RESULTS_JSON="):
            return json.loads(line.split("=", 1)[1])
    raise RuntimeError(f"subprocess failed:\n{proc.stderr[-3000:]}")


def test_2d_feature_shard_bitwise_vs_single_device(shard2d_results):
    """(1, P_f): the merged-argmax split search preserves the first-max
    tie-break bitwise on dense and sparse representations."""
    assert shard2d_results["dense_2d_bitwise"], shard2d_results
    assert shard2d_results["sparse_2d_bitwise"], shard2d_results


def test_2d_mesh_matches_1d_at_same_data_shards(shard2d_results):
    """(P_d, P_f) == P_d-shard 1D bitwise incl. leaves: the feature axis
    adds only the argmax merge, which picks the identical split."""
    assert shard2d_results["mesh_2x4_matches_1d_x2"], shard2d_results
    assert shard2d_results["trainer_2x2_matches_1d_x2"], shard2d_results
    assert shard2d_results["trainer_2x2_finite"], shard2d_results


def test_2d_collective_bytes_reduced(shard2d_results):
    """The (L,)-sized argmax merge replaces the full (2, L, F, B) histogram
    psum; sparse drops the owner-masked partition psum too."""
    b1 = shard2d_results["bytes_1d"]
    b2 = shard2d_results["bytes_2d_dense"]
    bs = shard2d_results["bytes_2d_sparse"]
    assert b2 < b1 / 10, shard2d_results
    assert bs < b2, shard2d_results


def test_golden_trace_replays_under_2d_mesh(shard2d_results):
    """Record once, replay anywhere: the committed golden trace replays
    under the block-distributed 2D mesh bit-for-bit as on one device, and
    to the committed forest within test_golden's tolerance."""
    assert shard2d_results["golden_replay_matches_committed"], shard2d_results
    assert shard2d_results["golden_replay_2d_bitwise"], shard2d_results
    assert shard2d_results["golden_replay_2d_sparse_bitwise"], shard2d_results
