import jax
import numpy as np
import pytest

from bench import data as D
from bench.common import load_module
from bench.counts import realized

hist = load_module("counts", "histogram")
scan = load_module("counts", "split_scan")
round_ = load_module("counts", "round")
walk = load_module("counts", "forest_traversal")
wave = load_module("counts", "wave")

DENSE = {"layout": "dense", "n_rows": 100, "features": 4, "features_used": 3,
         "n_bins": 8, "depth": 2, "rows": [90, 40], "entries": [0, 0]}
SPARSE = dict(DENSE, layout="sparse", entries=[300, 120])


def test_histogram_dense_by_hand():
    # level 0: 90 rows x (3 one-byte bins + 8 bytes g,h) + 2 x 1 x 3 x 8 x 4
    # level 1: 40 rows x 11 + 2 x 1 (smaller child) x 3 x 8 x 4
    c = hist.count(DENSE)
    assert c["bytes"] == 90 * 11 + 192 + 40 * 11 + 192
    assert c["ops"] == 2 * 90 * 3 + 2 * 40 * 3


def test_histogram_sparse_by_hand():
    c = hist.count(SPARSE)
    assert c["bytes"] == (300 * 5 + 90 * 8 + 192) + (120 * 5 + 40 * 8 + 192)
    assert c["ops"] == 2 * 300 + 2 * 120


def test_split_scan_by_hand():
    c = scan.count(DENSE)  # levels hold 1 and 2 nodes of 3 x 8 bins
    assert c["bytes"] == 2 * 24 * 4 + 2 * 48 * 4
    assert c["ops"] == 12 * (24 + 48)


def test_round_adds_gradient_routing_and_fold():
    c = round_.count(DENSE)
    h, s = hist.count(DENSE), scan.count(DENSE)
    assert c["bytes"] == h["bytes"] + s["bytes"] + 100 * 20 + 100 * 2 + 100 * 12
    assert c["ops"] == h["ops"] + s["ops"] + 8 * 100 + 2 * 100 * 2 + 100


def test_traversal_and_wave_by_hand():
    c = walk.count(rows=10, features=4, trees=3, depth=2)
    assert c["bytes"] == 10 * 4 + 3 * (2 * 3 + 4 * 4) + 10 * 4
    assert c["ops"] == 10 * 3 * 3
    w = wave.count(rows=10, features=4, trees=3, depth=2, n_bins=8)
    assert w["bytes"] == 10 * 4 * 4 + 4 * 7 * 4 + c["bytes"]
    assert w["ops"] == 10 * 4 * 3 + c["ops"]


def test_realized_counts_by_hand():
    """Routing a two-level tree by hand gives the same drawn-row counts."""
    import jax.numpy as jnp

    from bench import reference as R
    from repro.core.sgbdt import SGBDTConfig
    from repro.trees.learner import LearnerConfig

    data = D.dense_dataset(3, 512, 4, 8)
    cfg = SGBDTConfig(n_trees=4, learner=LearnerConfig(depth=2, n_bins=8))
    feature = np.array([[1, 2, 3]], np.int32)
    threshold = np.array([[3, 5, 1]], np.int32)
    trace = type("T", (), {"key_index": np.array([2])})()
    got = realized.builds(data, cfg, 9, trace, feature, threshold)[0]
    key = jax.random.split(jax.random.PRNGKey(9), 4)[2]
    m, mask = R.draws(key, R.Learner(2, 8, 1.0, 1e-3, 1.0, 0.8, 0.01), data.multiplicity, 4)
    m, mask = np.asarray(m), np.asarray(
        jax.random.uniform(jax.random.split(key)[1], (4,)) < 0.8)
    bins = np.asarray(data.bins)
    drawn = m > 0
    right = bins[:, 1] > 3
    left_rows, right_rows = int(np.sum(drawn & ~right)), int(np.sum(drawn & right))
    assert got["rows"] == [int(drawn.sum()), min(left_rows, right_rows)]
    assert got["features_used"] == int(mask.sum())
    del jnp


@pytest.mark.parametrize("layout", ["dense", "sparse"])
def test_counts_do_not_depend_on_the_backend(layout):
    """The staged, fused and ref builds of one round give one count."""
    from bench.drivers.ps_train import sgbdt_config
    from repro.core.sgbdt import init_state
    from repro.ps.engine import propose_tree

    if layout == "dense":
        data = D.dense_dataset(5, 1024, 8, 16)
        depth, backends = 3, ("ref", "pallas", "fused")
    else:
        data = D.sparse_dataset(6, 1024, 4000, 6, 16, 16)
        depth, backends = 3, ("ref", "pallas")  # the fused program is dense only
    config = {"forest": {"trees": 4}, "data": {"n_bins": 16},
              "learner": {"depth": depth, "lam": 1.0, "min_child_hess": 1e-3,
                          "feature_fraction": 0.8, "sampling_rate": 0.8,
                          "step_length": 0.01, "loss": "logistic",
                          "step_kind": "gradient", "hist_mode": "subtract",
                          "backend": "ref"}}
    key = jax.random.split(jax.random.PRNGKey(11), 4)[1]
    trace = type("T", (), {"key_index": np.array([1])})()
    counts, trees = [], []
    for backend in backends:
        config["learner"]["backend"] = backend
        cfg = sgbdt_config(config)
        tree, _ = propose_tree(cfg, data, init_state(cfg, data).f, key)
        trees.append((np.asarray(tree.feature), np.asarray(tree.threshold)))
        c = realized.builds(data, cfg, 11, trace, trees[-1][0][None], trees[-1][1][None])[0]
        counts.append((hist.count(c), scan.count(c), round_.count(c)))
    for t in trees[1:]:
        assert np.array_equal(t[0], trees[0][0]) and np.array_equal(t[1], trees[0][1])
    assert all(c == counts[0] for c in counts[1:])
