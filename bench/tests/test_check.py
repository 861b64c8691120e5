"""The output check against its control and the faults it must catch.

The control is the plain reference computed in bfloat16, put in the
program's place; the faults are planted in the timed path under a whole
run of the harness (its look for a chip skipped) at a size the CPU holds:
a fold that returns the state unchanged, half of the batch left out, and
an answer altered where it is produced. Each must turn ``correct`` false,
and the same run with nothing planted must keep it true.
"""
from __future__ import annotations

import io
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import data as D
from bench import reference as R
from bench import run as B

SEED = 2**33 + 101
# Training runs at sizes the CPU holds: the dense cell as configured, and
# the same cell over a real-sim-like sparse set, which keeps the harness's
# sparse layout (data, reference, counts) checked for a later cell.
SPARSE = {"config.data.layout": "sparse", "config.data.rows": 4000,
          "config.data.features": 12000, "config.data.nnz_per_row": 12,
          "config.data.feature_ell_width": 32, "config.learner.depth": 6,
          "config.check.leaf_gap": 1e-3, "config.train.round_s_nominal": 0.1}
TRAIN = {
    "dense": ("higgs.train", {"config.data.rows": 32768,
                              "config.train.round_s_nominal": 0.05}),
    "sparse": ("higgs.train", SPARSE),
}
SERVE = {"traffic.rate_per_s": 20.0}
# The control at a size whose reference accumulates over as many row
# blocks as at the published size (11M rows in blocks of 2^15 is 336), so
# the bfloat16 running sums grow about as deep as there: (cell, overrides,
# row block). Below about half a million rows the bfloat16 control keeps the
# roots' splits on some seeds and its leaf gap falls under the limit.
CONTROL = {
    "dense": ("higgs.train", {"config.data.rows": 524288}, 2048),
    "sparse": ("higgs.train", SPARSE, 64),
}


def run_cell(cell, overrides, seconds=1.0):
    buf = io.StringIO()
    res = B.run(["--workload", cell, "--seed", str(SEED), "--seconds", str(seconds),
                 "--trace", "0"], require_tpu=False, overrides=overrides, out=buf)
    assert json.loads(buf.getvalue().strip().splitlines()[-1]) == json.loads(json.dumps(res))
    return res


@pytest.mark.parametrize("layout", sorted(CONTROL))
def test_control_in_bfloat16_fails(layout):
    cell, overrides, block = CONTROL[layout]
    _, _, config, _ = B.load_cell(cell, overrides)
    data = D.make_dataset(SEED, config)
    ln = R.learner_from_config(config)
    bins = R.as_reference_bins(data.bins)
    key = jax.random.PRNGKey(D.program_seed(SEED))
    args = (bins, data.labels, data.multiplicity, key, config["forest"]["trees"],
            [0, 0, 1], [0, 1, 2], ln)
    ref = R.replay(*args, block=block)
    control = R.replay(*args, dtype=jnp.bfloat16, block=block)
    numbers = R.compare_trees(*control, *ref)
    assert numbers["leaf_gap"] > config["check"]["leaf_gap"], numbers


@pytest.mark.parametrize("layout", sorted(TRAIN))
def test_sound_training_run_is_correct(layout):
    assert run_cell(*TRAIN[layout])["correct"]


def _unchanged_fold(cfg, forest, f, tree, delta):
    return forest, f


def _half_batch(bernoulli_weights):
    def draw(rng, rate, multiplicity):
        m, q = bernoulli_weights(rng, rate, multiplicity)
        keep = (jnp.arange(m.shape[0]) % 2) == 0
        return jnp.where(keep, m, 0.0), q & keep
    return draw


def _altered_leaf(build_tree):
    def build(*args, **kwargs):
        tree = build_tree(*args, **kwargs)
        return tree._replace(leaf_value=tree.leaf_value.at[0].add(1.0))
    return build


@pytest.mark.parametrize("fault", ["unchanged_state", "half_batch", "altered_answer"])
@pytest.mark.parametrize("layout", sorted(TRAIN))
def test_training_faults_fail(monkeypatch, layout, fault):
    import repro.ps.engine as engine
    import repro.ps.runtime as runtime

    if fault == "unchanged_state":
        monkeypatch.setattr(runtime, "server_fold", _unchanged_fold)
    elif fault == "half_batch":
        monkeypatch.setattr(engine, "bernoulli_weights", _half_batch(engine.bernoulli_weights))
    else:
        monkeypatch.setattr(engine, "build_tree", _altered_leaf(engine.build_tree))
    assert not run_cell(*TRAIN[layout])["correct"]


def test_sound_serving_run_is_correct():
    res = run_cell("higgs.serve", SERVE, seconds=2.0)
    assert res["correct"] and res["failed"] == 0


@pytest.mark.parametrize("fault", ["half_batch", "altered_answer"])
def test_serving_faults_fail(monkeypatch, fault):
    import repro.kernels.ops as ops

    traverse = ops.forest_traverse

    def broken(bins, *args, **kwargs):
        out = traverse(bins, *args, **kwargs)
        if fault == "half_batch":
            return out.at[out.shape[0] // 2:].set(0.0)
        return out.at[0].add(1e-3)

    monkeypatch.setattr(ops, "forest_traverse", broken)
    assert not run_cell("higgs.serve", SERVE, seconds=2.0)["correct"]


def test_no_chip_no_result(capsys):
    assert B.run(["--workload", "higgs.serve", "--seed", "1", "--seconds", "1"]) is None
    assert capsys.readouterr().out == ""
