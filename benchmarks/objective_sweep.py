"""Objective sweep: every registered objective family, serial vs async.

The ROADMAP's "open a new workload" check, runnable in CI: for each
objective (binary logistic, squared error, quantile, multiclass softmax,
pairwise LambdaRank) train a quick forest serially (W = 1) and under an
8-worker round-robin delay schedule, and record init/final loss plus the
objective's own metrics -> ``experiments/objective_sweep.json``.

The async column is the paper's validity claim generalized: bounded
staleness should not wreck per-round convergence on high-diversity data,
whatever the loss — multiclass rounds push K trees per update, ranking
targets are pairwise fields, and both ride the same PS engine.

    PYTHONPATH=src python -m benchmarks.objective_sweep [--full]
"""
from __future__ import annotations

import repro.data as D
from benchmarks.common import save
from repro.core.sgbdt import SGBDTConfig, init_state, train_metrics
from repro.ps import clear_trainers, get_trainer
from repro.trees.learner import LearnerConfig
from repro.launch.compile_cache import enable_compile_cache

WORKERS = 8


def sweep_cases(quick: bool):
    """(tag, objective spec, dataset, step length). The pinball step is
    smaller: its gradients have constant magnitude, so W stale pushes
    overshoot at steps the curvature-damped losses tolerate."""
    n = 800 if quick else 4_000
    return [
        ("binary", "logistic", D.make_sparse_classification(n, 200, 10, seed=7), 0.2),
        ("mse", "mse", D.make_sparse_regression(n, 300, 12, seed=9), 0.2),
        (
            "quantile",
            "quantile:0.5",
            D.make_sparse_regression(n, 300, 12, seed=9),
            0.05,
        ),
        (
            "multiclass3",
            "multiclass:3",
            D.make_multiclass_classification(n, 30, 3, seed=11),
            0.2,
        ),
        ("ranking", "lambdarank", D.make_ranking(n // 16, 16, 24, seed=13), 0.2),
    ]


def run(quick: bool = True) -> dict:
    n_trees = 60 if quick else 300
    out: dict = {"n_trees": n_trees, "workers": WORKERS, "objectives": {}}
    for tag, spec, data, step in sweep_cases(quick):
        cfg = SGBDTConfig(
            n_trees=n_trees,
            step_length=step,
            sampling_rate=0.8,
            objective=spec,
            learner=LearnerConfig(depth=4, n_bins=64, feature_fraction=0.9),
        )
        trainer = get_trainer(cfg)
        init_m = train_metrics(cfg, data, init_state(cfg, data))
        serial = train_metrics(cfg, data, trainer.train(data, ("round_robin", 1)))
        asynch = train_metrics(
            cfg, data, trainer.train(data, ("round_robin", WORKERS))
        )
        row = {
            "spec": spec,
            "n_outputs": cfg.obj.n_outputs,
            "init": {k: float(v) for k, v in init_m.items()},
            "serial": {k: float(v) for k, v in serial.items()},
            f"async_w{WORKERS}": {k: float(v) for k, v in asynch.items()},
        }
        out["objectives"][tag] = row
        print(
            f"  {tag:12s} loss {row['init']['loss']:.4f} -> "
            f"serial {row['serial']['loss']:.4f} / "
            f"async{WORKERS} {row[f'async_w{WORKERS}']['loss']:.4f}",
            flush=True,
        )
        assert row["serial"]["loss"] < row["init"]["loss"], tag
        assert row[f"async_w{WORKERS}"]["loss"] < row["init"]["loss"], tag
        # one config per objective — release its Trainer's compiled programs
        # instead of letting the sweep accumulate them.
        clear_trainers()
    save("objective_sweep", out)
    return out


def main(quick: bool = True):
    enable_compile_cache()
    return run(quick)


if __name__ == "__main__":
    import argparse

    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true")
    main(quick=not ap.parse_args().full)
