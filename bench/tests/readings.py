"""Readings that the output check's limits are set from, on the chip, at
each cell's own size, in one process:

    python3 bench/tests/readings.py --workload higgs.train --seeds 12 --control-seeds 3

- program: the cell's whole run (set-up, a short window, the check) on each
  seed; its compared numbers are the lower readings;
- control: for a training cell the reference computed in bfloat16, put in
  the program's place; for a serving cell the program's own fp16 forest
  path (``quantize="fp16"``) under the same traffic;
- faults (training cells): half of the batch left out, and the first
  tree's first leaf altered, both planted in the reference put in the
  program's place; a fold that leaves the state unchanged reads 1 by the
  leaf measure and needs no run.

One JSON line per reading.
"""
from __future__ import annotations

import argparse
import io
import json
import pathlib
import sys

sys.path[0] = str(pathlib.Path(__file__).resolve().parents[2])

from bench import run as B  # noqa: E402

BASE_SEED = 1_000_003_000


def program_readings(cell: str, seeds, seconds: float, overrides=None):
    for seed in seeds:
        res = B.run(["--workload", cell, "--seed", str(seed), "--seconds", str(seconds),
                     "--trace", "0"], overrides=overrides, out=io.StringIO())
        if res is None:
            raise SystemExit("no TPU")
        yield seed, {k: v["value"] for k, v in res["check"].items()}


def training_controls(cell: str, seeds):
    import jax
    import jax.numpy as jnp

    from bench import data as D
    from bench import reference as R

    _, _, config, traffic = B.load_cell(cell)
    ln = R.learner_from_config(config)
    w = traffic["workers"]
    n = config["train"]["check_folds"]
    # A schedule the runtime realizes on one chip: the first W builds all
    # read the initial prediction.
    schedule = [max(0, j - w + 1) if j >= w else 0 for j in range(n)]
    for seed in seeds:
        data = D.make_dataset(seed, config)
        bins = R.as_reference_bins(data.bins)
        key = jax.random.PRNGKey(D.program_seed(seed))
        args = (bins, data.labels, data.multiplicity, key, config["forest"]["trees"],
                schedule, list(range(n)), ln)
        ref = R.replay(*args)
        rows = data.labels.shape[0]
        out = {
            "control_bf16": R.compare_trees(*R.replay(*args, dtype=jnp.bfloat16), *ref),
            "half_batch": R.compare_trees(
                *R.replay(*args, row_filter=(jnp.arange(rows) % 2) == 0), *ref),
        }
        feat, thr, leaf = ref
        altered = leaf.copy()
        altered[0, 0] += 1.0
        out["altered_answer"] = R.compare_trees(feat, thr, altered, *ref)
        del data, bins
        yield seed, out


def serving_control(cell: str, seeds, seconds: float):
    from repro.serving import continuous

    add = continuous.ForestEngine.add_version

    def fp16(self, name, forest, **kw):
        return add(self, name, forest, **dict(kw, quantize="fp16"))

    continuous.ForestEngine.add_version = fp16
    try:
        yield from program_readings(cell, seeds, seconds)
    finally:
        continuous.ForestEngine.add_version = add


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--control-seeds", type=int, default=3)
    ap.add_argument("--seconds", type=float, default=1.0)
    args = ap.parse_args()
    _, cell, _, traffic = B.load_cell(args.workload)
    seeds = [BASE_SEED + 7919 * i for i in range(args.seeds)]
    for seed, numbers in program_readings(args.workload, seeds, args.seconds):
        print(json.dumps({"kind": "program", "seed": seed, **numbers}), flush=True)
    controls = seeds[: args.control_seeds]
    if traffic["driver"] == "open_loop":
        for seed, numbers in serving_control(args.workload, controls, args.seconds):
            print(json.dumps({"kind": "control_fp16", "seed": seed, **numbers}), flush=True)
    else:
        for seed, out in training_controls(args.workload, controls):
            for kind, numbers in out.items():
                print(json.dumps({"kind": kind, "seed": seed, **numbers}), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
