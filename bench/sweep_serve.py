"""Sweep the offered rate of a serving cell once, on the chip, to find its
knee: the highest rate at which the p95 latency stays within the engine's
SLO and the backlog does not grow. The cell's traffic file then states a
fixed rate below it.

    python3 bench/sweep_serve.py --workload higgs.serve --seconds 10 \
        --rates 50,100,200,400

Each rate runs the cell's whole path (set-up, window, output check) in this
one process, and prints one JSON line.
"""
from __future__ import annotations

import argparse
import io
import json
import pathlib
import sys

sys.path[0] = str(pathlib.Path(__file__).resolve().parents[1])

from bench import run as B  # noqa: E402


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", default="higgs.serve")
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seed", type=int, default=7)
    args = ap.parse_args()
    for rate in (float(r) for r in args.rates.split(",")):
        buf = io.StringIO()
        res = B.run(["--workload", args.workload, "--seed", str(args.seed),
                     "--seconds", str(args.seconds), "--trace", "0"],
                    overrides={"traffic.rate_per_s": rate}, out=buf)
        if res is None:
            return 1
        m = res["metrics"]
        print(json.dumps({"rate_per_s": rate, "correct": res["correct"],
                          "failed": res["failed"],
                          "serve_p95_ms": m["serve_p95_ms"]["value"],
                          "serve_rows_per_s": m["serve_rows_per_s"]["value"]}), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
