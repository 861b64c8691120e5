"""Snapshot gates for the trace-time collective-byte accounting and the
serving-latency record.

Usage:
    python benchmarks/check_bench.py --collectives \
        --baseline BENCH_collectives.json --fresh experiments/gbdt_collectives.json
    python benchmarks/check_bench.py --serve --baseline BENCH_serve.json \
        --fresh experiments/gbdt_serve.json [--max-regression 0.75]
    python benchmarks/check_bench.py --selftest

Each mode diffs a fresh run against its committed snapshot; ``--selftest``
injects regressions into the snapshots and asserts every gate trips.
"""
from __future__ import annotations

import argparse
import json
import pathlib
import sys


def compare_collectives(baseline: dict, fresh: dict) -> list[str]:
    """Gate the collective-bytes accounting rows (BENCH_collectives.json
    vs a fresh ``benchmarks.gbdt_roofline --collectives`` run).

    Unlike wall times, these numbers are TRACE-TIME accounting
    (``jax.eval_shape`` + a byte recorder) — fully deterministic on any
    host — so the gate is exact equality, not a tolerance band:
      * ``geometry`` rows must match exactly: numbers from another
        geometry are not comparable;
      * every ``bytes_*`` row must match the baseline bit-for-bit — a
        drift means the builder's collective structure changed, and the
        fresh snapshot must be recommitted deliberately;
      * ``reduction_dense`` must stay >= 10 on every row: the 2D
        argmax-merge exists to beat the dense histogram psum by at least
        an order of magnitude (DESIGN.md §16), and ``reduction_sparse``
        must not fall below ``reduction_dense``.
    """
    failures: list[str] = []
    if "smoke_16k_x_256" not in fresh.get("rows", {}):
        failures.append(
            "smoke_16k_x_256: the acceptance row is missing from the fresh "
            "run (even the quick config measures it)"
        )
    for name, base_row in baseline.get("rows", {}).items():
        row = fresh.get("rows", {}).get(name)
        if row is None:
            # quick runs measure only the acceptance row; the full-geometry
            # rows are gated whenever a --full run provides them
            continue
        if "error" in row:
            failures.append(f"{name}: fresh run errored: {row['error'][:200]}")
            continue
        if row.get("geometry") != base_row.get("geometry"):
            failures.append(
                f"{name}: geometry changed: baseline {base_row.get('geometry')} "
                f"vs fresh {row.get('geometry')} — if intentional, commit the "
                "fresh snapshot"
            )
            continue
        for key, base_val in base_row.items():
            if not key.startswith("bytes_"):
                continue
            if row.get(key) != base_val:
                failures.append(
                    f"{name}.{key}: {row.get(key)} vs baseline {base_val} "
                    "(trace-time accounting is deterministic — the collective "
                    "structure of the build changed)"
                )
        red = row.get("reduction_dense", 0.0)
        if red < 10.0:
            failures.append(
                f"{name}: reduction_dense {red:.1f}x < 10x — the argmax "
                "merge no longer beats the dense histogram psum"
            )
        if row.get("reduction_sparse", 0.0) < red:
            failures.append(
                f"{name}: reduction_sparse {row.get('reduction_sparse'):.1f}x "
                f"fell below reduction_dense {red:.1f}x"
            )
    return failures


def compare_serve(baseline: dict, fresh: dict, max_regression: float) -> list[str]:
    """Gate the serving-latency record (``BENCH_serve.json`` vs a fresh
    ``benchmarks.gbdt_serve`` run's ``gate`` object).

    Geometry (batch/trees/depth/dim/bins/SLO) must match exactly; every
    ``*_p99_ms`` row fails if it grew past the tolerance (p50 rows are
    informational — tail latency is the serving contract); and the
    continuous engine must have met its SLO on at least half the
    requests (a broken cut policy serves everything late, which runner
    jitter cannot explain away)."""
    gate = fresh.get("gate", fresh)
    failures: list[str] = []
    if baseline.get("geometry") != gate.get("geometry"):
        failures.append(
            f"geometry changed: baseline {baseline.get('geometry')} vs "
            f"fresh {gate.get('geometry')} — latencies are not comparable; "
            "if intentional, commit the fresh snapshot"
        )
        return failures
    for key, base_val in baseline.items():
        if not key.endswith("_p99_ms"):
            continue
        fresh_val = gate.get(key)
        if not isinstance(fresh_val, (int, float)):
            failures.append(f"{key}: missing from the fresh run")
            continue
        limit = (1.0 + max_regression) * float(base_val)
        if float(fresh_val) > limit:
            failures.append(
                f"{key}: {fresh_val:.2f}ms vs baseline {base_val:.2f}ms "
                f"(+{100 * (fresh_val / base_val - 1):.0f}%, limit "
                f"+{100 * max_regression:.0f}%)"
            )
    met = gate.get("engine_slo_met")
    if not isinstance(met, (int, float)) or met < 0.5:
        failures.append(
            f"engine_slo_met {met} < 0.5 — the continuous engine is not "
            "cutting waves inside its latency budget"
        )
    return failures


def selftest(max_regression: float) -> int:
    """Prove the gates trip: inject drifts and regressions into copies of
    the committed snapshots and assert each is rejected, and that each
    unmodified snapshot passes against itself."""
    coll = json.loads(
        (pathlib.Path(__file__).resolve().parents[1]
         / "BENCH_collectives.json").read_text()
    )
    if compare_collectives(coll, coll):
        print("selftest FAILED: collectives snapshot does not pass vs itself")
        return 1
    drift = json.loads(json.dumps(coll))
    first = next(iter(drift["rows"]))
    drift["rows"][first]["bytes_2d_argmax_merge"] += 4
    if not compare_collectives(coll, drift):
        print("selftest FAILED: a collective-bytes drift passed the gate")
        return 1
    weak = json.loads(json.dumps(coll))
    row = weak["rows"][first]
    row["bytes_2d_argmax_merge"] = row["bytes_1d_dense_psum"] // 2
    row["reduction_dense"] = 2.0
    if not any("reduction_dense" in f
               for f in compare_collectives(coll, weak)):
        print("selftest FAILED: a sub-10x argmax merge passed the gate")
        return 1
    serve = json.loads(
        (pathlib.Path(__file__).resolve().parents[1] / "BENCH_serve.json")
        .read_text()
    )
    if compare_serve(serve, serve, max_regression):
        print("selftest FAILED: serve snapshot does not pass vs itself")
        return 1
    slow_serve = dict(serve)
    for key, val in serve.items():
        if key.endswith("_p99_ms"):
            slow_serve[key] = 1.5 * float(val)
    if not compare_serve(serve, slow_serve, max_regression):
        print("selftest FAILED: a 1.5x serving-p99 regression passed the gate")
        return 1
    late = dict(serve)
    late["engine_slo_met"] = 0.1
    if not any("slo_met" in f for f in compare_serve(serve, late, max_regression)):
        print("selftest FAILED: a 10%-SLO-met engine passed the gate")
        return 1
    serve_geo = dict(serve)
    serve_geo["geometry"] = dict(serve["geometry"], batch=1)
    if not compare_serve(serve, serve_geo, max_regression):
        print("selftest FAILED: a serving geometry mismatch passed the gate")
        return 1

    print("selftest ok: collective-bytes drift trips, sub-10x reduction "
          "trips, serving p99/SLO/geometry injections trip, clean diffs pass")
    return 0


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--baseline", help="committed snapshot to gate against")
    ap.add_argument("--fresh", help="freshly measured snapshot (same schema)")
    ap.add_argument("--max-regression", type=float, default=0.25,
                    help="allowed fractional growth per serving _p99_ms row")
    mode = ap.add_mutually_exclusive_group(required=True)
    mode.add_argument("--selftest", action="store_true",
                      help="verify the gates trip on injected regressions")
    mode.add_argument("--collectives", action="store_true",
                      help="gate collective-bytes rows (exact match + >=10x "
                           "reduction)")
    mode.add_argument("--serve", action="store_true",
                      help="gate serving p99 latency + SLO attainment "
                           "(BENCH_serve.json vs a fresh gbdt_serve run)")
    args = ap.parse_args()
    if args.selftest:
        return selftest(args.max_regression)
    if not (args.baseline and args.fresh):
        ap.error("--baseline and --fresh are required with --collectives/--serve")
    baseline = json.loads(pathlib.Path(args.baseline).read_text())
    fresh = json.loads(pathlib.Path(args.fresh).read_text())
    if args.serve:
        failures = compare_serve(baseline, fresh, args.max_regression)
        if failures:
            print("serving latency gate FAILED:")
            for f in failures:
                print(f"  - {f}")
            return 1
        gate = fresh.get("gate", fresh)
        p99s = {k: f"{gate[k]:.1f}ms" for k in gate if k.endswith("_p99_ms")}
        print(f"serving latency gate ok (<= +{100 * args.max_regression:.0f}% "
              f"vs baseline, SLO met on {100 * gate['engine_slo_met']:.0f}%): "
              f"{p99s}")
        return 0
    failures = compare_collectives(baseline, fresh)
    if failures:
        print("collective-bytes gate FAILED:")
        for f in failures:
            print(f"  - {f}")
        return 1
    reds = {name: f"x{row['reduction_dense']:.0f}"
            for name, row in fresh.get("rows", {}).items()}
    print(f"collective-bytes gate ok (exact match, reductions {reds})")
    return 0


if __name__ == "__main__":
    sys.exit(main())
