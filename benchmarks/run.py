"""Benchmark entrypoint — one harness per paper table/figure + roofline.

    PYTHONPATH=src python -m benchmarks.run [--full] [--only fig10,...]

Artifacts land in experiments/*.json; summaries print as they finish.
"""
from __future__ import annotations

import argparse
import pathlib
import time


BENCHES = [
    ("fig5_fig6", "benchmarks.fig5_fig6_convergence",
     "convergence vs workers (Figs. 5-6)"),
    ("fig7_fig8", "benchmarks.fig7_fig8_sampling_sensitivity",
     "sampling-rate sensitivity (Figs. 7-8)"),
    ("fig9", "benchmarks.fig9_extreme_sampling",
     "extreme small sampling rate (Fig. 9)"),
    ("fig10", "benchmarks.fig10_speedup",
     "speedup vs fork-join baselines (Fig. 10 / Eq. 13)"),
    ("ablation_newton", "benchmarks.ablation_newton",
     "gradient vs Newton steps under staleness (paper conclusion 2)"),
    ("ablation_prop1", "benchmarks.ablation_prop1",
     "max stable step length vs staleness (Prop. 1 law)"),
    ("gbdt_roofline", "benchmarks.gbdt_roofline",
     "distributed GBDT step roofline (16x16 mesh)"),
    ("roofline", "benchmarks.roofline",
     "arch-zoo roofline from dry-run artifacts"),
]


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true",
                    help="paper-scale runs (quick mode is the default)")
    ap.add_argument("--only", default=None,
                    help="comma-separated bench names")
    args = ap.parse_args()
    only = set(args.only.split(",")) if args.only else None

    import importlib

    # Harnesses assume the artifact sink exists even before the first
    # save(); cheap to guarantee here (e.g. a fresh clone, a CI runner).
    pathlib.Path("experiments").mkdir(parents=True, exist_ok=True)

    t00 = time.time()
    failures = []
    skipped = []
    for name, module, desc in BENCHES:
        if only and name not in only:
            continue
        print(f"\n=== {name}: {desc} ===", flush=True)
        t0 = time.time()
        try:
            importlib.import_module(module).main(quick=not args.full)
        except ImportError as e:
            # Optional deps (plotting, profiling) missing from the host is
            # not a benchmark failure — record the skip and keep going.
            skipped.append(name)
            print(f"  SKIPPED: missing dependency "
                  f"({getattr(e, 'name', None) or e})")
        except Exception as e:  # noqa: BLE001 — keep the suite running
            failures.append(name)
            print(f"  FAILED: {type(e).__name__}: {e}")
        print(f"  ({time.time() - t0:.1f}s)", flush=True)
    print(f"\nall benchmarks done in {time.time() - t00:.1f}s; "
          f"skipped: {skipped or 'none'}; failures: {failures or 'none'}")
    if failures:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
