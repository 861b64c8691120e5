"""CLI driver: ``PYTHONPATH=src python -m repro.analysis``.

Runs the four checkers, subtracts inline suppressions and the committed
baseline (``analysis_baseline.json`` at the repo root), prints the rest,
and exits non-zero when anything NEW is found. Modelled on the repo's
other ratchet gates (coverage floor, ``check_bench`` snapshot): the gate
only ever tightens, and loosening it is a reviewed one-line diff to the
baseline file.

    python -m repro.analysis                    # full run vs baseline
    python -m repro.analysis --only vmem        # one checker
    python -m repro.analysis --write-baseline   # accept current findings
    python -m repro.analysis --json out.json    # CI artifact
    python -m repro.analysis --selftest         # inject a violation,
                                                #   assert it is caught

``determinism`` needs the jax stack (it traces real jaxprs); the other
three are stdlib-only AST/JSON passes. When jax is absent — the lint-tier
runner — the determinism checker is skipped with a notice unless it was
requested by name, in which case the missing stack is an error.
"""
from __future__ import annotations

import argparse
import importlib
import json
import pathlib
import textwrap

from repro.analysis import findings as F

# name -> module (lazy-imported so `--only locks` never touches jax)
CHECKERS = ("determinism", "locks", "vmem", "lints")
NEEDS_JAX = {"determinism"}

BASELINE_NAME = "analysis_baseline.json"


def _repo_root() -> pathlib.Path:
    # src/repro/analysis/cli.py -> repo root
    return pathlib.Path(__file__).resolve().parents[3]


def run_checkers(
    root: pathlib.Path, only: list[str], *, explicit: bool
) -> tuple[list[F.Finding], list[str]]:
    """(findings, notices). Checkers the environment cannot run are
    skipped with a notice, unless the user named them (``explicit``)."""
    out: list[F.Finding] = []
    notices: list[str] = []
    for name in only:
        if name in NEEDS_JAX:
            try:
                importlib.import_module("jax")
            except ImportError:
                if explicit:
                    raise SystemExit(f"checker {name!r} needs jax, which is not installed")
                notices.append(f"skipped {name!r}: jax not installed (lint-tier run)")
                continue
        mod = importlib.import_module(f"repro.analysis.{name}")
        out.extend(mod.check_repo(root))
    return out, notices


def _sources_for(root: pathlib.Path, fs: list[F.Finding]) -> dict[str, list[str]]:
    sources: dict[str, list[str]] = {}
    for f in fs:
        if f.file in sources or not f.line:
            continue
        p = root / f.file
        if p.is_file():
            sources[f.file] = p.read_text().splitlines()
    return sources


def write_report(
    path: pathlib.Path,
    new: list[F.Finding],
    baselined: list[F.Finding],
    stale: list[str],
    notices: list[str],
) -> None:
    payload = {
        "new": [f.to_json() for f in new],
        "baselined": [f.to_json() for f in baselined],
        "stale_baseline_entries": stale,
        "notices": notices,
    }
    path.write_text(json.dumps(payload, indent=1) + "\n")


def selftest() -> int:
    """Inject one synthetic violation per stdlib checker and assert each
    is caught — proof the gate can actually fail (check_bench idiom)."""
    import tempfile

    from repro.analysis import lints, locks, vmem

    failures: list[str] = []

    def expect(name: str, got: list[F.Finding], code: str) -> None:
        if not any(f.code == code for f in got):
            failures.append(f"{name}: injected {code!r} was NOT flagged")

    with tempfile.TemporaryDirectory() as td:
        tdp = pathlib.Path(td)

        bad_lock = tdp / "bad_lock.py"
        bad_lock.write_text(
            textwrap.dedent(
                """\
                import threading
                lock = threading.Lock()
                shared = {}  # guarded-by: lock
                def worker():
                    shared["v"] = 1
                threading.Thread(target=worker).start()
                """
            )
        )
        expect("locks", locks.check_file(bad_lock, "bad_lock.py"), "unguarded-write")

        bad_spec = tdp / "bad_spec.py"
        bad_spec.write_text(
            "import jax.experimental.pallas as pl\n"
            "spec = pl.BlockSpec((1, 1), lambda i: (0, 0))\n"
        )
        expect("vmem", vmem.check_blockspecs(bad_spec, "bad_spec.py"), "blockspec-scalar")

        root = tdp / "repo"
        (root / "src" / "repro" / "core").mkdir(parents=True)
        (root / "benchmarks").mkdir()
        (root / "benchmarks" / "bad_bench.py").write_text(
            "def run(ops):\n    ops.histogram(interpret=True)\n"
        )
        (root / "src" / "repro" / "core" / "bad_rng.py").write_text(
            "import jax\nkey = jax.random.PRNGKey(0)\n"
        )
        got = lints.check_repo(root)
        expect("lints", got, "hardcoded-interpret")
        expect("lints", got, "prngkey-outside-ticket")

        # the baseline machinery itself: a baselined finding must not
        # count as new, an unlisted one must.
        fs = locks.check_file(bad_lock, "bad_lock.py")
        base = {fs[0].fingerprint: "selftest"}
        new, old, _ = F.split_by_baseline(fs, base)
        if new or len(old) != len(fs):
            failures.append("baseline: a baselined finding counted as new")
        new, _, _ = F.split_by_baseline(fs, {})
        if not new:
            failures.append("baseline: an unlisted finding did not count as new")

    if failures:
        for msg in failures:
            print(f"selftest FAILED: {msg}")
        return 1
    print("selftest ok: injected violations trip every stdlib checker "
          "and the baseline gate")
    return 0


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(
        prog="python -m repro.analysis",
        description="determinism / race / VMEM static analysis",
    )
    ap.add_argument(
        "--only", action="append", default=None, metavar="CHECKER",
        help=f"run a subset (repeatable; one of {', '.join(CHECKERS)})",
    )
    ap.add_argument(
        "--root", type=pathlib.Path, default=None,
        help="repo root to analyse (default: this checkout)",
    )
    ap.add_argument(
        "--baseline", type=pathlib.Path, default=None,
        help=f"baseline file (default: <root>/{BASELINE_NAME})",
    )
    ap.add_argument(
        "--write-baseline", action="store_true",
        help="accept the current findings as the new baseline and exit 0",
    )
    ap.add_argument(
        "--json", type=pathlib.Path, default=None, metavar="PATH",
        help="write the findings report as JSON (CI artifact)",
    )
    ap.add_argument(
        "--fail-on-new", action=argparse.BooleanOptionalAction, default=True,
        help="exit 1 when findings absent from the baseline exist (default)",
    )
    ap.add_argument(
        "--selftest", action="store_true",
        help="inject synthetic violations and assert the checkers fire",
    )
    args = ap.parse_args(argv)

    if args.selftest:
        return selftest()

    root = (args.root or _repo_root()).resolve()
    explicit = args.only is not None
    only = args.only or list(CHECKERS)
    for name in only:
        if name not in CHECKERS:
            ap.error(f"unknown checker {name!r} (have {', '.join(CHECKERS)})")

    try:
        raw, notices = run_checkers(root, only, explicit=explicit)
    except SystemExit:
        raise
    except Exception as e:  # a crashed checker must fail the gate loudly
        print(f"error: checker crashed: {type(e).__name__}: {e}")
        return 2

    fs = F.apply_suppressions(raw, _sources_for(root, raw))
    fs.sort(key=lambda f: (f.file, f.line, f.code))

    baseline_path = args.baseline or root / BASELINE_NAME
    if args.write_baseline:
        F.save_baseline(baseline_path, fs, "TODO: justify or fix")
        print(f"wrote {len(fs)} finding(s) to {baseline_path}")
        return 0

    baseline = F.load_baseline(baseline_path)
    new, baselined, stale = F.split_by_baseline(fs, baseline)

    for msg in notices:
        print(f"note: {msg}")
    for f in new:
        print(f.render())
    if baselined:
        print(f"{len(baselined)} baselined finding(s) "
              f"(justified in {baseline_path.name}):")
        for f in baselined:
            print(f"  [baselined] {f.render()}")
    for fp in stale:
        print(f"stale baseline entry (no longer produced — delete it): {fp}")

    if args.json:
        write_report(args.json, new, baselined, stale, notices)

    checked = ", ".join(only)
    if new:
        print(
            f"{len(new)} NEW finding(s) from [{checked}] — fix them, add "
            f"`# analysis: ignore[<code>]` with cause, or re-baseline via "
            f"--write-baseline and justify each entry"
        )
        return 1 if args.fail_on_new else 0
    print(f"analysis clean: [{checked}] — {len(baselined)} baselined, "
          f"{len(stale)} stale")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
