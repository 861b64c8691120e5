"""Run one benchmark cell once, on the chip it is started on.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell (``BENCHMARK.json`` ``workloads``) names a configuration,
``bench/configs/<config>.json``, and a traffic mix,
``bench/traffic/<traffic>.json``, whose ``driver`` is the generator in
``bench/drivers`` that reads it. The run makes its inputs from the seed on
the device, warms up every program the window uses (set-up), measures for
about ``--seconds``, checks what the timed path produced against the plain
reference in ``bench/reference.py``, and prints one JSON line last on
standard output. With ``--trace 0`` its metrics are the cell's end-to-end
metrics; with ``--trace 1`` the window runs under the profiler and its
metrics are the cell's per-layer metrics, each read by
``bench/metrics/<name>.py``. Each number the check compares is printed
beside its limit as the last lines on standard error and under ``check``,
the last key of the result line.

Without a TPU, or with fewer chips than the cell asks for, it exits
non-zero and prints no result.
"""
from __future__ import annotations

import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import pathlib  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

BENCH = pathlib.Path(__file__).resolve().parent
ROOT = BENCH.parent


class Context:
    """What a driver needs, and what it leaves for the per-layer readers."""

    def __init__(self, args, cell, config, traffic, spans, device):
        self.seed = args.seed
        self.seconds = args.seconds
        self.trace = bool(args.trace)
        self.cell, self.config, self.traffic = cell, config, traffic
        self.spans = spans
        self.device = device
        self.layer_inputs: dict = {}
        self.notes: dict = {}
        self.peaks = None
        self.trace_data = None
        self.trace_window = None
        self.memory_peak = None
        self.host_window = None
        self._trace_dir = None
        self.meter = None
        self.window_compiles = None

    def start_trace(self) -> None:
        """Called by a driver right before its window opens."""
        self._compiles_at_open = self.meter.compiles if self.meter else 0
        if self.trace:
            import jax

            from bench.common import profiler_options

            self._trace_dir = tempfile.mkdtemp(prefix="bench-trace-")
            jax.profiler.start_trace(self._trace_dir, profiler_options=profiler_options())

    def stop_trace(self) -> None:
        if self._trace_dir is not None and self.trace_data is None:
            import jax

            from bench import trace as TR

            jax.profiler.stop_trace()
            try:
                self.trace_data = TR.load(self._trace_dir)
            finally:
                shutil.rmtree(self._trace_dir, ignore_errors=True)

    def window(self, t0: float, t1: float) -> None:
        self.host_window = (t0, t1)
        if self.meter is not None:
            self.window_compiles = self.meter.compiles - self._compiles_at_open

    def read_memory(self) -> None:
        stats = self.device.memory_stats() or {}
        self.memory_peak = stats.get("peak_bytes_in_use")


def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def load_cell(name: str, overrides: dict | None = None):
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise SystemExit(f"unknown workload {name!r}; known: {sorted(cells)}")
    cell = cells[name]
    config = json.loads((BENCH / "configs" / f"{cell['config']}.json").read_text())
    traffic = json.loads((BENCH / "traffic" / f"{cell['traffic']}.json").read_text())
    for part, extra in (overrides or {}).items():
        target = {"config": config, "traffic": traffic}[part.split(".")[0]]
        keys = part.split(".")[1:]
        for k in keys[:-1]:
            target = target[k]
        target[keys[-1]] = extra
    return spec, cell, config, traffic


def metric_list(spec: dict, section: str, cell: str) -> list[dict]:
    return [m for m in spec[section] if cell in m.get("workloads", [cell])]


def passes(value, limit, op: str) -> bool:
    if not math.isfinite(float(value)):
        return False
    return value == limit if op == "==" else value <= limit


def run(argv=None, *, require_tpu: bool = True, overrides: dict | None = None,
        out=None) -> dict | None:
    """One run; returns the result line's object (None when refused)."""
    args = parse(argv)
    spec, cell, config, traffic = load_cell(args.workload, overrides)
    sys.path.insert(0, str(ROOT / "src"))
    import jax

    from bench.common import CompileMeter, Spans, peaks_for
    from repro.launch.compile_cache import enable_compile_cache

    devices = jax.devices()
    init_s = time.perf_counter() - T0  # interpreter, imports and backend start
    if require_tpu and (devices[0].platform != "tpu" or len(devices) < cell["chips"]):
        print(f"bench: needs {cell['chips']} TPU chip(s); JAX sees {len(devices)} "
              f"{devices[0].platform} device(s)", file=sys.stderr)
        return None
    enable_compile_cache()
    meter = CompileMeter()
    ctx = Context(args, cell, config, traffic, Spans(), devices[0])
    ctx.meter = meter
    if require_tpu:
        ctx.peaks = peaks_for(devices[0])
    driver = importlib.import_module(f"bench.drivers.{traffic['driver']}")
    res = driver.run(ctx)
    setup_s = res["setup_end"] - T0

    compared = res["compared"]
    correct = all(passes(v, lim, op) for _, v, lim, op in compared)
    device = {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
        "memory_peak_bytes": ctx.memory_peak,
    }
    result = {"correct": correct, "attempted": res["attempted"], "failed": res["failed"]}
    if not ctx.trace:
        values = dict(res["end_to_end"], setup_s=setup_s)
        result["metrics"] = {
            m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
            for m in metric_list(spec, "end_to_end", cell["name"])
        }
    else:
        from bench import trace as TR

        tr = ctx.trace_data
        first, last = res["trace_window"]
        lo = min(s.start_ns for s in tr.spans if s.name == first)
        hi = max(s.end_ns for s in tr.spans if s.name == last)
        ctx.trace_window = (lo, hi)
        device["busy_s"] = TR.device_busy_s(tr, lo, hi)
        device["window_s"] = (hi - lo) / 1e9
        result["metrics"] = {}
        for m in metric_list(spec, "per_layer", cell["name"]):
            from bench.common import load_module

            value = load_module("metrics", m["name"]).read(ctx)
            if value is not None:
                result["metrics"][m["name"]] = {"value": value, "unit": m["unit"]}
        result["breakdown"] = {
            "device_ops": TR.top_ops(tr, lo, hi, 10),
            "idle_gaps": TR.idle_gaps(tr, lo, hi, 10),
        }
    result["device"] = device
    if ctx.notes:
        result["notes"] = ctx.notes
    result["setup"] = {
        "setup_s": setup_s,
        "init": init_s,
        "compile_s": meter.compile_s,
        "cache_hits": meter.hits,
        "cache_misses": meter.misses,
        "compiles_in_window": ctx.window_compiles,
        **{name[len("bench.setup."):]: t1 - t0
           for name, t0, t1, _, _ in ctx.spans.records if name.startswith("bench.setup.")},
    }
    result["check"] = {name: {"value": v, "limit": lim, "pass": passes(v, lim, op)}
                       for name, v, lim, op in compared}
    for name, v, lim, op in compared:
        print(f"check {name} {v!r} {op} {lim!r}: "
              f"{'ok' if passes(v, lim, op) else 'FAIL'}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), file=out or sys.stdout, flush=True)
    return result


def main() -> int:
    return 0 if run(sys.argv[1:]) is not None else 1


if __name__ == "__main__":
    # The checkout's root, not bench/ (whose module names would shadow
    # top-level ones such as the standard library's ``trace``).
    sys.path[0] = str(ROOT)
    raise SystemExit(main())
