"""Shared pieces of the cell benchmark: compile metering, host spans on the
profiler's clock, the peaks table, and loading of per-name readers."""
from __future__ import annotations

import contextlib
import importlib.util
import json
import pathlib
import threading
import time

BENCH = pathlib.Path(__file__).resolve().parent


class CompileMeter:
    """Backend compile seconds and persistent-cache hits from JAX's
    monitoring events."""

    def __init__(self):
        import jax

        self.compile_s = 0.0
        self.hits = 0
        self.misses = 0
        self.compiles = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event: str, duration: float, **_) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.compile_s += duration
            self.compiles += 1

    def _event(self, event: str, **_) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1


class Spans:
    """Host spans the bench opens around its calls into the program: kept
    in memory with their host-clock times, and written into the profiler's
    trace as ``TraceAnnotation``s when one is recording."""

    def __init__(self):
        self._lock = threading.Lock()
        self.records: list[tuple[str, float, float, str, dict]] = []

    @contextlib.contextmanager
    def span(self, name: str, **info):
        import jax

        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation(name):
            yield info
        t1 = time.perf_counter()
        with self._lock:
            self.records.append((name, t0, t1, threading.current_thread().name, info))

    def wrap(self, name: str, fn):
        """``fn`` with every call inside a span named ``name``."""

        def wrapped(*args, **kwargs):
            with self.span(name):
                return fn(*args, **kwargs)

        return wrapped

    def named(self, name: str) -> list[tuple[str, float, float, str, dict]]:
        with self._lock:
            return [r for r in self.records if r[0] == name]


def peaks_for(device) -> dict:
    """The published peaks of ``device`` from ``peaks.json``. A device that
    is not in the table, a CPU among them, is an error."""
    table = json.loads((BENCH / "peaks.json").read_text())
    kind = getattr(device, "device_kind", None)
    if getattr(device, "platform", None) == "cpu" or kind not in table["devices"]:
        raise KeyError(f"no published peaks for device kind {kind!r} "
                       f"(platform {getattr(device, 'platform', None)!r})")
    return table["devices"][kind]


def least_time_s(ops: float, nbytes: float, peaks: dict, ops_peak_key: str) -> tuple[float, str]:
    """(least seconds, the bound that decides it) for work at the peaks."""
    t_ops = ops / peaks[ops_peak_key]
    t_bytes = nbytes / peaks["hbm_bytes_per_s"]
    return (t_ops, "ops") if t_ops >= t_bytes else (t_bytes, "bytes")


def load_module(kind: str, name: str):
    """``bench/<kind>/<name>.py`` as a module (names may hold dots)."""
    path = BENCH / kind / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"bench_{kind}_{name}", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def profiler_options():
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0  # the Python tracer makes traces 1000x larger
    opts.host_tracer_level = 1  # keeps TraceAnnotation spans
    opts.enable_hlo_proto = False
    return opts
