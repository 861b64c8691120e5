"""Reduction of the program's own spans and named scopes in a profiler trace.

``bench/trace.py`` reads the device planes and the bench's own ``bench.*``
spans. This module reads, from the same ``.xplane.pb``, what the program
leaves there when ``repro.obs`` tracing is on, and what it carries in its
compiled programs:

- host spans whose names start with ``ps.``, ``serve.`` or ``host.`` (and
  the bench's ``bench.`` ones), with their attributes (``TraceAnnotation``
  keyword arguments arrive as event stats);
- the ``XLA Modules`` line of the first ``/device:TPU:n`` plane: one event
  per program run, named ``jit_<function>(<fingerprint>)``;
- the ``XLA Ops`` line, each operation named by its HLO instruction.

A v5e trace carries no scope on an operation's event (its stats are the
device offset and duration only), so an operation is attributed to a
named scope through the compiled program's HLO text: ``scopes`` maps each
instruction to the innermost scope of its ``metadata={op_name=...}``.
Instruction names repeat across programs, so only operations that run
inside one of the program's own ``XLA Modules`` events are attributed.

The readings (each None when the trace or the records hold nothing to
read, as on a program without ``repro.obs``):

- ``build_device_ms``: device milliseconds of ``jit_propose`` runs in the
  window per ``ps.build`` span that overlaps it;
- ``scoped_ms``: device milliseconds of the propose program's operations
  under the given scopes (``child_counts``, ``leaf_sums``) per build;
- ``wave_fill``: real rows over real plus padding rows of the window's
  ``serve.wave`` spans, in %;
- ``wave_host_ms``: median over the window's waves of the ``serve.wave``
  span less the device time of the ``jit_predict`` runs inside it;
- ``hold_share``: summed ``serve.hold`` records (``repro.obs``, host
  clock) over the window, in %.
"""
from __future__ import annotations

import dataclasses
import glob
import os
import re
import statistics
from collections import defaultdict

from bench import trace as TR

PREFIXES = ("bench.", "ps.", "serve.", "host.")
MODULES_LINE = "XLA Modules"
# The scopes the program names (trees/learner.py, ps/engine.py).
SCOPES = re.compile(r"^(level\d+|child_counts|histogram|split|partition|leaf_sums|"
                    r"sample|gradient|build|delta|fold)$")


@dataclasses.dataclass(frozen=True)
class Span(TR.Event):
    attrs: dict = dataclasses.field(default_factory=dict, compare=False)


@dataclasses.dataclass
class ProgramTrace:
    ops: list  # [TR.Event] XLA Ops of the first device plane, by start
    modules: list  # [TR.Event] XLA Modules runs, named without fingerprint
    spans: list  # [Span] host spans with one of PREFIXES, by start
    async_ops: list = dataclasses.field(default_factory=list)  # Async XLA Ops


def module_name(event_name: str) -> str:
    """``jit_propose(6646509240411455201)`` -> ``jit_propose``."""
    return event_name.split("(", 1)[0]


def load(path: str) -> ProgramTrace:
    """Read an ``.xplane.pb`` file, or the newest one under a directory."""
    from jax.profiler import ProfileData

    if os.path.isdir(path):
        found = sorted(glob.glob(os.path.join(path, "**", "*.xplane.pb"),
                                 recursive=True), key=os.path.getmtime)
        if not found:
            raise FileNotFoundError(f"no .xplane.pb under {path}")
        path = found[-1]
    pd = ProfileData.from_file(path)
    ops, modules, spans, async_ops = [], [], [], []
    device_seen = False
    for plane in pd.planes:
        if TR.is_device_plane(plane.name):
            if device_seen:
                continue
            device_seen = True
            for line in plane.lines:
                into = {TR.OPS_LINE: ops, TR.ASYNC_LINE: async_ops}.get(line.name)
                if into is not None:
                    into += [TR.Event(TR.op_name(e.name), e.start_ns,
                                      e.start_ns + e.duration_ns) for e in line.events]
                elif line.name == MODULES_LINE:
                    modules += [TR.Event(module_name(e.name), e.start_ns,
                                         e.start_ns + e.duration_ns) for e in line.events]
        else:
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(PREFIXES):
                        spans.append(Span(e.name, e.start_ns, e.start_ns + e.duration_ns,
                                          dict(e.stats)))
    key = lambda e: e.start_ns  # noqa: E731
    return ProgramTrace(sorted(ops, key=key), sorted(modules, key=key),
                        sorted(spans, key=key), sorted(async_ops, key=key))


def scopes(hlo_text: str) -> dict[str, str]:
    """{instruction name: innermost named scope} from a compiled program's
    HLO text; instructions outside every scope are left out."""
    out = {}
    for line in hlo_text.splitlines():
        m = re.match(r'\s*(?:ROOT )?%([^ ]+) = .*op_name="([^"]*)"', line)
        if not m:
            continue
        inner = [part for part in m.group(2).split("/") if SCOPES.match(part)]
        if inner:
            out[m.group(1)] = inner[-1]
    return out


def _inside(events, spans) -> list:
    """The events that start inside one of ``spans`` (both sorted)."""
    out, k = [], 0
    for e in events:
        while k < len(spans) and spans[k].end_ns <= e.start_ns:
            k += 1
        if k < len(spans) and spans[k].start_ns <= e.start_ns:
            out.append(e)
    return out


def _named_in(spans, name: str, lo: float, hi: float) -> list:
    """The spans named ``name`` that overlap [lo, hi]: a build whose span
    opens just before the window's first build call still counts."""
    return [s for s in spans if s.name == name and s.start_ns < hi and s.end_ns > lo]


def build_device_ms(pt: ProgramTrace, lo: float, hi: float,
                    module: str = "jit_propose", span: str = "ps.build") -> float | None:
    builds = _named_in(pt.spans, span, lo, hi)
    runs = [e for e in TR.clip(pt.modules, lo, hi) if e.name == module]
    if not builds or not runs:
        return None
    return sum(e.dur_ns for e in runs) / len(builds) / 1e6


def scoped_ms(pt: ProgramTrace, op_scopes: dict, wanted: set, lo: float, hi: float,
              module: str = "jit_propose", span: str = "ps.build") -> float | None:
    """Device milliseconds per ``span`` of ``module``'s operations whose
    innermost scope is in ``wanted``."""
    builds = _named_in(pt.spans, span, lo, hi)
    runs = [e for e in pt.modules if e.name == module]
    if not builds or not runs or not op_scopes:
        return None
    ops = _inside(TR.clip(pt.ops, lo, hi), runs)
    total = sum(e.dur_ns for e in ops if op_scopes.get(e.name) in wanted)
    return total / len(builds) / 1e6


def scope_totals(pt: ProgramTrace, op_scopes: dict, lo: float, hi: float,
                 module: str = "jit_propose") -> dict[str, float]:
    """{scope: device seconds} of ``module``'s operations in the window,
    ``-`` for operations outside every scope."""
    runs = [e for e in pt.modules if e.name == module]
    totals: dict[str, float] = defaultdict(float)
    for e in _inside(TR.clip(pt.ops, lo, hi), runs):
        totals[op_scopes.get(e.name, "-")] += e.dur_ns / 1e9
    return dict(totals)


def wave_fill(pt: ProgramTrace, lo: float, hi: float) -> float | None:
    waves = _named_in(pt.spans, "serve.wave", lo, hi)
    rows = sum(int(s.attrs.get("rows", 0)) for s in waves)
    slots = rows + sum(int(s.attrs.get("pad", 0)) for s in waves)
    return 100.0 * rows / slots if slots else None


def wave_host_ms(pt: ProgramTrace, lo: float, hi: float,
                 module: str = "jit_predict") -> float | None:
    waves = _named_in(pt.spans, "serve.wave", lo, hi)
    if not waves:
        return None
    runs = [e for e in pt.modules if e.name == module]
    host = [(w.dur_ns - sum(e.dur_ns for e in TR.clip(runs, w.start_ns, w.end_ns))) / 1e6
            for w in waves]
    return statistics.median(host)


def hold_share(records, t0: float, t1: float) -> float | None:
    """Summed ``serve.hold`` records (``repro.obs``, ``perf_counter``
    seconds) inside [t0, t1] over the window, in %."""
    holds = [(max(r.t0, t0), min(r.t1, t1)) for r in records if r.name == "serve.hold"]
    if not holds:
        return None
    return 100.0 * sum(max(b - a, 0.0) for a, b in holds) / (t1 - t0)


def idle_gaps(pt: ProgramTrace, lo: float, hi: float, k: int = 10) -> list[list]:
    """[[host span, idle seconds], ...]: device-idle time inside the window
    summed by the innermost span of any prefix covering it (the program's
    where it has one, else the bench's)."""
    plane = "/device:TPU:0"
    whole = TR.Trace(device_ops={plane: pt.ops}, spans=pt.spans,
                     device_async={plane: pt.async_ops})
    return TR.idle_gaps(whole, lo, hi, k)
