"""Reduction of a profiler trace to the benchmark's device numbers.

Input is the ``.xplane.pb`` that ``jax.profiler`` writes, read with
``jax.profiler.ProfileData``. Device planes are those named
``/device:<accelerator>:<i>``; their ``XLA Ops`` line holds one event per
operation that ran, named by its HLO text (``%histogram_pallas.9 = ...``),
and their ``Async XLA Ops`` line the asynchronous copies and slices. Host
spans are ``TraceAnnotation`` events that the bench itself opened around
its calls into the program, all named with the ``bench.`` prefix, on the
host plane and on the same clock.

- busy: the union of the intervals of both lines' operations on each
  device inside the window, averaged over devices;
- kernel time: the summed device durations of ``XLA Ops`` operations
  whose HLO name (before `` = ``, without ``%`` and the ``.N`` suffix) is
  one of a kernel's names. The kernels carry no ``name=`` yet, so the
  names are those the trace shows: the jitted wrapper of each
  ``pallas_call``, listed in ``KERNELS``;
- idle gaps: every interval inside the window in which no operation runs,
  named by the innermost bench span that covers its midpoint.
"""
from __future__ import annotations

import dataclasses
import glob
import os
from collections import defaultdict

OPS_LINE = "XLA Ops"
ASYNC_LINE = "Async XLA Ops"
SPAN_PREFIX = "bench."

# The HLO names each kernel's operations carry in the trace. A later change
# that names its pallas_calls adds the stable name here.
KERNELS = {
    "histogram": ("histogram_pallas",),
    "histogram_sparse": ("histogram_sparse_pallas",),
    "split_scan": ("split_gain_pallas",),
    "level_build": ("level_build_pallas",),
    "forest_traversal": ("forest_traverse_pallas",),
}


def op_name(hlo_text: str) -> str:
    """``%histogram_pallas.9 = f32[...] custom-call(...)`` -> ``histogram_pallas.9``."""
    return hlo_text.split(" = ", 1)[0].lstrip("%")


def base_name(name: str) -> str:
    """``histogram_pallas.9`` -> ``histogram_pallas``."""
    head, _, tail = name.rpartition(".")
    return head if head and tail.isdigit() else name


@dataclasses.dataclass(frozen=True)
class Event:
    name: str
    start_ns: float
    end_ns: float

    @property
    def dur_ns(self) -> float:
        return self.end_ns - self.start_ns


@dataclasses.dataclass
class Trace:
    device_ops: dict  # device plane name -> [Event] sorted by start
    spans: list  # [Event] bench host spans
    device_async: dict = dataclasses.field(default_factory=dict)

    def window(self, name: str = "bench.window") -> tuple[float, float]:
        hits = [s for s in self.spans if s.name == name]
        if not hits:
            raise ValueError(f"no {name!r} span in the trace")
        return min(s.start_ns for s in hits), max(s.end_ns for s in hits)


def is_device_plane(name: str) -> bool:
    """``/device:TPU:0`` and the like; not the host, and not the profiler's
    ``/device:CUSTOM:...`` planes, which hold no operations."""
    parts = name.split(":")
    return (len(parts) == 3 and parts[0] == "/device" and parts[1] in ("TPU", "GPU")
            and parts[2].isdigit())


def load(path: str) -> Trace:
    """Read an ``.xplane.pb`` file, or the newest one under a directory."""
    from jax.profiler import ProfileData

    if os.path.isdir(path):
        found = sorted(glob.glob(os.path.join(path, "**", "*.xplane.pb"),
                                 recursive=True), key=os.path.getmtime)
        if not found:
            raise FileNotFoundError(f"no .xplane.pb under {path}")
        path = found[-1]
    pd = ProfileData.from_file(path)
    device_ops, device_async, spans = {}, {}, []
    for plane in pd.planes:
        if is_device_plane(plane.name):
            for line_name, into in ((OPS_LINE, device_ops), (ASYNC_LINE, device_async)):
                ops = [Event(op_name(e.name), e.start_ns, e.start_ns + e.duration_ns)
                       for line in plane.lines if line.name == line_name
                       for e in line.events]
                into[plane.name] = sorted(ops, key=lambda e: e.start_ns)
        else:
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(SPAN_PREFIX):
                        spans.append(Event(e.name, e.start_ns, e.start_ns + e.duration_ns))
    return Trace(device_ops=device_ops, spans=sorted(spans, key=lambda e: e.start_ns),
                 device_async=device_async)


def clip(events, lo: float, hi: float) -> list[Event]:
    out = []
    for e in events:
        s, t = max(e.start_ns, lo), min(e.end_ns, hi)
        if t > s:
            out.append(Event(e.name, s, t))
    return out


def union(events) -> list[tuple[float, float]]:
    """Merged, sorted (start, end) intervals covered by ``events``."""
    merged: list[list[float]] = []
    for e in sorted(events, key=lambda e: e.start_ns):
        if merged and e.start_ns <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e.end_ns)
        else:
            merged.append([e.start_ns, e.end_ns])
    return [(a, b) for a, b in merged]


def busy_ns(events, lo: float, hi: float) -> float:
    return sum(b - a for a, b in union(clip(events, lo, hi)))


def device_events(trace: Trace, plane: str) -> list[Event]:
    return trace.device_ops.get(plane, []) + trace.device_async.get(plane, [])


def device_busy_s(trace: Trace, lo: float, hi: float) -> float:
    """Busy seconds inside [lo, hi], averaged over the device planes."""
    if not trace.device_ops:
        return 0.0
    per = [busy_ns(device_events(trace, p), lo, hi) for p in trace.device_ops]
    return sum(per) / len(per) / 1e9


def kernel_time_s(trace: Trace, kernel: str, lo: float, hi: float) -> float:
    """Summed device seconds of ``kernel``'s operations inside [lo, hi],
    averaged over the device planes."""
    names = KERNELS[kernel]
    if not trace.device_ops:
        return 0.0
    total = 0.0
    for ev in trace.device_ops.values():
        total += sum(e.dur_ns for e in clip(ev, lo, hi) if base_name(e.name) in names)
    return total / len(trace.device_ops) / 1e9


def kernel_calls(trace: Trace, kernel: str, lo: float, hi: float) -> int:
    names = KERNELS[kernel]
    return sum(1 for ev in trace.device_ops.values() for e in clip(ev, lo, hi)
               if base_name(e.name) in names)


def top_ops(trace: Trace, lo: float, hi: float, k: int = 10) -> list[list]:
    """[[operation name, device seconds], ...]: the ``k`` largest totals."""
    totals: dict[str, float] = defaultdict(float)
    n = max(len(trace.device_ops), 1)
    for ev in trace.device_ops.values():
        for e in clip(ev, lo, hi):
            totals[e.name] += e.dur_ns / n / 1e9
    return [[name, s] for name, s in
            sorted(totals.items(), key=lambda kv: -kv[1])[:k]]


def gaps(events, lo: float, hi: float) -> list[tuple[float, float]]:
    """Intervals inside [lo, hi] in which none of ``events`` runs."""
    out, t = [], lo
    for a, b in union(clip(events, lo, hi)):
        if a > t:
            out.append((t, a))
        t = max(t, b)
    if hi > t:
        out.append((t, hi))
    return out


def spans_at(spans, times) -> list[str]:
    """For each time, the innermost (latest-starting) bench span covering
    it, other than the window's own span."""
    import numpy as np

    inner = [s for s in spans if s.name != "bench.window"]
    if not inner:
        return ["no bench span"] * len(times)
    starts = np.array([s.start_ns for s in inner])
    ends = np.array([s.end_ns for s in inner])
    out = []
    for t in times:
        cover = (starts <= t) & (t < ends)
        out.append(inner[int(np.argmax(np.where(cover, starts, -np.inf)))].name
                   if cover.any() else "no bench span")
    return out


def idle_gaps(trace: Trace, lo: float, hi: float, k: int = 10) -> list[list]:
    """[[host span, idle seconds], ...]: device-idle time inside the window
    summed by the bench span the host was in (first device plane)."""
    if not trace.device_ops:
        return [["no device plane", (hi - lo) / 1e9]]
    ev = device_events(trace, next(iter(trace.device_ops)))
    totals: dict[str, float] = defaultdict(float)
    idle = gaps(ev, lo, hi)
    for (a, b), name in zip(idle, spans_at(trace.spans, [(a + b) / 2 for a, b in idle])):
        totals[name] += (b - a) / 1e9
    return [[name, s] for name, s in
            sorted(totals.items(), key=lambda kv: -kv[1])[:k]]
