"""In-program tracing: host spans and counters, off unless enabled.

The program's one span and counter system. A span marks a stretch of host
work on one thread:

- ``span(name, **attrs)`` is a context manager. With tracing on it opens a
  ``jax.profiler.TraceAnnotation`` of the same name (so a profiler that is
  recording places it on the host plane, on the clock of the device
  planes) and, on exit, appends a ``Record`` stamped with
  ``time.perf_counter``. Its parent is the span open around it on the same
  thread. With tracing off it returns a shared null context and records
  nothing.
- ``timed(name, **attrs)`` is the same span for a site whose caller reads
  the stamps (``t0``, ``t1``, ``seconds``): it stamps whether or not
  tracing is on, so a column the program keeps (``RunTrace.t_fold``,
  ``PredictResult.compute_s``) and the span it is recorded with are one
  pair of stamps.
- ``begin(name, **attrs)`` opens a span that ``end`` closes in a later
  call, for a state that outlives one call (the engine's hold); such a
  span has no parent.
- ``count(name, n)`` adds to a named counter.

Spans of one unit of work share an identifier attribute: ``ticket`` and
``fold`` in training, ``uid`` in serving. While tracing is on, a
``gc.callbacks`` hook records every garbage collection as a ``host.gc``
span on the thread it stopped.

``enable()`` turns tracing on, ``disable()`` off, ``drain()`` returns and
clears what was recorded.
"""
from __future__ import annotations

import gc
import itertools
import threading
import time
from typing import NamedTuple

from jax.profiler import TraceAnnotation

_enabled = False
_lock = threading.Lock()
_records: list = []  # guarded-by: _lock
_counts: dict = {}  # guarded-by: _lock
_ids = itertools.count(1)
_local = threading.local()


class Record(NamedTuple):
    """One finished span. ``parent`` is the ``id`` of the span open around
    it on the same thread, 0 at the top."""

    name: str
    t0: float
    t1: float
    thread: str
    parent: int
    id: int
    attrs: dict


class Drained(NamedTuple):
    spans: list  # [Record], in the order they closed
    counts: dict  # counter name -> total


def enabled() -> bool:
    return _enabled


def _stack() -> list:
    stack = getattr(_local, "stack", None)
    if stack is None:
        stack = _local.stack = []
    return stack


def _append(rec: Record) -> None:
    with _lock:
        _records.append(rec)


class _Null:
    """What ``span`` returns with tracing off: one shared, stateless object."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def set(self, **attrs) -> None:
        pass


_NULL = _Null()


class Span:
    """A span's stamps; recorded (and annotated for the profiler) only when
    tracing was on as it was made."""

    __slots__ = ("name", "attrs", "t0", "t1", "id", "parent", "_ann")

    def __init__(self, name: str, attrs: dict, live: bool):
        self.name = name
        self.attrs = attrs
        self._ann = TraceAnnotation(name, **attrs) if live else None

    def __enter__(self):
        if self._ann is not None:
            stack = _stack()
            self.parent = stack[-1] if stack else 0
            self.id = next(_ids)
            stack.append(self.id)
            self._ann.__enter__()
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        self.t1 = time.perf_counter()
        if self._ann is not None:
            self._ann.__exit__(*exc)
            _stack().pop()
            _append(Record(self.name, self.t0, self.t1,
                           threading.current_thread().name, self.parent, self.id,
                           self.attrs))
        return False

    def set(self, **attrs) -> None:
        """Attributes known only inside the span (the ticket a draw gave)."""
        self.attrs.update(attrs)

    @property
    def seconds(self) -> float:
        return self.t1 - self.t0


def span(name: str, **attrs):
    """A span around the ``with`` block; the shared null context when off."""
    if not _enabled:
        return _NULL
    return Span(name, attrs, True)


def timed(name: str, **attrs) -> Span:
    """A span whose stamps the caller reads; stamped even when off."""
    return Span(name, attrs, _enabled)


class _Open(NamedTuple):
    name: str
    attrs: dict
    t0: float
    ann: TraceAnnotation


def begin(name: str, **attrs) -> _Open | None:
    """Open a span for ``end`` to close later; None when off."""
    if not _enabled:
        return None
    ann = TraceAnnotation(name, **attrs)
    ann.__enter__()
    return _Open(name, attrs, time.perf_counter(), ann)


def end(opened: _Open | None) -> None:
    if opened is None:
        return
    t1 = time.perf_counter()
    opened.ann.__exit__(None, None, None)
    _append(Record(opened.name, opened.t0, t1, threading.current_thread().name, 0,
                   next(_ids), opened.attrs))


def count(name: str, n: int = 1) -> None:
    if not _enabled:
        return
    with _lock:
        _counts[name] = _counts.get(name, 0) + n


def _on_gc(phase: str, info: dict) -> None:
    if phase == "start":
        ann = TraceAnnotation("host.gc", generation=info["generation"])
        ann.__enter__()
        _local.gc = (time.perf_counter(), ann)
        return
    started = getattr(_local, "gc", None)
    if started is None:  # tracing came on during this collection
        return
    _local.gc = None
    t0, ann = started
    ann.__exit__(None, None, None)
    stack = _stack()
    _append(Record("host.gc", t0, time.perf_counter(), threading.current_thread().name,
                   stack[-1] if stack else 0, next(_ids),
                   {"generation": info["generation"], "collected": info["collected"]}))


def enable() -> None:
    global _enabled
    if not _enabled:
        gc.callbacks.append(_on_gc)
        _enabled = True


def disable() -> None:
    global _enabled
    if _enabled:
        _enabled = False
        gc.callbacks.remove(_on_gc)


def drain() -> Drained:
    """Everything recorded since the last drain; clears it."""
    global _records, _counts
    with _lock:
        out = Drained(_records, _counts)
        _records, _counts = [], {}
    return out
