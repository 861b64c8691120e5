"""The in-program tracer (``repro.obs``) and the spans the runtime and the
serving engine record with it.

The contracts under test:
  * off by default: no span, counter or collection is recorded;
  * spans nest per thread (a span's parent is the span open around it on
    its own thread) and carry their attributes; counters add; ``drain``
    empties;
  * the threaded PS runtime records one ``ps.build`` per ticket and one
    ``ps.fold`` per fold, tied by the ticket id, and its ``RunTrace``
    columns are the spans' own stamps;
  * the engine counts every served row and every cut, and records a hold
    once per state change, not once per engine tick.
"""
import gc
import threading
import time

import numpy as np
import pytest

from repro import obs
from repro.core.sgbdt import SGBDTConfig
from repro.ps import AsyncRuntime
from repro.serving import ForestEngine
from repro.serving.forest_server import PredictRequest
from repro.trees.forest import empty_forest
from repro.trees.learner import LearnerConfig


@pytest.fixture()
def tracing():
    obs.drain()
    obs.enable()
    yield
    obs.disable()
    obs.drain()


def _named(spans, name):
    return [s for s in spans if s.name == name]


def test_off_records_nothing():
    obs.drain()
    assert not obs.enabled()
    with obs.span("a", uid=1) as s:
        s.set(more=2)
        with obs.timed("b") as t:
            pass
    obs.count("c", 3)
    assert obs.begin("d") is None
    obs.end(None)
    gc.collect()
    assert t.t1 >= t.t0  # timed spans stamp whether or not tracing is on
    assert obs.span("a") is obs.span("b")  # one shared null context
    out = obs.drain()
    assert out.spans == [] and out.counts == {}


def test_nesting_and_parents_across_threads(tracing):
    barrier = threading.Barrier(2)

    def work(tag):
        with obs.span("outer", tag=tag):
            barrier.wait()  # both outers open at once, on two threads
            with obs.span("inner", tag=tag) as inner:
                inner.set(late=tag)
            barrier.wait()

    threads = [threading.Thread(target=work, args=(k,), name=f"t{k}") for k in range(2)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    spans = obs.drain().spans
    assert sorted(s.name for s in spans) == ["inner", "inner", "outer", "outer"]
    by_id = {s.id: s for s in spans}
    for s in _named(spans, "inner"):
        parent = by_id[s.parent]
        assert parent.name == "outer"
        assert parent.thread == s.thread  # never the other thread's outer
        assert parent.attrs["tag"] == s.attrs["tag"] == s.attrs["late"]
        assert parent.t0 <= s.t0 <= s.t1 <= parent.t1
    assert all(s.parent == 0 for s in _named(spans, "outer"))


def test_counters_records_and_drain(tracing):
    obs.count("rows", 5)
    obs.count("rows")
    obs.count("cuts", 2)
    opened = obs.begin("hold", version="live")
    with obs.span("step"):
        pass
    obs.end(opened)
    gc.collect()
    out = obs.drain()
    assert out.counts == {"rows": 6, "cuts": 2}
    (hold,) = _named(out.spans, "hold")
    (step,) = _named(out.spans, "step")
    assert hold.attrs == {"version": "live"} and hold.parent == step.parent == 0
    assert hold.t0 <= step.t0 <= step.t1 <= hold.t1
    assert _named(out.spans, "host.gc")  # the collection, on this thread
    empty = obs.drain()
    assert empty.spans == [] and empty.counts == {}


@pytest.fixture(scope="module")
def traced_run(sparse_data):
    cfg = SGBDTConfig(
        n_trees=10, step_length=0.3, sampling_rate=0.8,
        learner=LearnerConfig(depth=3, n_bins=64),
    )
    rt = AsyncRuntime(cfg, sparse_data, n_workers=2)
    obs.drain()
    obs.enable()
    try:
        _, trace = rt.run(seed=0)
    finally:
        obs.disable()
    return cfg, trace, obs.drain().spans


def test_runtime_spans_match_tickets_and_folds(traced_run):
    cfg, trace, spans = traced_run
    n = cfg.n_trees
    builds = _named(spans, "ps.build")
    folds = sorted(_named(spans, "ps.fold"), key=lambda s: s.attrs["fold"])
    assert sorted(s.attrs["ticket"] for s in builds) == list(range(n))
    assert [s.attrs["fold"] for s in folds] == list(range(n))
    assert [s.attrs["ticket"] for s in folds] == trace.key_index.tolist()
    assert [s.attrs["staleness"] for s in folds] == trace.staleness.tolist()
    draws = _named(spans, "ps.ticket")
    drawn = [s.attrs["ticket"] for s in draws if "ticket" in s.attrs]
    assert sorted(drawn) == list(range(n))
    assert len(draws) - len(drawn) == 2  # each worker's last look: no ticket left
    assert len(_named(spans, "ps.run.prelude")) == 1
    assert len(_named(spans, "ps.commit")) == n
    by_id = {s.id: s for s in spans}
    for child in ("ps.build.dispatch", "ps.build.wait"):
        kids = _named(spans, child)
        assert len(kids) == n
        assert all(by_id[k.parent].name == "ps.build" for k in kids)
        assert all(by_id[k.parent].attrs["ticket"] == k.attrs["ticket"] for k in kids)


def test_runtime_columns_are_the_span_stamps(traced_run):
    cfg, trace, spans = traced_run
    pull = {s.attrs["ticket"]: s for s in _named(spans, "ps.pull")}
    build = {s.attrs["ticket"]: s for s in _named(spans, "ps.build")}
    push = {s.attrs["ticket"]: s for s in _named(spans, "ps.push")}
    fold = {s.attrs["fold"]: s for s in _named(spans, "ps.fold")}
    for j, i in enumerate(trace.key_index.tolist()):
        assert trace.t_build[j] == build[i].t1 - pull[i].t0
        assert trace.t_queue[j] == fold[j].t0 - push[i].t0
        assert trace.t_fold[j] == fold[j].t1 - fold[j].t0
        assert pull[i].t1 <= build[i].t0 and push[i].t0 >= build[i].t1


F = 6


def _engine(**kw):
    eng = ForestEngine(np.tile(np.linspace(-2.0, 2.0, 63, dtype=np.float32), (F, 1)), **kw)
    eng.add_version("live", empty_forest(8, 3))
    return eng


def _rows(n, seed=0):
    return np.random.default_rng(seed).standard_normal((n, F)).astype(np.float32)


def test_engine_counts_rows_and_cuts(tracing):
    eng = _engine(max_rows=16, slo_s=10.0)
    sizes = [5, 20, 3, 40, 1, 16]
    for uid, n in enumerate(sizes):
        eng.submit(PredictRequest(uid=uid, x=_rows(n, uid)))
    out = eng.step()  # cuts only full waves under a 10 s budget
    out += eng.flush()  # forces the rest
    out += eng.run([PredictRequest(uid=99, x=_rows(2))])
    assert len(out) == len(sizes) + 1
    server = eng._versions["live"].server
    counts = obs.drain().counts
    assert counts["serve.rows"] == sum(sizes) + 2
    assert counts["serve.pad_rows"] == 16 * server.waves_served - counts["serve.rows"]
    assert counts["serve.cut.full"] >= 1 and counts["serve.cut.force"] >= 1
    cuts = sum(v for k, v in counts.items() if k.startswith("serve.cut."))
    assert cuts == server.waves_served


def test_engine_due_cut_and_wave_spans(tracing):
    eng = _engine(max_rows=16, slo_s=1e-4)
    eng.submit(PredictRequest(uid=7, x=_rows(3)))
    time.sleep(0.01)
    (res,) = eng.step()  # the head of line waited past its budget
    out = obs.drain()
    assert out.counts["serve.cut.due"] == 1
    (submit,) = _named(out.spans, "serve.submit")
    assert submit.attrs == {"uid": 7}
    (wave,) = _named(out.spans, "serve.wave")
    by_id = {s.id: s for s in out.spans}
    kids = {s.name: s for s in out.spans if by_id.get(s.parent) is wave}
    assert set(kids) == {"serve.wave.pack", "serve.wave.run", "serve.wave.fetch",
                         "serve.wave.assemble"}
    run, fetch = kids["serve.wave.run"], kids["serve.wave.fetch"]
    assert res.compute_s == fetch.t1 - run.t0
    assert res.queue_s == run.t0 - submit.t0


def test_engine_hold_recorded_once(tracing):
    eng = _engine(max_rows=256, slo_s=10.0)
    eng.start(interval_s=0.001)
    try:
        eng.submit(PredictRequest(uid=1, x=_rows(4)))
        time.sleep(0.08)  # dozens of engine ticks that decline to cut
    finally:
        eng.stop(drain=True)
    assert len(eng.poll()) == 1
    (hold,) = _named(obs.drain().spans, "serve.hold")
    assert hold.attrs == {"version": "live"}
    assert 0.0 < hold.t1 - hold.t0 < 5.0
