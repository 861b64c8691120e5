"""The trace reduction, on hand-made events and on a small trace recorded on
a TPU v5e (``fixtures/serve_waves.xplane.pb``, made by
``record_trace_fixture.py``: three serving waves of a 1000-tree forest)."""
from __future__ import annotations

import pathlib

import pytest

from bench import trace as TR

E = TR.Event
FIXTURE = pathlib.Path(__file__).parent / "fixtures" / "serve_waves.xplane.pb"


def test_union_merges_overlaps_and_touching():
    ev = [E("a", 0, 10), E("b", 5, 12), E("c", 12, 15), E("d", 20, 30)]
    assert TR.union(ev) == [(0, 15), (20, 30)]
    assert TR.busy_ns(ev, 0, 100) == 25
    assert TR.busy_ns(ev, 8, 25) == 7 + 5  # clipped to the window


def test_gaps_and_their_host_spans():
    ev = [E("op", 10, 20), E("op", 40, 50)]
    assert TR.gaps(ev, 0, 60) == [(0, 10), (20, 40), (50, 60)]
    spans = [E("bench.window", 0, 60), E("bench.run", 0, 60), E("bench.submit", 25, 45)]
    trace = TR.Trace(device_ops={"/device:TPU:0": ev}, spans=spans)
    gaps = dict((k, v * 1e9) for k, v in TR.idle_gaps(trace, 0, 60))
    # the (20, 40) gap's midpoint 30 sits in bench.submit, the innermost span
    assert gaps == pytest.approx({"bench.run": 20.0, "bench.submit": 20.0})


def test_kernel_time_matches_by_op_name_only():
    ev = [E("histogram_pallas.3", 0, 10), E("histogram_pallas", 10, 15),
          E("histogram_sparse_pallas.1", 20, 30), E("fusion.2", 30, 40)]
    trace = TR.Trace(device_ops={"/device:TPU:0": ev}, spans=[])
    assert TR.kernel_time_s(trace, "histogram", 0, 100) == pytest.approx(15e-9)
    assert TR.kernel_time_s(trace, "histogram_sparse", 0, 100) == pytest.approx(10e-9)
    assert TR.kernel_calls(trace, "histogram", 0, 100) == 2


def test_op_names_from_hlo_text():
    assert TR.op_name("%histogram_pallas.9 = f32[16,1792]{1,0} custom-call(%x)") == \
        "histogram_pallas.9"
    assert TR.base_name("histogram_pallas.9") == "histogram_pallas"
    assert TR.base_name("fusion") == "fusion"


def test_async_copies_count_as_busy():
    trace = TR.Trace(device_ops={"/device:TPU:0": [E("f", 0, 10)]}, spans=[],
                     device_async={"/device:TPU:0": [E("copy-start", 5, 30)]})
    assert TR.device_busy_s(trace, 0, 40) == pytest.approx(30e-9)


def test_recorded_chip_trace():
    """Expected numbers worked out when the fixture was recorded, by an
    endpoint sweep over the TPU plane's events (``record_trace_fixture.py``)."""
    tr = TR.load(str(FIXTURE))
    assert list(tr.device_ops) == ["/device:TPU:0"]  # not the CUSTOM plane
    lo, hi = tr.window()
    assert (hi - lo) / 1e9 == pytest.approx(0.0071685, abs=1e-9)
    assert TR.device_busy_s(tr, lo, hi) == pytest.approx(0.001117833, abs=1e-9)
    assert TR.kernel_time_s(tr, "forest_traversal", lo, hi) == pytest.approx(
        0.000197578, abs=1e-9)
    assert TR.kernel_calls(tr, "forest_traversal", lo, hi) == 3
    assert len([s for s in tr.spans if s.name == "bench.wave"]) == 3
    idle = dict(TR.idle_gaps(tr, lo, hi))
    # every idle stretch of the window falls inside one of the three waves'
    # spans or outside all of them, and together they are window - busy
    assert sum(idle.values()) == pytest.approx((hi - lo) / 1e9 - 0.001117833, abs=1e-9)
    assert set(idle) <= {"bench.wave", "no bench span"}
