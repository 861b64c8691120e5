"""The reduction of the program's spans and named scopes
(``bench/program_trace.py``), on hand-made events and on a small trace
recorded on a TPU v5e with tracing on (``fixtures/program_spans.*``, made by
``record_program_fixture.py``), and its silence on a trace the program
wrote no spans into (``fixtures/serve_waves.xplane.pb``)."""
from __future__ import annotations

import json
import pathlib

import pytest

from bench import program_trace as PT
from bench import trace as TR
from repro.obs import Record

E, S = TR.Event, PT.Span
FIXTURES = pathlib.Path(__file__).parent / "fixtures"

HLO = """
ENTRY %main.1 (p: f32[8]) -> f32[8] {
  %fusion.19 = f32[32]{0} fusion(f32[8]{0} %p), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(propose)/build/jit(build_tree)/level1/histogram/child_counts/scatter-add" source_file="learner.py" source_line=115}
  %fusion.22 = f32[32]{0} fusion(f32[8]{0} %p), metadata={op_name="jit(propose)/build/jit(build_tree)/leaf_sums/scatter-add"}
  %histogram_pallas.9 = f32[16]{0} custom-call(f32[8]{0} %p), custom_call_target="tpu_custom_call", metadata={op_name="jit(propose)/build/jit(build_tree)/level1/histogram/jit(histogram_pallas)/histogram_pallas/pallas_call"}
  %copy.1 = f32[8]{0} copy(f32[8]{0} %p)
  ROOT %add.3 = f32[8]{0} add(f32[8]{0} %p, f32[8]{0} %p), metadata={op_name="jit(propose)/delta/add"}
}
"""


def test_scopes_from_hlo_metadata():
    assert PT.scopes(HLO) == {"fusion.19": "child_counts", "fusion.22": "leaf_sums",
                              "histogram_pallas.9": "histogram", "add.3": "delta"}
    assert PT.module_name("jit_propose(6646509240411455201)") == "jit_propose"


def _train_trace():
    modules = [E("jit_propose", 0, 100), E("jit_fold", 100, 110), E("jit_propose", 110, 200)]
    ops = [E("fusion.19", 10, 20), E("fusion.22", 30, 35), E("histogram_pallas.9", 40, 80),
           E("fusion.19", 101, 105),  # the fold program's own fusion.19
           E("fusion.19", 120, 130), E("copy.1", 130, 140)]
    spans = [S("bench.run", 0, 300), S("ps.build", 0, 105, {"ticket": 0}),
             S("ps.build", 50, 210, {"ticket": 1}), S("ps.fold", 100, 112, {"fold": 0})]
    return PT.ProgramTrace(ops=ops, modules=modules, spans=spans)


def test_module_runs_per_build():
    pt = _train_trace()
    assert PT.build_device_ms(pt, 0, 300) == pytest.approx((100 + 90) / 2 / 1e6)
    # a build whose span opened before the window still counts; one that
    # ended before it does not
    assert PT.build_device_ms(pt, 40, 300) == pytest.approx((60 + 90) / 2 / 1e6)
    assert PT.build_device_ms(pt, 106, 300) == pytest.approx(90 / 1 / 1e6)


def test_scope_attribution_inside_the_program_only():
    pt, sc = _train_trace(), PT.scopes(HLO)
    # 10 + 5 + 10 ns under child_counts / leaf_sums; the fold's fusion.19 is not
    assert PT.scoped_ms(pt, sc, {"child_counts", "leaf_sums"}, 0, 300) == \
        pytest.approx(25 / 2 / 1e6)
    assert PT.scope_totals(pt, sc, 0, 300) == pytest.approx(
        {"child_counts": 20e-9, "leaf_sums": 5e-9, "histogram": 40e-9, "-": 10e-9})


def test_program_spans_name_idle_gaps():
    spans = [S("bench.window", 0, 60), S("bench.wave", 0, 60), S("serve.wave", 15, 45),
             S("serve.wave.fetch", 25, 35), S("serve.hold", 50, 60)]
    pt = PT.ProgramTrace(ops=[E("op", 10, 20), E("op", 40, 50)], modules=[], spans=spans)
    gaps = {k: v * 1e9 for k, v in PT.idle_gaps(pt, 0, 60)}
    # each gap goes to the innermost span of either kind at its midpoint
    assert gaps == pytest.approx({"bench.wave": 10.0, "serve.wave.fetch": 20.0,
                                  "serve.hold": 10.0})


def test_wave_readings():
    spans = [S("bench.window", 0, 400), S("serve.wave", 0, 100, {"rows": 200, "pad": 56}),
             S("serve.wave", 200, 260, {"rows": 56, "pad": 200})]
    modules = [E("jit_predict", 10, 40), E("jit_predict", 210, 250)]
    pt = PT.ProgramTrace(ops=[], modules=modules, spans=spans)
    assert PT.wave_fill(pt, 0, 400) == pytest.approx(50.0)
    assert PT.wave_host_ms(pt, 0, 400) == pytest.approx((70 + 20) / 2 / 1e6)


def test_hold_share_from_records():
    holds = [Record("serve.hold", 1.0, 2.0, "engine", 0, 1, {}),
             Record("serve.hold", 2.5, 4.0, "engine", 0, 2, {}),
             Record("serve.wave", 0.0, 3.0, "engine", 0, 3, {})]
    assert PT.hold_share(holds, 0.0, 3.0) == pytest.approx(50.0)


def test_readers_silent_without_program_spans():
    """A trace and records from a program without ``repro.obs``: every
    reading is None, and idle gaps keep the bench's names."""
    pt = PT.load(str(FIXTURES / "serve_waves.xplane.pb"))
    tr = TR.load(str(FIXTURES / "serve_waves.xplane.pb"))
    lo, hi = tr.window()
    assert {s.name for s in pt.spans} == {"bench.window", "bench.wave"}
    assert PT.build_device_ms(pt, lo, hi) is None
    assert PT.scoped_ms(pt, PT.scopes(HLO), {"child_counts"}, lo, hi) is None
    assert PT.wave_fill(pt, lo, hi) is None
    assert PT.wave_host_ms(pt, lo, hi) is None
    assert PT.hold_share([], 0.0, 1.0) is None
    assert PT.idle_gaps(pt, lo, hi) == TR.idle_gaps(tr, lo, hi)


def test_recorded_chip_trace():
    """The fixture's numbers, worked out when it was recorded
    (``program_spans.json`` ``expected``), and what the attribution route
    must find there: the row reductions under their scopes inside the
    propose program, and the serving spans and counters."""
    pt = PT.load(str(FIXTURES / "program_spans.xplane.pb"))
    rec = json.loads((FIXTURES / "program_spans.json").read_text())
    op_scopes, want, counts = rec["propose_scopes"], rec["expected"], rec["counts"]
    train, serve = [s for s in pt.spans if s.name == "bench.window"]
    lo, hi = train.start_ns, train.end_ns
    builds = [s for s in pt.spans if s.name == "ps.build"]
    assert len(builds) == want["builds"] == 4
    assert {s.attrs["ticket"] for s in builds} == {0, 1, 2, 3}
    assert len([s for s in pt.spans if s.name == "ps.fold"]) == 4
    assert PT.build_device_ms(pt, lo, hi) == pytest.approx(want["build_device_ms"])
    totals = PT.scope_totals(pt, op_scopes, lo, hi)
    assert totals == pytest.approx(want["scope_totals"])
    assert totals["child_counts"] > 0 and totals["leaf_sums"] > 0
    assert PT.scoped_ms(pt, op_scopes, {"child_counts", "leaf_sums"}, lo, hi) == \
        pytest.approx(want["row_reduce_ms"])
    # the build's operations are named: almost none falls outside every scope
    assert totals["-"] < 0.01 * sum(totals.values())

    s_lo, s_hi = serve.start_ns, serve.end_ns
    waves = [s for s in pt.spans if s.name == "serve.wave"]
    serve_spans = {s.name for s in pt.spans if s.name.startswith("serve.")}
    assert serve_spans == set(want["serve_spans"])
    assert sum(v for k, v in counts.items() if k.startswith("serve.cut.")) == len(waves)
    assert sum(s.attrs["rows"] for s in waves) == counts["serve.rows"]
    assert PT.wave_fill(pt, s_lo, s_hi) == pytest.approx(
        100.0 * counts["serve.rows"] / (counts["serve.rows"] + counts["serve.pad_rows"]))
    assert PT.wave_host_ms(pt, s_lo, s_hi) == pytest.approx(want["wave_host_ms"])
    idle = dict(PT.idle_gaps(pt, s_lo, s_hi))
    assert set(idle) & {"serve.hold", "serve.wave.fetch"}  # holds and copies name gaps
