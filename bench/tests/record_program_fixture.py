"""Record the small chip trace that ``test_program_trace.py`` reads, on a TPU:

    python3 bench/tests/record_program_fixture.py <out_dir>

With ``repro.obs`` tracing on and the profiler recording: four builds and
folds of the threaded PS runtime (W=2, 16,384 x 28 dense rows, depth 5)
and a few serving waves of a 1000-tree forest through a started
``ForestEngine`` (so its loop holds a queue, then cuts), each part inside a
``bench.window`` span. Writes ``program_spans.xplane.pb`` and
``program_spans.json`` (the propose program's instruction -> scope map
from its compiled HLO text, the ``repro.obs`` counters, and the expected
numbers of the reduction) into ``out_dir``, and prints the JSON.
"""
from __future__ import annotations

import glob
import json
import pathlib
import shutil
import sys
import tempfile
import time

sys.path[0] = str(pathlib.Path(__file__).resolve().parents[2])
sys.path.insert(1, str(pathlib.Path(__file__).resolve().parents[2] / "src"))

ROWS, FEATURES, DEPTH, TREES, WORKERS = 16_384, 28, 5, 4, 2


def main(out_dir: str, require_tpu: bool = True) -> dict | None:
    import jax
    import numpy as np

    from bench import data as D
    from bench import program_trace as PT
    from bench.common import Spans, profiler_options
    from repro import obs
    from repro.core.sgbdt import SGBDTConfig, init_state
    from repro.data.synthetic import make_dense_low_diversity
    from repro.ps.runtime import AsyncRuntime
    from repro.serving.continuous import ForestEngine
    from repro.serving.forest_server import PredictRequest
    from repro.trees.learner import LearnerConfig

    if require_tpu and jax.devices()[0].platform != "tpu":
        print("needs a TPU", file=sys.stderr)
        return None
    data = make_dense_low_diversity(ROWS, FEATURES, ROWS, seed=5)
    cfg = SGBDTConfig(n_trees=TREES, step_length=0.1, sampling_rate=0.8,
                      learner=LearnerConfig(depth=DEPTH, n_bins=64, backend="auto"))
    rt = AsyncRuntime(cfg, data, WORKERS)
    state = init_state(cfg, data)
    key = jax.random.PRNGKey(0)
    hlo = rt._propose.lower(data, state.f, key).compile().as_text()
    forest = D.random_forest(3, 1000, DEPTH, FEATURES, 64)
    engine = ForestEngine(D.normal_edges(FEATURES, 64), slo_s=0.02)
    engine.add_version("live", forest)
    rows = np.random.default_rng(0).standard_normal((600, FEATURES), dtype=np.float32)
    engine.run([PredictRequest(uid=-1, x=rows[:1])])  # compile the wave
    rt.run(0)  # compile the build and the fold

    spans = Spans()
    tmp = tempfile.mkdtemp()
    obs.drain()
    obs.enable()
    jax.profiler.start_trace(tmp, profiler_options=profiler_options())
    with spans.span("bench.window", part="train"):
        rt.run(1)
    with spans.span("bench.window", part="serve"):
        engine.start()
        for k, n in enumerate((256, 100, 3, 200, 7)):
            engine.submit(PredictRequest(uid=k, x=rows[:n]))
            time.sleep(0.03)
        engine.stop(drain=True)
    jax.profiler.stop_trace()
    obs.disable()
    drained = obs.drain()
    out = pathlib.Path(out_dir)
    out.mkdir(parents=True, exist_ok=True)
    found = glob.glob(f"{tmp}/**/*.xplane.pb", recursive=True)
    shutil.copy(found[0], out / "program_spans.xplane.pb")
    shutil.rmtree(tmp)

    pt = PT.load(str(out / "program_spans.xplane.pb"))
    ran = {e.name for e in pt.ops}
    op_scopes = {k: v for k, v in PT.scopes(hlo).items() if k in ran}
    train, serve = [s for s in pt.spans if s.name == "bench.window"]
    lo, hi = train.start_ns, train.end_ns
    expected = {
        "builds": len([s for s in pt.spans if s.name == "ps.build"]),
        "build_device_ms": PT.build_device_ms(pt, lo, hi),
        "row_reduce_ms": PT.scoped_ms(pt, op_scopes, {"child_counts", "leaf_sums"}, lo, hi),
        "scope_totals": PT.scope_totals(pt, op_scopes, lo, hi),
        "wave_fill": PT.wave_fill(pt, serve.start_ns, serve.end_ns),
        "wave_host_ms": PT.wave_host_ms(pt, serve.start_ns, serve.end_ns),
        "holds": len([r for r in drained.spans if r.name == "serve.hold"]),
        "serve_spans": sorted({s.name for s in pt.spans if s.name.startswith("serve.")}),
    }
    record = {"propose_scopes": op_scopes, "counts": drained.counts, "expected": expected}
    (out / "program_spans.json").write_text(json.dumps(record, indent=1, sort_keys=True))
    print(json.dumps(expected, sort_keys=True))
    return record


if __name__ == "__main__":
    raise SystemExit(0 if main(sys.argv[1]) is not None else 1)
