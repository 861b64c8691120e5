"""Record the small chip trace that ``test_trace.py`` reads, on a TPU:

    python3 bench/tests/record_trace_fixture.py <out.xplane.pb>

Three serving waves of a 1000-tree forest, each inside a ``bench.wave``
span, with ``bench.window`` around them. Prints the expected numbers of
the reduction, worked out by a second method (an event sweep over sorted
interval endpoints), for the test to state.
"""
from __future__ import annotations

import glob
import json
import pathlib
import shutil
import sys
import tempfile

sys.path[0] = str(pathlib.Path(__file__).resolve().parents[2])
sys.path.insert(1, str(pathlib.Path(__file__).resolve().parents[2] / "src"))


def sweep_busy(intervals, lo, hi):
    """Busy time by counting open intervals across sorted endpoints."""
    points = []
    for a, b in intervals:
        a, b = max(a, lo), min(b, hi)
        if b > a:
            points += [(a, 1), (b, -1)]
    points.sort(key=lambda p: (p[0], -p[1]))
    busy, depth, since = 0.0, 0, None
    for t, d in points:
        if depth == 0 and d == 1:
            since = t
        depth += d
        if depth == 0 and d == -1:
            busy += t - since
    return busy


def main() -> int:
    import jax
    import numpy as np

    from bench import data as D
    from bench.common import Spans, profiler_options
    from repro.serving.forest_server import ForestServer, PredictRequest

    if jax.devices()[0].platform != "tpu":
        print("needs a TPU", file=sys.stderr)
        return 1
    forest = D.random_forest(3, 1000, 5, 28, 64)
    server = ForestServer(forest, D.normal_edges(28, 64))
    rows = np.random.default_rng(0).standard_normal((600, 28), dtype=np.float32)
    server.run([PredictRequest(uid=0, x=rows[:1])])
    spans = Spans()
    tmp = tempfile.mkdtemp()
    jax.profiler.start_trace(tmp, profiler_options=profiler_options())
    with spans.span("bench.window"):
        for k, n in enumerate((256, 100, 200)):
            server.submit(PredictRequest(uid=k + 1, x=rows[:n]))
            with spans.span("bench.wave"):
                server.serve_next_wave()
    jax.profiler.stop_trace()
    found = glob.glob(f"{tmp}/**/*.xplane.pb", recursive=True)
    shutil.copy(found[0], sys.argv[1])
    shutil.rmtree(tmp)

    from bench import trace as TR

    tr = TR.load(sys.argv[1])
    lo, hi = tr.window()
    plane = next(iter(tr.device_ops))
    events = TR.device_events(tr, plane)
    busy = sweep_busy([(e.start_ns, e.end_ns) for e in events], lo, hi) / 1e9
    kernel = sum(min(e.end_ns, hi) - max(e.start_ns, lo) for e in tr.device_ops[plane]
                 if e.name.startswith("forest_traverse_pallas") and e.end_ns > lo
                 and e.start_ns < hi) / 1e9
    print(json.dumps({"window_s": (hi - lo) / 1e9, "busy_s": busy,
                      "traversal_s": kernel, "traversal_calls":
                      sum(1 for e in tr.device_ops[plane]
                          if e.name.startswith("forest_traverse_pallas")),
                      "waves": len([s for s in tr.spans if s.name == "bench.wave"])}))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
