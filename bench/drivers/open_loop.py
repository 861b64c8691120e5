"""Serving traffic: an open loop of requests into the continuous engine.

The forest is the configuration's (published tree count and depth over
its features and bins) made from the seed, and it is served through
``repro.serving.continuous.ForestEngine`` with the engine's own defaults,
run from its background loop (``start`` / ``poll``). The main thread
sends each request at its scheduled time whether or not earlier ones have
finished (``bench.schedule.open_loop``); a second thread polls for
results and stamps each as it arrives. A request's latency runs from its
scheduled send time to that stamp, so a stall also counts against the
requests it delays. Requests that never come back count as missing.
"""
from __future__ import annotations

import threading
import time

import numpy as np

LATE_WAIT_S = 60.0  # how long past the window's close a result may still come


def run(ctx) -> dict:
    import jax

    from bench import data as D
    from bench import reference as R
    from bench import schedule as S
    from repro.serving.continuous import ForestEngine
    from repro.serving.forest_server import PredictRequest

    config, traffic, spans = ctx.config, ctx.traffic, ctx.spans
    feats = config["data"]["features"]
    n_bins = config["data"]["n_bins"]
    depth = config["learner"]["depth"]
    slots = config["forest"]["trees"]

    offsets, sizes = S.open_loop(ctx.seed, traffic["rate_per_s"], ctx.seconds,
                                 traffic["size_min"], traffic["size_max"])
    starts = np.concatenate([[0], np.cumsum(sizes)])
    with spans.span("bench.setup.data"):
        rows = S.request_rows(ctx.seed, int(starts[-1]), feats)
        forest = D.random_forest(ctx.seed, slots, depth, feats, n_bins)
        edges = D.normal_edges(feats, n_bins)
        jax.block_until_ready((forest, edges))
    engine = ForestEngine(edges)
    engine.add_version("live", forest)
    server = engine._versions["live"].server
    with spans.span("bench.setup.warmup"):
        # One wave shape: every wave pads to the engine's max_rows.
        engine.run([PredictRequest(uid=-1, x=rows[:1])])

    run_wave = server._run_wave

    def spanned_wave(wave):
        with spans.span("bench.wave", rows=sum(p.hi - p.lo for p in wave)):
            return run_wave(wave)

    server._run_wave = spanned_wave

    done: dict[int, tuple[float, object]] = {}
    stop = threading.Event()

    def poller():
        while True:
            results = engine.poll()
            now = time.perf_counter()
            for r in results:
                done[r.uid] = (now, r)
            if stop.is_set() and not results:
                return
            time.sleep(0.0002)

    ctx.start_trace()
    engine.start()
    poll_thread = threading.Thread(target=poller, name="bench-poller", daemon=True)
    poll_thread.start()
    n = len(sizes)
    late = np.zeros(n)
    failed = 0
    t0 = time.perf_counter() + 0.01
    due = t0 + offsets
    with spans.span("bench.window"):
        for k in range(n):
            wait = due[k] - time.perf_counter()
            if wait > 0:
                time.sleep(wait)
            sent = time.perf_counter()
            late[k] = sent - due[k]
            try:
                with spans.span("bench.submit"):
                    engine.submit(PredictRequest(uid=k, x=rows[starts[k]:starts[k + 1]]))
            except (ValueError, RuntimeError, KeyError):
                failed += 1
        t_close = t0 + ctx.seconds
        time.sleep(max(0.0, t_close - time.perf_counter()))
    deadline = t_close + LATE_WAIT_S
    while len(done) < n - failed and time.perf_counter() < deadline:
        time.sleep(0.01)
    ctx.stop_trace()
    engine.stop(drain=True)
    stop.set()
    poll_thread.join(10.0)
    ctx.window(t0, t_close)
    ctx.read_memory()

    # A request that never came back waited at least until the deadline.
    latency = deadline - due
    in_window_rows = 0
    for uid, (t_done, _) in done.items():
        latency[uid] = t_done - due[uid]
        if t_done <= t_close:
            in_window_rows += int(sizes[uid])
    missing = n - len(done)
    p95 = float(np.percentile(latency, 95))
    print(f"serve: {n} requests, {missing} missing; generator late p50 "
          f"{np.median(late) * 1e3:.3f} ms, p99 {np.percentile(late, 99) * 1e3:.3f} ms, "
          f"max {late.max() * 1e3:.3f} ms", flush=True)

    # The output check: a sample of the answered requests drawn from the
    # seed, with the largest request in it, against the float64 reference.
    rng = np.random.default_rng(np.random.SeedSequence([int(ctx.seed), 2]))
    answered = np.array(sorted(done))
    pick = set(rng.choice(answered, size=min(traffic["checked"], len(answered)),
                          replace=False).tolist()) if len(answered) else set()
    if len(answered):
        pick.add(int(answered[np.argmax(sizes[answered])]))
    feature = np.asarray(forest.feature)
    threshold = np.asarray(forest.threshold)
    leaf = np.asarray(forest.leaf_value)
    base = float(forest.base_score)
    edges_np = np.asarray(edges)
    gap = 0.0
    with spans.span("bench.reference"):
        for uid in sorted(pick):
            served = np.asarray(done[uid][1].scores, np.float64)
            want = R.score_rows(rows[starts[uid]:starts[uid + 1]], edges_np, feature,
                                threshold, leaf, slots, base)
            if served.shape != want.shape:
                gap = 1e300  # a wrong-shaped answer
                break
            gap = max(gap, float(np.max(np.abs(served - want))))
    limits = config["check"]
    compared = [
        ["missing_requests", missing, 0, "=="],
        ["score_gap", gap, limits["score_gap"], "<="],
    ]
    results = [r for _, r in done.values()]
    ctx.layer_inputs.update(results=results, waves=spans.named("bench.wave"),
                            trees=slots, depth=depth, features=feats, n_bins=n_bins,
                            window_s=ctx.seconds)
    return {
        "attempted": n,
        "failed": missing,
        "end_to_end": {
            "serve_p95_ms": p95 * 1e3,
            "serve_rows_per_s": in_window_rows / ctx.seconds,
        },
        "compared": compared,
        "setup_end": t0,
        "trace_window": ("bench.window", "bench.window"),
    }
