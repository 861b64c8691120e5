"""Forest-serving throughput: batch size x forest size sweep -> JSON record.

Measures the jitted serve-time predict (raw floats -> training-bin lookup ->
fused forest traversal) the way Anghel et al. (2018) benchmark GBT
inference: steady-state latency and rows/s per (batch, trees) cell, plus an
end-to-end continuous-engine measurement (``serving.ForestEngine``: per-
arrival admission, SLO-aware wave cuts) whose reported p99 includes queue
wait, and a quantized-traversal (int8/fp16) comparison. Forest contents are
random — traversal cost is data-independent — so the sweep needs no
training run.

    PYTHONPATH=src python -m benchmarks.gbdt_serve [--full] [--backend ref]

Writes ``experiments/gbdt_serve.json`` (the CI benchmark-smoke artifact).
The ``gate`` record (p50/p99 predict latency at the 256-row x 32-tree cell
plus engine p99 end-to-end latency) is what ``check_bench --serve`` diffs
against the committed ``BENCH_serve.json``.
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import save, time_call
from repro.serving import ForestEngine, ForestServer, PredictRequest, percentile_latencies
from repro.trees.binning import make_bins
from repro.trees.forest import Forest, quantization_atol
from repro.trees.tree import tree_num_nodes
from repro.launch.compile_cache import enable_compile_cache

GATE_BATCH, GATE_TREES = 256, 32  # the geometry check_bench --serve pins

QUICK = {"batches": [16, 64, 256], "trees": [8, 32, 128], "depth": 5, "dim": 32}
FULL = {"batches": [64, 256, 1024, 4096], "trees": [32, 128, 400], "depth": 7,
        "dim": 128}


def random_forest(capacity: int, depth: int, dim: int, n_bins: int,
                  seed: int = 0) -> Forest:
    """A fully-live forest with random splits/leaves (cost-equivalent to a
    trained one: traversal work does not depend on the values)."""
    key = jax.random.PRNGKey(seed)
    k1, k2, k3 = jax.random.split(key, 3)
    n_int, n_leaf = tree_num_nodes(depth)
    return Forest(
        feature=jax.random.randint(k1, (capacity, n_int), 0, dim, dtype=jnp.int32),
        threshold=jax.random.randint(k2, (capacity, n_int), 0, n_bins,
                                     dtype=jnp.int32),
        leaf_value=0.1 * jax.random.normal(k3, (capacity, n_leaf), jnp.float32),
        n_trees=jnp.asarray(capacity, jnp.int32),
        base_score=jnp.asarray(0.0, jnp.float32),
    )


def run(quick: bool = True, backend: str = "auto", seed: int = 0) -> dict:
    p = QUICK if quick else FULL
    n_bins = 64
    rng = np.random.default_rng(seed)
    edges = jnp.asarray(
        make_bins(rng.standard_normal((4096, p["dim"])).astype(np.float32), n_bins)
    )
    out: dict = {
        "backend": backend, "depth": p["depth"], "dim": p["dim"],
        "n_bins": n_bins, "sweep": [],
    }
    for n_trees in p["trees"]:
        forest = random_forest(n_trees, p["depth"], p["dim"], n_bins, seed)
        server = ForestServer(forest, edges, max_rows=max(p["batches"]),
                              backend=backend)
        for batch in p["batches"]:
            x = jnp.asarray(
                rng.standard_normal((batch, p["dim"])).astype(np.float32)
            )
            t_s, _ = time_call(server._predict, forest, edges, x)
            rec = {
                "batch": batch, "trees": n_trees,
                "latency_ms": 1e3 * t_s,
                "rows_per_s": batch / t_s,
                "tree_rows_per_s": batch * n_trees / t_s,
            }
            out["sweep"].append(rec)
            print(f"  trees={n_trees:4d} batch={batch:5d}: "
                  f"{rec['latency_ms']:8.3f} ms  {rec['rows_per_s']:12,.0f} rows/s",
                  flush=True)

    # End-to-end wave path: queueing + packing + padding included.
    n_trees = p["trees"][-1]
    forest = random_forest(n_trees, p["depth"], p["dim"], n_bins, seed)
    max_rows = p["batches"][-1]
    server = ForestServer(forest, edges, max_rows=max_rows, backend=backend)
    reqs = [
        PredictRequest(
            uid=i,
            x=rng.standard_normal(
                (int(rng.integers(1, max_rows // 2 + 1)), p["dim"])
            ).astype(np.float32),
        )
        for i in range(24)
    ]
    def serve_all():
        """One full pass; wave count deltas so warmup runs don't pollute it.
        (time_call's untimed warmup invocation also compiles the predict.)"""
        n0 = server.waves_served
        outs = server.run(reqs)
        return outs, server.waves_served - n0

    t_s, (outs, waves) = time_call(serve_all, reps=1)
    rows = sum(len(r.scores) for r in outs)
    out["engine"] = {
        "trees": n_trees, "max_rows": max_rows, "requests": len(reqs),
        "rows": rows, "wall_s": t_s, "rows_per_s": rows / t_s,
        "waves": waves,
    }
    print(f"  engine: {rows} rows over {len(reqs)} requests in {t_s:.3f}s "
          f"({rows / t_s:,.0f} rows/s)", flush=True)

    out["gate"] = gate_record(edges, p, n_bins, backend, rng, seed)
    out["quantized"] = quantized_record(edges, p, n_bins, backend, rng, seed)
    save("gbdt_serve", out)
    return out


def gate_record(edges, p, n_bins, backend, rng, seed) -> dict:
    """The check_bench --serve payload: p50/p99 steady-state predict
    latency at the pinned 256-row x 32-tree cell, and p50/p99 END-TO-END
    (queue + compute) latency through the continuous engine serving a
    mixed-size trickle under a 50ms SLO."""
    slo_ms = 50.0
    forest = random_forest(GATE_TREES, p["depth"], p["dim"], n_bins, seed)
    server = ForestServer(forest, edges, max_rows=GATE_BATCH, backend=backend)
    x = jnp.asarray(
        rng.standard_normal((GATE_BATCH, p["dim"])).astype(np.float32)
    )
    jax.block_until_ready(server._predict(forest, edges, x))  # compile
    times = []
    for _ in range(30):
        t0 = time.perf_counter()
        jax.block_until_ready(server._predict(forest, edges, x))
        times.append(1e3 * (time.perf_counter() - t0))
    rec = {
        "geometry": {
            "batch": GATE_BATCH, "trees": GATE_TREES, "depth": p["depth"],
            "dim": p["dim"], "n_bins": n_bins, "slo_ms": slo_ms,
        },
        "predict_p50_ms": float(np.percentile(times, 50)),
        "predict_p99_ms": float(np.percentile(times, 99)),
    }

    eng = ForestEngine(edges, max_rows=GATE_BATCH, slo_s=slo_ms / 1e3,
                       backend=backend)
    eng.add_version("live", forest)
    eng.run([PredictRequest(uid=0, x=np.asarray(x))])  # warm the jit cache
    eng.start(interval_s=0.002)
    try:
        for uid in range(1, 41):
            n = int(rng.integers(1, GATE_BATCH // 2))
            eng.submit(PredictRequest(
                uid=uid,
                x=rng.standard_normal((n, p["dim"])).astype(np.float32),
            ))
            time.sleep(0.002)
        got = []
        deadline = time.perf_counter() + 30.0
        while len(got) < 40 and time.perf_counter() < deadline:
            got.extend(eng.poll())
            time.sleep(0.005)
    finally:
        eng.stop()
    got.extend(eng.poll())
    stats = percentile_latencies(got)
    rec.update({f"engine_{k}": v for k, v in stats.items()})
    rec["engine_requests"] = len(got)
    rec["engine_slo_met"] = float(
        np.mean([r.latency_s * 1e3 <= slo_ms for r in got])
    )
    print(f"  gate ({GATE_BATCH}x{GATE_TREES}): predict p99 "
          f"{rec['predict_p99_ms']:.2f} ms; engine p99 "
          f"{rec.get('engine_latency_p99_ms', float('nan')):.2f} ms "
          f"(SLO {slo_ms:.0f} ms met on {100 * rec['engine_slo_met']:.0f}% "
          f"of requests)", flush=True)
    return rec


def quantized_record(edges, p, n_bins, backend, rng, seed) -> dict:
    """int8/fp16 traversal at the gate cell: latency vs f32 plus the
    observed-vs-documented score error (informational, not gated)."""
    forest = random_forest(GATE_TREES, p["depth"], p["dim"], n_bins, seed)
    server = ForestServer(forest, edges, max_rows=GATE_BATCH, backend=backend)
    x = jnp.asarray(
        rng.standard_normal((GATE_BATCH, p["dim"])).astype(np.float32)
    )
    t_f32, base = time_call(server._predict, forest, edges, x)
    rec: dict = {"f32_latency_ms": 1e3 * t_f32}
    for mode in ("int8", "fp16"):
        qf = forest.quantize(mode)
        qsrv = ForestServer(forest, edges, max_rows=GATE_BATCH,
                            backend=backend, quantize=mode)
        t_q, scores = time_call(qsrv._predict, qf, edges, x)
        err = float(jnp.max(jnp.abs(scores - base)))
        atol = quantization_atol(forest, qf)
        rec[mode] = {
            "latency_ms": 1e3 * t_q,
            "speedup_vs_f32": t_f32 / t_q,
            "max_abs_err": err,
            "documented_atol": atol,
            "parity_ok": bool(err <= atol + 1e-6),
        }
        print(f"  quantized {mode}: {1e3 * t_q:8.3f} ms "
              f"(f32 {1e3 * t_f32:.3f} ms), max|err| {err:.2e} "
              f"<= atol {atol:.2e}: {rec[mode]['parity_ok']}", flush=True)
    return rec


def main():
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", dest="quick", action="store_false", default=True)
    ap.add_argument("--backend", default="auto",
                    choices=["auto", "ref", "pallas"])
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    return run(quick=args.quick, backend=args.backend, seed=args.seed)


if __name__ == "__main__":
    main()
