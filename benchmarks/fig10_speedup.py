"""Paper Fig. 10 + Eq. 13: speedup of asynch-SGBDT vs fork-join baselines.

Wall-clock asynchrony cannot run on one CPU, so the timing geometry is
reproduced by the event-driven cluster simulator, parameterized by
COMPONENT TIMES MEASURED from the actual jitted implementation:
  t_build  — one build_tree call on a sampled subdataset,
  t_server — target rebuild (grad + sample + fold),
  t_comm   — tree pull+push bytes over the paper's 1 GbE TCP/IP network.
The paper's numbers to match: asynch-SGBDT 14x (real-sim) / 20x
(E2006-log1p) at 32 workers; LightGBM 5-7x; DimBoost 4-6x.

Beyond the simulation, ``async_measured`` is an EXECUTED speedup: the PS
engine's worker pool builds W trees in one vmapped call
(``repro.ps.worker``), and we time that block against W sequential
builds — the Fig. 10 claim running for real on this machine's vector
units rather than through the event model.
"""
from __future__ import annotations

import pathlib

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import e2006_like, paper_cfg, realsim_like, save, time_call
from repro.core.baselines import (
    max_workers_bound,
    speedup_model_async,
    speedup_model_dimboost,
    speedup_model_sync,
)
from repro.core.simulator import ClusterSpec, simulate_async, simulate_sync
from repro.core.sgbdt import init_state
from repro.data.sampling import bernoulli_weights
from repro.launch.compile_cache import enable_compile_cache
from repro.ps import clear_trainers
from repro.ps.worker import build_trees_batched
from repro.trees.learner import build_tree, build_tree_multi
from repro.trees.tree import apply_tree, apply_tree_stack

WORKERS = [1, 2, 4, 8, 16, 32]
GBE_BYTES_PER_S = 110e6  # ~1 GbE effective

# Accounting subprocess for the block-distributed 2D mesh: trace the REAL
# feature-sharded builder (argmax-merge split search, DESIGN.md §16) with
# a ByteRecorder on forced host devices and report what one tree build
# actually puts on the wire — fig10's 2D rows derive their communication
# bytes from this, never from shape arithmetic.
_MESH2D_CODE = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count={shards}"
import json

import jax
import jax.numpy as jnp

from repro.launch.mesh import make_gbdt_mesh
from repro.ps.sharded import collective_bytes_per_build
from repro.trees.binning import SparseBins
from repro.trees.learner import LearnerConfig

N, F, E, depth, shards = {N}, {F}, {E}, {depth}, {shards}
cfg = LearnerConfig(depth=depth, n_bins=64, backend="ref", hist_mode="subtract")
mesh = make_gbdt_mesh(1, shards)
dense = jax.ShapeDtypeStruct((N, F), jnp.int32)
C = max(N * E // F, 1)
sp = SparseBins(
    indices=jax.ShapeDtypeStruct((N, E), jnp.int32),
    codes=jax.ShapeDtypeStruct((N, E), jnp.int32),
    feat_rows=jax.ShapeDtypeStruct((F, C), jnp.int32),
    feat_codes=jax.ShapeDtypeStruct((F, C), jnp.int32),
    zero_bin=jax.ShapeDtypeStruct((F,), jnp.int32),
)
out = {{
    "bytes_2d_dense": collective_bytes_per_build(
        cfg, mesh, dense, feature_axis="feature")["realized_bytes"],
    "bytes_2d_sparse": collective_bytes_per_build(
        cfg, mesh, sp, feature_axis="feature")["realized_bytes"],
}}
print("MESH2D_JSON=" + json.dumps(out))
"""


def measure_mesh2d_comm(cfg, data, shards: int = 8) -> dict | None:
    """ACCOUNTING-derived per-round wire bytes on the (1, ``shards``) 2D
    mesh — the 2D analogue of ``measure_components``'s pull/tree payload,
    with the bytes MEASURED from the builder's own collectives
    (``ps.sharded.collective_bytes_per_build``) instead of hand-derived
    constants. Returns None when the feature count does not tile the mesh.
    """
    import json as _json
    import os
    import subprocess
    import sys

    from repro.trees.binning import SparseBins, to_sparse

    n, f = data.bins.shape
    if f % shards:
        return None
    sp = data.bins if isinstance(data.bins, SparseBins) \
        else to_sparse(data.bins)
    code = _MESH2D_CODE.format(
        N=n, F=f, E=sp.max_nnz_row, depth=cfg.learner.depth, shards=shards
    )
    # The child traces on virtual CPU devices: it must never reach for the
    # accelerator this process may hold.
    proc = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True,
        timeout=1400,
        env={**os.environ, "PYTHONPATH": "src", "JAX_PLATFORMS": "cpu"},
    )
    for line in proc.stdout.splitlines():
        if line.startswith("MESH2D_JSON="):
            out = _json.loads(line.split("=", 1)[1])
            out["shards"] = shards
            return out
    raise RuntimeError(
        f"2D-mesh accounting child failed (rc={proc.returncode}):\n"
        f"{proc.stderr[-2000:]}"
    )


def measure_components(cfg, data) -> dict:
    key = jax.random.PRNGKey(0)
    obj = cfg.obj
    k_out = obj.n_outputs
    state = init_state(cfg, data)
    g, h = obj.grad_hess(data.labels, state.f, qid=data.qid)
    m_prime, _ = bernoulli_weights(key, cfg.sampling_rate, data.multiplicity)

    if k_out == 1:
        t_build, tree = time_call(
            lambda: build_tree(cfg.learner, data.bins, m_prime * g, m_prime, key)
        )
        apply_fn = apply_tree
    else:
        t_build, tree = time_call(
            lambda: build_tree_multi(
                cfg.learner, data.bins, m_prime[:, None] * g,
                jnp.broadcast_to(m_prime[:, None], g.shape), key,
            )
        )
        apply_fn = apply_tree_stack

    def server_side():
        mp, _ = bernoulli_weights(key, cfg.sampling_rate, data.multiplicity)
        gg, _ = obj.grad_hess(data.labels, state.f, qid=data.qid)
        return state.f + cfg.step_length * apply_fn(tree, data.bins), mp, gg

    t_server, _ = time_call(jax.jit(server_side))

    # tree payload: feature/threshold int32 + leaf f32, x K trees per round
    n_int = tree.feature.shape[-1]
    n_leaf = tree.leaf_value.shape[-1]
    tree_bytes = 4 * (2 * n_int + n_leaf) * k_out
    # pull payload: the target field L'_random (N x K floats)
    pull_bytes = 4 * data.n_samples * k_out
    t_comm = (tree_bytes + pull_bytes) / GBE_BYTES_PER_S
    return {
        "t_build": t_build,
        "t_server": t_server,
        "t_comm": t_comm,
        "tree_bytes": tree_bytes,
        "pull_bytes": pull_bytes,
    }


def measure_worker_parallel(cfg, data, workers: list[int]) -> list[float]:
    """Executed speedup of the vmapped worker pool: (W x one-build time) /
    (one batched W-build time), per worker count."""
    key = jax.random.PRNGKey(0)
    state = init_state(cfg, data)

    t_one, _ = time_call(
        jax.jit(lambda k: build_trees_batched(
            cfg, data, state.f[None, ...], k)),
        jax.random.split(key, 1),
    )
    out = []
    for w in workers:
        targets = jnp.broadcast_to(state.f, (w,) + state.f.shape)
        t_blk, _ = time_call(
            jax.jit(lambda k, t: build_trees_batched(cfg, data, t, k)),
            jax.random.split(key, w), targets,
        )
        out.append(w * t_one / t_blk)
    return out


def measure_runtime_threads(
    cfg, data, workers: list[int], n_trees: int, tag: str
) -> dict:
    """EXECUTED wall-clock speedup of the real threaded runtime, plus the
    realized staleness cross-validated against the simulator's prediction
    for the measured cluster geometry (``RunTrace.crossvalidate``).

    One CPU serves every thread, so this measures the host-async overlap
    the runtime actually achieves here (XLA's intra-op pool), not an
    idealized cluster — the point is that it is *measured*, with the trace
    exported for the simulator to be validated against.
    """
    from repro.ps import AsyncRuntime

    rt_cfg = cfg._replace(n_trees=n_trees)
    rows = {
        "speedup": [], "makespan_s": [],
        "mean_staleness": [], "max_staleness": [],
        "sim_mean_staleness": [], "sim_max_staleness": [],
    }
    base = None
    last_trace = None
    for w in workers:
        state, trace = AsyncRuntime(rt_cfg, data, n_workers=w).run(seed=0)
        del state
        if base is None:
            base = trace.makespan
        xval = trace.crossvalidate()
        rows["speedup"].append(base / trace.makespan)
        rows["makespan_s"].append(float(trace.makespan))
        rows["mean_staleness"].append(xval["realized"]["mean_staleness"])
        rows["max_staleness"].append(xval["realized"]["max_staleness"])
        rows["sim_mean_staleness"].append(xval["simulated"]["mean_staleness"])
        rows["sim_max_staleness"].append(xval["simulated"]["max_staleness"])
        last_trace = trace
    trace_path = last_trace.save(
        pathlib.Path("experiments") / f"runtime_trace_{tag}.json"
    )
    rows["trace_json"] = str(trace_path)
    return rows


def measure_sharded_pulls(cfg, data, n_trees: int) -> dict:
    """EXECUTED pull-byte reduction from sharding the server leaf table.

    Runs the threaded runtime at W=4 with the leaf table split into P
    partitions for a sweep of P; each worker derives its Bernoulli sample
    from the ticket key and pulls only the partitions its sampled rows
    touch, and the trace records the bytes each pull actually moved
    (request bitmap + touched-partition payload). Reported per P: the mean
    realized pull bytes, the reduction vs. the full 4*N*K pull, and the
    Eq.-13-style simulated speedup with t_comm rescaled to the reduced
    payload — what the saved bytes are worth on the paper's 1 GbE wire.
    """
    from repro.ps import AsyncRuntime

    rt_cfg = cfg._replace(n_trees=n_trees)
    n = data.n_samples
    full = 4 * cfg.obj.n_outputs * n
    sweep = sorted({min(16, n), min(256, n), n})
    out = {"n_parts": [], "pull_bytes_mean": [], "reduction": [],
           "sim_speedup_32w": [], "full_pull_bytes": full}
    comp = measure_components(cfg, data)
    base = simulate_async(
        ClusterSpec(n_workers=1, t_build=comp["t_build"],
                    t_comm=comp["t_comm"], t_server=comp["t_server"]),
        n_trees,
    ).makespan
    for p in sweep:
        _, trace = AsyncRuntime(
            rt_cfg, data, n_workers=4, shard_pulls=p
        ).run(seed=0)
        mean_bytes = float(trace.pull_bytes.mean())
        reduction = 1.0 - mean_bytes / full
        t_comm = (comp["tree_bytes"] + mean_bytes) / GBE_BYTES_PER_S
        sharded = simulate_async(
            ClusterSpec(n_workers=32, t_build=comp["t_build"],
                        t_comm=t_comm, t_server=comp["t_server"]),
            n_trees,
        ).makespan
        out["n_parts"].append(p)
        out["pull_bytes_mean"].append(mean_bytes)
        out["reduction"].append(reduction)
        out["sim_speedup_32w"].append(base / sharded)
    return out


def _objective_dataset(objective: str, quick: bool):
    """(tag, data) for a requested --objective override — the launch
    driver's shared objective -> workload dispatch, benchmark-sized."""
    from repro.launch.train import gbdt_dataset_for

    obj, data = gbdt_dataset_for(objective, seed=7, n=1_600 if quick else 6_400)
    tag = obj.name if obj.n_outputs == 1 else f"{obj.name}{obj.n_outputs}"
    return tag, data


def run(quick: bool = True, objective: str | None = None) -> dict:
    """Default: the paper's two workloads. With ``objective``, the same
    speedup measurement on that objective's matched workload — the paper's
    scalability claim checked beyond binary classification (multiclass
    rounds build K trees per push; the measured vmapped-pool ratio and the
    Eq. 13 model both see the bigger build/comm payloads)."""
    n_trees = 150 if quick else 400
    if objective is None:
        cases = [
            ("realsim", realsim_like(quick), 6, "logistic"),
            ("e2006", e2006_like(quick), 6, "mse"),
        ]
    else:
        tag, data = _objective_dataset(objective, quick)
        cases = [(tag, data, 6, objective)]
    out: dict = {"workers": WORKERS, "objective": objective, "datasets": {}}
    for tag, data, depth, loss in cases:
        cfg = paper_cfg(n_trees, depth, objective=loss)
        comp = measure_components(cfg, data)
        print(f"  {tag}: t_build={comp['t_build']*1e3:.1f}ms "
              f"t_server={comp['t_server']*1e3:.1f}ms "
              f"t_comm={comp['t_comm']*1e3:.1f}ms "
              f"(Eq.13 max workers ~ {max_workers_bound(**{k: comp[k] for k in ('t_build','t_comm','t_server')}):.0f})",
              flush=True)
        rows = {"async_sim": [], "sync_sim": [], "dimboost_sim": []}
        base = None
        for w in WORKERS:
            spec = ClusterSpec(
                n_workers=w, t_build=comp["t_build"],
                t_comm=comp["t_comm"], t_server=comp["t_server"],
            )
            a = simulate_async(spec, n_trees).makespan
            s = simulate_sync(spec, n_trees)
            d = simulate_sync(spec, n_trees, comm_model="central")
            if w == 1:
                base = max(a, s, d)
            rows["async_sim"].append(base / a)
            rows["sync_sim"].append(base / s)
            rows["dimboost_sim"].append(base / d)
        warr = np.asarray(WORKERS, float)
        rows["async_eq13"] = speedup_model_async(
            warr, comp["t_build"], comp["t_comm"], comp["t_server"]
        ).tolist()
        # The paper's environment: ps-lite over 1 GbE TCP/IP put
        # T(comm)+T(server) at ~T(build)/25 (their Eq. 13 discussion says
        # 16-32 workers is close to the max for real-sim), which is what
        # caps their async speedup at 14-22x. Same algorithm, their wire.
        t_over = comp["t_build"] / 25.0

        def _paper_env_makespan(w: int) -> float:
            # ps-lite's server owns the NIC: comm serializes *on the server*
            # (that is exactly Eq. 13's T(Communicate + BuildTarget) term).
            spec = ClusterSpec(
                n_workers=w, t_build=comp["t_build"],
                t_comm=0.0, t_server=t_over,
            )
            return simulate_async(spec, n_trees).makespan

        base_pe = _paper_env_makespan(1)
        rows["async_paper_env"] = [base_pe / _paper_env_makespan(w) for w in WORKERS]
        rows["async_measured"] = measure_worker_parallel(cfg, data, WORKERS)
        print(f"  {tag} measured vmapped-pool speedup @"
              f"{WORKERS[-1]}w: {rows['async_measured'][-1]:.1f}x", flush=True)
        rows["runtime_measured"] = measure_runtime_threads(
            cfg, data, WORKERS, n_trees=32 if quick else 96, tag=tag
        )
        rt = rows["runtime_measured"]
        print(f"  {tag} threaded-runtime speedup @{WORKERS[-1]}w: "
              f"{rt['speedup'][-1]:.2f}x, staleness "
              f"{rt['mean_staleness'][-1]:.1f} realized vs "
              f"{rt['sim_mean_staleness'][-1]:.1f} simulated "
              f"(trace -> {rt['trace_json']})", flush=True)
        if cfg.obj.rowwise:
            rows["sharded_pulls"] = measure_sharded_pulls(
                cfg, data, n_trees=24 if quick else 64
            )
            sp = rows["sharded_pulls"]
            print(f"  {tag} sharded pulls: " + "  ".join(
                f"P={p}: -{100 * r:.0f}% bytes"
                for p, r in zip(sp["n_parts"], sp["reduction"])
            ), flush=True)
        mesh2d = measure_mesh2d_comm(cfg, data)
        if mesh2d is not None:
            # The 2D-mesh speedup rows: same Eq.-13 event model, but the
            # per-round communication payload is the build's OWN measured
            # collective bytes (argmax merge + partition column) + the
            # tree push — pull_bytes is replaced by accounting, because on
            # the block-distributed mesh the target never crosses the
            # wire; only the build collectives do.
            for kind in ("dense", "sparse"):
                wire = mesh2d[f"bytes_2d_{kind}"] + comp["tree_bytes"]
                t_comm = wire / GBE_BYTES_PER_S
                sims = []
                base2d = None
                for w in WORKERS:
                    spec = ClusterSpec(
                        n_workers=w, t_build=comp["t_build"],
                        t_comm=t_comm, t_server=comp["t_server"],
                    )
                    m = simulate_async(spec, n_trees).makespan
                    base2d = base2d or m
                    sims.append(base2d / m)
                mesh2d[f"round_wire_bytes_{kind}"] = wire
                mesh2d[f"async_sim_2d_{kind}"] = sims
            rows["mesh2d"] = mesh2d
            print(f"  {tag} 2D mesh (1x{mesh2d['shards']}) accounting: "
                  f"{mesh2d['bytes_2d_dense']:,}B/round dense, "
                  f"{mesh2d['bytes_2d_sparse']:,}B/round sparse "
                  f"(vs {comp['pull_bytes']:,}B pull constant); "
                  f"@32w sim {mesh2d['async_sim_2d_dense'][-1]:.1f}x / "
                  f"{mesh2d['async_sim_2d_sparse'][-1]:.1f}x", flush=True)
        rows["sync_model"] = speedup_model_sync(
            warr, comp["t_build"], comp["t_comm"], comp["t_server"]
        ).tolist()
        rows["dimboost_model"] = speedup_model_dimboost(
            warr, comp["t_build"], comp["t_comm"], comp["t_server"]
        ).tolist()
        out["datasets"][tag] = {"components": comp, "speedup": rows}
        print(f"  {tag} @32w: async {rows['async_sim'][-1]:.1f}x "
              f"sync {rows['sync_sim'][-1]:.1f}x dimboost {rows['dimboost_sim'][-1]:.1f}x",
              flush=True)
        # each case is a distinct SGBDTConfig; drop its cached Trainer (and
        # the compiled programs it pins) before the next one.
        clear_trainers()
    name = "fig10_speedup" if objective is None else f"fig10_speedup_{objective.replace(':', '')}"
    save(name, out)
    return out


def main(quick: bool = True, objective: str | None = None):
    enable_compile_cache()
    res = run(quick, objective=objective)
    print("\npaper targets @32: async 14-20x, LightGBM 5-7x, DimBoost 4-6x")
    return res


if __name__ == "__main__":
    import argparse

    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--objective", default=None,
                    help="objective registry spec (e.g. multiclass:3, "
                         "lambdarank); default = the paper's two workloads")
    a = ap.parse_args()
    main(quick=not a.full, objective=a.objective)
