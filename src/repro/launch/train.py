"""Training driver — runs any assigned architecture end-to-end on the local
device (reduced configs) or a production mesh (full configs on real pods).

    PYTHONPATH=src python -m repro.launch.train --arch granite-3-2b \
        --reduced --steps 200 --batch 8 --seq 128 [--delay 4] [--sample 0.8]

``--delay`` wraps the optimizer in the paper's DelayedGradient staleness
mechanism; ``--sample`` draws Bernoulli importance weights per batch — the
two halves of asynch-SGBDT applied to NN training.

``--arch gbdt`` instead drives the paper's own model through the
parameter-server engine (``repro.ps``):

    PYTHONPATH=src python -m repro.launch.train --arch gbdt \
        --steps 200 --workers 16 [--sample 0.8] [--scan] \
        [--objective logistic|mse|quantile:0.9|huber|multiclass:5|lambdarank]
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

import repro.configs as configs
import repro.sharding as sharding
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_host_mesh
from repro.launch.steps import make_train_step
from repro.models import init_params
from repro.optim import adamw, cosine_schedule, delayed_gradient, staleness_step_scale


def synthetic_batches(cfg, batch: int, seq: int, steps: int, seed: int = 0):
    """Markov-chain token stream: learnable (non-uniform) bigram structure."""
    rng = np.random.default_rng(seed)
    v = cfg.vocab_size
    # sparse row-stochastic transition matrix with strong modes
    nxt = rng.integers(0, v, size=(v, 4))
    for i in range(steps):
        toks = np.empty((batch, seq + 1), np.int64)
        toks[:, 0] = rng.integers(0, v, size=batch)
        choice = rng.integers(0, 4, size=(batch, seq))
        mix = rng.random((batch, seq)) < 0.1  # 10% noise
        noise = rng.integers(0, v, size=(batch, seq))
        for t in range(seq):
            step_tok = nxt[toks[:, t], choice[:, t]]
            toks[:, t + 1] = np.where(mix[:, t], noise[:, t], step_tok)
        batch_d = {
            "tokens": jnp.asarray(toks[:, :-1], jnp.int32),
            "labels": jnp.asarray(toks[:, 1:], jnp.int32),
        }
        if cfg.family in ("vlm", "audio"):
            batch_d["media"] = jnp.asarray(
                rng.standard_normal((batch, cfg.n_media_tokens, cfg.d_model)) * 0.02,
                jnp.dtype(cfg.dtype),
            )
        yield batch_d


def gbdt_dataset_for(objective, seed: int, n: int = 4_000):
    """Objective-matched synthetic workload (see data.synthetic).

    The single objective -> workload dispatch, shared by this driver and
    the benchmarks (``benchmarks.fig10_speedup --objective``).
    """
    import repro.data as D
    from repro.objectives import get_objective

    obj = get_objective(objective)
    if obj.name == "lambdarank":
        return obj, D.make_ranking(max(n // 16, 16), 16, 40, seed=seed)
    if obj.n_outputs > 1:
        return obj, D.make_multiclass_classification(n, 60, obj.n_outputs, seed=seed)
    if obj.name in ("mse", "quantile", "huber"):
        return obj, D.make_sparse_regression(n, 1_000, 20, seed=seed)
    return obj, D.make_sparse_classification(n, 1_000, 20, seed=seed)


def run_gbdt(args) -> None:
    """Asynch-SGBDT on the PS engine: round-robin W workers, loop or scan.

    ``--objective`` selects the training objective (and a matched synthetic
    workload): ``logistic`` (default), ``mse``, ``quantile[:a]``,
    ``huber``, ``multiclass:K``, ``lambdarank``.

    ``--runtime threads`` swaps the simulated delay schedule for the REAL
    host-async runtime (``repro.ps.runtime``): W worker threads race the
    server fold loop, the realized k(j) is recorded, and (with
    ``--verify-replay``) the trace is replayed through the deterministic
    engine and checked bit-for-bit against the threaded forest.
    ``--trace-out FILE`` dumps the RunTrace JSON.
    """
    from repro.core.sgbdt import SGBDTConfig, train_loss, train_metrics
    from repro.ps import Trainer
    from repro.trees.learner import LearnerConfig

    obj, data = gbdt_dataset_for(args.objective, args.seed)
    if args.sparse:
        from repro.trees import binning

        data = data._replace(bins=binning.to_sparse(data.bins))
        print(f"sparse bins: {data.bins.max_nnz_row} nnz/row ELL "
              f"(dense round-trip exact)")
    cfg = SGBDTConfig(
        n_trees=args.steps,
        step_length=0.15,
        sampling_rate=args.sample or 0.8,
        objective=args.objective,
        learner=LearnerConfig(
            depth=6, n_bins=64, feature_fraction=0.8, hist_mode=args.hist_mode,
            backend=args.backend,
        ),
    )
    if args.runtime == "threads":
        if args.mesh != "none":
            raise SystemExit(
                "--mesh applies to the simulated PS engine; the threaded "
                "runtime builds on the local device"
            )
        return run_gbdt_threads(args, cfg, data, obj)
    mesh = None
    if args.mesh != "none":
        from repro.launch.mesh import make_gbdt_mesh, make_mesh

        shape = args.mesh_shape or ("2" if args.mesh == "1d" else "1x2")
        if args.mesh == "1d":
            pd, pf = int(shape.partition("x")[0]), 1
            mesh = make_mesh((pd,), ("data",))
        else:
            pd, _, pf = shape.partition("x")
            pd, pf = int(pd), int(pf or 1)
            mesh = make_gbdt_mesh(pd, pf)
        print(f"mesh: {args.mesh} {dict(mesh.shape)} "
              f"({len(mesh.devices.ravel())} devices)")
    trainer = Trainer(cfg, mesh=mesh)
    cb = trainer.collective_bytes(data)
    if cb is not None:
        # One tree build per round: the realized (wire) bytes of every
        # collective in the sharded build, by primitive kind.
        kinds = ", ".join(
            f"{k}={v:,}B" for k, v in sorted(cb["realized_by_kind"].items())
        )
        print(f"collective bytes/round: {cb['realized_bytes']:,}B "
              f"realized ({kinds})")
    schedule = ("round_robin", args.workers)
    print(f"gbdt[{obj.name}, K={obj.n_outputs}]: {args.steps} rounds, "
          f"{args.workers} PS workers ({'scan' if args.scan else 'loop'} form)")
    t0 = time.time()
    if args.scan:
        state, losses = trainer.train_scan(data, schedule, seed=args.seed)
        print(f"loss {float(losses[0]):.4f} -> {float(losses[-1]):.4f}")
    else:
        def on_eval(st, j):
            print(f"  round {j:4d}: train loss "
                  f"{float(train_loss(cfg, data, st)):.4f}")

        state = trainer.train(
            data, schedule, seed=args.seed,
            eval_every=max(args.log_every, 1) * 5, eval_fn=on_eval,
        )
        metrics = {k: f"{float(v):.4f}"
                   for k, v in train_metrics(cfg, data, state).items()}
        print(f"final {metrics}")
    print(f"trained in {time.time() - t0:.1f}s")
    assert np.isfinite(float(train_loss(cfg, data, state))), "training diverged"


def run_gbdt_threads(args, cfg, data, obj) -> None:
    """The real host-async PS runtime: threads, recorded k(j), elastic
    membership faults, sharded pulls, checkpoints, and bitwise
    replay/resume verification."""
    from repro.core.sgbdt import train_loss
    from repro.ps import AsyncRuntime, FaultPlan, RunTrace

    join_at = {}
    for spec in args.join or ():
        w, _, at = spec.partition(":")
        join_at[int(w)] = int(at)
    faults = FaultPlan(
        crash_tickets=frozenset(args.crash_ticket or ()),
        leave_tickets=frozenset(args.leave_ticket or ()),
        join_at=join_at,
    )
    if args.adaptive_step:
        cfg = cfg._replace(adaptive_step=args.adaptive_step)
    rt = AsyncRuntime(
        cfg, data, n_workers=args.workers,
        faults=faults, shard_pulls=args.shard_pulls,
    )
    print(f"gbdt[{obj.name}, K={obj.n_outputs}]: {cfg.n_trees} rounds, "
          f"{args.workers} REAL worker threads (host-async runtime)")
    run_kw = dict(
        checkpoint_dir=args.checkpoint_dir,
        checkpoint_every=args.checkpoint_every,
        halt_at_fold=args.halt_at_fold,
        trace_path=args.trace_out,
    )
    if args.checkpoint_every and not args.checkpoint_dir:
        raise SystemExit("--checkpoint-every needs --checkpoint-dir")
    if args.resume_from:
        if not args.checkpoint_dir:
            raise SystemExit("--resume-from needs --checkpoint-dir")
        prefix = RunTrace.load(args.resume_from)
        print(f"resuming from trace prefix {args.resume_from} "
              f"({prefix.n_trees}/{cfg.n_trees} folds) + checkpoints under "
              f"{args.checkpoint_dir}")
        state, trace = rt.resume(prefix, args.checkpoint_dir, **{
            k: v for k, v in run_kw.items() if k != "checkpoint_dir"
        })
    else:
        state, trace = rt.run(seed=args.seed, **run_kw)
    s = trace.summary()
    print(f"makespan {s['makespan_s']:.2f}s  "
          f"staleness mean {s['mean_staleness']:.2f} max {s['max_staleness']}  "
          f"build {s['t_build_mean_s']*1e3:.1f}ms "
          f"queue {s['t_queue_mean_s']*1e3:.1f}ms "
          f"fold {s['t_fold_mean_s']*1e3:.1f}ms")
    print(f"staleness histogram: {trace.staleness_histogram()}")
    if trace.events:
        print(f"membership events ({trace.n_epochs} epochs):")
        for e in trace.events:
            print(f"  fold {e['fold']:4d}: {e['kind']} worker {e['worker']}"
                  + (f" (ticket {e['ticket']})" if e["ticket"] >= 0 else ""))
    if trace.n_parts:
        print(f"sharded pulls (P={trace.n_parts}): "
              f"{s['pull_bytes_mean']:.0f} B/pull vs {s['pull_bytes_full']} B "
              f"full ({100 * s['pull_reduction']:.1f}% reduction)")
    if trace.adaptive_rho:
        print(f"adaptive step (rho={trace.adaptive_rho}): mean scale "
              f"{s['step_scale_mean']:.4f}")
    loss = float(train_loss(cfg, data, state))
    print(f"final train loss {loss:.4f}")
    assert np.isfinite(loss), "training diverged"
    if args.trace_out:
        path = trace.save(args.trace_out)
        print(f"trace -> {path}")
    if args.halt_at_fold is not None:
        print(f"halted at fold {args.halt_at_fold} (simulated crash); "
              f"resume with --resume-from {args.trace_out or '<trace>'}")
        if args.verify_replay:
            raise SystemExit(
                "--verify-replay needs a complete run; a halted prefix "
                "replays only via --resume-from or --verify-resume"
            )
    if args.verify_resume:
        if not args.checkpoint_dir:
            raise SystemExit("--verify-resume needs --checkpoint-dir")
        st_ckpt = rt.replay_from_checkpoint(args.checkpoint_dir, trace)
        identical = (
            np.array_equal(np.asarray(state.f), np.asarray(st_ckpt.f))
            and np.array_equal(
                np.asarray(state.forest.leaf_value),
                np.asarray(st_ckpt.forest.leaf_value),
            )
        )
        print(f"checkpoint + trace-suffix replay identical: {identical}")
        assert identical, "crash-resume replay drifted from the live run"
    if args.verify_replay and args.halt_at_fold is None:
        st_replay, _ = rt.replay(trace)
        identical = (
            np.array_equal(np.asarray(state.f), np.asarray(st_replay.f))
            and np.array_equal(
                np.asarray(state.forest.leaf_value),
                np.asarray(st_replay.forest.leaf_value),
            )
            and np.array_equal(
                np.asarray(state.forest.feature),
                np.asarray(st_replay.forest.feature),
            )
            and np.array_equal(
                np.asarray(state.forest.threshold),
                np.asarray(st_replay.forest.threshold),
            )
        )
        print(f"record-and-replay identical forest: {identical}")
        assert identical, "replay drifted from the threaded run"


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="granite-3-2b")
    ap.add_argument("--reduced", action="store_true", default=True)
    ap.add_argument("--full", dest="reduced", action="store_false")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--delay", type=int, default=0,
                    help="gradient staleness tau (DelayedGradient wrapper)")
    ap.add_argument("--rho", type=float, default=0.3,
                    help="overlap probability for the Prop.-1 step scaling")
    ap.add_argument("--sample", type=float, default=0.0,
                    help="Bernoulli sampling rate for importance-weighted batches")
    ap.add_argument("--accum", type=int, default=1)
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--workers", type=int, default=8,
                    help="parameter-server worker count (--arch gbdt)")
    ap.add_argument("--scan", action="store_true",
                    help="run the GBDT trainer in its lax.scan form")
    ap.add_argument("--runtime", choices=("simulated", "threads"),
                    default="simulated",
                    help="PS execution: 'simulated' replays a delay "
                         "schedule; 'threads' runs real worker threads and "
                         "records the realized k(j) (--arch gbdt)")
    ap.add_argument("--trace-out", default=None,
                    help="write the realized RunTrace JSON here "
                         "(--runtime threads)")
    ap.add_argument("--verify-replay", action="store_true",
                    help="replay the recorded trace through the "
                         "deterministic engine and assert the forests are "
                         "bit-identical (--runtime threads)")
    ap.add_argument("--crash-ticket", type=int, action="append",
                    help="crash the worker that first draws this build "
                         "ticket (repeatable; the ticket is re-issued)")
    ap.add_argument("--leave-ticket", type=int, action="append",
                    help="worker gracefully leaves after building this "
                         "ticket (repeatable)")
    ap.add_argument("--join", action="append", metavar="W:J",
                    help="worker W (re)joins when the server reaches fold "
                         "count J (repeatable)")
    ap.add_argument("--shard-pulls", type=int, default=0, metavar="P",
                    help="shard the server leaf table into P partitions; "
                         "workers pull only partitions their sample "
                         "touches (rowwise objectives only)")
    ap.add_argument("--adaptive-step", type=float, default=0.0,
                    metavar="RHO",
                    help="staleness-adaptive server fold: scale each fold "
                         "by 1/(1 + 6*RHO*tau) with tau the observed "
                         "staleness")
    ap.add_argument("--checkpoint-dir", default=None,
                    help="runtime checkpoint directory (--runtime threads)")
    ap.add_argument("--checkpoint-every", type=int, default=0, metavar="K",
                    help="checkpoint the server + in-flight versions every "
                         "K folds")
    ap.add_argument("--halt-at-fold", type=int, default=None, metavar="J",
                    help="simulate a whole-process crash: stop the server "
                         "after J folds and write the prefix trace")
    ap.add_argument("--resume-from", default=None, metavar="TRACE",
                    help="resume a halted run from its prefix trace JSON + "
                         "--checkpoint-dir; unfolded tickets are re-issued")
    ap.add_argument("--verify-resume", action="store_true",
                    help="after the run, rebuild the final state from the "
                         "newest checkpoint + trace suffix and assert it "
                         "matches bitwise")
    ap.add_argument("--hist-mode", choices=("subtract", "rebuild"),
                    default="subtract", dest="hist_mode",
                    help="GBDT level-histogram strategy: 'subtract' derives "
                         "each split's sibling from the cached parent "
                         "histogram (~half the kernel work); 'rebuild' "
                         "re-histograms every node (exact reference mode)")
    ap.add_argument("--backend", choices=("auto", "ref", "pallas"),
                    default="auto",
                    help="GBDT kernel backend: 'pallas' is the staged "
                         "kernel pipeline; 'ref' the jnp oracles; 'auto' "
                         "picks pallas on TPU, ref elsewhere")
    ap.add_argument("--mesh", choices=("none", "1d", "2d"), default="none",
                    help="GBDT build sharding: '1d' shards samples over a "
                         "('data',) mesh (psum-merged histograms); '2d' the "
                         "block-distributed (data x feature) mesh with the "
                         "argmax-merge split search (DESIGN.md §16). Needs "
                         "enough devices — on CPU set "
                         "XLA_FLAGS=--xla_force_host_platform_device_count=N")
    ap.add_argument("--mesh-shape", default=None, metavar="PDxPF",
                    help="mesh shape, e.g. '4' (--mesh 1d) or '2x2' / '1x4' "
                         "(--mesh 2d; sparse bins need Pd=1)")
    ap.add_argument("--sparse", action="store_true",
                    help="convert the binned dataset to the SparseBins "
                         "explicit-zero-bin layout (exact round-trip; "
                         "histogram cost scales with nnz, and feature-"
                         "sharded builds move only the argmax merge)")
    ap.add_argument("--objective", default="logistic",
                    help="GBDT objective registry spec: logistic | mse | "
                         "quantile[:a] | huber | multiclass:K | lambdarank")
    args = ap.parse_args()
    enable_compile_cache()

    if args.arch == "gbdt":
        return run_gbdt(args)

    cfg = configs.get(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    mesh = make_host_mesh()
    baxes = sharding.batch_axes(mesh)

    lr = args.lr
    if args.delay:
        lr *= staleness_step_scale(args.delay, args.rho)
        print(f"delay={args.delay}: scaling lr by Prop. 1 -> {lr:.2e}")
    opt = adamw(
        cosine_schedule(lr, max(args.steps // 20, 1), args.steps),
        weight_decay=0.01, max_grad_norm=1.0,
    )
    if args.delay:
        opt = delayed_gradient(opt, args.delay)

    step_fn = jax.jit(make_train_step(
        cfg, opt, mesh, baxes, accum=args.accum, sampling_rate=args.sample
    ))

    key = jax.random.PRNGKey(args.seed)
    params = init_params(cfg, key)
    opt_state = opt.init(params)
    n_params = sum(p.size for p in jax.tree.leaves(params))
    print(f"{cfg.name}: {n_params/1e6:.2f}M params, family={cfg.family}")

    t0 = time.time()
    losses = []
    for i, batch in enumerate(
        synthetic_batches(cfg, args.batch, args.seq, args.steps, args.seed)
    ):
        key, sub = jax.random.split(key)
        params, opt_state, metrics = step_fn(params, opt_state, batch, sub)
        losses.append(float(metrics["loss"]))
        if (i + 1) % args.log_every == 0:
            rate = args.batch * args.seq * args.log_every / (time.time() - t0)
            print(f"step {i+1:5d} loss={losses[-1]:.4f} tok/s={rate:,.0f}")
            t0 = time.time()
    print(f"final loss: {losses[-1]:.4f} (start {losses[0]:.4f})")
    assert np.isfinite(losses[-1]), "training diverged"


if __name__ == "__main__":
    main()
