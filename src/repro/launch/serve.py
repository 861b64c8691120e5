"""Serving driver — batched prefill + greedy decode against the ring cache.

    PYTHONPATH=src python -m repro.launch.serve --arch zamba2-1.2b \
        --reduced --batch 4 --prompt-len 64 --gen 32

``--arch gbdt`` instead serves the paper's own model: train an
asynch-SGBDT forest on the PS engine, checkpoint it mid-run and at the
end, then answer batched raw-float prediction requests through the
``ForestServer`` (serve-time binning + fused traversal), hot-swapping to
the newest checkpoint between waves:

    PYTHONPATH=src python -m repro.launch.serve --arch gbdt \
        --trees 60 --requests 12 [--rows 64] [--workers 8] \
        [--objective logistic|multiclass:3|...]

``--engine continuous`` serves the same traffic through the
continuous-batching ``ForestEngine`` instead: the mid-training and final
checkpoints load as two named versions, traffic A/B-splits between them
by uid hash, and per-request p50/p99 queue+compute latency is reported
against ``--slo-ms``. ``--quantize int8|fp16`` packs the served forests
(both engines) with the documented score-error bound.
"""
from __future__ import annotations

import argparse
import tempfile
import time

import jax
import jax.numpy as jnp
import numpy as np

import repro.configs as configs
import repro.sharding as sharding
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_host_mesh
from repro.launch.steps import make_decode_step, make_prefill_step
from repro.models import init_params


def run_gbdt(args) -> None:
    """Train -> checkpoint -> serve handoff, with a live hot swap.

    ``--objective`` picks the training objective; the server applies its
    ``link`` inside the jitted predict, so multiclass serves (rows, K)
    softmax probabilities and logistic serves p(y=1).
    """
    from repro.checkpoint import CheckpointManager
    from repro.core.sgbdt import SGBDTConfig
    from repro.objectives import get_objective
    from repro.ps import Trainer
    from repro.serving import (
        ForestEngine,
        ForestServer,
        PredictRequest,
        load_forest_checkpoint,
        percentile_latencies,
    )
    from repro.trees.binning import bin_dataset
    from repro.trees.learner import LearnerConfig

    obj = get_objective(args.objective)
    rng = np.random.default_rng(args.seed)
    n, dim = 2_000, 40
    if obj.n_outputs > 1 or obj.name == "lambdarank":
        # Objectives with structured targets (class ids, query groups) use
        # the shared objective -> workload dispatch.
        from repro.launch.train import gbdt_dataset_for

        _, data = gbdt_dataset_for(args.objective, args.seed, n=n)
        dim = data.n_features
    else:
        # Scalar-target objectives (logistic/mse/quantile/huber) all train
        # on the demo's lightweight dense set — fast enough for CI smokes.
        x = rng.standard_normal((n, dim)).astype(np.float32)
        w = rng.standard_normal(dim).astype(np.float32)
        y = (x @ w + 0.1 * rng.standard_normal(n) > 0).astype(np.float32)
        data = bin_dataset(x, y, n_bins=64)

    cfg = SGBDTConfig(
        n_trees=args.trees,
        step_length=0.15,
        sampling_rate=0.8,
        objective=args.objective,
        learner=LearnerConfig(depth=5, n_bins=64, feature_fraction=0.8),
    )
    ckpt_dir = args.ckpt_dir or tempfile.mkdtemp(prefix="gbdt_serve_")
    ckpt = CheckpointManager(ckpt_dir, save_every=1, keep=4)
    half = max(args.trees // 2, 1)
    print(f"gbdt: training {args.trees} trees ({args.workers} PS workers), "
          f"checkpointing steps {half} and {args.trees} -> {ckpt_dir}")
    trainer = Trainer(cfg)
    state = trainer.train(
        data, ("round_robin", args.workers), seed=args.seed,
        eval_every=half, eval_fn=lambda st, j: ckpt.maybe_save(j, st),
    )
    ckpt.maybe_save(args.trees, state)  # idempotent when half divides trees

    quantize = None if args.quantize == "none" else args.quantize
    reqs = [
        PredictRequest(
            uid=i,
            x=rng.standard_normal((int(rng.integers(1, args.rows // 2 + 1)), dim))
            .astype(np.float32),
        )
        for i in range(args.requests)
    ]

    if args.engine == "continuous":
        # Two checkpoints, two live versions: traffic A/B-splits by uid
        # hash, each result labeled with its version and that version's
        # own model_step.
        eng = ForestEngine(
            data.bin_edges, max_rows=args.rows, slo_s=args.slo_ms / 1e3
        )
        eng.add_version(
            "half", load_forest_checkpoint(ckpt_dir, half),
            model_step=half, objective=obj, quantize=quantize,
        )
        t0 = time.time()
        first = eng.run(reqs[: args.requests // 2])
        eng.add_version(
            "full", load_forest_checkpoint(ckpt_dir, args.trees),
            model_step=args.trees, objective=obj, quantize=quantize,
            weight=3.0,  # ramp the new version to 75% of the split
        )
        second = eng.run(reqs[args.requests // 2:])
        dt = time.time() - t0
        outs = first + second
        rows = sum(len(r.scores) for r in outs)
        split: dict[str, int] = {}
        for r in second:
            split[r.version] = split.get(r.version, 0) + 1
        stats = percentile_latencies(outs)
        print(f"continuous engine: served {len(outs)} requests / {rows} rows "
              f"in {dt:.2f}s (quantize={quantize or 'off'}); "
              f"post-ramp A/B split {split}")
        print(f"  latency p50/p99: queue {stats['queue_p50_ms']:.2f}/"
              f"{stats['queue_p99_ms']:.2f} ms, compute "
              f"{stats['compute_p50_ms']:.2f}/{stats['compute_p99_ms']:.2f} ms,"
              f" end-to-end {stats['latency_p50_ms']:.2f}/"
              f"{stats['latency_p99_ms']:.2f} ms (SLO {args.slo_ms:.0f} ms)")
        for r in outs[:3]:
            print(f"  req {r.uid}: {len(r.scores)} rows, "
                  f"version={r.version}, model_step={r.model_step}, "
                  f"scores[:4]={np.round(r.scores[:4], 4).tolist()}")
        assert {r.model_step for r in first} == {half}
        assert all(
            r.model_step == (half if r.version == "half" else args.trees)
            for r in second
        )
        assert all(np.isfinite(r.scores).all() for r in outs), "non-finite"
        return

    # Serve from the mid-training (partially-filled) checkpoint first; the
    # checkpoint root is attached only after the first batch so the demo
    # shows both model versions answering live traffic.
    server = ForestServer(
        load_forest_checkpoint(ckpt_dir, half),
        data.bin_edges,
        max_rows=args.rows,
        model_step=half,
        objective=obj,
        quantize=quantize,
    )
    t0 = time.time()
    first = server.run(reqs[: args.requests // 2])
    server.ckpt_root = ckpt_dir
    swapped = server.maybe_reload()
    second = server.run(reqs[args.requests // 2:])
    dt = time.time() - t0
    outs = first + second
    rows = sum(len(r.scores) for r in outs)
    print(f"served {len(outs)} requests / {rows} rows in {dt:.2f}s "
          f"({rows / dt:,.0f} rows/s incl. compile) over "
          f"{server.waves_served} waves (quantize={quantize or 'off'})")
    step_before = first[-1].model_step if first else half
    print(f"hot swap: step {step_before} -> {server.model_step} "
          f"(reloaded={swapped})")
    for r in outs[:3]:
        print(f"  req {r.uid}: {len(r.scores)} rows, model_step={r.model_step}, "
              f"scores[:4]={np.round(r.scores[:4], 4).tolist()}")
    assert swapped and server.model_step == args.trees
    assert all(np.isfinite(r.scores).all() for r in outs), "non-finite scores"


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="granite-3-2b")
    ap.add_argument("--reduced", action="store_true", default=True)
    ap.add_argument("--full", dest="reduced", action="store_false")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=64)
    ap.add_argument("--gen", type=int, default=32)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--trees", type=int, default=60,
                    help="forest size to train then serve (--arch gbdt)")
    ap.add_argument("--workers", type=int, default=8,
                    help="PS worker count for the training phase (--arch gbdt)")
    ap.add_argument("--requests", type=int, default=12,
                    help="prediction requests to serve (--arch gbdt)")
    ap.add_argument("--rows", type=int, default=64,
                    help="wave capacity in rows (--arch gbdt)")
    ap.add_argument("--ckpt-dir", default=None,
                    help="checkpoint directory (default: fresh tempdir)")
    ap.add_argument("--objective", default="logistic",
                    help="GBDT objective spec; served outputs go through "
                         "its link (e.g. multiclass:3 -> softmax rows)")
    ap.add_argument("--engine", default="wave",
                    choices=["wave", "continuous"],
                    help="wave: drain-the-queue ForestServer demo; "
                         "continuous: multi-version SLO-cutting ForestEngine")
    ap.add_argument("--quantize", default="none",
                    choices=["none", "int8", "fp16"],
                    help="serve a quantized forest payload (documented "
                         "score-error bound, 4x/2x smaller VMEM blocks)")
    ap.add_argument("--slo-ms", type=float, default=50.0,
                    help="latency SLO for continuous-engine wave cutting")
    args = ap.parse_args()
    enable_compile_cache()

    if args.arch == "gbdt":
        return run_gbdt(args)

    cfg = configs.get(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    mesh = make_host_mesh()
    baxes = sharding.batch_axes(mesh)

    key = jax.random.PRNGKey(args.seed)
    params = init_params(cfg, key)
    max_len = args.prompt_len + args.gen

    prefill_fn = jax.jit(make_prefill_step(cfg, mesh, baxes, max_len=max_len))
    decode_fn = jax.jit(make_decode_step(cfg, mesh, baxes))

    prompts = jax.random.randint(
        key, (args.batch, args.prompt_len), 0, cfg.vocab_size
    )
    batch = {"tokens": prompts}
    if cfg.family in ("vlm", "audio"):
        batch["media"] = (
            jax.random.normal(
                key, (args.batch, cfg.n_media_tokens, cfg.d_model)
            ) * 0.02
        ).astype(jnp.dtype(cfg.dtype))

    t0 = time.time()
    next_tok, logits, cache = prefill_fn(params, batch)
    next_tok.block_until_ready()
    t1 = time.time()
    out = [np.asarray(next_tok)]
    tok = next_tok[:, None]
    for _ in range(args.gen - 1):
        tok_next, cache = decode_fn(params, tok, cache)
        out.append(np.asarray(tok_next))
        tok = tok_next[:, None]
    jax.block_until_ready(tok)
    t2 = time.time()

    gen = np.stack(out, axis=1)
    print(f"{cfg.name}: prefill {args.batch}x{args.prompt_len} in {t1-t0:.2f}s; "
          f"decoded {args.gen} tokens in {t2-t1:.2f}s "
          f"({args.batch*args.gen/(t2-t1):,.1f} tok/s)")
    print("sample:", gen[0, :16].tolist())
    assert gen.min() >= 0 and gen.max() < cfg.vocab_size


if __name__ == "__main__":
    main()
