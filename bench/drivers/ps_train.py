"""Training traffic: the threaded parameter-server runtime over a window.

The window drives ``repro.ps.runtime.AsyncRuntime.run`` with W worker
threads, the path ``launch/train.py --runtime threads`` runs. The forest
keeps the configuration's published capacity; the run folds J rounds,
J = max(2W, round(seconds / round_s_nominal)) (and no fewer than
the folds the output check replays): a fixed amount of work per
cell, set so that the window lasts about ``--seconds`` on the chip it was
measured on. The workers that draw the last W tickets leave after pushing
them (``FaultPlan.leave_tickets``), so every ticket drawn is folded and no
build runs past the window's end; the server halts at fold J.

The window opens when the first worker thread calls the build program and
closes when ``run`` returns, after the J-th fold has been drained. What
``run`` does before its threads start (a build and a fold of its own, on
the calling thread) falls between set-up and the window and is timed by
neither.
"""
from __future__ import annotations

import threading
import time

import numpy as np


def sgbdt_config(config: dict):
    from repro.core.sgbdt import SGBDTConfig
    from repro.trees.learner import LearnerConfig

    ln = config["learner"]
    return SGBDTConfig(
        n_trees=config["forest"]["trees"],
        step_length=ln["step_length"],
        sampling_rate=ln["sampling_rate"],
        loss=ln["loss"],
        step_kind=ln["step_kind"],
        learner=LearnerConfig(
            depth=ln["depth"], n_bins=config["data"]["n_bins"], lam=ln["lam"],
            min_child_hess=ln["min_child_hess"],
            feature_fraction=ln["feature_fraction"], backend=ln["backend"],
            hist_mode=ln["hist_mode"],
        ),
    )


def window_rounds(config: dict, traffic: dict, seconds: float) -> int:
    workers = traffic["workers"]
    train = config["train"]
    return max(2 * workers, train["check_folds"], round(seconds / train["round_s_nominal"]))


def run(ctx) -> dict:
    import jax
    import jax.numpy as jnp

    from bench import data as D
    from bench import reference as R
    from repro.core.sgbdt import init_state
    from repro.ps.runtime import AsyncRuntime, FaultPlan

    config, traffic, spans = ctx.config, ctx.traffic, ctx.spans
    workers = traffic["workers"]
    n_rounds = window_rounds(config, traffic, ctx.seconds)
    pseed = D.program_seed(ctx.seed)

    with spans.span("bench.setup.data"):
        data = D.make_dataset(ctx.seed, config)
        jax.block_until_ready(data)
    cfg = sgbdt_config(config)
    rt = AsyncRuntime(cfg, data, workers,
                      faults=FaultPlan(leave_tickets=range(n_rounds - workers, n_rounds)))
    with spans.span("bench.setup.warmup"):
        # The programs the window runs, at the window's shapes: the build,
        # the fold, and the round keys' split inside ``run``.
        state = init_state(cfg, data)
        keys = jax.random.split(jax.random.PRNGKey(pseed), cfg.n_trees)
        tree, delta = rt._propose(data, state.f, keys[0])
        jax.block_until_ready(rt._fold(state.forest, state.f, tree, delta, jnp.int32(0)))
        del state, tree, delta, keys

    main = threading.current_thread()
    propose, fold = rt._propose, rt._fold

    def spanned_propose(*args):
        name = "bench.build.warmup" if threading.current_thread() is main else "bench.build"
        with spans.span(name):
            return propose(*args)

    rt._propose = spanned_propose
    rt._fold = spans.wrap("bench.fold", fold)

    ctx.start_trace()
    before = set(threading.enumerate())
    with spans.span("bench.run"):
        state, run_trace = rt.run(pseed, halt_at_fold=n_rounds)
    t_end = time.perf_counter()
    for t in set(threading.enumerate()) - before:
        if t.name.endswith("(worker)"):  # the runtime's worker threads
            t.join(60.0)
    ctx.stop_trace()
    first = min(r[1] for r in spans.named("bench.build"))
    window_s = t_end - first
    ctx.window(first, t_end)
    ctx.read_memory()

    n_check = config["train"]["check_folds"]
    forest = state.forest
    program = tuple(np.asarray(a[:n_check]) for a in
                    (forest.feature, forest.threshold, forest.leaf_value))
    folded = (np.asarray(forest.feature[:n_rounds]), np.asarray(forest.threshold[:n_rounds]))
    live = int(forest.n_trees)
    del state, forest, rt
    with spans.span("bench.reference"):
        ln = R.learner_from_config(config)
        ref = R.replay(R.as_reference_bins(data.bins), data.labels, data.multiplicity,
                       jax.random.PRNGKey(pseed), cfg.n_trees,
                       run_trace.schedule[:n_check], run_trace.key_index[:n_check], ln)
    numbers = R.compare_trees(*program, *ref)
    limits = config["check"]
    compared = [
        ["folded_rounds", live, n_rounds, "=="],
        ["leaf_gap", numbers["leaf_gap"], limits["leaf_gap"], "<="],
    ]
    # Not compared: near-tied splits flip between the program's f32 sums
    # and the reference's on sound runs (PERF.md, the output check).
    ctx.notes["split_mismatch"] = numbers["split_mismatch"]
    ctx.layer_inputs.update(run_trace=run_trace, n_rounds=n_rounds, data=data,
                            cfg=cfg, pseed=pseed, window_s=window_s)
    if ctx.trace:
        from bench.counts import realized

        ctx.layer_inputs["builds"] = realized.builds(data, cfg, pseed, run_trace, *folded)
    return {
        "attempted": n_rounds,
        "failed": n_rounds - len(run_trace.schedule),
        "end_to_end": {"train_rounds_per_s": n_rounds / window_s},
        "compared": compared,
        "setup_end": first,
        "trace_window": ("bench.build", "bench.run"),
    }

