"""Paper Figs. 5 & 6: convergence vs number of workers, fixed sampling rate.

Fig. 5 (Higgs, low diversity): more workers => visibly slower per-epoch
convergence. Fig. 6 (real-sim, high diversity): worker count barely moves
the curve. Workers are executed exactly as delay schedules k(j) = j - W + 1
(threads-as-workers steady state, the paper's validity-experiment setup).
"""
from __future__ import annotations

import numpy as np

from benchmarks.common import higgs_like, paper_cfg, realsim_like, save
from repro.core.async_sgbdt import train_async, worker_round_robin
from repro.core.sgbdt import train_loss
from repro.launch.compile_cache import enable_compile_cache

WORKERS = [1, 4, 8, 16, 32]


def run(quick: bool = True) -> dict:
    n_trees = 120 if quick else 400
    out: dict = {"workers": WORKERS, "n_trees": n_trees, "curves": {}}
    for tag, data, depth, rate in [
        ("fig6_realsim", realsim_like(quick), 6, 0.5),
        ("fig5_higgs", higgs_like(quick), 4, 0.5),
    ]:
        cfg = paper_cfg(n_trees, depth, sampling_rate=rate)
        curves = {}
        for w in WORKERS:
            losses: list[float] = []
            train_async(
                cfg, data, worker_round_robin(n_trees, w), seed=0,
                eval_every=max(n_trees // 20, 1),
                eval_fn=lambda st, j: losses.append(
                    float(train_loss(cfg, data, st))
                ),
            )
            curves[str(w)] = losses
            print(f"  {tag} W={w:3d}: final loss {losses[-1]:.4f}", flush=True)
        out["curves"][tag] = curves
        # sensitivity index: area between the W curve and the W=1 curve
        base = np.asarray(curves["1"])
        out.setdefault("sensitivity", {})[tag] = {
            str(w): float(np.mean(np.asarray(curves[str(w)]) - base))
            for w in WORKERS
        }
    save("fig5_fig6_convergence", out)
    return out


def main(quick: bool = True):
    enable_compile_cache()
    res = run(quick)
    s = res["sensitivity"]
    print("\nsensitivity to workers (mean loss gap vs W=1; paper: higgs >> realsim)")
    for tag in s:
        print(f"  {tag}: " + " ".join(f"W{w}={v:+.4f}" for w, v in s[tag].items()))
    return res


if __name__ == "__main__":
    main()
