"""Paper Figs. 7 & 8: sensitivity to the sampling rate at a fixed worker
count. Higher sampling rates make the algorithm MORE sensitive to
asynchrony (conclusion 3); the effect is strong on low-diversity data
(Higgs, Fig. 7) and mild on high-diversity data (real-sim, Fig. 8)."""
from __future__ import annotations

import numpy as np

from benchmarks.common import higgs_like, paper_cfg, realsim_like, save
from repro.core.async_sgbdt import train_async, worker_round_robin
from repro.core.sgbdt import train_loss
from repro.data.sampling import diversity_stats
from repro.launch.compile_cache import enable_compile_cache

RATES = [0.2, 0.4, 0.6, 0.8]
W = 16


def run(quick: bool = True) -> dict:
    n_trees = 120 if quick else 400
    out: dict = {"rates": RATES, "workers": W, "curves": {}, "diversity": {}}
    for tag, data, depth in [
        ("fig8_realsim", realsim_like(quick), 6),
        ("fig7_higgs", higgs_like(quick), 4),
    ]:
        curves = {}
        for rate in RATES:
            cfg = paper_cfg(n_trees, depth, sampling_rate=rate)
            for w in (1, W):
                losses: list[float] = []
                train_async(
                    cfg, data, worker_round_robin(n_trees, w), seed=0,
                    eval_every=max(n_trees // 10, 1),
                    eval_fn=lambda st, j: losses.append(
                        float(train_loss(cfg, data, st))
                    ),
                )
                curves[f"rate{rate}_W{w}"] = losses
            stats = diversity_stats(rate, data.multiplicity)
            out["diversity"].setdefault(tag, {})[str(rate)] = {
                k: float(v) for k, v in stats.items()
            }
            gap = np.mean(
                np.asarray(curves[f"rate{rate}_W{W}"])
                - np.asarray(curves[f"rate{rate}_W1"])
            )
            print(f"  {tag} rate={rate}: async gap {gap:+.4f} "
                  f"delta={out['diversity'][tag][str(rate)]['delta']:.3f}",
                  flush=True)
        out["curves"][tag] = curves
    save("fig7_fig8_sampling_sensitivity", out)
    return out


def main(quick: bool = True):
    enable_compile_cache()
    res = run(quick)
    print("\nasync gap should grow with sampling rate (conclusion 3),")
    print("and be larger on the low-diversity (higgs) dataset (conclusion 5).")
    return res


if __name__ == "__main__":
    main()
