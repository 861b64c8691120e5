"""Ablation: gradient vs Newton (xgboost-style) steps under staleness.

The paper's counter-intuitive conclusion 2: "Only gradient step can use
asynchronous parallel manner. Thus, xgboost cannot be modified into
asynch-parallel manner." Mechanism: the Newton leaf -G/(H+lam) divides by a
curvature estimated at the STALE F^{k(j)}; near the optimum the stale
hessian underestimates p(1-p) drift and the effective step inflates, so
staleness hurts Newton steps disproportionately. The gradient leaf only
rescales by sample counts, which are staleness-independent.

We train both step kinds at matched effective speed (Newton needs no
step-length tuning; gradient uses the same v) and compare the relative
degradation from W=1 to W=16/32.
"""
from __future__ import annotations


from benchmarks.common import paper_cfg, realsim_like, save
from repro.core.async_sgbdt import train_async, worker_round_robin
from repro.core.sgbdt import train_loss
from repro.launch.compile_cache import enable_compile_cache

WORKERS = [1, 16, 32]


def run(quick: bool = True) -> dict:
    n_trees = 120 if quick else 400
    data = realsim_like(quick)
    out: dict = {"workers": WORKERS, "final_loss": {}}
    for kind in ("gradient", "newton"):
        cfg = paper_cfg(n_trees, 6, sampling_rate=0.8, step=0.3)._replace(
            step_kind=kind
        )
        losses = {}
        for w in WORKERS:
            st = train_async(
                cfg, data, worker_round_robin(n_trees, w), seed=0
            )
            losses[str(w)] = float(train_loss(cfg, data, st))
        out["final_loss"][kind] = losses
        base = losses["1"]
        degr = {w: losses[w] - base for w in losses}
        print(f"  {kind:9s}: " + "  ".join(
            f"W{w}={losses[w]:.4f} (Δ{degr[w]:+.4f})" for w in losses
        ), flush=True)
    g = out["final_loss"]["gradient"]
    n = out["final_loss"]["newton"]
    out["degradation_ratio_w32"] = float(
        (n["32"] - n["1"]) / max(g["32"] - g["1"], 1e-9)
        if (g["32"] - g["1"]) > 0 else (n["32"] - n["1"])
    )
    save("ablation_newton", out)
    return out


def main(quick: bool = True):
    enable_compile_cache()
    res = run(quick)
    print("\npaper conclusion 2: Newton (xgboost-style) steps should degrade "
          "more under staleness than gradient steps.")
    return res


if __name__ == "__main__":
    main()
