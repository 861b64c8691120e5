"""Ablation: Proposition 1's step-length law, measured.

Prop. 1 prescribes v ∝ 1 / (1 + 6ρτ + O(τ²)): the maximum step length that
keeps asynchrony harmless shrinks roughly hyperbolically with staleness τ.
Measurement: over a grid of step lengths v, call (v, W) *stable* when the
W-worker run's final loss is within 10% (of the achievable improvement) of
the SAME-v serial run — i.e. staleness cost ≈ 0 at that step size. For each
W, report the largest stable v; fit ρ to the decay and report the residual.
"""
from __future__ import annotations

import numpy as np

import repro.data as D
from benchmarks.common import paper_cfg, save
from repro.core.async_sgbdt import train_async, worker_round_robin
from repro.core.sgbdt import init_state, train_loss
from repro.launch.compile_cache import enable_compile_cache

WORKERS = [1, 2, 4, 8, 16, 32]
STEPS = [0.05, 0.1, 0.2, 0.4, 0.8, 1.2, 1.8, 2.5]


def run(quick: bool = True) -> dict:
    n_trees = 80 if quick else 200
    data = D.make_sparse_classification(1_200, 400, 12, seed=5)
    base = paper_cfg(n_trees, 5, sampling_rate=0.8)
    l0 = float(train_loss(base, data, init_state(base, data)))

    # serial reference per step length
    serial = {}
    for v in STEPS:
        cfg = base._replace(step_length=v)
        st = train_async(cfg, data, worker_round_robin(n_trees, 1), seed=0)
        serial[v] = float(train_loss(cfg, data, st))

    vmax: dict[int, float] = {}
    grid: dict[str, dict] = {}
    for w in WORKERS:
        best = 0.0
        grid[str(w)] = {}
        for v in STEPS:
            cfg = base._replace(step_length=v)
            st = train_async(cfg, data, worker_round_robin(n_trees, w), seed=0)
            lw = float(train_loss(cfg, data, st))
            slack = 0.10 * max(l0 - serial[v], 1e-9)
            stable = np.isfinite(lw) and lw <= serial[v] + slack
            grid[str(w)][str(v)] = {"loss": lw, "stable": bool(stable)}
            if stable:
                best = max(best, v)
        vmax[w] = best
        print(f"  W={w:3d}: max stable step = {best:.2f}", flush=True)

    v0 = max(vmax[1], 1e-9)
    taus = np.array([w - 1 for w in WORKERS if w > 1], float)
    ratios = np.array([vmax[w] / v0 for w in WORKERS if w > 1])
    ok = ratios > 0
    rho = (
        float(np.mean(((1.0 / ratios[ok]) - 1.0) / (6.0 * taus[ok])))
        if ok.any() else 0.0
    )
    pred = 1.0 / (1.0 + 6.0 * rho * taus)
    resid = float(np.max(np.abs(pred[ok] - ratios[ok]))) if ok.any() else 1.0
    monotone = all(
        vmax[a] >= vmax[b] - 1e-9 for a, b in zip(WORKERS, WORKERS[1:])
    )

    out = {
        "workers": WORKERS,
        "steps_grid": STEPS,
        "max_stable_step": {str(w): vmax[w] for w in WORKERS},
        "serial_loss_by_step": {str(v): serial[v] for v in STEPS},
        "grid": grid,
        "fitted_rho": rho,
        "max_abs_residual": resid,
        "monotone_decreasing": monotone,
    }
    save("ablation_prop1", out)
    return out


def main(quick: bool = True):
    enable_compile_cache()
    res = run(quick)
    print(f"\nmax stable step: " + "  ".join(
        f"W{w}={res['max_stable_step'][str(w)]:.2f}" for w in res["workers"]
    ))
    print(f"monotone decreasing: {res['monotone_decreasing']}; "
          f"fitted rho = {res['fitted_rho']:.3f} "
          f"(residual {res['max_abs_residual']:.3f})")
    print("expected (Prop. 1): the stable-step ceiling falls with worker "
          "count, ~1/(1+6*rho*tau).")
    return res


if __name__ == "__main__":
    main()
