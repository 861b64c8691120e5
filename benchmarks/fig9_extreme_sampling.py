"""Paper Fig. 9: an extremely small sampling rate kills sensitivity to
asynchrony (conclusion 1+3) but slows convergence — the trees are built
from too few samples and get 'distorted'."""
from __future__ import annotations


from benchmarks.common import paper_cfg, realsim_like, save
from repro.core.async_sgbdt import train_async, worker_round_robin
from repro.core.sgbdt import train_loss
from repro.launch.compile_cache import enable_compile_cache


def run(quick: bool = True) -> dict:
    n_trees = 120 if quick else 400
    data = realsim_like(quick)
    # paper: 0.000005 on 72k samples ~ 500 rows; scale to our N
    tiny = max(200.0 / data.n_samples, 1e-4)
    out: dict = {"rates": [tiny, 0.6], "curves": {}}
    for rate in (tiny, 0.6):
        cfg = paper_cfg(n_trees, 6, sampling_rate=rate)
        for w in (1, 16):
            losses: list[float] = []
            train_async(
                cfg, data, worker_round_robin(n_trees, w), seed=0,
                eval_every=max(n_trees // 10, 1),
                eval_fn=lambda st, j: losses.append(
                    float(train_loss(cfg, data, st))
                ),
            )
            out["curves"][f"rate{rate:.6f}_W{w}"] = losses
            print(f"  rate={rate:.6f} W={w}: final {losses[-1]:.4f}", flush=True)
    save("fig9_extreme_sampling", out)
    return out


def main(quick: bool = True):
    enable_compile_cache()
    res = run(quick)
    c = res["curves"]
    keys = sorted(c)
    tiny_keys = [k for k in keys if not k.startswith("rate0.6")]
    big_keys = [k for k in keys if k.startswith("rate0.6")]
    gap_tiny = abs(c[tiny_keys[1]][-1] - c[tiny_keys[0]][-1])
    gap_big = abs(c[big_keys[1]][-1] - c[big_keys[0]][-1])
    slower = c[tiny_keys[0]][-1] > c[big_keys[0]][-1]
    print(f"\nasync gap tiny-rate={gap_tiny:.4f} vs normal-rate={gap_big:.4f} "
          f"(paper: tiny < normal); tiny-rate converges slower: {slower}")
    return res


if __name__ == "__main__":
    main()
