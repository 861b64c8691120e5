"""Paper-faithful Figs. 5/6: v = 0.01, 400 trees, held-out evaluation.

This is the configuration under which the paper's C1 claim reproduces
INCLUDING direction (see EXPERIMENTS.md §Validity): on held-out loss,
asynchrony is free on the high-diversity sparse dataset and degrades
monotonically with worker count on the low-diversity dense dataset.

Slow (~6 full 400-tree runs); not part of the default benchmark suite —
run explicitly:  PYTHONPATH=src python -m benchmarks.fig5_fig6_paperfaithful
"""
from __future__ import annotations


import repro.data as D
from benchmarks.common import save
from repro.core.async_sgbdt import train_async, worker_round_robin
from repro.core.sgbdt import SGBDTConfig, train_loss
from repro.trees import forest_predict
from repro.trees.learner import LearnerConfig
from repro.trees.losses import logistic_loss
from repro.launch.compile_cache import enable_compile_cache

WORKERS = [1, 16, 32]


def run(quick: bool = False) -> dict:
    n_trees = 100 if quick else 400
    out: dict = {}
    for tag, data_all, depth in [
        ("realsim", D.make_sparse_classification(4000, 1500, 25, seed=7), 7),
        ("higgs", D.make_dense_low_diversity(300, 28, 60000, seed=11), 5),
    ]:
        n = data_all.n_samples
        ntr = int(n * 0.8)
        tr = data_all._replace(
            bins=data_all.bins[:ntr], labels=data_all.labels[:ntr],
            multiplicity=data_all.multiplicity[:ntr],
        )
        te_b, te_y = data_all.bins[ntr:], data_all.labels[ntr:]
        cfg = SGBDTConfig(
            n_trees=n_trees, step_length=0.01, sampling_rate=0.8,
            learner=LearnerConfig(depth=depth, n_bins=64, feature_fraction=0.8),
        )
        for w in WORKERS:
            st = train_async(cfg, tr, worker_round_robin(n_trees, w), seed=0)
            trl = float(train_loss(cfg, tr, st))
            tel = float(logistic_loss(te_y, forest_predict(st.forest, te_b)))
            out[f"{tag}_W{w}"] = {"train": trl, "test": tel}
            print(f"  {tag} W={w:3d}: train {trl:.4f} test {tel:.4f}", flush=True)
    save("fig56_paperfaithful", out)
    return out


def main(quick: bool = False):
    enable_compile_cache()
    res = run(quick)
    print("\npaper C1: realsim test loss flat in W; higgs test loss rises "
          "monotonically with W.")
    return res


if __name__ == "__main__":
    main()
