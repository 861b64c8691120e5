"""Smoke test of the asynch-SGBDT train and serve path on a TPU.

    python chip_smoke.py             # one chip
    python chip_smoke.py --chips 4   # the sharded path on four chips

One chip: trains the paper's validity-higgs configuration (depth 5, 64
bins, feature fraction 0.8, sampling rate 0.8, logistic loss) through
``repro.ps.Trainer`` with the platform's default (``auto``) tree-build
backend, W=4 round-robin workers, 8 rounds, on data at the published HIGGS
geometry (11,000,000 x 28). It checks that the train loss is finite and
falls, that the compiled round runs Mosaic kernels, and that one tree
build under the ``pallas`` kernels matches the jnp oracle. It then serves
requests of several sizes from a 1000-slot forest through ``ForestEngine``
and checks the scores against ``ref.forest_traverse_ref``.

Four chips (``--chips 4``): trains on the (4, 1), (2, 2) and (1, 4)
(data x feature) meshes, dense at the HIGGS geometry and, on (1, 4), a
``SparseBins`` set at real-sim's published geometry, and compares each
forest with its contract: (1, P_f) is bitwise equal to the single-device
Trainer, (P_d, P_f) to the 1D P_d-shard build. It also checks that the
(4, 1) layout draws the same per-round sampling weights and gradients as
one device, and measures how far each one's first-round leaves sit from
their float64 sums.

Every phase prints its wall seconds, its compile seconds and its hits in
JAX's persistent compilation cache. Any failed check raises. The script
exits non-zero, printing no result line, unless JAX's first device is a
TPU; the last line of stdout is ``{"ok": true, "device": {...}}``.
"""
from __future__ import annotations

import argparse
import contextlib
import functools
import json
import pathlib
import sys
import time

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

HIGGS_ROWS, HIGGS_FEATURES = 11_000_000, 28  # the published HIGGS geometry
# real-sim (LIBSVM binary datasets): 72,309 x 20,958 with 3,709,083
# nonzeros, 51.3 per row.
REALSIM_ROWS, REALSIM_FEATURES, REALSIM_NNZ = 72_309, 20_958, 51
SEED = 0
ROUNDS, WORKERS = 8, 4
SERVE_SLOTS = 1000  # the paper's Higgs tree count
REQUEST_ROWS = (1, 17, 256, 300, 1000)
# Leaves are -G / (H + lam) over the same samples on every backend once the
# structure agrees; the sums only differ in f32 accumulation order.
LEAF_RTOL, LEAF_ATOL = 1e-4, 1e-7
SCORE_RTOL, SCORE_ATOL = 1e-5, 1e-6


def check(ok: bool, what: str) -> None:
    if not ok:
        raise RuntimeError(f"check failed: {what}")


class CompileMeter:
    """Sums backend compile seconds and persistent-cache hits/misses from
    JAX's monitoring events."""

    def __init__(self):
        self.compile_s = 0.0
        self.hits = 0
        self.misses = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event: str, duration: float, **_) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.compile_s += duration

    def _event(self, event: str, **_) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    @contextlib.contextmanager
    def phase(self, name: str, report: dict):
        c0, h0, m0 = self.compile_s, self.hits, self.misses
        t0 = time.perf_counter()
        yield
        row = {
            "wall_s": time.perf_counter() - t0,
            "compile_s": self.compile_s - c0,
            "cache_hits": self.hits - h0,
            "cache_misses": self.misses - m0,
        }
        report[name] = row
        print(f"phase {name}: wall {row['wall_s']:.2f}s compile "
              f"{row['compile_s']:.2f}s cache hits {row['cache_hits']} "
              f"misses {row['cache_misses']}", flush=True)


def higgs_config(rounds: int):
    """The paper's validity-higgs settings (``configs.gbdt``) with
    ``rounds`` boosting rounds and the platform's default backend."""
    from repro.configs.gbdt import EXPERIMENTS

    cfg = EXPERIMENTS["validity-higgs"].config
    return cfg._replace(
        n_trees=rounds, learner=cfg.learner._replace(backend="auto")
    )


def host_binned(make):
    """Build a dataset with its binning on the host CPU (the raw floats
    never occupy the chip), then move it to the default device."""
    with jax.default_device(jax.devices("cpu")[0]):
        data = make()
    device = jax.devices()[0]

    def put(x):
        return None if x is None else jax.device_put(x, device)

    return data._replace(
        bins=jax.tree.map(put, data.bins), bin_edges=put(data.bin_edges),
        labels=put(data.labels), multiplicity=put(data.multiplicity),
        qid=put(data.qid),
    )


def higgs_data(rows: int):
    import repro.data as D

    return host_binned(
        lambda: D.make_dense_low_diversity(rows, HIGGS_FEATURES, rows, seed=SEED)
    )


def realsim_data(feature_multiple: int):
    """real-sim's geometry in ``SparseBins``, widened by empty features
    (nothing stored, zero bin 0) until the width divides
    ``feature_multiple``: ``shard_map`` splits the feature axis evenly. An
    empty feature puts every sample in one bin, so it never wins a split."""
    import repro.data as D

    def make():
        data = D.make_sparse_classification(
            REALSIM_ROWS, REALSIM_FEATURES, REALSIM_NNZ, seed=SEED, sparse=True
        )
        sp, pad = data.bins, -REALSIM_FEATURES % feature_multiple
        rows = lambda x, v: jnp.pad(x, ((0, pad), (0, 0)), constant_values=v)  # noqa: E731
        return data._replace(
            bins=sp._replace(
                feat_rows=rows(sp.feat_rows, -1), feat_codes=rows(sp.feat_codes, 0),
                zero_bin=jnp.pad(sp.zero_bin, (0, pad)),
            ),
            bin_edges=rows(data.bin_edges, 0.0),
        )

    return host_binned(make)


def same_forest(a, b) -> bool:
    return all(
        bool(np.array_equal(np.asarray(getattr(a, k)), np.asarray(getattr(b, k))))
        for k in ("feature", "threshold", "leaf_value")
    )


def widen(forest, capacity: int):
    """The same live trees in a ``capacity``-slot forest (dead slots zero)."""
    pad = capacity - forest.feature.shape[0]
    slots = lambda x: jnp.pad(x, ((0, pad), (0, 0)))  # noqa: E731
    return forest._replace(
        feature=slots(forest.feature), threshold=slots(forest.threshold),
        leaf_value=slots(forest.leaf_value),
    )


# ------------------------------------------------------------------ one chip
def one_chip(meter: CompileMeter, report: dict) -> None:
    from repro.kernels import ref
    from repro.ps import Trainer
    from repro.ps.engine import propose_tree, round_body
    from repro.serving.continuous import ForestEngine
    from repro.serving.forest_server import PredictRequest
    from repro.trees.binning import apply_bins

    rows = HIGGS_ROWS
    print(f"rows: {rows} x {HIGGS_FEATURES} (published HIGGS {HIGGS_ROWS}; "
          f"cut {HIGGS_ROWS - rows} rows)", flush=True)
    with meter.phase("data", report):
        data = higgs_data(rows)
        jax.block_until_ready(data.bins)
    print(f"bins {data.bins.shape} {data.bins.dtype} "
          f"{data.bins.nbytes / 2**30:.3f} GiB on {data.bins.sharding}",
          flush=True)
    cfg = higgs_config(ROUNDS)
    check(jax.default_backend() == "tpu", "auto backend resolves on a TPU")

    trainer = Trainer(cfg)
    with meter.phase("train", report):
        state, losses = trainer.train_scan(data, ("round_robin", WORKERS), seed=SEED)
        losses = np.asarray(losses)
    print(f"train loss by round: {losses.tolist()}", flush=True)
    check(bool(np.isfinite(losses).all()), "train loss is finite")
    check(bool(losses[-1] < losses[0]), "train loss falls")
    report["loss_first"], report["loss_last"] = float(losses[0]), float(losses[-1])

    key = jax.random.PRNGKey(SEED + 1)
    with meter.phase("round_program", report):
        compiled = jax.jit(functools.partial(round_body, cfg)).lower(
            data, state.forest, state.f, state.f, key
        ).compile()
        kernels = compiled.as_text().count("tpu_custom_call")
    print(f"compiled round: {kernels} tpu_custom_call sites", flush=True)
    check(kernels > 0, "the compiled round runs Pallas kernels (tpu_custom_call)")
    report["round_kernels"] = kernels

    trees = {}
    for backend in ("ref", "pallas"):
        cfg_b = cfg._replace(learner=cfg.learner._replace(backend=backend))
        with meter.phase(f"tree_{backend}", report):
            tree, _ = propose_tree(cfg_b, data, state.f, key)
            trees[backend] = jax.device_get(tree)
    got, want = trees["pallas"], trees["ref"]
    same = (np.array_equal(got.feature, want.feature)
            and np.array_equal(got.threshold, want.threshold))
    dleaf = float(np.max(np.abs(got.leaf_value - want.leaf_value)))
    print(f"tree pallas vs ref: splits equal {same}, "
          f"max |leaf diff| {dleaf:.3e}", flush=True)
    check(same, "pallas tree splits equal the ref tree's")
    check(np.allclose(got.leaf_value, want.leaf_value,
                      rtol=LEAF_RTOL, atol=LEAF_ATOL),
          f"pallas leaves within rtol {LEAF_RTOL} atol {LEAF_ATOL}")
    report["tree_pallas_max_leaf_diff"] = dleaf

    forest = widen(state.forest, SERVE_SLOTS)
    rng = np.random.default_rng(SEED + 2)
    requests = [
        PredictRequest(uid=i, x=rng.standard_normal((n, HIGGS_FEATURES)).astype(np.float32))
        for i, n in enumerate(REQUEST_ROWS)
    ]
    engine = ForestEngine(data.bin_edges, max_rows=256)
    engine.add_version("live", forest)
    with meter.phase("serve", report):
        results = engine.run(requests)
    worst = 0.0
    for req, res in zip(requests, results):
        bins = apply_bins(jnp.asarray(req.x), data.bin_edges)
        want = np.asarray(forest.base_score + ref.forest_traverse_ref(
            bins, forest.feature, forest.threshold, forest.leaf_value,
            forest.n_trees, forest.depth,
        ))
        check(res.uid == req.uid and res.scores.shape == want.shape,
              f"request {req.uid} answered with {want.shape} scores")
        check(np.allclose(res.scores, want, rtol=SCORE_RTOL, atol=SCORE_ATOL),
              f"request {req.uid} scores match forest_traverse_ref")
        worst = max(worst, float(np.max(np.abs(res.scores - want))))
    print(f"served {len(results)} requests ({sum(REQUEST_ROWS)} rows) from a "
          f"{SERVE_SLOTS}-slot forest; max |score diff| vs ref {worst:.3e}",
          flush=True)
    report["serve_max_score_diff"] = worst


# --------------------------------------------------------------- four chips
def sample_and_grad(cfg):
    """``propose_tree``'s sampling and gradient step as one program: the
    Bernoulli weights m' and the weighted grad/hess the tree build sums."""
    from repro.data.sampling import bernoulli_weights

    @jax.jit
    def run(data, f, key):
        r_sample, _ = jax.random.split(key)
        m, _ = bernoulli_weights(r_sample, cfg.sampling_rate, data.multiplicity)
        g, h = cfg.obj.grad_hess(data.labels, f, qid=data.qid)
        return m, m * g, (m * h if cfg.step_kind == "newton" else m)

    return run


def row_layout_witness(cfg, dense, single, m41, f_single, report: dict) -> bool:
    """Why the (4, 1) forest's leaves differ from one device's: are the
    per-round draws and gradients the same, and how far is each forest's
    first tree (built on the identical initial predictions) from the float64
    leaf sums over its own routing?"""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.core.sgbdt import init_state
    from repro.launch.mesh import make_gbdt_mesh
    from repro.ps import Trainer
    from repro.trees.tree import Tree, leaf_indices

    mesh = make_gbdt_mesh(4, 1)
    placed = Trainer(cfg, mesh=mesh).place(dense)
    rows = lambda x: jax.device_put(x, NamedSharding(mesh, P("data")))  # noqa: E731
    run = sample_and_grad(cfg)
    # The trainer's round keys (Trainer._prep), gradients at the trained f.
    keys = jax.random.split(jax.random.PRNGKey(SEED), cfg.n_trees)
    f_rows = rows(f_single)
    same = []
    for key in keys:
        one, four = jax.device_get((run(dense, f_single, key), run(placed, f_rows, key)))
        same.append([bool(np.array_equal(a, b)) for a, b in zip(one, four)])
    print("mesh_4x1 vs single, per round [m', m'g, hess weight] bitwise: "
          f"{same}", flush=True)

    f0 = init_state(cfg, dense).f
    _, gw, hw = jax.device_get(run(dense, f0, keys[0]))
    leaf = np.asarray(leaf_indices(
        Tree(single.feature[0], single.threshold[0], single.leaf_value[0]), dense.bins
    ))
    n_leaf = single.leaf_value.shape[1]
    g64 = np.bincount(leaf, weights=gw.astype(np.float64), minlength=n_leaf)
    h64 = np.bincount(leaf, weights=hw.astype(np.float64), minlength=n_leaf)
    exact = cfg.step_length * np.where(h64 > 0, -g64 / (h64 + cfg.learner.lam), 0.0)
    err = {name: float(np.max(np.abs(f.leaf_value[0] - exact)))
           for name, f in (("single", single), ("mesh_4x1", m41))}
    by_slot = np.max(np.abs(m41.leaf_value - single.leaf_value), axis=1)
    by_slot = [float(x) for x in by_slot[: cfg.n_trees]]
    print(f"round-0 tree, max |leaf - float64 leaf|: single {err['single']:.3e}, "
          f"mesh_4x1 {err['mesh_4x1']:.3e}, max |leaf| {np.max(np.abs(exact)):.3e}; "
          f"mesh_4x1 vs single max |leaf diff| by round {by_slot}", flush=True)
    report["mesh_4x1_witness"] = {
        "draws_bitwise_by_round": same, "round0_leaf_err_vs_f64": err,
        "leaf_diff_by_round": by_slot,
    }
    return all(all(s) for s in same)


def four_chips(meter: CompileMeter, report: dict) -> None:
    from repro.launch.mesh import make_gbdt_mesh, make_mesh
    from repro.ps import Trainer

    check(len(jax.devices()) >= 4, "four devices are visible")
    f_pad = REALSIM_FEATURES + (-REALSIM_FEATURES % 4)
    print(f"dense: {HIGGS_ROWS} x {HIGGS_FEATURES} (published HIGGS, no cut); "
          f"sparse: {REALSIM_ROWS} x {REALSIM_FEATURES}, {REALSIM_NNZ} nnz/row "
          f"(published real-sim), widened by {f_pad - REALSIM_FEATURES} empty "
          f"features to {f_pad} = 4 x {f_pad // 4}", flush=True)
    with meter.phase("data_dense", report):
        dense = higgs_data(HIGGS_ROWS)
        jax.block_until_ready(dense.bins)
    with meter.phase("data_sparse", report):
        sparse = realsim_data(4)
        jax.block_until_ready(sparse.bins)
    sp = sparse.bins
    print(f"sparse store: row ELL {sp.indices.shape}, feature ELL "
          f"{sp.feat_rows.shape}", flush=True)
    cfg = higgs_config(4)
    schedule = ("round_robin", WORKERS)

    def train(data, mesh, name):
        trainer = Trainer(cfg, mesh=mesh)
        placed = trainer.place(data)
        where = jax.tree.map(lambda x: str(x.sharding), placed.bins)
        print(f"{name}: bins on {where}", flush=True)
        with meter.phase(f"train_{name}", report):
            state, _ = trainer.train_scan(placed, schedule, seed=SEED)
            jax.block_until_ready(state.f)
        return jax.device_get(state.forest), state.f

    single, f_single = train(dense, None, "single")
    single_sparse, _ = train(sparse, None, "single_sparse")
    d4, _ = train(dense, make_mesh((4,), ("data",)), "1d_4")
    d2, _ = train(dense, make_mesh((2,), ("data",)), "1d_2")
    m41, _ = train(dense, make_gbdt_mesh(4, 1), "mesh_4x1")
    m22, _ = train(dense, make_gbdt_mesh(2, 2), "mesh_2x2")
    m14, _ = train(dense, make_gbdt_mesh(1, 4), "mesh_1x4")
    m14s, _ = train(sparse, make_gbdt_mesh(1, 4), "mesh_1x4_sparse")

    parity = {
        "mesh_1x4 == single (bitwise)": same_forest(m14, single),
        "mesh_1x4_sparse == single_sparse (bitwise)": same_forest(m14s, single_sparse),
        "mesh_2x2 == 1d_2 (bitwise)": same_forest(m22, d2),
        "mesh_4x1 == 1d_4 (bitwise)": same_forest(m41, d4),
    }
    split_equal = (np.array_equal(m41.feature, single.feature)
                   and np.array_equal(m41.threshold, single.threshold))
    dleaf = float(np.max(np.abs(m41.leaf_value - single.leaf_value)))
    print(f"mesh_4x1 vs single: splits equal {split_equal}, max |leaf diff| "
          f"{dleaf:.3e}", flush=True)
    report["mesh_4x1_vs_single"] = {"splits_equal": split_equal, "max_leaf_diff": dleaf}
    with meter.phase("witness", report):
        parity["mesh_4x1 draws == single draws (bitwise)"] = row_layout_witness(
            cfg, dense, single, m41, f_single, report
        )
    report["parity"] = parity
    for name, ok in parity.items():
        print(f"parity {name}: {ok}", flush=True)
    for name, ok in parity.items():
        check(ok, name)


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args()
    devices = jax.devices()
    if devices[0].platform != "tpu":
        sys.exit(f"chip_smoke: no TPU (first device is {devices[0].platform})")
    from repro.launch.compile_cache import enable_compile_cache

    print(f"compile cache: {enable_compile_cache()}", flush=True)
    print(f"devices: {len(devices)} x {devices[0].device_kind}", flush=True)
    meter = CompileMeter()
    report: dict = {}
    (four_chips if args.chips == 4 else one_chip)(meter, report)
    print("report " + json.dumps(report, default=str), flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
    }}))


if __name__ == "__main__":
    main()
