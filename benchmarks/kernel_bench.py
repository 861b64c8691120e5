"""Kernel micro-benchmarks: jnp oracle vs Pallas (interpret mode on CPU).

Interpret-mode wall times do NOT reflect TPU performance — the meaningful
artifacts are (a) correctness at benchmark scale, (b) the ref-backend CPU
time that parameterizes the Fig. 10 component model, and (c) the kernels'
arithmetic-intensity table (bytes/flops per tile) used by the roofline.

The `fused_level` section is the exception: fused-vs-staged compares two
Pallas programs under the SAME interpreter, so the ratio measures what the
fusion actually removes (per-stage dispatch + the staged intermediates),
and it is the ratio CI gates on. This run also REGENERATES the committed
autotuner table (src/repro/kernels/tuning_table.json) and the top-level
BENCH_gbdt.json snapshot:

    PYTHONPATH=src python -m benchmarks.kernel_bench [--full] [--check]
"""
from __future__ import annotations

import json
import pathlib

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import save, time_call
from repro.kernels import autotune, ops
from repro.kernels.ref import level_build_ref
from repro.launch.compile_cache import enable_compile_cache

CASES = [
    # (n, f, n_bins, n_nodes)
    (4_096, 128, 64, 8),
    (16_384, 256, 64, 32),
    (65_536, 64, 64, 64),
]

# The fused-vs-staged comparison geometries. The first row is the CI smoke
# geometry (small enough for a PR gate); the second is the contractual
# 16K x 256 win the tuning table must witness.
FUSED_CASES = [
    (4_096, 128, 64, 8),
    (16_384, 256, 64, 32),
]

BENCH_SNAPSHOT = pathlib.Path(__file__).resolve().parent.parent / "BENCH_gbdt.json"

# CI gate: fused must not be slower than (1 + slack) x staged at the smoke
# geometry. Fused runs ~5x FASTER under the interpreter, so tripping this
# means the fusion itself broke, not timing noise.
REGRESSION_SLACK = 0.10


def hist_intensity(n, f, n_bins, n_nodes, sample_block=512, feature_block=8):
    """Analytic FLOPs/bytes per histogram kernel invocation (MXU path)."""
    rows = 2 * n_nodes
    flops = 2.0 * rows * n * f * n_bins  # dense one-hot contraction
    bytes_in = n * f * 4 + 3 * n * 4  # bins + node/grad/hess
    bytes_out = rows * f * n_bins * 4
    return flops, bytes_in + bytes_out


def tree_hist_rows(depth: int, mode: str) -> int:
    """Node-histograms built per tree: rebuild histograms every node of
    every level (2^d - 1); subtract builds the root plus one child per
    parent below it (2^(d-1))."""
    if mode == "rebuild":
        return (1 << depth) - 1
    return 1 + sum(1 << (level - 1) for level in range(1, depth))


def _per_tree_hist_fn(mode: str, backend: str, depth: int, n_bins: int):
    """All of one tree's level-histogram kernel calls as a single jitted
    program (random fixed node ids per level stand in for the routing;
    the kernel cost depends only on the row count, not which nodes)."""

    @jax.jit
    def run_levels(bins, g, h, level_nodes):
        total = 0.0
        for level in range(depth):
            n_nodes = 1 << level
            node = level_nodes[level]
            if mode == "rebuild" or level == 0:
                hist = ops.build_histogram(
                    bins, node, g, h, n_nodes, n_bins, backend=backend
                )
            else:
                active = 2 * jnp.arange(n_nodes // 2, dtype=jnp.int32)
                hist = ops.build_histogram_subset(
                    bins, node, g, h, active, n_nodes, n_bins, backend=backend
                )
            total = total + jnp.sum(hist)  # keep every level live
        return total

    return run_levels


def run_hist_subtract(quick: bool = True) -> dict:
    """The `hist_subtract` rows: per-tree histogram kernel work at depth 7,
    subtraction builder vs full rebuild.

    The contractual number is the MXU work model: kernel cost is linear
    in GH rows, so subtract/rebuild = 64/127 node-histograms = 0.504
    (exact, `hist_flops_*`). CPU wall times bracket it from above:

      * `pallas` — the real kernel program; on CPU the row-independent
        one-hot factor construction (VPU work the MXU overlaps on real
        hardware) dilutes the dot saving, so the measured ratio lands
        between the flop ratio and 1 and shrinks with scale;
      * `ref` — segment_sum scatters all N*F entries regardless of the
        node subset: ~1.0 by construction. Listed so nobody mistakes the
        oracle backend for the optimized path.
    """
    depth, n_bins = 7, 64

    def measure(backend: str, n: int, f: int) -> dict:
        key = jax.random.PRNGKey(7)
        k1, k2, k3 = jax.random.split(key, 3)
        bins = jax.random.randint(k1, (n, f), 0, n_bins, dtype=jnp.int32)
        g = jax.random.normal(k2, (n,))
        h = jax.random.uniform(k3, (n,))
        level_nodes = [
            jax.random.randint(jax.random.PRNGKey(100 + level), (n,), 0,
                               1 << level, dtype=jnp.int32)
            for level in range(depth)
        ]
        times = {}
        for mode in ("rebuild", "subtract"):
            fn = _per_tree_hist_fn(mode, backend, depth, n_bins)
            t, _ = time_call(lambda: fn(bins, g, h, level_nodes))
            times[mode] = t
        print(f"  hist_subtract[{backend}] depth={depth} N={n} F={f}: "
              f"rebuild {times['rebuild']*1e3:.1f}ms "
              f"subtract {times['subtract']*1e3:.1f}ms "
              f"(time x{times['subtract']/times['rebuild']:.2f})", flush=True)
        return {
            "n": n, "f": f,
            "rebuild_ms": times["rebuild"] * 1e3,
            "subtract_ms": times["subtract"] * 1e3,
            "time_ratio": times["subtract"] / times["rebuild"],
        }

    rows = {m: tree_hist_rows(depth, m) for m in ("rebuild", "subtract")}
    n_model, f_model = (16_384, 64)
    flops = {m: 2.0 * (2 * r) * n_model * f_model * n_bins
             for m, r in rows.items()}
    out = {
        "depth": depth, "n_bins": n_bins, "n": n_model, "f": f_model,
        "node_hists_rebuild": rows["rebuild"],
        "node_hists_subtract": rows["subtract"],
        "hist_flops_rebuild": flops["rebuild"],
        "hist_flops_subtract": flops["subtract"],
        "flop_ratio": flops["subtract"] / flops["rebuild"],
        "measured": {
            "pallas": measure("pallas", *((2_048, 8) if quick else (16_384, 64))),
            "ref": measure("ref", *((4_096, 16) if quick else (16_384, 64))),
        },
    }
    print(f"  hist_subtract kernel-work model: {rows['subtract']}/"
          f"{rows['rebuild']} node-histograms = x{out['flop_ratio']:.3f} "
          f"MXU flops per tree", flush=True)
    return out


def staged_level_hbm_bytes(n: int, f: int, b: int, l: int) -> int:
    """Modeled HBM traffic of ONE staged level: input stream, the histogram
    round-trip into the split kernel, the gain round-trip into the argmax,
    and the partition's gathers. The 4*L*F*B floats of intermediates are
    exactly what the fused program keeps in VMEM."""
    fp32 = 4
    stream = (n * f + 3 * n) * fp32  # bins + node/grad/hess, read once
    hist = 2 * l * f * b * fp32  # histogram: kernel out + scan in
    gain = l * f * b * fp32  # gain surface: kernel out + argmax in
    partition = 3 * n * fp32  # bins-column gather + node read/write
    return stream + 2 * hist + 2 * gain + partition


def fused_level_hbm_bytes(n: int, f: int, b: int, l: int) -> int:
    """Modeled HBM traffic of ONE fused level. The histogram/gain staging
    is gone; the price is that the partition phase re-streams the row
    blocks (the split feature is dynamic, so whole blocks flow again).
    Net savings therefore need 4*L*F*B > N*F + 3*N - 2*N — deep levels
    win on bytes, every level wins on dispatches (1 program vs 2 kernels
    + 2 jnp stages). Both columns are reported so the crossover is
    visible rather than implied."""
    fp32 = 4
    stream = 2 * (n * f + 3 * n) * fp32  # phases A and C both stream rows
    hist_out = 2 * l * f * b * fp32  # the next level's subtraction cache
    return stream + hist_out + n * fp32  # + the re-routed node map


def _staged_level_fn(n_nodes: int, n_bins: int):
    """The staged pipeline as one jitted program — the fair baseline: the
    same work the fused kernel absorbs, with its HBM round-trips intact."""

    @jax.jit
    def staged(bins, node, g, h):
        hist = ops.build_histogram(bins, node, g, h, n_nodes, n_bins,
                                   backend="pallas")
        gain = ops.split_gain(hist, 1.0, 1e-3, backend="pallas")
        flat = gain.reshape(n_nodes, -1)
        idx = jnp.argmax(flat, axis=-1)
        best = jnp.take_along_axis(flat, idx[:, None], axis=-1)[:, 0]
        feat = (idx // n_bins).astype(jnp.int32)
        thr = (idx % n_bins).astype(jnp.int32)
        ok = jnp.isfinite(best) & (best > 0.0)
        feat = jnp.where(ok, feat, 0)
        thr = jnp.where(ok, thr, n_bins - 1)
        val = jnp.take_along_axis(
            bins, jnp.take(feat, node)[:, None], axis=1)[:, 0]
        return hist, feat, thr, 2 * node + (val > jnp.take(thr, node)).astype(
            jnp.int32)

    return staged


def run_fused_level(quick: bool = True, retune: bool = True) -> dict:
    """Fused-vs-staged per-level rows + the tuning-table regeneration.

    Per geometry: sweep the autotuner grid (winners merged into the
    committed ``tuning_table.json`` when ``retune``), then time the staged
    pipeline against the fused program at its autotuned blocks, checking
    the fused outputs against the jnp oracle."""
    rows = []
    entries: dict[str, dict] = {}
    for n, f, n_bins, n_nodes in FUSED_CASES[: 1 if quick else len(FUSED_CASES)]:
        key = jax.random.PRNGKey(42)
        k1, k2, k3, k4 = jax.random.split(key, 4)
        bins = jax.random.randint(k1, (n, f), 0, n_bins, dtype=jnp.int32)
        node = jax.random.randint(k2, (n,), 0, n_nodes, dtype=jnp.int32)
        g = jax.random.normal(k3, (n,))
        h = jax.random.uniform(k4, (n,))

        entry, _ = autotune.sweep_level_build(
            bins, node, g, h, n_nodes, n_bins, reps=2 if quick else 3)
        gkey = autotune.geometry_key(n, f, n_bins, n_nodes)
        entries[gkey] = entry

        staged = _staged_level_fn(n_nodes, n_bins)
        t_staged, (h_st, f_st, t_st, nn_st) = time_call(
            lambda: staged(bins, node, g, h))

        active = jnp.arange(n_nodes, dtype=jnp.int32)
        mask = jnp.ones((f,), jnp.float32)
        sb, fb = entry["sample_block"], entry["feature_block"]
        t_fused, (h_fu, f_fu, t_fu, _, nn_fu) = time_call(
            lambda: ops.level_build(
                bins, node, g, h, active, None, mask, 1.0, 1e-3,
                n_nodes, n_bins, backend="fused",
                sample_block=sb, feature_block=fb))

        _, f_rf, t_rf, _, nn_rf = level_build_ref(
            bins, node, g, h, active, None, mask, 1.0, 1e-3,
            n_nodes, n_bins)
        parity = bool(
            np.array_equal(np.asarray(f_fu), np.asarray(f_rf))
            and np.array_equal(np.asarray(t_fu), np.asarray(t_rf))
            and np.array_equal(np.asarray(nn_fu), np.asarray(nn_rf))
            and np.array_equal(np.asarray(f_fu), np.asarray(f_st))
            and np.array_equal(np.asarray(nn_fu), np.asarray(nn_st))
        )

        row = {
            "n": n, "f": f, "n_bins": n_bins, "n_nodes": n_nodes,
            "staged_ms": t_staged * 1e3,
            "fused_ms": t_fused * 1e3,
            "speedup": t_staged / t_fused,
            "staged_hbm_bytes": staged_level_hbm_bytes(n, f, n_bins, n_nodes),
            "fused_hbm_bytes": fused_level_hbm_bytes(n, f, n_bins, n_nodes),
            "sample_block": sb, "feature_block": fb,
            "parity_ok": parity,
        }
        rows.append(row)
        print(f"  fused_level N={n} F={f} L={n_nodes}: staged "
              f"{row['staged_ms']:.0f}ms fused {row['fused_ms']:.0f}ms "
              f"(x{row['speedup']:.2f}, blocks sb={sb} fb={fb}) "
              f"HBM {row['staged_hbm_bytes']/2**20:.1f}->"
              f"{row['fused_hbm_bytes']/2**20:.1f}MiB parity={parity}",
              flush=True)

    if retune and entries:
        path = autotune.save_table(entries)
        print(f"  tuning table -> {path}", flush=True)
    return {"cases": rows, "tuned": entries}


def write_snapshot(out: dict) -> pathlib.Path:
    """The committed top-level BENCH_gbdt.json: the smoke-geometry
    fused-vs-staged numbers CI regenerates, uploads, and gates on."""
    smoke = out["fused_level"]["cases"][0]
    snapshot = {
        "comment": "regenerate with `PYTHONPATH=src python -m "
                   "benchmarks.kernel_bench`; CI fails if fused_ms > "
                   f"(1 + {REGRESSION_SLACK}) * staged_ms at the smoke "
                   "geometry",
        "host": jax.default_backend(),
        "smoke_geometry": {k: smoke[k] for k in
                           ("n", "f", "n_bins", "n_nodes")},
        "staged_ms": smoke["staged_ms"],
        "fused_ms": smoke["fused_ms"],
        "speedup": smoke["speedup"],
        "parity_ok": smoke["parity_ok"],
        "hist_subtract_flop_ratio": out["hist_subtract"]["flop_ratio"],
    }
    BENCH_SNAPSHOT.write_text(json.dumps(snapshot, indent=1) + "\n")
    return BENCH_SNAPSHOT


def check_snapshot(out: dict) -> None:
    """The CI gate: fused must beat (1 + slack) x staged and match the
    oracle at the smoke geometry."""
    smoke = out["fused_level"]["cases"][0]
    assert smoke["parity_ok"], "fused kernel diverged from the oracle"
    limit = (1.0 + REGRESSION_SLACK) * smoke["staged_ms"]
    assert smoke["fused_ms"] <= limit, (
        f"fused level-build regressed: {smoke['fused_ms']:.0f}ms > "
        f"{limit:.0f}ms (staged {smoke['staged_ms']:.0f}ms + "
        f"{REGRESSION_SLACK:.0%} slack)")
    print(f"  bench gate OK: fused {smoke['fused_ms']:.0f}ms vs staged "
          f"{smoke['staged_ms']:.0f}ms (limit {limit:.0f}ms)", flush=True)


def run(quick: bool = True) -> dict:
    out: dict = {"cases": []}
    key = jax.random.PRNGKey(0)
    for n, f, n_bins, n_nodes in CASES[: 2 if quick else 3]:
        k1, k2, k3, k4 = jax.random.split(key, 4)
        bins = jax.random.randint(k1, (n, f), 0, n_bins, dtype=jnp.int32)
        node = jax.random.randint(k2, (n,), 0, n_nodes, dtype=jnp.int32)
        g = jax.random.normal(k3, (n,))
        h = jax.random.uniform(k4, (n,))

        t_ref, h_ref = time_call(
            lambda: ops.build_histogram(bins, node, g, h, n_nodes, n_bins,
                                        backend="ref")
        )
        h_pal = ops.build_histogram(bins, node, g, h, n_nodes, n_bins,
                                    backend="pallas")
        ok = bool(np.allclose(h_ref, h_pal, atol=1e-3))

        t_gain, _ = time_call(
            lambda: ops.split_gain(h_ref, 1.0, 1e-3, backend="ref")
        )
        flops, bts = hist_intensity(n, f, n_bins, n_nodes)
        case = {
            "n": n, "f": f, "n_bins": n_bins, "n_nodes": n_nodes,
            "hist_ref_ms": t_ref * 1e3,
            "gain_ref_ms": t_gain * 1e3,
            "pallas_matches_ref": ok,
            "hist_flops": flops,
            "hist_bytes": bts,
            "arithmetic_intensity": flops / bts,
        }
        out["cases"].append(case)
        print(f"  N={n} F={f}: hist {t_ref*1e3:.1f}ms gain {t_gain*1e3:.2f}ms "
              f"pallas_ok={ok} AI={flops/bts:.1f} flop/byte", flush=True)
    out["hist_subtract"] = run_hist_subtract(quick)
    out["fused_level"] = run_fused_level(quick)
    print(f"  snapshot -> {write_snapshot(out)}", flush=True)
    save("kernel_bench", out)
    return out


def main(quick: bool = True, check: bool = False):
    enable_compile_cache()
    out = run(quick)
    if check:
        check_snapshot(out)
    return out


if __name__ == "__main__":
    import argparse

    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true",
                    help="all geometries incl. the 16K x 256 contract row")
    ap.add_argument("--check", action="store_true",
                    help="fail if fused regresses >10%% vs staged (CI gate)")
    args = ap.parse_args()
    main(quick=not args.full, check=args.check)
