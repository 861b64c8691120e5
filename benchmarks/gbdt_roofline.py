"""Beyond the zoo: the paper's own GBDT training step on the production
mesh — lower + compile the PS engine's scan form with the dataset sharded
over 'data' (samples) x 'model' (features), and report its roofline terms
through the shared harness (``benchmarks.roofline_common``).

The tree build inside the step is the sharded-histogram path
(``repro.ps.sharded``): every 'data' shard runs the histogram kernel on
its local samples and the level histograms merge with a psum across the
axis — the distributed form of the DimBoost comparison, with the
parameter-server aggregation on ICI instead of one server NIC.
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
import textwrap

from benchmarks.common import save
from benchmarks.roofline_common import roofline_terms
from repro.launch.compile_cache import enable_compile_cache

_CODE = textwrap.dedent(
    """
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count={n_dev}"
    import json
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding

    from repro.core.sgbdt import SGBDTConfig
    from repro.ps import Trainer
    from repro.ps.schedules import max_staleness, worker_round_robin
    from repro.sharding import gbdt_data_specs
    from repro.trees.binning import BinnedData
    from repro.trees.learner import LearnerConfig
    from repro.launch.hlo_analysis import analyze_hlo
    from repro.launch.mesh import make_mesh, make_production_mesh

    mesh = make_mesh(({mesh_shape}), ("data", "model"))
    N, F, T = {N}, {F}, {T}
    cfg = SGBDTConfig(
        n_trees=T, step_length=0.1, sampling_rate=0.8,
        learner=LearnerConfig(
            depth={depth}, n_bins=64, backend="ref", hist_mode="{hist_mode}"
        ),
    )
    data_abs = BinnedData(
        bins=jax.ShapeDtypeStruct((N, F), jnp.int32),
        bin_edges=jax.ShapeDtypeStruct((F, 63), jnp.float32),
        labels=jax.ShapeDtypeStruct((N,), jnp.float32),
        multiplicity=jax.ShapeDtypeStruct((N,), jnp.float32),
        n_bins=64,
    )
    specs = gbdt_data_specs(mesh, shard_features=True)
    data_sh = jax.tree.map(
        lambda s: NamedSharding(mesh, s), specs,
        is_leaf=lambda x: not isinstance(x, BinnedData),
    )

    trainer = Trainer(cfg, mesh=mesh)       # sharded shard_map+psum builds
    # Lower the W-worker round-robin steady state: ring carries W versions.
    W = {W}
    ring_size = max_staleness(worker_round_robin(T, W)) + 1
    fn = jax.jit(
        lambda d, s, r: trainer.scan_with(d, s, r, ring_size),
        in_shardings=(data_sh, None, None),
    )
    lowered = fn.lower(
        data_abs,
        jax.ShapeDtypeStruct((T,), jnp.int32),
        jax.ShapeDtypeStruct((T, 2), jnp.uint32),
    )
    compiled = lowered.compile()
    st = analyze_hlo(compiled.as_text())
    mem = compiled.memory_analysis()
    out = {{
        "n_samples": N, "n_features": F, "n_trees": T,
        "dot_flops": st.dot_flops,
        "hbm_bytes": st.hbm_bytes,
        "collective_bytes": st.total_collective_bytes,
        "collective_by_kind": {{k: v for k, v in st.collective_bytes.items()}},
        "temp_gib": mem.temp_size_in_bytes / 2**30,
    }}
    print("GBDT_ROOFLINE_JSON=" + json.dumps(out))
    """
)


def _run_child(code: str, marker: str) -> dict:
    """Run an accounting child and return the JSON it prints after
    ``marker``. The child traces on virtual CPU devices, so it never
    reaches for an accelerator this process may hold; a child that fails
    raises here."""
    proc = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True,
        timeout=1400,
        env={**os.environ, "PYTHONPATH": "src", "JAX_PLATFORMS": "cpu"},
    )
    for line in proc.stdout.splitlines():
        if line.startswith(marker):
            return json.loads(line.split("=", 1)[1])
    raise RuntimeError(
        f"accounting child failed (rc={proc.returncode}):\n{proc.stderr[-2000:]}"
    )


def _run_mode(shape: dict, hist_mode: str) -> dict:
    payload = _run_child(
        _CODE.format(hist_mode=hist_mode, **shape), "GBDT_ROOFLINE_JSON="
    )
    payload.update(roofline_terms(
        payload["dot_flops"], payload["hbm_bytes"], payload["collective_bytes"],
    ))
    return payload


def run(quick: bool = True) -> dict:
    shape = dict(
        n_dev=16, mesh_shape="8, 2", N=32_768, F=256, T=8, depth=5, W=4,
    ) if quick else dict(
        n_dev=256, mesh_shape="16, 16", N=262_144, F=2_048, T=64, depth=7, W=32,
    )
    # One compile per histogram mode: 'subtract' is the production default,
    # the 'rebuild' row quantifies what the subtraction builder saves in
    # the lowered program (hbm/collective bytes; the ref-backend build has
    # no dots, so it reports no flop delta).
    modes = {m: _run_mode(shape, m) for m in ("subtract", "rebuild")}
    payload = dict(modes["subtract"])
    payload["hist_modes"] = modes
    sub, reb = modes["subtract"], modes["rebuild"]
    payload["hist_subtract_hbm_ratio"] = sub["hbm_bytes"] / max(reb["hbm_bytes"], 1)
    payload["hist_subtract_collective_ratio"] = (
        sub["collective_bytes"] / max(reb["collective_bytes"], 1)
    )
    save("gbdt_roofline", payload)
    print(f"  GBDT sharded-histogram step on {shape['mesh_shape']} "
          f"(hist_mode=subtract): "
          f"compute {sub['compute_s']:.3e}s "
          f"memory {sub['memory_s']:.3e}s "
          f"collective {sub['collective_s']:.3e}s "
          f"-> {sub['dominant']}-bound")
    print(f"  vs rebuild: hbm x{payload['hist_subtract_hbm_ratio']:.3f} "
          f"collective x{payload['hist_subtract_collective_ratio']:.3f}")
    return payload


# ------------------------------------------------- collective-bytes rows
# Trace-time accounting (jax.eval_shape + collectives.ByteRecorder —
# nothing executes, so paper-scale geometries account in seconds): the
# per-tree-build bytes on the wire for the three build shapes of
# DESIGN.md §16. The committed snapshot is BENCH_collectives.json at the
# repo root; check_bench.py --collectives gates it (the numbers are
# DETERMINISTIC, so the gate is exact equality, not a tolerance).
_COLLECTIVES_CODE = textwrap.dedent(
    """
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=16"
    import json

    import jax
    import jax.numpy as jnp

    from repro.launch.mesh import make_gbdt_mesh, make_mesh
    from repro.ps.sharded import collective_bytes_per_build
    from repro.trees.binning import SparseBins
    from repro.trees.learner import LearnerConfig

    N, F, B, E, depth = {N}, {F}, {B}, {E}, {depth}
    cfg = LearnerConfig(
        depth=depth, n_bins=B, backend="ref", hist_mode="subtract"
    )
    dense = jax.ShapeDtypeStruct((N, F), jnp.int32)
    C = max(N * E // F, 1)  # feature-major ELL capacity at this density
    sp = SparseBins(
        indices=jax.ShapeDtypeStruct((N, E), jnp.int32),
        codes=jax.ShapeDtypeStruct((N, E), jnp.int32),
        feat_rows=jax.ShapeDtypeStruct((F, C), jnp.int32),
        feat_codes=jax.ShapeDtypeStruct((F, C), jnp.int32),
        zero_bin=jax.ShapeDtypeStruct((F,), jnp.int32),
    )
    mesh_1d = make_mesh((16,), ("data",))
    mesh_2d = make_gbdt_mesh(1, 16)
    row = {{"geometry": {{
        "N": N, "F": F, "B": B, "depth": depth, "nnz_row": E,
        "hist_mode": "subtract", "shards": 16,
    }}}}
    row["bytes_1d_dense_psum"] = collective_bytes_per_build(
        cfg, mesh_1d, dense
    )["realized_bytes"]
    s2 = collective_bytes_per_build(
        cfg, mesh_2d, dense, feature_axis="feature"
    )
    row["bytes_2d_argmax_merge"] = s2["realized_bytes"]
    row["by_kind_2d"] = s2["realized_by_kind"]
    ss = collective_bytes_per_build(cfg, mesh_2d, sp, feature_axis="feature")
    row["bytes_2d_sparse"] = ss["realized_bytes"]
    row["by_kind_2d_sparse"] = ss["realized_by_kind"]
    row["reduction_dense"] = (
        row["bytes_1d_dense_psum"] / max(row["bytes_2d_argmax_merge"], 1)
    )
    row["reduction_sparse"] = (
        row["bytes_1d_dense_psum"] / max(row["bytes_2d_sparse"], 1)
    )
    print("GBDT_COLLECTIVES_JSON=" + json.dumps(row))
    """
)

# (name, N, F, B, nnz/row, depth) — the acceptance row first, then the
# paper-dataset lookalikes (real-sim ~72K x 21K, E2006 ~16K x 150K).
COLLECTIVE_GEOMETRIES = [
    ("smoke_16k_x_256", 16_384, 256, 64, 64, 7),
    ("realsim_like", 65_536, 20_992, 64, 52, 7),
    ("e2006_like", 16_384, 150_528, 64, 96, 7),
]


def _run_collectives_row(N, F, B, E, depth) -> dict:
    return _run_child(
        _COLLECTIVES_CODE.format(N=N, F=F, B=B, E=E, depth=depth),
        "GBDT_COLLECTIVES_JSON=",
    )


def collectives(quick: bool = True) -> dict:
    """Measure per-tree-build collective bytes for every geometry row."""
    geoms = COLLECTIVE_GEOMETRIES[:1] if quick else COLLECTIVE_GEOMETRIES
    rows = {}
    for name, N, F, B, E, depth in geoms:
        row = _run_collectives_row(N, F, B, E, depth)
        rows[name] = row
        print(f"  {name} (N={N} F={F} B={B} depth={depth}): "
              f"dense-psum {row['bytes_1d_dense_psum']:,}B "
              f"argmax-merge {row['bytes_2d_argmax_merge']:,}B "
              f"(x{row['reduction_dense']:.0f}) "
              f"sparse {row['bytes_2d_sparse']:,}B "
              f"(x{row['reduction_sparse']:.0f})")
    payload = {"rows": rows}
    save("gbdt_collectives", payload)
    return payload


def main(quick: bool = True):
    enable_compile_cache()
    out = run(quick)
    out["collectives"] = collectives(quick)["rows"]
    return out


if __name__ == "__main__":
    import argparse

    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--collectives", action="store_true",
                    help="only the collective-bytes accounting rows")
    args = ap.parse_args()
    if args.collectives:
        collectives(quick=not args.full)
    else:
        main(quick=not args.full)
