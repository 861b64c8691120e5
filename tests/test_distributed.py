"""Distributed-equivalence tests: the sharded program must compute the SAME
numbers as the single-device program. Runs in a subprocess so the forced
8-device CPU platform never leaks into the rest of the suite."""
import json
import os
import subprocess
import sys
import textwrap

import pytest

_SCRIPT = textwrap.dedent(
    """
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import json

    import jax
    import jax.numpy as jnp
    import numpy as np

    import repro.configs as configs
    import repro.models as M
    import repro.optim as O
    import repro.sharding as SH
    from repro.launch.steps import make_decode_step, make_train_step

    assert jax.device_count() == 8
    from repro.launch.mesh import make_mesh
    mesh = make_mesh((4, 2), ("data", "model"))

    results = {}
    key = jax.random.PRNGKey(0)

    for arch in ["granite-3-2b", "phi3.5-moe-42b-a6.6b", "xlstm-1.3b"]:
        cfg = configs.get(arch).reduced()
        params = M.init_params(cfg, key)
        opt = O.adamw(1e-3, max_grad_norm=1.0)
        ostate = opt.init(params)
        B, S = 8, 32
        toks = jax.random.randint(key, (B, S + 1), 0, cfg.vocab_size)
        batch = {"tokens": toks[:, :S], "labels": toks[:, 1:]}

        # single device
        step0 = jax.jit(make_train_step(cfg, opt))
        p0, o0, m0 = step0(params, ostate, batch, key)

        # sharded: params over rules, batch over data
        pspecs = SH.param_specs(cfg, mesh)
        pshard = SH.tree_shardings(mesh, pspecs)
        oshard = SH.tree_shardings(
            mesh, SH.optimizer_state_specs(jax.eval_shape(opt.init, params), pspecs)
        )
        bshard = SH.tree_shardings(mesh, SH.data_specs(cfg, mesh, B))
        rep = jax.sharding.NamedSharding(mesh, jax.sharding.PartitionSpec())
        step1 = jax.jit(
            make_train_step(cfg, opt, mesh, ("data",), grad_specs=pspecs),
            in_shardings=(pshard, oshard, bshard, rep),
            out_shardings=(pshard, oshard, None),
        )
        p1, o1, m1 = step1(params, ostate, batch, key)

        err = max(
            float(jnp.max(jnp.abs(a.astype(jnp.float32) - b.astype(jnp.float32))))
            for a, b in zip(jax.tree.leaves(p0), jax.tree.leaves(p1))
        )
        results[arch] = {
            "loss_single": float(m0["loss"]),
            "loss_sharded": float(m1["loss"]),
            "max_param_diff": err,
        }

    # decode equivalence on one arch (serving placement)
    cfg = configs.get("granite-3-2b").reduced()
    params = M.init_params(cfg, key)
    B, S = 8, 32
    toks = jax.random.randint(key, (B, S), 0, cfg.vocab_size)
    _, cache = M.prefill(params, cfg, {"tokens": toks}, max_len=S + 4)
    d0 = jax.jit(make_decode_step(cfg))
    t0, _ = d0(params, toks[:, :1], cache)
    pshard = SH.tree_shardings(
        mesh, SH.param_specs(cfg, mesh, rules=SH.serving_rules())
    )
    cshard = SH.tree_shardings(mesh, SH.cache_specs(cfg, mesh, B, S + 4))
    d1 = jax.jit(
        make_decode_step(cfg, mesh, ("data",)),
        in_shardings=(pshard, None, cshard),
        out_shardings=(None, cshard),
    )
    t1, _ = d1(params, toks[:, :1], cache)
    results["decode_tokens_equal"] = bool((np.asarray(t0) == np.asarray(t1)).all())

    print("RESULTS_JSON=" + json.dumps(results))
    """
)


@pytest.fixture(scope="module")
def dist_results():
    proc = subprocess.run(
        [sys.executable, "-c", _SCRIPT],
        capture_output=True, text=True, timeout=900,
        env={**os.environ, "PYTHONPATH": "src"},
    )
    for line in proc.stdout.splitlines():
        if line.startswith("RESULTS_JSON="):
            return json.loads(line.split("=", 1)[1])
    raise RuntimeError(f"subprocess failed:\n{proc.stderr[-3000:]}")


@pytest.mark.slow
@pytest.mark.parametrize(
    "arch", ["granite-3-2b", "phi3.5-moe-42b-a6.6b", "xlstm-1.3b"]
)
def test_sharded_train_step_matches_single_device(dist_results, arch):
    r = dist_results[arch]
    # MoE tolerates more: expert capacity is enforced per data shard in the
    # expert-parallel path, so a few tokens drop differently than under the
    # single-device global-capacity rule (locality-aware dropping is the
    # standard semantics — GShard does the same).
    tol = 5e-2 if "moe" in arch else 2e-2
    assert abs(r["loss_single"] - r["loss_sharded"]) < tol, r
    assert r["max_param_diff"] < 5e-2, r


@pytest.mark.slow
def test_sharded_decode_matches_single_device(dist_results):
    assert dist_results["decode_tokens_equal"]
