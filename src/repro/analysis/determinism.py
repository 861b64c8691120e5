"""Checker 1: the jaxpr determinism auditor.

The repo's bit-for-bit record→replay contract (DESIGN.md §11/§14) rests on
three numeric-core invariants that, until this pass, were enforced by
convention and caught only when a nightly replay flaked:

  seam        — the propose→fold seam in ``engine.round_body`` must be
                pinned by ``optimization_barrier``, and NO value may flow
                from the propose side into the fold side around it. A
                bypassing edge lets XLA optimize (e.g. FMA-contract a
                ``mul`` into the fold's ``add``) across the exact boundary
                where the threaded runtime compiles two separate programs
                — the contraction then happens in some compilation forms
                and not others, and replay drifts by program shape.
                Keuper & Pfreundt (arXiv:1505.04956) locate async-SGD
                convergence exactly in these numeric-core details.
  f64         — no float64 intermediate may appear in the traced round
                path: the PR-7 host-twin rule says every constant rounds
                f64→f32 ONCE, on the host (``6*rho`` folds in python f64,
                then one f32 cast), so the jnp twin and the numpy twin
                report bitwise-equal step scales. An in-trace f64 op means
                a value rounds once in programs that keep it f64 and twice
                in programs that don't. The audit both scans dtypes and
                cross-checks ``engine.staleness_scale`` against its host
                twin ``schedules.staleness_scales`` value-by-value.
  psum-order  — in the sharded build, f32 aggregation order IS the
                determinism: shards must psum their LOCAL partial
                histograms first and derive siblings (parent − child)
                AFTER the collective (``ps/sharded.py``). Reordering is
                algebraically equal but rounds differently per shard and
                breaks lockstep with the single-device goldens. The audit
                taints shard-local aggregates in the shard_map jaxpr and
                flags any non-additive combine (sub/div/max/min) of a
                not-yet-merged aggregate upstream of a ``psum``. The 2D
                block-distributed build adds a second ordering edge: the
                merged-argmax collectives (``pmax``/``pmin``, DESIGN.md
                §16) must consume gains derived from row-psum-MERGED
                histograms — an argmax merge of partial sums is flagged
                the same way.

All three audits run on JAXPRS — traced, never executed — so they check
the program XLA will actually see, not the source text.
"""
from __future__ import annotations

from repro.analysis.findings import Finding

CHECKER = "determinism"

# Primitives that aggregate across the sample axis: a tainted (shard-local)
# input makes the output a LOCAL AGGREGATE that must reach a psum before
# any non-additive combine touches it.
_REDUCTION_PRIMS = {
    "dot_general",
    "reduce_sum",
    "scatter-add",
    "scatter_add",
    "segment_sum",
    "reduce_window_sum",
}
# Non-additive combines: applying one of these to two local aggregates and
# THEN psumming changes the f32 rounding order vs psum-first (sub/div) or
# the value outright (max/min) — either way shards leave lockstep with the
# single-device build.
_NONADDITIVE_PRIMS = {"sub", "div", "max", "min", "pow", "rem"}
_BARRIER_PRIMS = {"optimization_barrier", "opt_barrier"}
_COLLECTIVE_PRIMS = {"psum", "psum2", "psum_invariant", "all_reduce", "allreduce"}
# Non-additive COLLECTIVES — the 2D merged-argmax split search (pmax of
# per-shard best gains, pmin of global flat indices; DESIGN.md §16). Their
# outputs are merged like psum's, but feeding one a shard-local partial
# aggregate is itself the violation: max/min do not commute with the row
# psum, so an argmax merge that runs BEFORE the data-axis histogram merge
# picks its winner from partial sums and the forest leaves lockstep.
_NONADDITIVE_COLLECTIVES = {"pmax", "pmin"}


# ------------------------------------------------------------ jaxpr walking
def _sub_jaxprs(eqn):
    """Every sub-jaxpr an equation carries (pjit, scan, cond, shard_map...)."""
    for val in eqn.params.values():
        vals = val if isinstance(val, (list, tuple)) else [val]
        for v in vals:
            if hasattr(v, "jaxpr"):  # ClosedJaxpr
                yield v.jaxpr
            elif hasattr(v, "eqns"):  # raw Jaxpr
                yield v


def iter_eqns(jaxpr):
    """Depth-first over every equation, recursing into sub-jaxprs."""
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in _sub_jaxprs(eqn):
            yield from iter_eqns(sub)


def _find_eqns(jaxpr, prim_names: set) -> list:
    return [e for e in iter_eqns(jaxpr) if e.primitive.name in prim_names]


def _ancestors(jaxpr, seed_vars) -> tuple[set, set]:
    """(eqn ids, var ids) of everything ``seed_vars`` depend on, walking
    producers within ONE jaxpr level (sub-jaxprs are opaque nodes)."""
    producer = {}
    for eqn in jaxpr.eqns:
        for v in eqn.outvars:
            producer[id(v)] = eqn
    eqn_ids: set = set()
    var_ids: set = set()
    stack = [v for v in seed_vars if not _is_literal(v)]
    while stack:
        v = stack.pop()
        if id(v) in var_ids:
            continue
        var_ids.add(id(v))
        eqn = producer.get(id(v))
        if eqn is not None and id(eqn) not in eqn_ids:
            eqn_ids.add(id(eqn))
            stack.extend(u for u in eqn.invars if not _is_literal(u))
    return eqn_ids, var_ids


def _is_literal(v) -> bool:
    return type(v).__name__ == "Literal"


def _invar_list(eqn):
    return [v for v in eqn.invars if not _is_literal(v)]


# ------------------------------------------------------------- audit: seam
def audit_seam(jaxpr, where: str = "engine.round_body") -> list[Finding]:
    """The propose→fold seam must be barrier-pinned and leak-free.

    Leak = a value produced on the propose side (an ancestor equation of
    the barrier's inputs) consumed by a fold-side equation (downstream of
    the barrier's outputs) without passing through the barrier. The
    mul→add special case is named in the message: that pair is exactly
    what XLA FMA-contracts differently across compilation forms.
    """
    jaxpr = getattr(jaxpr, "jaxpr", jaxpr)
    barriers = [e for e in jaxpr.eqns if e.primitive.name in _BARRIER_PRIMS]
    if not barriers:
        return [
            Finding(
                CHECKER, "seam-unpinned", "error", "<traced>", 0,
                f"{where}: no optimization_barrier between the worker's "
                "propose and the server's fold — XLA may contract or CSE "
                "across the seam differently per compilation form, breaking "
                "bitwise record→replay",
                ident=where,
            )
        ]
    findings: list[Finding] = []
    # Propose side: everything the barrier inputs depend on.
    propose_eqns: set = set()
    propose_outvars: set = set()
    for b in barriers:
        eqn_ids, _ = _ancestors(jaxpr, _invar_list(b))
        propose_eqns |= eqn_ids
    for eqn in jaxpr.eqns:
        if id(eqn) in propose_eqns:
            propose_outvars |= {id(v) for v in eqn.outvars}
    # Fold side: everything reachable from the barrier outputs.
    barrier_out = set()
    for b in barriers:
        barrier_out |= {id(v) for v in b.outvars}
    downstream: set = set()
    reach: set = set(barrier_out)
    changed = True
    while changed:
        changed = False
        for eqn in jaxpr.eqns:
            if id(eqn) in downstream or eqn.primitive.name in _BARRIER_PRIMS:
                continue
            if any(id(v) in reach for v in _invar_list(eqn)):
                downstream.add(id(eqn))
                reach |= {id(v) for v in eqn.outvars}
                changed = True
    producer = {id(v): e for e in jaxpr.eqns for v in e.outvars}
    for eqn in jaxpr.eqns:
        if id(eqn) not in downstream:
            continue
        for v in _invar_list(eqn):
            if id(v) in propose_outvars and id(v) not in barrier_out:
                src = producer.get(id(v))
                pair = ""
                if src is not None and src.primitive.name == "mul" and (
                    eqn.primitive.name == "add"
                ):
                    pair = " (mul feeding add: an FMA-contractible pair)"
                findings.append(
                    Finding(
                        CHECKER, "seam-crossing", "error", "<traced>", 0,
                        f"{where}: value {v} flows from the propose side "
                        f"into fold-side `{eqn.primitive.name}` without "
                        f"passing the optimization_barrier{pair} — the "
                        "threaded runtime compiles the two sides as "
                        "separate programs, so cross-seam optimization "
                        "diverges between forms",
                        ident=f"{where}:{src.primitive.name if src else '?'}"
                        f"->{eqn.primitive.name}",
                    )
                )
    return findings


# -------------------------------------------------------------- audit: f64
def audit_f64(jaxpr, where: str) -> list[Finding]:
    """No float64 intermediate in the traced round path (round-once rule)."""
    jaxpr = getattr(jaxpr, "jaxpr", jaxpr)
    findings = []
    seen = set()
    for eqn in iter_eqns(jaxpr):
        for v in eqn.outvars:
            dtype = getattr(getattr(v, "aval", None), "dtype", None)
            if dtype is not None and str(dtype) == "float64":
                key = f"{where}:{eqn.primitive.name}"
                if key in seen:
                    continue
                seen.add(key)
                findings.append(
                    Finding(
                        CHECKER, "f64-intermediate", "error", "<traced>", 0,
                        f"{where}: `{eqn.primitive.name}` produces float64 "
                        "inside the traced round path — constants must fold "
                        "in host f64 and round to f32 ONCE (the PR-7 "
                        "host-twin rule); an in-trace f64 value double-"
                        "rounds in mixed-precision program forms",
                        ident=key,
                    )
                )
    return findings


def audit_staleness_twin() -> list[Finding]:
    """Bitwise cross-check: ``engine.staleness_scale`` (the jnp form the
    fused replay computes) against ``schedules.staleness_scales`` (the
    host-numpy form the trace records). Any mismatch at any (rho, tau)
    means the recorded ``step_scale`` column would disagree with the
    replayed fold — the exact drift the round-once rule exists to stop."""
    import numpy as np

    from repro.ps import schedules
    from repro.ps.engine import staleness_scale

    findings = []
    taus = np.arange(32, dtype=np.int32)
    schedule = np.arange(32) - taus  # realized k(j) with staleness tau_j = j
    for rho in (0.01, 0.1, 0.3, 0.9, 1.0, 3.0):
        host = schedules.staleness_scales(schedule, rho)
        jnp_scales = np.asarray(
            [np.asarray(staleness_scale(rho, int(t))) for t in taus],
            np.float32,
        )
        if not (host.view(np.uint32) == jnp_scales.view(np.uint32)).all():
            bad = int(np.flatnonzero(host != jnp_scales)[0])
            findings.append(
                Finding(
                    CHECKER, "twin-mismatch", "error", "<traced>", 0,
                    f"staleness_scale(rho={rho}, tau={bad}) = "
                    f"{jnp_scales[bad]!r} but the host twin "
                    f"schedules.staleness_scales reports {host[bad]!r} — "
                    "the trace's step_scale column would not match the "
                    "replayed fold bitwise",
                    ident=f"rho={rho}",
                )
            )
    return findings


# ------------------------------------------------------- audit: psum order
def audit_psum_order(jaxpr, where: str = "ps.sharded") -> list[Finding]:
    """Local aggregates must merge (psum) before any non-additive combine.

    Taint model, per shard_map body:
      local[v] — v depends on shard-local data (a sharded block argument)
                 via a path with no intervening psum;
      agg[v]   — that dependency passes a reduction (dot/segment-sum/...),
                 i.e. v holds a shard-local PARTIAL AGGREGATE.
    psum output clears both. A sub/div/max/min consuming a local aggregate
    is the violation: psum(a) − psum(b) and psum(a − b) agree in algebra
    but not in f32 rounding order (and max/min not even in algebra), so
    the sharded build would leave bitwise lockstep with the single-device
    path — the subtract-AFTER-psum invariant of ps/sharded.py.
    """
    jaxpr = getattr(jaxpr, "jaxpr", jaxpr)
    findings: list[Finding] = []
    for eqn in iter_eqns(jaxpr):
        if eqn.primitive.name != "shard_map":
            continue
        for body in _sub_jaxprs(eqn):
            findings.extend(_audit_shard_body(body, eqn, where))
    return findings


def _audit_shard_body(body, shmap_eqn, where: str) -> list[Finding]:
    # Sharded block args: invars whose in_spec names a mesh axis. Specs can
    # be jax-version-shaped several ways; default to "all sharded" if the
    # param is missing (conservative: more taint, never less).
    specs = shmap_eqn.params.get("in_names") or shmap_eqn.params.get("in_specs")
    invars = list(body.invars)
    local_in = []
    for i in range(len(invars)):
        sharded = True
        if specs is not None and i < len(specs):
            spec = specs[i]
            names = spec if isinstance(spec, (dict, tuple, list)) else [spec]
            flat = []
            for x in (names.values() if isinstance(names, dict) else names):
                flat.extend(x if isinstance(x, (tuple, list)) else [x])
            sharded = any(x is not None for x in flat)
        local_in.append(sharded)
    findings: list[Finding] = []
    _propagate(body, local_in, [False] * len(invars), where, findings)
    return findings


def _propagate(
    body, local_in: list, agg_in: list, where: str, findings: list
) -> tuple[list, list]:
    """Taint-propagate through one jaxpr; recurse into call-like
    sub-jaxprs (pjit/closed_call, whose invars map 1:1 onto the call's)
    so reductions hidden inside jitted helpers still register. Other
    structured eqns (scan/cond/while) are treated opaquely: any tainted
    input taints every output — conservative in `local`, and `agg` only
    combines with `local`, so no false negative hides a real violation
    at the top level where the repo's collectives live. Returns
    (local, agg) flags for ``body.outvars``."""
    body = getattr(body, "jaxpr", body)
    local: set = set()
    agg: set = set()
    for v, loc in zip(body.invars, local_in):
        if loc:
            local.add(id(v))
    for v, ag in zip(body.invars, agg_in):
        if ag:
            agg.add(id(v))
    for eqn in body.eqns:
        name = eqn.primitive.name
        ivs = _invar_list(eqn)
        in_local = [id(v) in local for v in ivs]
        in_agg = [id(v) in agg for v in ivs]
        if name in _COLLECTIVE_PRIMS:
            continue  # outputs merged: neither local nor agg
        if name in _NONADDITIVE_COLLECTIVES:
            if any(loc and ag for loc, ag in zip(in_local, in_agg)):
                findings.append(
                    Finding(
                        CHECKER, "premerge-combine", "error", "<traced>", 0,
                        f"{where}: `{name}` merges a shard-local partial "
                        "aggregate — the argmax-merge collective must run "
                        "on gains derived from row-psum-MERGED histograms "
                        "(max/min do not commute with the data-axis psum; "
                        "DESIGN.md §16): merging partial sums picks a "
                        "different winner per program form and the forest "
                        "leaves bitwise lockstep",
                        ident=f"{where}:{name}",
                    )
                )
            continue  # outputs merged across the axis: clear both taints
        subs = list(_sub_jaxprs(eqn))
        if name in ("pjit", "closed_call", "core_call", "xla_call") and len(subs) == 1:
            sub = subs[0]
            n_sub = len(getattr(sub, "invars", []))
            call_local = [id(v) in local for v in eqn.invars[-n_sub:]] if n_sub else []
            call_agg = [id(v) in agg for v in eqn.invars[-n_sub:]] if n_sub else []
            out_loc, out_ag = _propagate(sub, call_local, call_agg, where, findings)
            for v, loc, ag in zip(eqn.outvars, out_loc, out_ag):
                if loc:
                    local.add(id(v))
                if ag:
                    agg.add(id(v))
            continue
        if name in _NONADDITIVE_PRIMS and any(
            loc and ag for loc, ag in zip(in_local, in_agg)
        ):
            findings.append(
                Finding(
                    CHECKER, "premerge-combine", "error", "<traced>", 0,
                    f"{where}: `{name}` combines a shard-local partial "
                    "aggregate BEFORE its psum — derive siblings / take "
                    "ratios only after the collective (subtract-after-psum "
                    "invariant, ps/sharded.py): pre-merge combines reorder "
                    "the f32 reduction and break cross-shard bitwise "
                    "lockstep",
                    ident=f"{where}:{name}",
                )
            )
        out_local = any(in_local)
        out_agg = any(in_agg) or (name in _REDUCTION_PRIMS and any(in_local))
        for v in eqn.outvars:
            if out_local:
                local.add(id(v))
            if out_agg:
                agg.add(id(v))
    out_loc = [id(v) in local for v in body.outvars]
    out_ag = [id(v) in agg for v in body.outvars]
    return out_loc, out_ag


# ------------------------------------------------------------- repo driver
def _tiny_problem():
    """A minimal (cfg, data) pair for tracing — 64 samples, 8 features."""
    from repro.core.sgbdt import SGBDTConfig, init_state
    from repro.data.synthetic import make_sparse_classification
    from repro.trees.learner import LearnerConfig

    data = make_sparse_classification(64, 8, 3, seed=0)
    cfg = SGBDTConfig(
        n_trees=4,
        learner=LearnerConfig(depth=2, n_bins=64),
        adaptive_step=0.3,  # exercise the scale_push path in the audit
    )
    state = init_state(cfg, data)
    return cfg, data, state


def check_repo(root=None) -> list[Finding]:
    """Trace the engine's round path and the sharded builder; audit all."""
    del root  # jaxpr audits are source-location-free
    import jax
    import jax.numpy as jnp

    from repro.ps import engine

    cfg, data, state = _tiny_problem()
    # Tracer-only key: never folded into a model, so nothing to replay.
    rng = jax.random.PRNGKey(0)  # analysis: ignore[prngkey-outside-ticket]
    findings: list[Finding] = []

    round_jaxpr = jax.make_jaxpr(
        lambda forest, f, f_target, rng: engine.round_body(
            cfg, data, forest, f, f_target, rng, None, jnp.int32(2)
        )
    )(state.forest, state.f, state.f, rng)
    findings += audit_seam(round_jaxpr, "engine.round_body")
    findings += audit_f64(round_jaxpr, "engine.round_body")

    propose_jaxpr = jax.make_jaxpr(
        lambda f_target, rng: engine.propose_tree(cfg, data, f_target, rng)
    )(state.f, rng)
    findings += audit_f64(propose_jaxpr, "engine.propose_tree")

    tree, delta = engine.propose_tree(cfg, data, state.f, rng)
    fold_jaxpr = jax.make_jaxpr(
        lambda forest, f, tree, delta: engine.server_fold(cfg, forest, f, tree, delta)
    )(state.forest, state.f, tree, delta)
    findings += audit_f64(fold_jaxpr, "engine.server_fold")

    scale_jaxpr = jax.make_jaxpr(lambda tau: engine.staleness_scale(0.3, tau))(jnp.int32(3))
    findings += audit_f64(scale_jaxpr, "engine.staleness_scale")
    findings += audit_staleness_twin()

    findings += _check_sharded(cfg, data)
    return findings


def _check_sharded(cfg, data) -> list[Finding]:
    """Trace the shard_map builds on 1-device meshes (the jaxpr is
    identical in structure to the multi-shard program — psum, pmax/pmin
    and all — which is all the ordering audit needs): the 1D data-parallel
    build, and the 2D (data × feature) build with its argmax-merge
    collective, on dense and on SparseBins data."""
    import jax

    from repro.launch.mesh import make_gbdt_mesh, make_mesh
    from repro.ps.sharded import make_sharded_builder, make_sharded_builder_2d
    from repro.trees.binning import to_sparse

    g = jax.numpy.zeros((data.n_samples,), jax.numpy.float32)
    rng = jax.random.PRNGKey(0)  # analysis: ignore[prngkey-outside-ticket]
    findings = []
    mesh_1d = make_mesh((1,), ("data",))
    mesh_2d = make_gbdt_mesh(1, 1)
    sparse_bins = to_sparse(data.bins)
    for mode in ("subtract", "rebuild"):
        cfg_m = cfg.learner._replace(hist_mode=mode)
        builder_m = make_sharded_builder(cfg_m, mesh_1d, "data")
        jaxpr = jax.make_jaxpr(builder_m)(data.bins, g, g, rng)
        findings += audit_psum_order(jaxpr, f"ps.sharded[{mode}]")
        builder_2d = make_sharded_builder_2d(cfg_m, mesh_2d)
        jaxpr = jax.make_jaxpr(builder_2d)(data.bins, g, g, rng)
        findings += audit_psum_order(jaxpr, f"ps.sharded2d[{mode}]")
        jaxpr = jax.make_jaxpr(builder_2d)(sparse_bins, g, g, rng)
        findings += audit_psum_order(jaxpr, f"ps.sharded2d-sparse[{mode}]")
    return findings
