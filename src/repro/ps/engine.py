"""The parameter-server training engine: ONE round body, every trainer.

Algorithm 3 splits a boosting round across the PS roles:

  worker  — pull a (possibly stale) prediction vector F^{k(j)}, draw the
            Bernoulli subdataset Q, build the gradient target, fit a tree
            (``propose_tree``);
  server  — fold the pushed tree into the live state F <- F + v * Tree
            (``server_fold``).

``round_body`` composes the two; it is the only place that logic exists.
The legacy entry points (``core.sgbdt.train_serial``,
``core.async_sgbdt.train_async`` / ``train_async_scan``) are thin shims
over ``Trainer``, which executes the same step function in two forms:

  * a Python loop with per-round eval hooks (experiments), and
  * a single ``lax.scan`` program (the form the distributed dry-run lowers).

The serial trainer is not a separate code path: it is the round-robin
schedule with W = 1 (k(j) = j, zero staleness).

Sharding: given a mesh whose ``'data'`` axis has more than one shard, the
tree build runs as ``shard_map`` over data shards — each shard feeds its
local samples to the histogram kernel and the level histograms merge with
a ``psum`` (see ``repro.ps.sharded``) — the block-distributed /
DimBoost-style central-aggregation shape, but on ICI collectives instead
of one server NIC.

Determinism is PER HISTOGRAM MODE: ``LearnerConfig.hist_mode`` selects the
worker's level-histogram strategy ('subtract' derives siblings from cached
parents, 'rebuild' re-histograms every node; see ``trees.learner``). The
mode rides inside ``cfg.learner`` through every execution form — threaded
runtime, loop, fused scan replay — so the record-and-replay contract
(DESIGN.md §11) stays bit-for-bit within a mode; the two modes agree with
each other only to f32 subtraction tolerance.
"""
from __future__ import annotations

from collections import OrderedDict
from typing import Callable

import jax
import jax.numpy as jnp

from repro.core.sgbdt import SGBDTConfig, TrainState, init_state
from repro.data.sampling import DrawPlan, bernoulli_weights, count_draw, draw_plan
from repro.ps.schedules import max_staleness, resolve_schedule
from repro.trees.binning import BinnedData, SparseBins
from repro.trees.forest import forest_push
from repro.trees.learner import build_tree, build_tree_multi
from repro.trees.tree import Tree, apply_tree, apply_tree_stack

# (bins, g, h, rng) -> Tree; None means the plain single-device build.
TreeBuilder = Callable[[jax.Array, jax.Array, jax.Array, jax.Array], Tree]


# ------------------------------------------------------------- round body
def propose_tree(
    cfg: SGBDTConfig,
    data: BinnedData,
    f_target: jax.Array,
    rng: jax.Array,
    builder: TreeBuilder | None = None,
    plan: DrawPlan | jax.Array | None = None,
) -> tuple[Tree, jax.Array]:
    """Worker side: sample Q -> build target from F^{k(j)} -> fit tree(s).

    Returns the tree and its prediction delta on the training bins (the
    "push" payload: the server folds the delta without re-evaluating).
    K-output objectives fit one tree per output against the (N, K)
    gradient field — a vmapped stacked build, still ONE push: the K trees
    travel as one stacked ``Tree`` group with a (N, K) delta.

    The step length v is applied HERE, to the leaf table, not by the
    server: ``delta`` gathers pre-scaled leaves, so the server fold is a
    pure add. This is what keeps every execution form bit-identical — a
    ``v * delta`` multiply next to the fold's add is FMA-contractable, and
    XLA contracts it in some programs (a small standalone fold) but not
    others (the fused scan body), which would break the threaded runtime's
    record-and-replay contract. ``round(v*leaf)[idx] == round(v*leaf[idx])``
    elementwise, so the trained values are unchanged.

    ``plan`` is ``sampling.draw_plan(data.multiplicity, rate)``: the same
    Q with less work where a plan applies. None draws from the multiplicity.
    """
    obj = cfg.obj
    # Named scopes label the device operations of each step in the
    # compiled program's metadata; they change no value.
    with jax.named_scope("sample"):
        r_sample, r_feat = jax.random.split(rng)
        m_prime, _ = bernoulli_weights(
            r_sample, cfg.sampling_rate, data.multiplicity if plan is None else plan)
    v = jnp.float32(cfg.step_length)
    if obj.n_outputs == 1:
        with jax.named_scope("gradient"):
            g, h = obj.grad_hess(data.labels, f_target, qid=data.qid)
            hess_w = m_prime * h if cfg.step_kind == "newton" else m_prime
            g_w = m_prime * g
        with jax.named_scope("build"):
            if builder is None:
                tree = build_tree(cfg.learner, data.bins, g_w, hess_w, r_feat)
            else:
                tree = builder(data.bins, g_w, hess_w, r_feat)
        with jax.named_scope("delta"):
            tree = tree._replace(leaf_value=v * tree.leaf_value)
            return tree, apply_tree(tree, data.bins)
    with jax.named_scope("gradient"):
        g, h = obj.grad_hess(data.labels, f_target, qid=data.qid)
        g_w = m_prime[:, None] * g
        if cfg.step_kind == "newton":
            h_w = m_prime[:, None] * h
        else:
            h_w = jnp.broadcast_to(m_prime[:, None], g.shape)
    with jax.named_scope("build"):
        if builder is None:
            trees = build_tree_multi(cfg.learner, data.bins, g_w, h_w, r_feat)
        else:
            # Builders (e.g. the shard_map data-parallel build) are defined on
            # single-output signatures; run one per output and stack the group.
            built = [
                builder(data.bins, g_w[:, k], h_w[:, k], r_feat)
                for k in range(obj.n_outputs)
            ]
            trees = jax.tree.map(lambda *xs: jnp.stack(xs), *built)
    with jax.named_scope("delta"):
        trees = trees._replace(leaf_value=v * trees.leaf_value)
        return trees, apply_tree_stack(trees, data.bins)


def server_fold(cfg, forest, f_live, tree, delta):
    """Server side: F <- F + v * Tree (Algorithm 3, server step 2).

    The pushed tree's leaves arrive pre-scaled by v (see ``propose_tree``),
    so the fold is a PURE ADD plus a slot write — deliberately: a lone add
    whose other operand crosses a gather cannot be FMA-contracted, so this
    fold computes the identical f32 value whether it is compiled standalone
    (the threaded runtime's server program), in the per-round loop, in the
    fused lax.scan replay, or inside a vmapped worker block.
    """
    with jax.named_scope("fold"):
        return forest_push(forest, tree, jnp.float32(1.0)), f_live + delta


def staleness_scale(rho: float, staleness) -> jax.Array:
    """Prop.-1 step deflation for a tau-stale push: 1 / (1 + 6*rho*tau).

    The jnp twin of ``optim.staleness_step_scale`` (quadratic term dropped
    — the high-diversity regime), usable on traced staleness values so the
    fused scan replay computes the identical f32 scale the threaded server
    computed from (j, k(j)) at fold time.
    """
    # 6*rho folds in python f64 and rounds ONCE, exactly like the host twin
    # ``schedules.staleness_scales`` — trace-reported scales match bitwise.
    tau = jnp.asarray(staleness, jnp.float32)
    coef = jnp.float32(6.0 * rho)
    return (jnp.float32(1.0) / (jnp.float32(1.0) + coef * tau)).astype(
        jnp.float32
    )


def scale_push(cfg, data, tree, scale):
    """Server-side staleness-adaptive deflation of a pushed tree.

    Scales the LEAF TABLE and re-derives the delta by re-applying the
    scaled tree to the training bins — mul-then-GATHER-then-add, never a
    mul feeding the fold's add, for the same FMA-contraction reason
    ``propose_tree`` pre-scales by v: ``s * delta`` next to ``f + delta``
    contracts in some programs and not others, while a gathered operand
    cannot contract and ``round(s*leaf)[idx] == round(s*leaf[idx])``. The
    pushed delta is discarded (in a real PS the adaptive server would not
    request it: the tree alone determines the update).
    """
    tree = tree._replace(leaf_value=scale * tree.leaf_value)
    if cfg.obj.n_outputs == 1:
        return tree, apply_tree(tree, data.bins)
    return tree, apply_tree_stack(tree, data.bins)


def round_body(cfg, data, forest, f_live, f_target, rng, builder=None,
               staleness=None, plan=None):
    """One boosting round. Splitting ``f_target`` from ``f_live`` is what
    makes this body shared between every trainer: the tree is built against
    (possibly stale) ``f_target`` but folded into the live server state.

    The barrier pins the worker->server seam: the threaded runtime
    (``ps.runtime``) compiles ``propose_tree`` and ``server_fold`` as two
    separate programs, so the fused forms must not let XLA optimize across
    that boundary or record-and-replay would drift by compilation form.

    ``staleness`` is tau_j = j - k(j), known only at FOLD time (the fold
    order j is decided by the race, not the builder) — so the adaptive
    deflation lives on the server side of the barrier, exactly where the
    threaded runtime's fold program applies it.
    """
    tree, delta = propose_tree(cfg, data, f_target, rng, builder, plan)
    tree, delta = jax.lax.optimization_barrier((tree, delta))
    if cfg.adaptive_step and staleness is not None:
        scale = staleness_scale(cfg.adaptive_step, staleness)
        tree, delta = scale_push(cfg, data, tree, scale)
    return server_fold(cfg, forest, f_live, tree, delta)


# ---------------------------------------------------------------- trainer
class Trainer:
    """Mesh-aware parameter-server GBDT trainer.

    One instance per ``SGBDTConfig`` (jit caches live on the instance).
    The delay schedule is supplied per ``train`` call — anything
    ``ps.schedules.resolve_schedule`` accepts: a closed form spec, a
    realized k(j) array, or a ``ClusterSpec`` to simulate on the spot.

    With ``mesh`` whose ``axis_name`` axis has > 1 shard, tree builds run
    data-parallel via ``shard_map`` + ``psum`` (samples must divide the
    shard count; pad the dataset if needed). A mesh that ALSO carries a
    ``feature_axis`` axis (any size) selects the block-distributed 2D
    build: feature columns shard across it and split decisions merge with
    the (L,)-sized argmax collective instead of full-histogram psums
    (``ps.sharded.make_sharded_builder_2d``, DESIGN.md §16).
    """

    def __init__(
        self,
        cfg: SGBDTConfig,
        *,
        mesh: jax.sharding.Mesh | None = None,
        axis_name: str = "data",
        feature_axis: str = "feature",
    ):
        self.cfg = cfg
        self.mesh = mesh
        self.axis_name = axis_name
        self.feature_axis = feature_axis
        self.builder: TreeBuilder | None = None
        self._is_2d = mesh is not None and feature_axis in mesh.axis_names
        if self._is_2d:
            from repro.ps.sharded import make_sharded_builder_2d

            self.builder = make_sharded_builder_2d(
                cfg.learner, mesh, data_axis=axis_name, feature_axis=feature_axis
            )
        elif mesh is not None and dict(mesh.shape).get(axis_name, 1) > 1:
            from repro.ps.sharded import make_sharded_builder

            self.builder = make_sharded_builder(cfg.learner, mesh, axis_name)
        self._loop_cache: dict[int, Callable] = {}
        self._scan_cache: dict[int, Callable] = {}

    def place(self, data: BinnedData) -> BinnedData:
        """Lay the dataset out on the mesh once, the way the sharded
        builder reads it (``sharding.gbdt_data_specs``): rows over the
        data axis, feature columns over the feature axis. ``train`` and
        ``scan_with`` call this, so no round reshards the bins. Without a
        mesh the data is returned unchanged."""
        if self.mesh is None:
            return data
        from jax.sharding import NamedSharding

        from repro.sharding import gbdt_data_specs

        specs = gbdt_data_specs(self.mesh, sparse=isinstance(data.bins, SparseBins))

        def put(x, spec):
            return jax.device_put(x, NamedSharding(self.mesh, spec))

        return data._replace(
            bins=jax.tree.map(put, data.bins, specs.bins),
            bin_edges=put(data.bin_edges, specs.bin_edges),
            labels=put(data.labels, specs.labels),
            multiplicity=put(data.multiplicity, specs.multiplicity),
            qid=None if data.qid is None else put(data.qid, specs.labels),
        )

    def draw_plan(self, data: BinnedData) -> DrawPlan | jax.Array:
        """What ``data``'s rounds draw from (``sampling.draw_plan``): on a
        mesh the rows are sharded, and each round draws in full."""
        if self.mesh is not None:
            return data.multiplicity
        return draw_plan(data.multiplicity, self.cfg.sampling_rate)

    def collective_bytes(self, data: BinnedData) -> dict | None:
        """MEASURED per-tree-build collective bytes on this trainer's mesh
        (trace-time accounting; see ``ps.sharded.collective_bytes_per_build``).
        None when builds are single-device (no collectives at all)."""
        if self.builder is None:
            return None
        from repro.ps.sharded import collective_bytes_per_build

        return collective_bytes_per_build(
            self.cfg.learner, self.mesh, data.bins,
            data_axis=self.axis_name,
            feature_axis=self.feature_axis if self._is_2d else None,
        )

    # The unified step: loop and scan trace exactly this function. The scan
    # form adds a per-round loss as a scan output; the loop form does not
    # pay for it.
    def _step(self, ring_size: int):
        cfg, builder = self.cfg, self.builder

        def step(data, plan, carry, xs):
            forest, f, ring = carry
            j, k_j, rng = xs
            f_target = ring[k_j % ring_size]
            staleness = (j - k_j) if cfg.adaptive_step else None
            forest, f = round_body(
                cfg, data, forest, f, f_target, rng, builder, staleness, plan
            )
            ring = jax.lax.dynamic_update_index_in_dim(
                ring, f, (j + 1) % ring_size, 0
            )
            return (forest, f, ring)

        return step

    def _prep(self, data, schedule, seed):
        sched = resolve_schedule(schedule, self.cfg.n_trees)
        ring_size = max_staleness(sched) + 1
        keys = jax.random.split(jax.random.PRNGKey(seed), self.cfg.n_trees)
        state = init_state(self.cfg, data)
        ring = jnp.broadcast_to(state.f, (ring_size,) + state.f.shape)
        return sched, ring_size, keys, state, ring

    def train(
        self,
        data: BinnedData,
        schedule=("round_robin", 1),
        seed: int = 0,
        eval_every: int = 0,
        eval_fn: Callable[[TrainState, int], None] | None = None,
    ) -> TrainState:
        """Python-loop execution with per-round eval hooks."""
        data = self.place(data)
        plan = self.draw_plan(data)
        sched, ring_size, keys, state, ring = self._prep(data, schedule, seed)
        if ring_size not in self._loop_cache:
            self._loop_cache[ring_size] = jax.jit(self._step(ring_size))
        step = self._loop_cache[ring_size]
        forest, f = state.forest, state.f
        carry = (forest, f, ring)
        for j in range(self.cfg.n_trees):
            count_draw(keys[j], self.cfg.sampling_rate, plan)
            carry = step(
                data,
                plan,
                carry,
                (
                    jnp.asarray(j, jnp.int32),
                    jnp.asarray(int(sched[j]), jnp.int32),
                    keys[j],
                ),
            )
            if eval_fn is not None and eval_every and (j + 1) % eval_every == 0:
                eval_fn(
                    TrainState(carry[0], carry[1], jnp.asarray(j + 1, jnp.int32)),
                    j + 1,
                )
        forest, f, _ = carry
        return TrainState(
            forest=forest, f=f, step=jnp.asarray(self.cfg.n_trees, jnp.int32)
        )

    def scan_with(
        self,
        data: BinnedData,
        schedule: jax.Array,
        rngs: jax.Array,
        ring_size: int,
    ) -> tuple[TrainState, jax.Array]:
        """Whole run as one ``lax.scan`` over an explicit (k(j), keys) pair;
        returns per-round train losses too. The program the dry-run lowers."""
        cfg = self.cfg
        data = self.place(data)
        plan = self.draw_plan(data)
        if ring_size not in self._scan_cache:
            step = self._step(ring_size)

            @jax.jit
            def run(data, plan, schedule, rngs):
                def body(carry, xs):
                    carry = step(data, plan, carry, xs)
                    loss = cfg.obj.loss(
                        data.labels, carry[1], data.multiplicity, qid=data.qid
                    )
                    return carry, loss

                state = init_state(cfg, data)
                ring = jnp.broadcast_to(state.f, (ring_size,) + state.f.shape)
                (forest, f, _), losses = jax.lax.scan(
                    body,
                    (state.forest, state.f, ring),
                    (
                        jnp.arange(cfg.n_trees, dtype=jnp.int32),
                        schedule,
                        rngs,
                    ),
                )
                return (
                    TrainState(forest, f, jnp.asarray(cfg.n_trees, jnp.int32)),
                    losses,
                )

            self._scan_cache[ring_size] = run
        count_draw(rngs[0], cfg.sampling_rate, plan, len(rngs))
        return self._scan_cache[ring_size](data, plan, jnp.asarray(schedule), rngs)

    def train_scan(
        self, data: BinnedData, schedule=("round_robin", 1), seed: int = 0
    ) -> tuple[TrainState, jax.Array]:
        """scan_with, but resolving the schedule provider and drawing keys."""
        sched, ring_size, keys, _, _ = self._prep(data, schedule, seed)
        return self.scan_with(data, jnp.asarray(sched), keys, ring_size)


# One cached Trainer per config so the legacy shims share jit caches the way
# the old module-level ``@jax.jit(static_argnames=('cfg', ...))`` entry
# points did. The cache is LRU-bounded: each Trainer pins its compiled
# programs, so an unbounded dict leaks executables linearly in any config
# sweep (objective_sweep, fig10 --objective, hyperparameter scans).
_TRAINERS: "OrderedDict[SGBDTConfig, Trainer]" = OrderedDict()
_TRAINERS_MAX = 8


def get_trainer(cfg: SGBDTConfig) -> Trainer:
    trainer = _TRAINERS.get(cfg)
    if trainer is None:
        trainer = Trainer(cfg)
        _TRAINERS[cfg] = trainer
        while len(_TRAINERS) > _TRAINERS_MAX:
            _TRAINERS.popitem(last=False)
    else:
        _TRAINERS.move_to_end(cfg)
    return trainer


def clear_trainers() -> None:
    """Drop every cached Trainer (and the jit executables it pins).

    Config sweeps should call this between unrelated configs; pytest /
    benchmark processes that iterate many ``SGBDTConfig``s otherwise hold
    compiled programs for configs that will never run again.
    """
    _TRAINERS.clear()


def train(
    cfg: SGBDTConfig,
    data: BinnedData,
    schedule=("round_robin", 1),
    seed: int = 0,
    eval_every: int = 0,
    eval_fn=None,
) -> TrainState:
    """Functional convenience over the cached per-config Trainer."""
    return get_trainer(cfg).train(
        data, schedule, seed=seed, eval_every=eval_every, eval_fn=eval_fn
    )
