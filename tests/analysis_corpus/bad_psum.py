"""Corpus: sibling derived BEFORE the psum — the exact inversion of the
subtract-after-psum invariant in ``ps/sharded.py``.

``parent`` and ``left`` are shard-local partial aggregates; subtracting
them pre-merge reorders the f32 reduction per shard, so the merged result
leaves bitwise lockstep with the single-device build.
``make_good_builder`` subtracts after the collective and must be clean.
"""
import jax
import jax.numpy as jnp

from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P


def make_bad_builder(mesh: Mesh):
    def body(bins, g):
        parent = jnp.sum(g)
        left = jnp.sum(jnp.where(bins > 0, g, jnp.float32(0.0)))
        sibling = parent - left  # pre-merge subtract: the violation
        return jax.lax.psum(sibling, "data")

    return shard_map(body, mesh=mesh, in_specs=(P("data"), P("data")), out_specs=P())


def make_good_builder(mesh: Mesh):
    def body(bins, g):
        parent = jax.lax.psum(jnp.sum(g), "data")
        left = jax.lax.psum(jnp.sum(jnp.where(bins > 0, g, jnp.float32(0.0))), "data")
        return parent - left  # post-merge: commutes with the collective

    return shard_map(body, mesh=mesh, in_specs=(P("data"), P("data")), out_specs=P())


def make_bad_argmax_builder(mesh: Mesh):
    """Argmax merge BEFORE the row psum — the 2D mesh inversion.

    ``best`` is a max over shard-local PARTIAL histogram sums: pmax-merging
    it picks the winner from per-shard partials (max does not commute with
    the data-axis psum), so different shard counts elect different splits.
    """

    def body(bins, g):
        hist = jax.ops.segment_sum(g, bins, num_segments=8)  # local partial
        best = jnp.max(hist)  # gain over UNMERGED sums
        return jax.lax.pmax(best, "data")  # premerge argmax: the violation

    return shard_map(body, mesh=mesh, in_specs=(P("data"), P("data")), out_specs=P())


def make_good_argmax_builder(mesh: Mesh):
    """Row psum first, argmax merge after — DESIGN.md §16 ordering."""

    def body(bins, g):
        hist = jax.ops.segment_sum(g, bins, num_segments=8)
        hist = jax.lax.psum(hist, "data")  # merge rows FIRST
        best = jnp.max(hist)  # gain over merged sums
        return jax.lax.pmax(best, "data")  # merged-argmax collective: clean

    return shard_map(body, mesh=mesh, in_specs=(P("data"), P("data")), out_specs=P())
