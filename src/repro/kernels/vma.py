"""Output types for ``pallas_call`` inside ``shard_map``.

``shard_map``'s replication check tracks, for every value, the mesh axes it
varies over (its ``vma``). A ``pallas_call`` cannot infer that for its
outputs, so every kernel here declares them with ``out_struct``.
"""
from __future__ import annotations

import jax


def out_struct(shape, dtype, *operands) -> jax.ShapeDtypeStruct:
    """A ``ShapeDtypeStruct`` varying over every mesh axis any of
    ``operands`` varies over (none outside ``shard_map``)."""
    vma = frozenset().union(*(jax.typeof(x).vma for x in operands))
    return jax.ShapeDtypeStruct(shape, dtype, vma=vma)
