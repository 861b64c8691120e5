"""Production meshes. Functions, not module constants — importing this
module never touches jax device state (the dry-run sets
XLA_FLAGS=--xla_force_host_platform_device_count=512 before first init)."""
from __future__ import annotations

import jax


def make_mesh(shape, axes) -> jax.sharding.Mesh:
    """Every mesh in the repo: ``jax.make_mesh`` with ``Auto`` axis types.

    ``jax.make_mesh`` defaults to ``Explicit`` axes, under which sharding
    is part of each value's type and the engine's slot writes
    (``forest_push``'s dynamic_update_slice) reject operands sharded
    differently; the sharded builders rely on Auto propagation instead.
    """
    types = (jax.sharding.AxisType.Auto,) * len(axes)
    return jax.make_mesh(shape, axes, axis_types=types)


def make_production_mesh(*, multi_pod: bool = False) -> jax.sharding.Mesh:
    """16x16 = 256 chips per pod; 2x16x16 = 512 across two pods."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_host_mesh() -> jax.sharding.Mesh:
    """Degenerate 1x1 mesh over the local device — smoke tests / CPU runs."""
    return make_mesh((1, 1), ("data", "model"))


def make_gbdt_mesh(n_data: int = 1, n_feature: int = 1) -> jax.sharding.Mesh:
    """The block-distributed GBDT training mesh: rows × feature columns.

    ``(n_data, 1)`` is the classic 1D data-parallel shape re-expressed in
    2D; ``(1, n_feature)`` is the sparse/high-dimensional regime where the
    full-histogram psum disappears in favor of the (L,)-sized argmax merge
    (DESIGN.md §16). Requires ``n_data * n_feature`` visible devices.
    """
    return make_mesh((n_data, n_feature), ("data", "feature"))


# TPU v5e hardware constants used by the roofline analysis (per chip).
PEAK_FLOPS_BF16 = 197e12  # FLOP/s
HBM_BW = 819e9  # bytes/s
ICI_BW = 50e9  # bytes/s per link
