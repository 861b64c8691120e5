"""Pallas TPU kernel: fused batched forest traversal for serving.

Inference cost in GBT deployments is dominated by batched traversal
throughput (Anghel et al., 2018): for every request row, T trees each do a
depth-d heap descent and the T leaf values are summed. Evaluated naively
(one tree at a time, XLA scan like ``kernels.ref.apply_forest_ref``), the
per-tree prediction vector (N,) round-trips HBM T times and nothing of the
tree arrays is reused across samples.

The kernel evaluates a (sample_block, tree_block) tile per grid step with
everything resident in VMEM:

- tree arrays arrive pre-transposed as (n_int, T) / (n_leaf, T) so node k
  of every tree in the block is one (1, T_blk) row;
- no gathers (Mosaic lowers none of this shape): the bin each internal
  node k tests is ``bins @ onehot(feature[k])`` — an (S, F) x (F, T_blk)
  MXU product that selects one column per tree, exact at f32 precision —
  and the heap descent is one pass over the nodes in heap order,
  ``node = where(node == k, 2k + 1 + (bin > threshold[k]), node)``: children
  follow their parent, so one ascending sweep finishes every descent;
- leaf values are picked the same way (compare-and-select per leaf slot),
  masked by the live-tree count (partially-filled forests serve correctly
  even if dead slots hold stale trees) and reduced on-chip; only the (N,)
  partial sum is written back, accumulated across tree blocks — nothing of
  size (N, T) ever touches HBM.

Grid: (sample_blocks, tree_blocks); the tree axis is innermost and
accumulates into the same output block (the histogram kernel's reduce
pattern).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.vma import out_struct


def _traverse_kernel(
    bins_ref,  # (S_blk, F) int32
    feat_ref,  # (n_int, T_blk) int32 — transposed tree arrays
    thr_ref,  # (n_int, T_blk) int32 — or int8/int16 quantized
    leaf_ref,  # (n_leaf, T_blk) f32 — or int8/fp16 quantized
    *rest,  # [scale_ref (1, T_blk) f32 for int8 leaves], ntree_ref, out_ref
    depth: int,
    tree_block: int,
    n_outputs: int,
    scaled: bool,
):
    if scaled:
        scale_ref, ntree_ref, out_ref = rest
    else:
        (ntree_ref, out_ref), scale_ref = rest, None
    tb = pl.program_id(1)

    @pl.when(tb == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    bins = bins_ref[...].astype(jnp.float32)  # bin ids: exact in f32
    s_blk, n_feat = bins.shape
    n_int = (1 << depth) - 1
    # Dequantize-in-VMEM epilogue (DESIGN.md §17): quantized blocks travel
    # HBM->VMEM packed (4x fewer bytes for int8) and widen on-chip once
    # per block. On the f32/int32 layout both converts are same-dtype
    # no-ops, so that path's program is unchanged.
    thr = thr_ref[...].astype(jnp.int32)
    leaf = leaf_ref[...].astype(jnp.float32)
    if scaled:
        leaf = leaf * scale_ref[...]  # (n_leaf, T_blk)
    feat = feat_ref[...]
    f_iota = jax.lax.broadcasted_iota(jnp.int32, (n_feat, tree_block), 0)

    # Heap descent, all (sample, tree) pairs at once: node k's test runs
    # on the samples currently at k, and every child index exceeds its
    # parent's, so one ascending pass over k completes all depth levels.
    node = jnp.zeros((s_blk, tree_block), jnp.int32)
    for k in range(n_int):
        pick = (f_iota == feat[k : k + 1, :]).astype(jnp.float32)  # (F, T)
        v = jax.lax.dot(  # (S, T): bin of node k's feature, per tree
            bins, pick, precision=jax.lax.Precision.HIGHEST,
            preferred_element_type=jnp.float32,
        )
        right = (v > thr[k : k + 1, :].astype(jnp.float32)).astype(jnp.int32)
        node = jnp.where(node == k, 2 * k + 1 + right, node)

    vals = jnp.zeros((s_blk, tree_block), jnp.float32)
    for j in range(1 << depth):
        vals = jnp.where(node == n_int + j, leaf[j : j + 1, :], vals)
    tree_idx = tb * tree_block + jax.lax.broadcasted_iota(
        jnp.int32, vals.shape, 1
    )
    vals = jnp.where(tree_idx < ntree_ref[0, 0], vals, 0.0)
    if n_outputs == 1:
        out_ref[...] += jnp.sum(vals, axis=1, keepdims=True)
    else:
        # Slot t belongs to output t % K (round-major/output-minor forest
        # layout): K masked on-chip reductions into the (S, K) accumulator.
        out_k = tree_idx % n_outputs
        for k in range(n_outputs):
            out_ref[:, k : k + 1] += jnp.sum(
                jnp.where(out_k == k, vals, 0.0), axis=1, keepdims=True
            )


@functools.partial(
    jax.jit,
    static_argnames=("depth", "sample_block", "tree_block", "interpret", "n_outputs"),
)
def forest_traverse_pallas(
    bins: jax.Array,  # (N, F) int32 — N % sample_block == 0 (wrapper pads)
    feature: jax.Array,  # (T, 2^d - 1) int32 — T % tree_block == 0
    threshold: jax.Array,  # (T, 2^d - 1) int32 — or int8/int16 quantized
    leaf_value: jax.Array,  # (T, 2^d) f32 — or int8/fp16 quantized
    n_trees: jax.Array,  # () int32 — live slots; slots >= n_trees add 0
    depth: int,
    sample_block: int = 256,
    tree_block: int = 512,
    interpret: bool | None = None,
    n_outputs: int = 1,
    leaf_scale: jax.Array | None = None,  # (T,) f32 — int8 mode only
) -> jax.Array:
    """Masked forest sum (N,) f32 — or (N, K) with ``n_outputs`` = K > 1,
    where slot t reduces into output column t % K. See module docstring.

    Quantized forests (int8 leaves + ``leaf_scale``, int8/int16
    thresholds) ride the same grid with a dequantize-in-VMEM epilogue;
    fp16 leaves widen to f32 before the call. ``interpret=None`` auto-detects (Mosaic
    on TPU, interpreter elsewhere).
    """
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    n, f = bins.shape
    t, n_int = feature.shape
    n_leaf = leaf_value.shape[1]
    assert n % sample_block == 0, "wrapper must pad samples"
    assert t % tree_block == 0, "wrapper must pad trees"
    ns, nt = n // sample_block, t // tree_block
    scaled = leaf_value.dtype == jnp.int8
    if scaled:
        assert leaf_scale is not None, "int8 leaves need leaf_scale"
    if leaf_value.dtype == jnp.float16:
        # Mosaic loads no f16 vectors on TPU v5e: fp16 leaves widen to f32
        # (exactly) before the call and travel like the f32 layout.
        leaf_value = leaf_value.astype(jnp.float32)

    in_specs = [
        pl.BlockSpec((sample_block, f), lambda sb, tb: (sb, 0)),
        pl.BlockSpec((n_int, tree_block), lambda sb, tb: (0, tb)),
        pl.BlockSpec((n_int, tree_block), lambda sb, tb: (0, tb)),
        pl.BlockSpec((n_leaf, tree_block), lambda sb, tb: (0, tb)),
    ]
    operands = [bins, feature.T, threshold.T, leaf_value.T]
    if scaled:
        # Per-tree dequant scales ride VMEM next to the leaf block they
        # rescale — (1, tree_block) per grid step, broadcast on-chip.
        in_specs.append(pl.BlockSpec((1, tree_block), lambda sb, tb: (0, tb)))
        operands.append(leaf_scale.reshape(1, t).astype(jnp.float32))
    in_specs.append(
        pl.BlockSpec((1, 1), lambda sb, tb: (0, 0), memory_space=pltpu.SMEM)
    )
    operands.append(jnp.asarray(n_trees, jnp.int32).reshape(1, 1))

    out = pl.pallas_call(
        functools.partial(
            _traverse_kernel,
            depth=depth,
            tree_block=tree_block,
            n_outputs=n_outputs,
            scaled=scaled,
        ),
        grid=(ns, nt),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((sample_block, n_outputs), lambda sb, tb: (sb, 0)),
        out_shape=out_struct((n, n_outputs), jnp.float32, *operands),
        interpret=interpret,
        name="forest_traverse_pallas",  # its stable name in the device trace
    )(*operands)
    return out[:, 0] if n_outputs == 1 else out
