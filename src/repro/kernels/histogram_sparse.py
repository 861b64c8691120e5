"""Pallas TPU kernel: sparse (explicit-zero-bin) grad/hess histograms.

The dense histogram kernel contracts over ALL N samples for every feature
block — cost N * F * B regardless of how many entries actually carry
information. On the high-dimensional sparse datasets this paper's PS
setting targets (real-sim, E2006: F ≫ N * density), almost every
(sample, feature) cell sits in the feature's majority bin. This kernel
contracts only the STORED entries of ``trees.binning.SparseBins``'s
feature-major ELL layout — cost rows * C * B per feature with
C ≈ N * density — so histogram work scales with nnz, not N * F.

Formulation mirrors the dense kernel's one-hot MXU contraction, batched
over the feature lanes of a block:

    out[f, r, b] = sum_c GH[f, r, c] * onehot[f, c, b]

where entry c of feature f carries (sample's node, grad, hess, bin code),
pre-gathered into (F, C) operand arrays by the wrapper; GH masks each
entry's grad/hess onto the GH row whose node it sits on (``row_map``
operand — the same node-subset mechanism as the dense kernel, so the
subtraction builder's smaller-child build works unchanged); onehot marks
the entry's stored bin code. ELL pads carry node -1 and never match a row.

The result is the STORED-entry histogram only. The zero-bin complement —
every absent entry lands at ``zero_bin[f]`` — is a subtraction
(node_total - stored_row_sum) and therefore MUST run after the data-axis
psum (the subtract-after-psum invariant); ``kernels.ops.build_histogram``
owns that step, this kernel never sees ``zero_bin``.

Grid: (feature_blocks, entry_blocks); entry axis is innermost and
accumulates into the same output block.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.vma import out_struct


def _sparse_hist_kernel(
    enode_ref,  # (F_blk, C_blk) int32 — node id of each entry's sample, -1 pad
    egrad_ref,  # (F_blk, C_blk) f32
    ehess_ref,  # (F_blk, C_blk) f32
    ecode_ref,  # (F_blk, C_blk) int32 — stored bin code
    rowmap_ref,  # (rows, 1) int32 — node id each GH row selects
    out_ref,  # (F_blk, rows * B) f32
    *,
    n_bins: int,
):
    f_blk, c_blk = enode_ref.shape
    rows = rowmap_ref.shape[0]

    entry_axis = pl.program_id(1)

    @pl.when(entry_axis == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    e_node = enode_ref[...]  # (F, C)
    e_grad = egrad_ref[...]
    e_hess = ehess_ref[...]
    row_node = rowmap_ref[:, 0]  # (rows,)

    # GH: (F, rows, C). Row r selects entries on node row_map[r]; even rows
    # carry grad, odd rows hess. ELL pads (node -1) never match.
    row_is_h = jax.lax.broadcasted_iota(jnp.int32, (1, rows, 1), 1) % 2
    gh_val = jnp.where(row_is_h == 0, e_grad[:, None, :], e_hess[:, None, :])
    gh = jnp.where(e_node[:, None, :] == row_node[None, :, None], gh_val, 0.0)

    # One-hot over stored codes: (F, C, B).
    bin_iota = jax.lax.broadcasted_iota(jnp.int32, (f_blk, c_blk, n_bins), 2)
    onehot = (ecode_ref[...][..., None] == bin_iota).astype(jnp.float32)

    # Batched over the feature lanes: (F, rows, C) x (F, C, B) -> (F, rows, B).
    # At f32 precision, as the dense kernel: the one-hot factor is exact in
    # any precision, but Mosaic's default rounds grad/hess to bf16 (a 5e-4
    # relative error against float64 sums on a v5e, 4e-8 at HIGHEST).
    blk = jax.lax.dot_general(
        gh, onehot, (((2,), (1,)), ((0,), (0,))),
        precision=jax.lax.Precision.HIGHEST, preferred_element_type=jnp.float32,
    )
    out_ref[...] += blk.reshape(f_blk, rows * n_bins)


@functools.partial(
    jax.jit,
    static_argnames=("n_nodes", "n_bins", "entry_block", "feature_block", "interpret"),
)
def histogram_sparse_pallas(
    feat_rows: jax.Array,  # (F, C) int32 sample ids, -1 = pad
    feat_codes: jax.Array,  # (F, C) int32 stored bin codes
    node_ids: jax.Array,  # (N,) int32, -1 = inactive
    grad: jax.Array,  # (N,) f32
    hess: jax.Array,  # (N,) f32
    n_nodes: int,
    n_bins: int,
    entry_block: int = 512,
    feature_block: int = 8,
    interpret: bool | None = None,
    active_nodes: jax.Array | None = None,  # (n_sub,) int32 node subset
) -> jax.Array:
    """Returns (2, R, F, n_bins) f32 STORED-entry histograms.

    ``R`` follows the dense kernel's contract: ``n_nodes`` rows for the
    full-level build, else one row per ``active_nodes`` entry. The caller
    (``kernels.ops``) adds the zero-bin complement after any data-axis
    psum. Operand padding (features to ``feature_block``, entries to
    ``entry_block``) happens here; pad entries carry node -1 and
    contribute exactly 0.
    """
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    f, c = feat_rows.shape

    # Pre-gather per-entry node/grad/hess once — (F, C) operands so the
    # kernel never touches the (N,) sample arrays. The scope names these
    # XLA-side gathers in the device trace.
    with jax.named_scope("histogram_sparse.gather"):
        valid = feat_rows >= 0
        safe = jnp.where(valid, feat_rows, 0)
        e_node = jnp.where(valid, jnp.take(node_ids, safe), -1).astype(jnp.int32)
        e_grad = jnp.take(grad, safe).astype(jnp.float32)
        e_hess = jnp.take(hess, safe).astype(jnp.float32)
    e_code = feat_codes.astype(jnp.int32)

    fp = -f % feature_block
    cp = -c % entry_block
    if fp or cp:
        pad = ((0, fp), (0, cp))
        e_node = jnp.pad(e_node, pad, constant_values=-1)
        e_grad = jnp.pad(e_grad, pad)
        e_hess = jnp.pad(e_hess, pad)
        e_code = jnp.pad(e_code, pad)
    fpad, cpad = f + fp, c + cp
    nf, nc = fpad // feature_block, cpad // entry_block

    if active_nodes is None:
        active_nodes = jnp.arange(n_nodes, dtype=jnp.int32)
    n_sub = active_nodes.shape[0]
    rows = 2 * n_sub
    row_map = jnp.repeat(active_nodes.astype(jnp.int32), 2)  # (rows,)

    out = pl.pallas_call(
        functools.partial(_sparse_hist_kernel, n_bins=n_bins),
        grid=(nf, nc),
        in_specs=[
            pl.BlockSpec((feature_block, entry_block), lambda fb, cb: (fb, cb)),
            pl.BlockSpec((feature_block, entry_block), lambda fb, cb: (fb, cb)),
            pl.BlockSpec((feature_block, entry_block), lambda fb, cb: (fb, cb)),
            pl.BlockSpec((feature_block, entry_block), lambda fb, cb: (fb, cb)),
            pl.BlockSpec((rows, 1), lambda fb, cb: (0, 0)),
        ],
        out_specs=pl.BlockSpec(
            (feature_block, rows * n_bins), lambda fb, cb: (fb, 0)
        ),
        out_shape=out_struct(
            (fpad, rows * n_bins), jnp.float32, e_node, e_grad, e_hess, e_code, row_map
        ),
        interpret=interpret,
        name="histogram_sparse_pallas",  # its stable name in the device trace
    )(e_node, e_grad, e_hess, e_code, row_map[:, None])
    # (Fpad, rows*B) -> (rows, F, B) -> (gh, sub, F, B), dropping feature pad
    out = out[:f].reshape(f, rows, n_bins).transpose(1, 0, 2)
    return out.reshape(n_sub, 2, f, n_bins).transpose(1, 0, 2, 3)
