"""Pallas TPU kernel: gradient/hessian histograms as one-hot MXU matmuls.

LightGBM's (and every GPU GBDT's) hot loop scatter-adds grad/hess into
per-(node, feature, bin) buckets — atomics into shared memory. TPUs have no
atomics and weak scatter throughput, but a 128x128 systolic MXU. We therefore
reformulate the whole level-histogram as a single dense contraction:

    out[r, f*B + b] = sum_s GH[r, s] * onehot[f*B + b, s]

where row r carries (node_of_row[r], grad-or-hess), GH masks each sample's
grad/hess onto its current tree node, and onehot marks the sample's bin for
feature f. Both factor matrices are built on the fly inside VMEM from
integer inputs — nothing of size (N, F*B) ever touches HBM.

The dot is one bf16 MXU pass that loses nothing. The one-hot is 0/1, exact
in bf16. Each f32 grad/hess splits exactly into three bf16 parts
(``split_bf16``), stacked as three row blocks of GH: every product is exact
and accumulates in f32, and the three blocks' sums are added at the end
(``merge_parts``). At 3 * rows <= 128 the stack fills no more of the MXU's
tile than GH alone; the one-hot, the big operand, passes through once, where
an f32 dot at ``Precision.HIGHEST`` would pass it once per bf16 pass.

The row -> node mapping is an explicit operand (``row_map``), not an iota:
rows [0, R) carry the grad and rows [R, 2R) the hess of node
``row_map[r]``. The full-level build passes ``arange(n_nodes)`` twice; the
histogram-subtraction tree builder (``trees.learner`` with
``hist_mode='subtract'``) passes the smaller child of every parent only,
halving the GH rows of every level below the root. That halves the MXU
work only where the stacked 3 * rows pass one 128-row tile: below it, a
level costs one pass of the one-hot whatever its node count.

Samples ride the lane axis everywhere: node, grad and hess arrive as
(1, N) rows (the layout GH needs) and the bins as the feature-major (F, N)
transpose of the (N, F) matrix. An (N, F) block would pad F = 28 to 128
lanes in VMEM and in HBM, and an (N, 1) operand a single lane to 128;
(F, N) keeps both dense, and is a free view of the feature-major layout
XLA gives a narrow (N, F) array. Block shapes follow ``kernels.autotune``:
the feature block spans the padded width up to 128 features and is a
multiple of 128 above it.

Grid: (feature_blocks, sample_blocks); sample axis is innermost and
accumulates into the same output block (standard Pallas reduce pattern).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.vma import out_struct


def split_bf16(x):
    """``(hi, mid, lo)`` bf16 with ``hi + mid + lo == x`` exactly, in f32.

    ``hi`` takes the top 8 significant bits of ``x``, ``mid`` the next 8 of
    the exact residual and ``lo`` the rest, which fits bf16's 8. Exact for
    0 and for 2**-103 <= |x| up to half a bf16 step below f32's largest
    value: there ``hi`` rounds to inf, the one limit a gradient could meet.
    (Below 2**-103 ``lo`` would be subnormal, and what is lost lies under
    2**-126.)"""
    hi = x.astype(jnp.bfloat16)
    rest = x - hi.astype(jnp.float32)
    mid = rest.astype(jnp.bfloat16)
    lo = (rest - mid.astype(jnp.float32)).astype(jnp.bfloat16)
    return hi, mid, lo


def merge_parts(acc):
    """``(3 * rows, ...)`` sums of the hi, mid and lo row blocks ->
    ``(rows, ...)``, added hi + mid + lo in that order."""
    rows = acc.shape[0] // 3
    return acc[:rows] + acc[rows : 2 * rows] + acc[2 * rows :]


def gh_factor(row_node, node, grad, hess):
    """The (3 * rows, S) bf16 GH factor from a (rows, 1) row map and (1, S)
    sample rows: row r < rows/2 carries the grad of samples on node
    ``row_node[r]``, row r >= rows/2 their hess, and the three row blocks
    are the hi, mid and lo parts of those f32 values (``split_bf16``).
    Inactive samples (node < 0) never match (row maps hold real node ids
    >= 0)."""
    rows, s_blk = row_node.shape[0], node.shape[1]
    row_is_h = jax.lax.broadcasted_iota(jnp.int32, (rows, s_blk), 0) >= rows // 2
    gh_val = jnp.where(row_is_h, hess, grad)
    gh = jnp.where(row_node == node, gh_val, 0.0)
    return jnp.concatenate(split_bf16(gh), axis=0)


def onehot_factor(bins_t, n_bins: int):
    """The (F_blk * B, S) bf16 one-hot factor of an (F_blk, S) feature-major
    bins block: ``[f*B + b, s] = 1{bins_t[f, s] == b}``."""
    f_blk, s_blk = bins_t.shape
    bin_iota = jax.lax.broadcasted_iota(jnp.int32, (f_blk, n_bins, s_blk), 1)
    onehot = (bins_t[:, None, :] == bin_iota).astype(jnp.bfloat16)
    return onehot.reshape(f_blk * n_bins, s_blk)


def hist_dot(gh, onehot):
    """GH @ onehot^T, (3 * rows, S) x (F_blk*B, S) -> (3 * rows, F_blk*B)
    f32: one bf16 pass whose products are exact, accumulated in f32."""
    return jax.lax.dot_general(
        gh, onehot, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )


def _hist_kernel(
    bins_ref,  # (F_blk, S_blk) int32 — feature-major
    node_ref,  # (1, S_blk) int32, -1 = inactive
    grad_ref,  # (1, S_blk) f32
    hess_ref,  # (1, S_blk) f32
    rowmap_ref,  # (rows, 1) int32 — node id each GH row selects
    out_ref,  # (3 * rows, F_blk*B) f32 — hi, mid and lo row blocks
    *,
    n_bins: int,
):
    @pl.when(pl.program_id(1) == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    gh = gh_factor(rowmap_ref[...], node_ref[...], grad_ref[...], hess_ref[...])
    out_ref[...] += hist_dot(gh, onehot_factor(bins_ref[...], n_bins))


@functools.partial(
    jax.jit,
    static_argnames=("n_nodes", "n_bins", "sample_block", "feature_block", "interpret"),
)
def histogram_pallas(
    bins: jax.Array,  # (N, F) int32 — N % sample_block == 0 (wrapper pads)
    node_ids: jax.Array,  # (N,) int32
    grad: jax.Array,  # (N,) f32
    hess: jax.Array,  # (N,) f32
    n_nodes: int,
    n_bins: int,
    sample_block: int = 512,
    feature_block: int = 128,
    interpret: bool | None = None,
    active_nodes: jax.Array | None = None,  # (n_sub,) int32 node subset
) -> jax.Array:
    """Returns (2, R, F, n_bins) f32 histograms. See module docstring.

    ``R = n_nodes`` for the full-level build (``active_nodes=None``), else
    ``R = len(active_nodes)`` and row r histograms node ``active_nodes[r]``
    only — the entry point of the parent-minus-child subtraction builder.
    ``active_nodes`` values must be valid node ids in ``[0, n_nodes)``;
    its length is static (it fixes the kernel's row count).

    ``interpret=None`` auto-detects: compile to Mosaic on TPU, run the
    Pallas interpreter elsewhere — so direct callers (tests, benches) get
    the real kernel on real hardware instead of silently interpreting.
    """
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    n, f = bins.shape
    feature_block = min(feature_block, f)
    assert n % sample_block == 0, "wrapper must pad samples"
    assert f % feature_block == 0, "wrapper must pad features"
    ns, nf = n // sample_block, f // feature_block
    if active_nodes is None:
        active_nodes = jnp.arange(n_nodes, dtype=jnp.int32)
    n_sub = active_nodes.shape[0]
    rows = 2 * n_sub
    row_map = jnp.tile(active_nodes.astype(jnp.int32), 2)  # (rows,)

    out = pl.pallas_call(
        functools.partial(_hist_kernel, n_bins=n_bins),
        grid=(nf, ns),
        in_specs=[
            pl.BlockSpec((feature_block, sample_block), lambda fb, sb: (fb, sb)),
            pl.BlockSpec((1, sample_block), lambda fb, sb: (0, sb)),
            pl.BlockSpec((1, sample_block), lambda fb, sb: (0, sb)),
            pl.BlockSpec((1, sample_block), lambda fb, sb: (0, sb)),
            pl.BlockSpec((rows, 1), lambda fb, sb: (0, 0)),
        ],
        out_specs=pl.BlockSpec(
            (3 * rows, feature_block * n_bins), lambda fb, sb: (0, fb)
        ),
        out_shape=out_struct(
            (3 * rows, f * n_bins), jnp.float32, bins, node_ids, grad, hess, row_map
        ),
        interpret=interpret,
        name="histogram_pallas",  # its stable name in the device trace
    )(
        bins.T,
        node_ids[None, :],
        grad[None, :],
        hess[None, :],
        row_map[:, None],
    )
    # rows are (part, grad|hess, row) -> (gh, row, feature, bin)
    return merge_parts(out).reshape(2, n_sub, f, n_bins)
