"""Pallas TPU kernel: ONE fused program per tree level.

The staged build pays three HBM round-trips per level: the histogram kernel
writes the (2, L, F, B) level histogram, ``split_scan`` reads it back and
writes the (L, F, B) gain surface, and the learner reads THAT back for the
argmax before a fourth pass re-routes every sample. This kernel fuses the
whole level:

  phase A (grid steps 0..ns-1) — stream sample blocks from HBM (the grid
      pipeline double-buffers the DMA) and accumulate the built nodes'
      grad/hess histogram in a VMEM scratch via the same one-hot MXU
      contraction as ``histogram.py`` — identical dot shapes, identical
      accumulation order, so single-shard results stay bit-compatible with
      the staged kernel;
  phase B (first step of the partition sweep) — derive the sibling rows
      from the cached parent histogram (subtract mode), run the cumulative
      split-gain scan IN REGISTERS over the full (L, F, B) block, apply
      the feature mask, argmax, and fix unsplittable nodes to the
      pass-left convention — only the (2, L, F, B) level histogram (the
      next level's subtraction cache) and three (L,)-sized split vectors
      ever reach HBM, never a gain surface;
  phase C (grid steps ns..2*ns-1) — second sample sweep: gather each
      sample's node's winning (feature, threshold) from the VMEM-resident
      split tables and emit the new row -> node map.

Grid: (2 * ns,) — ns sample-block steps of histogram accumulation, then ns
steps of partition. Scalars (lam, min_child_hess) ride in SMEM; everything
data-dependent (the active-node subset, the feature mask) is an operand so
one compiled program serves a whole training run.

Layouts are the ones Mosaic lowers without relayouts: histograms stay
(rows, F * B) end to end (the gain scan is ``split_scan.split_gain_tile``
on that layout, never a lane-splitting reshape), per-sample operands come
as (1, N) lane rows and the bins as their feature-major (F, N) transpose
(as in ``histogram.py``: no lane padding in VMEM or HBM), split decisions
leave as (L, 2) / (L, 1) columns, and row selections (sibling expansion,
the split-table lookup) are 0/1 matmuls.

The row -> node semantics, the gain formula, the validity mask, and the
argmax tie-break (first maximum in (f * B + b) row-major order) all match
``ref.level_build_ref`` / the staged ``trees.learner`` path exactly.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.histogram import gh_factor, hist_dot, merge_parts, onehot_factor
from repro.kernels.split_scan import split_gain_tile
from repro.kernels.vma import out_struct


def _select(sel, x):
    """``sel @ x`` for a 0/1 row-selection matrix — exact at f32 precision."""
    return jax.lax.dot(
        sel, x, precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32,
    )


def _level_kernel(
    bins_ref,  # (F_pad, S_blk) int32 — feature-major
    node_ref,  # (1, S_blk) int32, -1 = inactive
    grad_ref,  # (1, S_blk) f32
    hess_ref,  # (1, S_blk) f32
    rowmap_ref,  # (2 * L_sub, 1) int32 — node id each GH row selects
    parent_ref,  # (2, L_sub, FB_pad) f32 — parent cache (zeros in full mode)
    mask_ref,  # (1, FB_pad) f32 — 1.0 = the lane's feature is in the subsample
    params_ref,  # (2,) f32 in SMEM — [lam, min_child_hess]
    hist_ref,  # out (2, L, FB_pad) f32 — the full level histogram
    split_ref,  # out (L, 2) int32 — [best_feature, best_bin] per node
    gain_ref,  # out (L, 1) f32 — best gain per node (pre pass-left fix)
    node_out_ref,  # out (1, S_blk) int32 — new row -> node map
    acc_ref,  # scratch (6 * L_sub, FB_pad) f32 — built rows' hi, mid, lo sums
    *,
    ns: int,
    n_bins: int,
    feature_block: int,
    n_nodes: int,
    derive_sibling: bool,
):
    t = pl.program_id(0)
    f_pad, s_blk = bins_ref.shape
    l_sub = acc_ref.shape[0] // 6
    l = n_nodes
    fb = f_pad * n_bins
    chunk = feature_block * n_bins

    @pl.when(t == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(t < ns)
    def _accumulate():
        # Same GH factor and dot as histogram.py, one dot of identical
        # shape per (feature chunk, sample block): the per-cell f32
        # accumulation order matches the staged kernel's grid bit for bit.
        gh = gh_factor(rowmap_ref[...], node_ref[...], grad_ref[...], hess_ref[...])
        for c in range(f_pad // feature_block):
            blk = bins_ref[c * feature_block : (c + 1) * feature_block, :]
            acc_ref[:, c * chunk : (c + 1) * chunk] += hist_dot(
                gh, onehot_factor(blk, n_bins)
            )

    @pl.when(t == ns)
    def _decide():
        acc = merge_parts(acc_ref[...])  # as the staged wrapper adds them
        g_full = acc[:l_sub, :]  # (L_sub, FB) grad rows
        h_full = acc[l_sub:, :]  # hess rows
        if derive_sibling:
            # Node n (parent p = n >> 1) is either the built child or the
            # derived sibling ``parent - built`` — the subtraction runs on
            # the already-merged parent cache, so under shard_map the
            # learner keeps the collective BEFORE this kernel (see
            # ps/sharded.py); single-shard, this is the same arithmetic as
            # the staged learner's post-psum gather.
            sel = (
                jax.lax.broadcasted_iota(jnp.int32, (l, l_sub), 0) >> 1
                == jax.lax.broadcasted_iota(jnp.int32, (l, l_sub), 1)
            ).astype(jnp.float32)  # (L, L_sub): node n <- row n >> 1
            built_of = _select(sel, rowmap_ref[:l_sub, :].astype(jnp.float32))
            is_built = (
                jax.lax.broadcasted_iota(jnp.int32, (l, 1), 0)
                == built_of.astype(jnp.int32)
            )
            g_b, h_b = _select(sel, g_full), _select(sel, h_full)
            g_p, h_p = _select(sel, parent_ref[0]), _select(sel, parent_ref[1])
            g_full = jnp.where(is_built, g_b, g_p - g_b)
            h_full = jnp.where(is_built, h_b, h_p - h_b)
        hist_ref[0] = g_full
        hist_ref[1] = h_full

        # The staged split kernel's gain formula on the same layout.
        gain, valid = split_gain_tile(
            g_full, h_full, params_ref[0], params_ref[1], n_bins
        )
        gain = jnp.where(valid & (mask_ref[...] > 0.0), gain, -jnp.inf)

        # Argmax with the first-maximum tie-break (== jnp.argmax): max,
        # then the smallest flat index attaining it.
        best = jnp.max(gain, axis=-1, keepdims=True)  # (L, 1)
        pos = jax.lax.broadcasted_iota(jnp.int32, (l, fb), 1)
        idx = jnp.min(jnp.where(gain == best, pos, fb), axis=-1, keepdims=True)
        ok = jnp.isfinite(best) & (best > 0.0)
        feat = jnp.where(ok, idx // n_bins, 0)
        thr = jnp.where(ok, idx % n_bins, n_bins - 1)
        split_ref[...] = jnp.concatenate([feat, thr], axis=1).astype(jnp.int32)
        gain_ref[...] = best

    @pl.when(t >= ns)
    def _partition():
        # Route every sample: look up its node's winning (feature, bin)
        # in the VMEM-resident split table with a one-hot contraction (no
        # TPU gathers), read the sample's bin for that feature, go right
        # iff bin > threshold. Matches the staged learner's
        # ``2 * node + (bins[s, feat[node]] > thr[node])`` update.
        node = node_ref[...]  # (1, S)
        onehot_l = (
            jax.lax.broadcasted_iota(jnp.int32, (l, s_blk), 0) == node
        ).astype(jnp.float32)
        sel = jax.lax.dot_general(  # (2, S): [feature; threshold] per sample
            split_ref[...].astype(jnp.float32), onehot_l,
            (((0,), (0,)), ((), ())), precision=jax.lax.Precision.HIGHEST,
            preferred_element_type=jnp.float32,
        )
        feat_s = sel[0:1, :].astype(jnp.int32)  # exact: values < F_pad
        f_iota = jax.lax.broadcasted_iota(jnp.int32, (f_pad, s_blk), 0)
        val = jnp.sum(
            jnp.where(f_iota == feat_s, bins_ref[...], 0), axis=0, keepdims=True
        ).astype(jnp.float32)
        go_right = (val > sel[1:2, :]).astype(jnp.int32)
        node_out_ref[...] = 2 * node + go_right


@functools.partial(
    jax.jit,
    static_argnames=(
        "n_nodes",
        "n_bins",
        "derive_sibling",
        "sample_block",
        "feature_block",
        "interpret",
    ),
)
def level_build_pallas(
    bins: jax.Array,  # (N_pad, F_pad) int32 — wrapper pads both axes
    node_ids: jax.Array,  # (N_pad,) int32, -1 = padding/inactive
    grad: jax.Array,  # (N_pad,) f32
    hess: jax.Array,  # (N_pad,) f32
    active_nodes: jax.Array,  # (L_sub,) int32 node ids to histogram
    parent_hist: jax.Array | None,  # (2, L_sub, F_pad, B) merged parent cache
    feat_mask: jax.Array,  # (F_pad,) f32 — 1.0 = feature available
    lam: jax.Array,
    min_child_hess: jax.Array,
    n_nodes: int,
    n_bins: int,
    derive_sibling: bool = False,
    sample_block: int = 512,
    feature_block: int = 128,
    interpret: bool | None = None,
) -> tuple[jax.Array, jax.Array, jax.Array, jax.Array, jax.Array]:
    """One fused level: (hist (2, L, F_pad, B), feat (L,), thr (L,),
    best_gain (L,), new_node (N_pad,)). See the module docstring.

    ``derive_sibling=False`` is the full-level build (``active_nodes`` must
    enumerate all ``n_nodes``); ``True`` is the subtraction mode —
    ``active_nodes[p]`` is the smaller child of parent ``p`` and
    ``parent_hist`` the (already psum-merged, when sharded) previous-level
    cache. ``interpret=None`` auto-detects like every kernel here.
    """
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    n, f_pad = bins.shape
    feature_block = min(feature_block, f_pad)
    assert n % sample_block == 0, "wrapper must pad samples"
    assert f_pad % feature_block == 0, "wrapper must pad features"
    ns = n // sample_block
    l_sub = active_nodes.shape[0]
    if derive_sibling:
        assert parent_hist is not None and 2 * l_sub == n_nodes
    else:
        assert l_sub == n_nodes
        parent_hist = jnp.zeros((2, l_sub, f_pad, n_bins), jnp.float32)
    fb = f_pad * n_bins
    row_map = jnp.tile(active_nodes.astype(jnp.int32), 2)
    params = jnp.stack(
        [jnp.asarray(lam, jnp.float32), jnp.asarray(min_child_hess, jnp.float32)]
    )

    kernel = functools.partial(
        _level_kernel,
        ns=ns,
        n_bins=n_bins,
        feature_block=feature_block,
        n_nodes=n_nodes,
        derive_sibling=derive_sibling,
    )
    # Bins and node ids stream in both phases; grad/hess only in phase A
    # and the new node map only in phase C — their maps park the block
    # while the other phase runs, so no step DMAs a block it does not use.
    operands = (
        bins.T,
        node_ids[None, :],
        grad[None, :],
        hess[None, :],
        row_map[:, None],
        parent_hist.reshape(2, l_sub, fb),
        jnp.repeat(feat_mask.astype(jnp.float32), n_bins)[None, :],
        params,
    )
    phase_a = lambda t: jnp.minimum(t, ns - 1)
    phase_c = lambda t: jnp.where(t < ns, 0, t - ns)
    hist, split, gain, new_node = pl.pallas_call(
        kernel,
        grid=(2 * ns,),
        in_specs=[
            pl.BlockSpec((f_pad, sample_block), lambda t: (0, jax.lax.rem(t, ns))),
            pl.BlockSpec((1, sample_block), lambda t: (0, jax.lax.rem(t, ns))),
            pl.BlockSpec((1, sample_block), lambda t: (0, phase_a(t))),
            pl.BlockSpec((1, sample_block), lambda t: (0, phase_a(t))),
            pl.BlockSpec((2 * l_sub, 1), lambda t: (0, 0)),
            pl.BlockSpec((2, l_sub, fb), lambda t: (0, 0, 0)),
            pl.BlockSpec((1, fb), lambda t: (0, 0)),
            pl.BlockSpec(memory_space=pltpu.SMEM),
        ],
        out_specs=[
            pl.BlockSpec((2, n_nodes, fb), lambda t: (0, 0, 0)),
            pl.BlockSpec((n_nodes, 2), lambda t: (0, 0)),
            pl.BlockSpec((n_nodes, 1), lambda t: (0, 0)),
            pl.BlockSpec((1, sample_block), lambda t: (0, phase_c(t))),
        ],
        out_shape=[
            out_struct((2, n_nodes, fb), jnp.float32, *operands),
            out_struct((n_nodes, 2), jnp.int32, *operands),
            out_struct((n_nodes, 1), jnp.float32, *operands),
            out_struct((1, n), jnp.int32, *operands),
        ],
        scratch_shapes=[pltpu.VMEM((6 * l_sub, fb), jnp.float32)],
        interpret=interpret,
        name="level_build_pallas",  # its stable name in the device trace
    )(*operands)
    return (
        hist.reshape(2, n_nodes, f_pad, n_bins),
        split[:, 0],
        split[:, 1],
        gain[:, 0],
        new_node[0],
    )


# Fall back to the staged pipeline when a level's resident set would not
# leave headroom in the ~16 MB/core VMEM (DESIGN.md §13 has the budget
# math). Deep wide levels are exactly where histogram tiling wins anyway.
FUSED_VMEM_BUDGET = 12 * 2**20


def fused_level_fits(
    n: int,
    n_nodes: int,
    n_sub: int,
    n_feat: int,
    n_bins: int,
    budget: int = FUSED_VMEM_BUDGET,
) -> bool:
    """Whether one fused level fits the VMEM budget at its tuned blocks
    (priced at the padded feature width the kernel runs)."""
    from repro.kernels import autotune

    blocks = autotune.lookup(n, n_feat, n_bins, n_nodes)
    f_pad, _ = autotune.feature_tiling(n_feat, n_bins, blocks["feature_block"])
    return (
        fused_level_vmem_bytes(
            n_nodes, n_sub, f_pad, n_bins,
            blocks["sample_block"], blocks["feature_block"],
        )
        <= budget
    )


def fused_level_vmem_bytes(
    n_nodes: int,
    n_sub: int,
    n_feat: int,
    n_bins: int,
    sample_block: int,
    feature_block: int,
) -> int:
    """The fused program's peak VMEM footprint model (DESIGN.md §13).

    Resident blocks: the built-row accumulator (6*L_sub, F, B) (hi, mid
    and lo sums), the parent cache (2, L_sub, F, B), the level-histogram
    output window (2, L, F, B), the (S_blk, F) bins block, the bf16
    (S_blk, F_blk * B) one-hot, and phase B's scan
    temporaries (~3 extra (L, F, B) values for prefix sums and the gain).
    The learner falls back to the staged path for any level whose estimate
    exceeds the budget — deep wide levels, where histogram tiling is the
    right call anyway.
    """
    fb = n_feat * n_bins
    acc = 6 * n_sub * fb
    parent = 2 * n_sub * fb
    hist_out = 2 * n_nodes * fb
    bins_blk = sample_block * n_feat
    onehot = sample_block * feature_block * n_bins
    scan_tmp = 3 * n_nodes * fb
    return 4 * (acc + parent + hist_out + bins_blk + scan_tmp) + 2 * onehot
