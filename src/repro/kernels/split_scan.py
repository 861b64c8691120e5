"""Pallas TPU kernel: fused cumulative-sum + split-gain over histograms.

After histograms are built, split search scans every (node, feature) bin row:
left sums are prefix sums over bins, and the gain formula touches each bin a
handful of times. Unfused, XLA materializes four (L, F, B) temporaries in
HBM (cumsum-g, cumsum-h, gain, validity). The kernel fuses the whole
pipeline per VMEM tile so each histogram element is read from HBM exactly
once and only the (L, F, B) gain surface is written back.

Layout: histograms travel as (L, F * B) — bins of one feature are adjacent
lanes — so no kernel ever splits the lane dimension (Mosaic cannot).
Mosaic has no ``cumsum`` lowering either; the prefix sum is a log-step
(Hillis-Steele) scan of lane rolls and adds inside each B-lane run
(``bin_prefix_sums``), and ``ref.bin_prefix_sum`` adds the same operands
in the same order, so kernel and oracle scans agree bit for bit on equal
histograms.

Grid: (node_blocks, feature_blocks); each program owns an
(L_blk, F_blk * B) tile. Every row of a tile is scanned on its own, so
the tiling changes no value; ``kernels.autotune.split_tiling`` picks the
blocks from the geometry so that the scoped VMEM stays bounded at any
level width.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.vma import out_struct

LANES = 128


def scan_width(n_bins: int) -> int:
    """Lanes per whole group of bin runs: the least common multiple of
    ``n_bins`` and 128, so padded features fill whole lane tiles — or
    ``n_bins`` itself when that multiple is large."""
    width = math.lcm(n_bins, LANES)
    return width if width <= 4 * LANES else n_bins


def bin_prefix_sums(x: jax.Array, n_bins: int) -> tuple[jax.Array, jax.Array]:
    """``(inclusive prefix, run total)`` of every B-lane run of ``x`` (R, F*B).

    Step k of the scan adds each lane's value k lanes to its left within
    the run (zero at the run's start): ``ref.bin_prefix_sum``'s operands
    and order. The run total is then broadcast from the run's last lane by
    the mirrored scan, which adds only zeros to it — exact.
    """
    lanes = x.shape[1]
    pos = jax.lax.broadcasted_iota(jnp.int32, x.shape, 1) % n_bins
    k = 1
    while k < n_bins:
        x = x + jnp.where(pos >= k, pltpu.roll(x, k, 1), 0.0)
        k *= 2
    total = jnp.where(pos == n_bins - 1, x, 0.0)
    k = 1
    while k < n_bins:
        total = total + jnp.where(
            pos + k < n_bins, pltpu.roll(total, lanes - k, 1), 0.0
        )
        k *= 2
    return x, total


def split_gain_tile(g, h, lam, min_h, n_bins: int):
    """``(gain, valid)`` over an (L, F*B) grad/hess tile pair."""
    gl, gt = bin_prefix_sums(g, n_bins)
    hl, ht = bin_prefix_sums(h, n_bins)
    gr = gt - gl
    hr = ht - hl
    parent = gt * gt / (ht + lam)
    gain = gl * gl / (hl + lam) + gr * gr / (hr + lam) - parent
    bin_pos = jax.lax.broadcasted_iota(jnp.int32, g.shape, 1) % n_bins
    valid = (hl >= min_h) & (hr >= min_h) & (bin_pos < n_bins - 1)
    return gain, valid


def _split_kernel(g_ref, h_ref, params_ref, gain_ref, *, n_bins: int):
    # Scalars ride in SMEM via scalar prefetch — available before the tile
    # DMA lands, and never occupying a (1, 1) vector tile.
    gain, valid = split_gain_tile(
        g_ref[...], h_ref[...], params_ref[0], params_ref[1], n_bins
    )
    gain_ref[...] = jnp.where(valid, gain, -jnp.inf)


@functools.partial(
    jax.jit, static_argnames=("node_block", "feature_block", "interpret")
)
def split_gain_pallas(
    hist: jax.Array,  # (2, L, F, B) f32
    lam: jax.Array,  # scalar
    min_child_hess: jax.Array,
    node_block: int | None = None,
    feature_block: int = 8,
    interpret: bool | None = None,
) -> jax.Array:
    """Gain surface (L, F, B); invalid split points are -inf.

    ``L`` must be a multiple of ``node_block`` (``None``: all of ``L``),
    which is a multiple of 8 or the whole ``L``; ``F`` a multiple of
    ``feature_block``, and ``feature_block * B`` a multiple of
    ``scan_width(B)`` (the ``kernels.ops`` wrapper pads).
    ``interpret=None`` auto-detects (Mosaic on TPU, interpreter elsewhere).
    """
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    _, l, f, b = hist.shape
    node_block = l if node_block is None else node_block
    lanes = feature_block * b
    assert l % node_block == 0 and (node_block % 8 == 0 or node_block == l)
    assert f % feature_block == 0 and lanes % scan_width(b) == 0
    params = jnp.stack([
        jnp.asarray(lam, jnp.float32),
        jnp.asarray(min_child_hess, jnp.float32),
    ])  # (2,) SMEM-resident scalars
    flat = hist.reshape(2, l, f * b)

    gain = pl.pallas_call(
        functools.partial(_split_kernel, n_bins=b),
        grid=(l // node_block, f // feature_block),
        in_specs=[
            pl.BlockSpec((node_block, lanes), lambda nb, fb: (nb, fb)),
            pl.BlockSpec((node_block, lanes), lambda nb, fb: (nb, fb)),
            pl.BlockSpec(memory_space=pltpu.SMEM),
        ],
        out_specs=pl.BlockSpec((node_block, lanes), lambda nb, fb: (nb, fb)),
        out_shape=out_struct((l, f * b), jnp.float32, hist, params),
        interpret=interpret,
        name="split_gain_pallas",  # its stable name in the device trace
    )(flat[0], flat[1], params)
    return gain.reshape(l, f, b)
