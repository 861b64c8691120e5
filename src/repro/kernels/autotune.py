"""Kernel block shapes as a function of the geometry.

Every block this module hands out is one Mosaic can lower, and is computed
from the problem's shape alone — nothing is measured or looked up:

  * ``feature_tiling``: the histogram's feature block, the whole (padded)
    feature width up to 128 features and 128 above it;
  * ``default_sample_block``: the histogram's sample block, a multiple of
    128 sized so the one-hot factor fits ``ONEHOT_BYTES``;
  * ``split_tiling``: the split scan's node and feature blocks, priced by
    ``split_vmem_bytes`` against ``SPLIT_VMEM_BUDGET``.
"""
from __future__ import annotations

import math

from repro.kernels.split_scan import LANES, scan_width

# The (F_blk * B, S) one-hot factor the histogram dot consumes is the
# histogram kernel's largest VMEM value; the default sample block keeps it
# under this many bytes priced at 4 bytes an entry. The one-hot is bf16
# (2 bytes), and the block stays at its f32 size on purpose: a larger
# block is a choice of its own, to be timed on its own.
ONEHOT_BYTES = 4 * 2**20
MAX_SAMPLE_BLOCK = 512

# The split scan's scoped VMEM, in f32 (node_block, F_blk * B) blocks: its
# three operands double-buffered and the scan's temporaries. v5e's
# compiler reports 6 to 9.5 blocks for tiles of 0.25 to 1 MiB, and has
# asked 14.7 for a whole 64-node tile of 2 MiB; the chooser keeps 15
# blocks within half of v5e's 16 MiB scoped limit.
SPLIT_VMEM_BLOCKS = 15
SPLIT_VMEM_BUDGET = 8 * 2**20


def feature_tiling(f: int, n_bins: int) -> tuple[int, int]:
    """``(padded F, feature block)`` for an F-wide dense bin matrix.

    Mosaic tiles a block's last two dims in (8, 128): the feature-major
    (F_blk, S) bins block needs a multiple of 8 features or the whole padded
    width, and the (rows, F_blk * B) histogram block a multiple of 128
    lanes or the whole row. The block — the padded width up to 128
    features, 128 above it — satisfies both. F is padded so that F * B
    fills whole 128-lane tiles (``split_scan.scan_width``).
    """
    align = scan_width(n_bins) // n_bins
    whole = -(-max(f, 1) // align) * align
    feature_block = whole if whole <= LANES else LANES
    unit = math.lcm(feature_block, align)
    return -(-max(f, 1) // unit) * unit, feature_block


def split_vmem_bytes(node_block: int, feature_block: int, n_bins: int) -> int:
    """Modelled scoped VMEM of one split-scan tile: ``SPLIT_VMEM_BLOCKS``
    f32 blocks of ``node_block`` rows (whole 8-row sublane tiles) by
    ``feature_block * n_bins`` lanes."""
    rows = -(-node_block // 8) * 8
    return SPLIT_VMEM_BLOCKS * rows * feature_block * n_bins * 4


def split_tiling(l: int, f: int, n_bins: int) -> tuple[int, int, int, int]:
    """``(padded L, node block, padded F, feature block)`` of the split scan
    over an (L, F, n_bins) level.

    The feature block is ``feature_tiling``'s. The node block is the whole
    level when its tile fits ``SPLIT_VMEM_BUDGET`` (one node block, as at
    HIGGS's L <= 16 x 28 features); else the level splits into the fewest
    blocks of a multiple of 8 nodes that fit, and L pads to whole blocks.
    A geometry whose 8-node tile exceeds the budget (over 136 bins at
    more than 128 features) raises.
    """
    f_pad, fb = feature_tiling(f, n_bins)
    tile8 = split_vmem_bytes(8, fb, n_bins)
    if tile8 > SPLIT_VMEM_BUDGET:
        raise ValueError(
            f"split scan: an 8-node tile of {fb} features x {n_bins} bins "
            f"prices at {tile8} bytes, over the {SPLIT_VMEM_BUDGET}-byte budget")
    if split_vmem_bytes(l, fb, n_bins) <= SPLIT_VMEM_BUDGET:
        return l, l, f_pad, fb
    n_blocks = -(-l // (SPLIT_VMEM_BUDGET // tile8 * 8))
    node_block = -(-l // (8 * n_blocks)) * 8
    return n_blocks * node_block, node_block, f_pad, fb


def default_sample_block(feature_block: int, n_bins: int) -> int:
    """The largest multiple of 128 (at most 512) whose one-hot factor, priced
    at 4 bytes an entry, fits ``ONEHOT_BYTES`` — never below 128, the lane
    width of the per-sample operand rows."""
    rows = ONEHOT_BYTES // (4 * feature_block * n_bins)
    return max(LANES, min(MAX_SAMPLE_BLOCK, rows // LANES * LANES))
