"""Least work of one whole serving wave: read the raw f32 request rows,
bin every value against its feature's edges (a binary search: log2 of the
edge count compares), then the traversal's work."""
from __future__ import annotations

import math

from bench.common import load_module


def count(rows: int, features: int, trees: int, depth: int, n_bins: int) -> dict:
    walk = load_module("counts", "forest_traversal").count(rows, features, trees, depth)
    nbytes = rows * features * 4 + features * (n_bins - 1) * 4 + walk["bytes"]
    ops = rows * features * math.ceil(math.log2(n_bins)) + walk["ops"]
    return {"ops": float(ops), "bytes": float(nbytes)}
