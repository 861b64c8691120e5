"""Least work of one serving wave's traversal: read each request row's bins
once (a byte a feature, ``n_bins`` <= 256) and the live forest once
(feature and threshold ids at a byte, leaves at four bytes), walk every
(row, tree) pair through ``depth`` compares and add its leaf, and write
one score a row."""
from __future__ import annotations


def count(rows: int, features: int, trees: int, depth: int) -> dict:
    n_int, n_leaf = (1 << depth) - 1, 1 << depth
    nbytes = rows * features + trees * (2 * n_int + 4 * n_leaf) + rows * 4
    ops = rows * trees * (depth + 1)
    return {"ops": float(ops), "bytes": float(nbytes)}
