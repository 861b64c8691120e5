"""Least work of one tree build's split scans: per level, read the grad and
hess histograms of every node of the level over the kept features once
(four bytes a bin), take the two prefix sums and the gain of every
candidate (about ten operations a bin), and keep the best."""
from __future__ import annotations

GAIN_OPS_PER_BIN = 12  # two prefix adds, two differences, three divisions, squares, sum


def count(build: dict) -> dict:
    f_used, b = build["features_used"], build["n_bins"]
    ops = nbytes = 0
    for level in range(build["depth"]):
        bins = (1 << level) * f_used * b
        nbytes += 2 * bins * 4
        ops += GAIN_OPS_PER_BIN * bins
    return {"ops": float(ops), "bytes": float(nbytes)}
