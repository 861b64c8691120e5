"""Least work of one whole boosting round: the tree build (histograms and
split scans), the gradient pass (read label, prediction and multiplicity,
draw and weight: about eight operations a row), one routing read of a byte
a row at every level, and the fold (read the prediction and the delta,
write the prediction)."""
from __future__ import annotations

from bench.common import load_module

GRADIENT_OPS_PER_ROW = 8


def count(build: dict) -> dict:
    hist = load_module("counts", "histogram").count(build)
    scan = load_module("counts", "split_scan").count(build)
    n, depth = build["n_rows"], build["depth"]
    nbytes = hist["bytes"] + scan["bytes"] + n * (12 + 8) + n * depth + n * 12
    ops = hist["ops"] + scan["ops"] + GRADIENT_OPS_PER_ROW * n + 2 * n * depth + n
    return {"ops": float(ops), "bytes": float(nbytes)}
