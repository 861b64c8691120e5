"""Least work of one tree build's histograms, whatever implements them.

Per level, a histogram pass must read, for every drawn row of the nodes it
covers (all rows at the root, the smaller child of each split below it),
the row's bin in each kept feature at one byte (``n_bins`` <= 256) and its
g and h at four bytes each, and must write the level's (L, F_kept, B)
grad and hess histograms of those nodes once at four bytes. A sparse set
reads, instead of every kept feature, the row's stored entries in kept
features (a one-byte code and a four-byte feature id each). Each value read
is added once into g and once into h.
"""
from __future__ import annotations


def count(build: dict) -> dict:
    f_used, b = build["features_used"], build["n_bins"]
    ops = nbytes = 0
    for level, (rows, entries) in enumerate(zip(build["rows"], build["entries"])):
        built = max((1 << level) // 2, 1) if level else 1
        if build["layout"] == "sparse":
            reads = entries * (1 + 4) + rows * 8
            adds = 2 * entries
        else:
            reads = rows * f_used * 1 + rows * 8
            adds = 2 * rows * f_used
        nbytes += reads + 2 * built * f_used * b * 4
        ops += adds
    return {"ops": float(ops), "bytes": float(nbytes)}
