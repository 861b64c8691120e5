"""Realized sizes of the window's tree builds, for the work counts.

For each folded tree: the round's draw (which rows carry weight, which
features the mask keeps, from the round key as the reference draws them)
and the tree's own routing give, at every level, the drawn rows of the
nodes a histogram must cover: all of them at the root, and below it the
smaller child of every split (its sibling is its parent minus it). For a
sparse set the stored entries of those rows in kept features count too.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np


@functools.partial(jax.jit, static_argnames=("depth", "n_features", "rate", "fraction"))
def _levels(bins, multiplicity, key, feature, threshold, *, depth: int, n_features: int,
            rate: float, fraction: float):
    from bench.reference import draws, lookup, Learner

    ln = Learner(depth, 0, 0.0, 0.0, fraction, rate, 0.0)
    m, mask = draws(key, ln, multiplicity, n_features)
    drawn = m > 0
    sparse = isinstance(bins, dict)
    if sparse:
        idx = bins["indices"]
        kept = (idx >= 0) & mask[jnp.where(idx >= 0, idx, 0)]
        row_entries = jnp.sum(kept, axis=1).astype(jnp.int32)
    else:
        row_entries = jnp.zeros_like(multiplicity, jnp.int32)
    n = multiplicity.shape[0]
    node = jnp.zeros((n,), jnp.int32)
    rows, entries = [], []
    for level in range(depth):
        n_nodes = 1 << level
        on = node[:, None] == jnp.arange(n_nodes)  # reductions, not scatters
        count = jnp.sum(on & drawn[:, None], axis=0, dtype=jnp.int32)
        ents = jnp.sum(jnp.where(on & drawn[:, None], row_entries[:, None], 0), axis=0,
                       dtype=jnp.int32)
        if level:
            smaller = count[0::2] > count[1::2]  # True: the odd child is smaller
            rows.append(jnp.sum(jnp.where(smaller, count[1::2], count[0::2])))
            entries.append(jnp.sum(jnp.where(smaller, ents[1::2], ents[0::2])))
        else:
            rows.append(count[0])
            entries.append(ents[0])
        heap = (1 << level) - 1 + node
        val = lookup(bins, feature[heap])
        node = 2 * node + (val > threshold[heap]).astype(jnp.int32)
    return jnp.stack(rows), jnp.stack(entries), jnp.sum(mask)


def builds(data, cfg, pseed: int, run_trace, features, thresholds) -> list[dict]:
    from bench.reference import as_reference_bins

    bins = as_reference_bins(data.bins)
    n_features = data.bins.shape[1]
    keys = jax.random.split(jax.random.PRNGKey(pseed), cfg.n_trees)
    ln = cfg.learner
    out = []
    for j, ticket in enumerate(np.asarray(run_trace.key_index)):
        rows, entries, used = _levels(
            bins, data.multiplicity, keys[int(ticket)], jnp.asarray(features[j]),
            jnp.asarray(thresholds[j]), depth=ln.depth, n_features=n_features,
            rate=float(cfg.sampling_rate), fraction=float(ln.feature_fraction))
        out.append({
            "layout": "sparse" if isinstance(bins, dict) else "dense",
            "n_rows": int(data.multiplicity.shape[0]),
            "features": int(n_features),
            "features_used": int(used),
            "n_bins": int(ln.n_bins),
            "depth": int(ln.depth),
            "rows": [int(r) for r in np.asarray(rows)],
            "entries": [int(e) for e in np.asarray(entries)],
        })
    return out
