"""A split check for training cells: the gain the program's splits leave.

``leaf_gap`` (``reference.compare_trees``) looks only at leaves whose whole
root path matches the reference's tree, so a wrong split hides every leaf
below it. ``split_regret`` grades the splits themselves. It walks each
checked tree the program built, from the prediction the program built it
from (the initial score plus the program's earlier trees in fold order),
with the reference's own draws and float32 arithmetic
(``bench/reference.py``): at each internal node, the rows the program's
splits route there give the reference's gain surface, and the node's
regret is the surface's best valid gain (0 when none is above 0) less the
gain of the program's choice as the reference computes it (0 for a node
left unsplit). Rounding that flips a near tie leaves a regret at
rounding's size; a split chosen from another node's or feature's
histogram, or from sums at lower precision, leaves one at a gain's size.
Nothing here imports the program. No driver compares it yet: the training
driver (``drivers/ps_train.py``) would append it to its compared numbers,
under a limit of its configuration's ``check``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from bench import reference as R


def _gains(hist, ln: R.Learner):
    """(L, F, B) gains of every split, and whether each is valid: the
    arithmetic of ``reference.best_splits`` before the feature mask."""
    b = hist.shape[-1]
    lam = jnp.asarray(ln.lam, hist.dtype)
    gl = jnp.cumsum(hist[0], axis=-1)
    hl = jnp.cumsum(hist[1], axis=-1)
    gt, ht = gl[..., -1:], hl[..., -1:]
    gr, hr = gt - gl, ht - hl
    gain = gl * gl / (hl + lam) + gr * gr / (hr + lam) - gt * gt / (ht + lam)
    min_h = jnp.asarray(ln.min_child_hess, hist.dtype)
    return gain, (hl >= min_h) & (hr >= min_h) & (jnp.arange(b) < b - 1)


@functools.partial(jax.jit, static_argnames=("ln", "block"))
def _tree_regret(bins, g, h, feat_mask, feat, thr, leaf, ln: R.Learner, block: int):
    """Per internal node (heap order) the regret and the best gain of one
    program tree, and each row's leaf value under it."""
    n = g.shape[0]
    node = jnp.zeros((n,), jnp.int32)
    regrets, bests = [], []
    for level in range(ln.depth):
        n_nodes = 1 << level
        f_l = feat[n_nodes - 1:2 * n_nodes - 1]
        t_l = thr[n_nodes - 1:2 * n_nodes - 1]
        if isinstance(bins, dict):
            hist = R.sparse_histogram(bins["feat_rows"], bins["feat_codes"],
                                      bins["zero_bin"], node, g, h, n_nodes,
                                      ln.n_bins, jnp.float32, block)
        else:
            hist = R.dense_histogram(bins, node, g, h, n_nodes, ln.n_bins,
                                     jnp.float32, block)
        gain, valid = _gains(hist, ln)
        valid = valid & feat_mask[None, :, None]
        best = jnp.max(jnp.where(valid, gain, -jnp.inf).reshape(n_nodes, -1), axis=1)
        best = jnp.maximum(best, 0.0)
        # The choice's gain as the reference computes it, valid or not (a
        # side's hessian within rounding of the minimum is no fault); a
        # masked-out feature is.
        chosen = jnp.where(feat_mask[f_l], gain[jnp.arange(n_nodes), f_l, t_l], -jnp.inf)
        unsplit = (f_l == 0) & (t_l == ln.n_bins - 1)
        regrets.append(best - jnp.where(unsplit, 0.0, chosen))
        bests.append(best)
        val = R.lookup(bins, f_l[node])
        node = 2 * node + (val > t_l[node]).astype(jnp.int32)
    return jnp.concatenate(regrets), jnp.concatenate(bests), leaf[node]


def split_regret(bins, labels, multiplicity, seed_key, n_slots: int, schedule,
                 tickets, ln: R.Learner, feat, thr, leaf) -> float:
    """The largest node regret over the program's trees ``feat``, ``thr``,
    ``leaf`` (T, ...) of the first T folds, over the largest root gain
    among them. A split on a masked-out feature reads 1.0, as does any
    regret when no tree has a root gain."""
    n_features = (bins["zero_bin"].shape[0] if isinstance(bins, dict)
                  else bins.shape[1])
    block = 256 if isinstance(bins, dict) else 1 << 15
    keys = jax.random.split(seed_key, n_slots)
    versions = [jnp.full(labels.shape, R.init_score(labels, multiplicity), jnp.float32)]
    worst, scale = 0.0, 0.0
    for j, (k, i) in enumerate(zip(schedule, tickets)):
        g, h, mask = R.round_inputs(keys[int(i)], labels, multiplicity,
                                    versions[int(k)], ln, n_features, jnp.float32)
        regret, best, row_leaf = _tree_regret(
            bins, g, h, mask, jnp.asarray(feat[j]), jnp.asarray(thr[j]),
            jnp.asarray(leaf[j], jnp.float32), ln, block)
        worst = max(worst, float(jnp.max(regret)))
        scale = max(scale, float(best[0]))
        versions.append(versions[j] + row_leaf)
    if worst <= 0.0:
        return 0.0
    if not np.isfinite(worst) or scale == 0.0:
        return 1.0
    return worst / scale
