"""Median milliseconds of wave compute a served request rode (bin, pad,
predict, copy back, summed over its sub-waves): ``PredictResult.compute_s``,
over every request answered."""
from __future__ import annotations

import numpy as np


def read(ctx):
    results = ctx.layer_inputs.get("results")
    if not results:
        return None
    return float(np.median([r.compute_s for r in results])) * 1e3
