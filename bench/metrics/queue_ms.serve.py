"""Median milliseconds a served request waited from its admission
(``ForestServer.submit`` stamps it) to the start of its first wave:
``PredictResult.queue_s``, over every request answered."""
from __future__ import annotations

import numpy as np


def read(ctx):
    results = ctx.layer_inputs.get("results")
    if not results:
        return None
    return float(np.median([r.queue_s for r in results])) * 1e3
