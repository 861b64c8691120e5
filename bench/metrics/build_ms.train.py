"""Median milliseconds of a worker's jitted build (draw, gradient, tree,
delta), drained: ``RunTrace.t_build`` of the threaded runtime, over every
fold of the window."""
from __future__ import annotations

import numpy as np


def read(ctx):
    run_trace = ctx.layer_inputs.get("run_trace")
    if run_trace is None or not len(run_trace.t_build):
        return None
    return float(np.median(run_trace.t_build)) * 1e3
