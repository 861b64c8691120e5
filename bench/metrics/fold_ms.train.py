"""Median milliseconds of the server's jitted fold, drained:
``RunTrace.t_fold`` of the threaded runtime, over every fold of the window."""
from __future__ import annotations

import numpy as np


def read(ctx):
    run_trace = ctx.layer_inputs.get("run_trace")
    if run_trace is None or not len(run_trace.t_fold):
        return None
    return float(np.median(run_trace.t_fold)) * 1e3
