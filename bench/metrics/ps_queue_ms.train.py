"""Median milliseconds a pushed tree waited in the server's queue, from its
push to the start of its fold: ``RunTrace.t_queue`` of the threaded
runtime, over every fold of the window."""
from __future__ import annotations

import numpy as np


def read(ctx):
    run_trace = ctx.layer_inputs.get("run_trace")
    if run_trace is None or not len(run_trace.t_queue):
        return None
    return float(np.median(run_trace.t_queue)) * 1e3
