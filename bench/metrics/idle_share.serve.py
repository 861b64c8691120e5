"""Share of the traced serving window in which no operation ran on the
device: 1 - busy / window, busy being the union of the device's operation
intervals (``bench.trace``)."""
from __future__ import annotations

from bench import trace as TR


def read(ctx):
    if ctx.trace_data is None or not ctx.trace_data.device_ops:
        return None
    lo, hi = ctx.trace_window
    return 100.0 * (1.0 - TR.device_busy_s(ctx.trace_data, lo, hi) / ((hi - lo) / 1e9))
