"""The whole round's share of the chip's peaks: the least time the chip
needs for the window's rounds (``bench/counts/round.py``: build, gradient
pass, routing and fold, from the realized node sizes) at its published
peaks, over the window's host-clock time. The bound that decides is
recorded under ``notes``."""
from __future__ import annotations

from bench.common import least_time_s, load_module


def read(ctx):
    builds = ctx.layer_inputs.get("builds")
    window_s = ctx.layer_inputs.get("window_s")
    if not builds or not window_s or ctx.peaks is None:
        return None
    counts = [load_module("counts", "round").count(b) for b in builds]
    least, bound = least_time_s(sum(c["ops"] for c in counts),
                                sum(c["bytes"] for c in counts), ctx.peaks,
                                "bf16_flops_per_s")
    ctx.notes["round_mfu.train"] = {"bound": bound, "least_s": least, "window_s": window_s}
    return 100.0 * least / window_s
