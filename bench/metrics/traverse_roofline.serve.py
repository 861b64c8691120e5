"""The ``forest_traversal`` kernel's share of its roofline in the traced
window: the least time of the waves' traversal work
(``bench/counts/forest_traversal.py``, real rows of each wave against the
live forest) at the chip's peaks, over the kernel's device time. The bound
that decides is recorded under ``notes``."""
from __future__ import annotations

from bench import trace as TR
from bench.common import least_time_s, load_module


def read(ctx):
    waves = ctx.layer_inputs.get("waves")
    if not waves or ctx.trace_data is None or ctx.peaks is None:
        return None
    lo, hi = ctx.trace_window
    kernel_s = TR.kernel_time_s(ctx.trace_data, "forest_traversal", lo, hi)
    if kernel_s <= 0:
        return None
    li = ctx.layer_inputs
    walk = load_module("counts", "forest_traversal")
    counts = [walk.count(w[4]["rows"], li["features"], li["trees"], li["depth"])
              for w in waves if ctx.host_window[0] <= w[1] <= ctx.host_window[1]]
    least, bound = least_time_s(sum(c["ops"] for c in counts),
                                sum(c["bytes"] for c in counts), ctx.peaks,
                                "bf16_flops_per_s")
    ctx.notes["traverse_roofline.serve"] = {"bound": bound, "kernel_s": kernel_s,
                                            "least_s": least, "waves": len(counts)}
    return 100.0 * least / kernel_s
