"""The split-scan kernel's share of its roofline in the traced window: the
least time the chip needs for the window's split-scan work
(``bench/counts/split_scan``, from every folded build's kept features) at
its published peaks, over the device time of ``split_gain_pallas``
(``bench/trace.KERNELS["split_scan"]``). The bound that decides the least
time is recorded under ``notes``."""
from __future__ import annotations

from bench import trace as TR
from bench.common import least_time_s, load_module


def read(ctx):
    builds = ctx.layer_inputs.get("builds")
    if not builds or ctx.trace_data is None or ctx.peaks is None:
        return None
    lo, hi = ctx.trace_window
    kernel_s = TR.kernel_time_s(ctx.trace_data, "split_scan", lo, hi)
    if kernel_s <= 0:
        return None
    scan = load_module("counts", "split_scan")
    ops = nbytes = 0.0
    for b in builds:
        c = scan.count(b)
        ops += c["ops"]
        nbytes += c["bytes"]
    least, bound = least_time_s(ops, nbytes, ctx.peaks, "bf16_flops_per_s")
    ctx.notes["split_roofline.train"] = {"bound": bound, "kernel_s": kernel_s,
                                         "least_s": least, "builds": len(builds)}
    return 100.0 * least / kernel_s
