"""The tree-build kernels' share of their roofline in the traced window:
the least time the chip needs for the window's histogram and split-scan
work (``bench/counts``: ``histogram``, ``split_scan``, from the realized
node sizes of every folded build) at its published peaks, over the device
time of the kernels that do that work (``histogram``, ``histogram_sparse``,
``split_scan``, ``level_build``). The bound that decides the least time is
recorded under ``notes``."""
from __future__ import annotations

from bench import trace as TR
from bench.common import least_time_s, load_module

KERNELS = ("histogram", "histogram_sparse", "split_scan", "level_build")


def read(ctx):
    builds = ctx.layer_inputs.get("builds")
    if not builds or ctx.trace_data is None or ctx.peaks is None:
        return None
    lo, hi = ctx.trace_window
    kernel_s = sum(TR.kernel_time_s(ctx.trace_data, k, lo, hi) for k in KERNELS)
    if kernel_s <= 0:
        return None
    hist, scan = load_module("counts", "histogram"), load_module("counts", "split_scan")
    ops = nbytes = 0.0
    for b in builds:
        for c in (hist.count(b), scan.count(b)):
            ops += c["ops"]
            nbytes += c["bytes"]
    least, bound = least_time_s(ops, nbytes, ctx.peaks, "bf16_flops_per_s")
    ctx.notes["build_roofline.train"] = {"bound": bound, "kernel_s": kernel_s,
                                         "least_s": least, "builds": len(builds)}
    return 100.0 * least / kernel_s
