"""The whole wave's share of the chip's peaks: the least time of each
wave's work (``bench/counts/wave.py``: read the raw rows, bin them, walk
the forest) at the chip's peaks, over the waves' traced host-clock time
(the bench's span around ``ForestServer._run_wave``), summed over the
window's waves. The bound that decides is recorded under ``notes``."""
from __future__ import annotations

from bench.common import least_time_s, load_module


def read(ctx):
    li = ctx.layer_inputs
    waves = [w for w in li.get("waves") or ()
             if ctx.host_window[0] <= w[1] <= ctx.host_window[1]]
    if not waves or ctx.peaks is None:
        return None
    wave = load_module("counts", "wave")
    counts = [wave.count(w[4]["rows"], li["features"], li["trees"], li["depth"],
                         li["n_bins"]) for w in waves]
    least, bound = least_time_s(sum(c["ops"] for c in counts),
                                sum(c["bytes"] for c in counts), ctx.peaks,
                                "bf16_flops_per_s")
    spent = sum(w[2] - w[1] for w in waves)
    ctx.notes["wave_mfu.serve"] = {"bound": bound, "least_s": least, "wave_s": spent}
    return 100.0 * least / spent
