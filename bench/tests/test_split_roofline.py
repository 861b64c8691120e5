"""The ``split_roofline.train`` reader by hand, on a made-up trace."""
from __future__ import annotations

import pytest

from bench.common import load_module
from bench.tests.test_counts import DENSE


def test_split_roofline_reader_by_hand():
    """``split_roofline.train``: two DENSE builds' split-scan reads at the
    peak bandwidth, over the ``split_gain_pallas`` time inside the window
    (other kernels and time outside the window do not count)."""
    from types import SimpleNamespace

    from bench import trace as TR

    E = TR.Event
    ev = [E("split_gain_pallas.3", 100, 400), E("split_gain_pallas.4", 500, 600),
          E("histogram_sparse_pallas.1", 600, 5000), E("split_gain_pallas.3", 6000, 7000)]
    peaks = {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e9}
    ctx = SimpleNamespace(
        layer_inputs={"builds": [DENSE, DENSE]}, peaks=peaks, notes={},
        trace_data=TR.Trace(device_ops={"/device:TPU:0": ev}, spans=[]),
        trace_window=(0, 5000))
    reader = load_module("metrics", "split_roofline.train")
    # Each build reads 2 x (24 + 48) bins of 4 bytes: 576 bytes, 0.576 us at
    # 1 GB/s; its 864 ops take 0.864 ns at 1 TFLOP/s, so bytes decide.
    least_s = 2 * 576 / 1e9
    kernel_s = 400e-9
    assert reader.read(ctx) == pytest.approx(100 * least_s / kernel_s)
    assert ctx.notes["split_roofline.train"]["bound"] == "bytes"
    assert ctx.notes["split_roofline.train"]["builds"] == 2


def test_split_roofline_reader_silent_without_kernel():
    """No ``split_gain_pallas`` in the window (a program that takes the
    kernel off the path, or no trace): no reading, and no error."""
    from types import SimpleNamespace

    from bench import trace as TR

    reader = load_module("metrics", "split_roofline.train")
    ev = [TR.Event("histogram_pallas.1", 0, 10)]
    ctx = SimpleNamespace(
        layer_inputs={"builds": [DENSE]}, notes={},
        peaks={"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e9},
        trace_data=TR.Trace(device_ops={"/device:TPU:0": ev}, spans=[]),
        trace_window=(0, 100))
    assert reader.read(ctx) is None
    ctx.trace_data = None
    assert reader.read(ctx) is None
