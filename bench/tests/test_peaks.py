import types

import jax
import pytest

from bench.common import least_time_s, peaks_for


def test_v5e_peaks_come_from_the_table():
    dev = types.SimpleNamespace(platform="tpu", device_kind="TPU v5 lite")
    peaks = peaks_for(dev)
    assert peaks["bf16_flops_per_s"] == 197e12
    assert peaks["int8_ops_per_s"] == 393e12
    assert peaks["hbm_bytes_per_s"] == 819e9


@pytest.mark.parametrize("dev", [
    types.SimpleNamespace(platform="tpu", device_kind="TPU v9 imaginary"),
    types.SimpleNamespace(platform="cpu", device_kind="TPU v5 lite"),
])
def test_unknown_device_and_cpu_raise(dev):
    with pytest.raises(KeyError):
        peaks_for(dev)


def test_this_cpu_raises():
    with pytest.raises(KeyError):
        peaks_for(jax.devices()[0])


def test_least_time_names_its_bound():
    peaks = {"bf16_flops_per_s": 100.0, "hbm_bytes_per_s": 10.0}
    assert least_time_s(1000.0, 10.0, peaks, "bf16_flops_per_s") == (10.0, "ops")
    assert least_time_s(10.0, 1000.0, peaks, "bf16_flops_per_s") == (100.0, "bytes")
