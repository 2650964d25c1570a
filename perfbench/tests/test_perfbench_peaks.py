"""Roofline and utilisation arithmetic; an unknown device is an error."""

import pytest

import _pb  # noqa: F401
from perfbench import peaks


def test_v5e_peaks_come_with_their_source():
    p = peaks.peaks("TPU v5 lite")
    assert p["bf16_flops"] == 197e12
    assert p["int8_ops"] == 393e12
    assert p["hbm_bytes_per_s"] == 819e9
    assert p["fp32_flops"] == p["bf16_flops"]
    assert "TPU v5e" in p["source"]


def test_unknown_device_kind_raises():
    with pytest.raises(KeyError, match="no peaks"):
        peaks.peaks("TPU v9 imaginary")


def test_roofline_share_takes_the_binding_bound():
    p = {"fp32_flops": 100e12, "hbm_bytes_per_s": 1e12}
    # compute-bound: 1e12 FLOP needs 10 ms; 1 GB needs 1 ms
    share, bound = peaks.roofline_share(1e12, 1e9, 0.020, p)
    assert bound == "compute" and share == pytest.approx(50.0)
    # memory-bound: 1e9 FLOP needs 10 us; 4 GB needs 4 ms
    share, bound = peaks.roofline_share(1e9, 4e9, 0.005, p)
    assert bound == "memory" and share == pytest.approx(80.0)
    with pytest.raises(ValueError):
        peaks.roofline_share(1.0, 1.0, 0.0, p)


def test_mfu_is_model_flops_per_second_over_the_chips_peak():
    p = {"fp32_flops": 200e12}
    assert peaks.mfu(2e12, 1.0, p) == pytest.approx(1.0)
    assert peaks.mfu(2e12, 1.0, p, chips=4) == pytest.approx(0.25)
