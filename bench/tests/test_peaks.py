"""The peaks table: v5e's published numbers, and an unknown device is an
error."""

import pytest

from bench import peaks


def test_v5e_peaks():
    p = peaks.peaks("TPU v5 lite")
    assert p["bf16_flops_s"] == 197e12 and p["hbm_bytes_s"] == 819e9


def test_unknown_device_is_an_error():
    with pytest.raises(KeyError):
        peaks.peaks("cpu")
