"""The table of peaks is keyed by ``device_kind``; an unknown kind is an
error, never a default."""
import pytest

import run


def test_v5e_peaks():
    peaks = run.device_peaks("TPU v5 lite")
    assert peaks["flops_per_s"] == 197e12
    assert peaks["hbm_bytes_per_s"] == 819e9


def test_unknown_device_kind_raises():
    with pytest.raises(KeyError):
        run.device_peaks("TPU v99 imaginary")
