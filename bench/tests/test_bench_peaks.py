import pytest

from bench import peaks


def test_v5e_peaks():
    p = peaks.lookup("TPU v5 lite")
    assert p["bf16_flops_per_s"] == 197e12
    assert p["hbm_bytes_per_s"] == 819e9


def test_an_unknown_device_is_an_error():
    with pytest.raises(peaks.UnknownDevice):
        peaks.lookup("TPU v9 imaginary")
    with pytest.raises(peaks.UnknownDevice):
        peaks.lookup("cpu")
