import pytest

from bench.peaks import peaks_for


def test_v5e_peaks_from_the_published_table():
    p = peaks_for("TPU v5 lite")
    assert p["bf16_flops"] == 197e12 and p["int8_ops"] == 393e12
    assert p["hbm_bytes_per_s"] == 819e9


def test_unknown_device_kind_is_refused():
    with pytest.raises(KeyError, match="no published peaks"):
        peaks_for("TPU v9 imaginary")
    with pytest.raises(KeyError):
        peaks_for("cpu")
