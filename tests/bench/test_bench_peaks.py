import pytest

from bench import peaks


def test_known_kind_has_its_published_peaks():
    row = peaks.peak("TPU v5 lite")
    assert row["flops_per_s"] == 197e12
    assert row["bytes_per_s"] == 819e9
    assert "Google Cloud" in row["source"]


def test_unknown_kind_is_an_error():
    with pytest.raises(KeyError, match="no published peaks"):
        peaks.peak("TPU v9 imaginary")
