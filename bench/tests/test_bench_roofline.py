"""The bytes an operation must move, and the peaks table."""
from __future__ import annotations

import pytest

from bench import roofline


def test_useful_bytes():
    # per key: 4 B key, a 96 B bucket, the value read and written
    assert roofline.get_bytes(1, 8) == 4 + 96 + 64
    assert roofline.get_bytes(16384, 250) == 16384 * (4 + 96 + 2000)
    # per entry and replica: a 9 B log entry read, an 8 B entry written
    assert roofline.apply_bytes(65536 * 2) == 65536 * 2 * 17


def test_peaks_of_a_v5e():
    assert roofline.peak("TPU v5 lite")["hbm_bytes_per_s"] == 819e9


def test_an_unknown_device_kind_is_an_error():
    with pytest.raises(KeyError, match="no peaks"):
        roofline.peak("TPU v9 imaginary")
