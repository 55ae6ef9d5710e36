"""The least bytes of one iteration come from (N, E) alone."""

import pytest

from benchmark.roofline import iteration_bytes, least_seconds, peaks


def test_bytes_from_sizes():
    assert iteration_bytes(1_000_000, 50_000_000) == 408_000_000
    assert iteration_bytes(10_000_000, 50_000_000) == 480_000_000


def test_least_time_is_memory_bound_on_v5e():
    peak = peaks("TPU v5 lite")
    assert least_seconds(1_000_000, 50_000_000, peak) == pytest.approx(408e6 / 819e9)


def test_unknown_device_kind_is_an_error():
    with pytest.raises(KeyError):
        peaks("TPU v9 imaginary")
