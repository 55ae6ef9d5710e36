"""``g22-trickle``'s limit holds for the program and fails for the
control and for each fault of ``test_correct``, on its own
configuration cut to scale 12 (4,096 ids, 65,536 tuples)."""

import pytest

from benchmark.tests import test_correct

WORKLOAD = "g22-trickle"
SIZE = (1 << 12, 16 << 12)


@pytest.fixture(autouse=True)
def _g22_size(monkeypatch):
    monkeypatch.setitem(test_correct.SIZES, WORKLOAD, SIZE)


def test_program_is_correct():
    test_correct.test_program_is_correct(WORKLOAD)


@pytest.mark.parametrize("fault", sorted(test_correct.FAULTS))
def test_fault_is_not_correct(fault, monkeypatch):
    test_correct.test_fault_is_not_correct(WORKLOAD, fault, monkeypatch)


def test_control_is_not_correct():
    test_correct.test_control_is_not_correct(WORKLOAD)
