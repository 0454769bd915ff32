"""A run of the MVCC cell whose timed path is broken underneath comes out
not correct (faults: tests/bench/faults.py).  Its calls hold one
configuration each, so it has no grid half to leave out."""
import pytest

from faults import (  # noqa: F401  (fresh_programs is an autouse fixture)
    assert_not_correct, fresh_programs, gather_altered, step_unchanged,
)

CELL = "ycsb-mvcc-single"


@pytest.mark.parametrize("fault", [step_unchanged, gather_altered],
                         ids=lambda f: f.__name__)
def test_fault_is_not_correct(fault, monkeypatch):
    assert_not_correct(CELL, fault, monkeypatch)
