"""A run of a SmallBank cell whose timed path is broken underneath comes
out not correct (faults: tests/bench/faults.py)."""
import pytest

from faults import (  # noqa: F401  (fresh_programs is an autouse fixture)
    assert_not_correct, fresh_programs, gather_altered, half_batch, step_unchanged,
)

CASES = [
    ("smallbank-nowait-grid64", step_unchanged),
    ("smallbank-nowait-single", step_unchanged),
    ("smallbank-nowait-grid64", half_batch),
    ("smallbank-nowait-grid64", gather_altered),
    ("smallbank-nowait-single", gather_altered),
]


@pytest.mark.parametrize("cell,fault", CASES, ids=[f"{c}-{f.__name__}" for c, f in CASES])
def test_fault_is_not_correct(cell, fault, monkeypatch):
    assert_not_correct(cell, fault, monkeypatch)
