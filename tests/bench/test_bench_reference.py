"""The plain reference agrees with the program, and its lower-precision
control does not pass the check: here for the cells on one chip; a node
cell gets the same checks on four devices (``test_bench_node.py``)."""
import pytest

from cells import CELLS_ONE, control, run_tiny, tiny


@pytest.mark.parametrize("cell", CELLS_ONE)
def test_reference_matches_the_program(cell):
    numbers, ok, run = run_tiny(cell)
    assert numbers == {"int_mismatch": 0.0, "float_gap": 0.0}
    assert ok and run.attempted == 1 and run.failed == 0


@pytest.mark.parametrize("cell", CELLS_ONE)
def test_bfloat16_control_fails(cell):
    """The reference computed with bfloat16 latency accumulators, put in the
    program's place, must come out not correct."""
    from bench import correct

    c, _, _ = tiny(cell)
    numbers = control(cell)
    assert numbers["int_mismatch"] == 0
    assert numbers["float_gap"] > c.limits["float_gap"]
    assert not correct.judge(numbers, c.limits)


def test_batched_rows_are_the_rows():
    """A deployment laid over chips (``node_shards``, here one device): rows
    computed ``NODE_BATCH`` at a time, the last batch filled up with a copy,
    are the rows computed all at once on one device."""
    from bench import reference

    _, dep, tr = tiny("smallbank-nowait-grid64")
    knobs = [{"hybrid": code, "seed": 500 + code} for code in (1, 22, 43)]  # 2 batches of 2
    call = {"protocol": tr["protocol"], "ticks": tr["ticks"], "warmup": tr["warmup"]}
    assert reference.rows(dict(dep, node_shards=1), call, knobs) == reference.rows(dep, call, knobs)
