"""Faults planted in the program for one tiny run of a cell.

A step that returns its state unchanged, half of a grid's batch left out
(its rows copied from the other half), an answer altered where it is
produced (every word the store gather returns: ``engine.gather_rows`` on
one chip, ``planes.node_read``/``node_read_batch`` in the node layout),
and, for a deployment laid out over several chips (the node layout), the
exchange between chips left out: each shard keeps its own addend of a
round's reply, so a read loses the owner's answer.  A node cell's runs
with :data:`NODE_FAULTS` planted are made in ``node_cell.py``'s
four-device child (tests/bench/test_bench_node.py).
"""
import jax
import jax.numpy as jnp
import pytest

from cells import run_tiny


@pytest.fixture(autouse=True)
def fresh_programs():
    """Drop the program's compiled grid programs around each planted fault,
    so the fault is traced in and no later test reuses it."""
    from repro.core import sweep

    sweep._run_grid_jit.clear_cache()
    yield
    sweep._run_grid_jit.clear_cache()


def step_unchanged(mp):
    from repro.core import rounds, sweep

    mp.setattr(rounds, "run_stage_round", lambda ec, cm, wl, st, store, spec, salt: (st, store))
    mp.setattr(sweep, "_NODE_RUNNERS", {})  # trace the node runner anew, with the fault


def half_batch(mp):
    from repro.core import sweep

    full = sweep._run_grid_jit

    def half(gs, knobs):
        n = knobs.hybrid.shape[0]
        out = full(gs, jax.tree_util.tree_map(lambda x: x[: n // 2], knobs))
        return {k: jnp.concatenate([v, v[: n - n // 2]]) for k, v in out.items()}

    mp.setattr(sweep, "_run_grid_jit", half)


def gather_altered(mp):
    from repro.core import engine, planes, sweep

    plain = engine.gather_rows
    mp.setattr(engine, "gather_rows", lambda arr, keys: plain(arr, keys) + 1)
    # the node layout's reads, where each shard answers for the records it owns
    read, batch = planes.node_read, planes.node_read_batch
    mp.setattr(planes, "node_read", lambda *a: read(*a) + 1)
    mp.setattr(planes, "node_read_batch", lambda *a, **k: tuple(v + 1 for v in batch(*a, **k)))
    mp.setattr(sweep, "_NODE_RUNNERS", {})  # trace the node runner anew, with the fault


def exchange_dropped(mp):
    from repro.core import planes, sweep

    mp.setattr(planes, "_psum", lambda x, axis: x)
    mp.setattr(sweep, "_NODE_RUNNERS", {})  # trace the node runner anew, with the fault


def assert_not_correct(cell, fault, monkeypatch):
    fault(monkeypatch)
    numbers, ok, _ = run_tiny(cell)
    assert not ok, numbers


# the faults a node cell's calls of one configuration can have (node_cell.py)
NODE_FAULTS = (step_unchanged, gather_altered, exchange_dropped)
