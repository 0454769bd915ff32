"""Faults planted in the program for one tiny run of a cell.

A step that returns its state unchanged, half of a grid's batch left out
(its rows copied from the other half), an answer altered where it is
produced (every word the store gather returns), and, for a deployment laid
out over several chips (the node layout), the exchange between chips left
out: each shard keeps its own addend of a round's reply, so a read loses
the owner's answer (tests/bench/test_bench_node.py).
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
    from repro.core import rounds

    mp.setattr(rounds, "run_stage_round", lambda ec, cm, wl, st, store, spec, salt: (st, store))


def half_batch(mp):
    from repro.core import sweep

    full = sweep._run_grid_jit

    def half(gs, knobs):
        n = knobs.hybrid.shape[0]
        out = full(gs, jax.tree_util.tree_map(lambda x: x[: n // 2], knobs))
        return {k: jnp.concatenate([v, v[: n - n // 2]]) for k, v in out.items()}

    mp.setattr(sweep, "_run_grid_jit", half)


def gather_altered(mp):
    from repro.core import engine

    plain = engine.gather_rows
    mp.setattr(engine, "gather_rows", lambda arr, keys: plain(arr, keys) + 1)


def exchange_dropped(mp):
    from repro.core import planes, sweep

    mp.setattr(planes, "_psum", lambda x, axis: x)
    mp.setattr(sweep, "_NODE_RUNNERS", {})  # trace the node runner anew, with the fault


def assert_not_correct(cell, fault, monkeypatch):
    fault(monkeypatch)
    numbers, ok, _ = run_tiny(cell)
    assert not ok, numbers
