"""CI perf-regression gate (bench-smoke job), driven through ``repro.api``.

Guards the planner/executor's load-bearing properties:

  1. single-compile: the paper's exhaustive 2^6 hybrid enumeration must run
     as ONE vmapped program.  ``plan()`` accounts for it
     (``ExecutionPlan.expected_compiles == 1``) and the measured jit-cache
     delta must match.  A protocol accidentally Python-branching on a
     traced knob silently falls back to 64 compilations — this gate
     catches it.
  2. bucketed static axes: a co-routine sweep whose points share one shape
     bucket must compile exactly ``expected_compiles`` (== n_buckets == 1)
     more programs, not one per config.  A regression in the bucketing
     planner or in the active-extent knob plumbing (EngineConfig.active_*)
     shows up as one compile per distinct static shape.
  3. node-sharded tick: the node-sharded engine must compile ONE SPMD
     program per mesh shape — every knob stays traced, so a family of
     configs on a fixed mesh shares the compiled sharded tick.
  4. wall-clock budgets: each sweep must finish inside its ``--budget``
     seconds end-to-end (compile + run).  The budgets are generous for
     slow CI runners; a per-cell-compile regression blows them by an
     order of magnitude.
  5. kernel plane (DESIGN.md §9): the same sweep runs on the jnp plane
     and on the Pallas plane (interpret mode when no accelerator is
     attached), and their counters must match — the fast check of the
     parity contract for MVCC and SUNDIAL (tests/test_kernel_parity.py
     runs SUNDIAL's only as a slow case).

Run from a fresh interpreter (the compile-cache assertions count programs
compiled in THIS process).
"""
import argparse
import os
import sys
import time

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for _p in (_ROOT, os.path.join(_ROOT, "src")):
    if _p not in sys.path:
        sys.path.insert(0, _p)

from benchmarks.common import (  # jax-free
    add_device_args,
    configure_compile_cache,
    configure_devices,
)


def gate_hybrid_enumeration(budget_s: float) -> None:
    from repro import api

    spec = api.ExperimentSpec(
        protocol="sundial",
        workload="smallbank",
        configs=[{"hybrid": c} for c in api.all_hybrid_codes()],
        n_nodes=2, coroutines=12, records_per_node=4096, ticks=96, warmup=8,
    )
    pl = api.plan(spec)
    print(pl.summary())
    assert pl.expected_compiles == 1, (
        f"planner budgeted {pl.expected_compiles} compiles for the 2^6 enumeration (want 1)"
    )
    before = api.compile_stats()
    t0 = time.time()
    rows = api.execute(pl).rows
    wall = time.time() - t0
    assert len(rows) == 64 and all(r["commits"] > 0 for r in rows), "sweep produced bad rows"
    delta = api.compile_stats()[pl.cache] - before[pl.cache]
    assert delta == pl.expected_compiles, (
        f"2^6 hybrid enumeration compiled {delta} programs "
        f"(planner budgeted {pl.expected_compiles}): a static/traced knob split regression"
    )
    assert wall < budget_s, f"hybrid enumeration took {wall:.1f}s (budget {budget_s:.0f}s)"
    print(f"perf gate ok: 64-coding sweep = {delta} compile(s), {wall:.1f}s < {budget_s:.0f}s budget")


def gate_bucketed_coroutines(budget_s: float) -> None:
    """A 4-point co-routine sweep inside one power-of-two shape bucket must
    cost exactly one compilation (== expected_compiles), not one per config."""
    from repro import api

    spec = api.ExperimentSpec(
        protocol="sundial",
        workload="smallbank",
        configs=[{"hybrid": 0b010101, "coroutines": c} for c in (10, 12, 14, 16)],
        n_nodes=2, coroutines=12, records_per_node=4096, ticks=96, warmup=8,
    )
    pl = api.plan(spec)
    print(pl.summary())
    assert pl.expected_compiles == 1, (
        f"4-point co-routine sweep planned {pl.expected_compiles} bucket(s)/compile(s) (want 1)"
    )
    before = api.compile_stats()
    t0 = time.time()
    rows = api.execute(pl).rows
    wall = time.time() - t0
    assert all(r["commits"] > 0 for r in rows), "bucketed sweep produced bad rows"
    assert [r["coroutines"] for r in rows] == [10, 12, 14, 16]
    assert rows[0]["n_buckets"] == 1
    delta = api.compile_stats()[pl.cache] - before[pl.cache]
    assert delta == pl.expected_compiles, (
        f"bucketed co-routine sweep compiled {delta} programs "
        f"(planner budgeted {pl.expected_compiles} for {len(spec.configs)} configs): "
        "the bucketing planner or active-extent knobs regressed"
    )
    assert wall < budget_s, f"bucketed co-routine sweep took {wall:.1f}s (budget {budget_s:.0f}s)"
    print(
        f"perf gate ok: 4-point co-routine sweep = 1 bucket, "
        f"{delta} compile(s), {wall:.1f}s < {budget_s:.0f}s budget"
    )


def gate_node_sharded_tick(budget_s: float) -> None:
    """The node-sharded engine must compile ONE SPMD program per mesh shape:
    every knob (hybrid coding, seed) stays traced through the api 'node'
    layout, so a family of configs on a fixed mesh shares the compiled
    sharded tick.  Runs on however many devices the process sees (1 in
    bench-smoke; the spmd-test job exercises the same contract on a
    4-fake-host mesh)."""
    from repro import api

    kw = dict(n_nodes=2, coroutines=12, records_per_node=4096, ticks=96, warmup=8)
    plans = [
        api.plan(
            api.ExperimentSpec(
                protocol="sundial", workload="smallbank", configs=(cfg,),
                node_shards=1, layout="node", **kw,
            )
        )
        for cfg in ({"hybrid": 0b010101}, {"hybrid": 0b101010}, {"seed": 7})
    ]
    assert all(pl.expected_compiles == 1 for pl in plans)
    before = api.compile_stats()
    t0 = time.time()
    rows = [api.execute(pl).row for pl in plans]
    wall = time.time() - t0
    assert all(r["commits"] > 0 for r in rows), "node-sharded cells produced bad rows"
    delta = api.compile_stats()["node"] - before["node"]
    # expected_compiles is a cold-cache bound per plan; the three plans
    # share one (GridSpec, mesh) program, so the measured total is 1
    assert delta == 1, (
        f"node-sharded tick compiled {delta} programs for 3 configs on one mesh "
        "(want 1): a knob leaked into the compiled program structure"
    )
    assert wall < budget_s, f"node-sharded cells took {wall:.1f}s (budget {budget_s:.0f}s)"
    print(f"perf gate ok: 3 node-sharded configs = {delta} compile(s), {wall:.1f}s < {budget_s:.0f}s budget")


_PARITY_COUNTERS = ("commits", "aborts", "abort_rate", "throughput_mtps", "avg_round_trips")


def gate_kernel_plane(budget_s: float) -> None:
    """Counter parity between the jnp and the Pallas kernel plane (DESIGN.md §9)."""
    import numpy as np

    from repro import api
    from repro.kernels import ops

    kernel_plane = ops.PALLAS if ops.default_plane() == ops.PALLAS else ops.PALLAS_INTERPRET
    kw = dict(n_nodes=2, coroutines=12, records_per_node=1024, ticks=96, warmup=8)
    configs = tuple({"hybrid": c} for c in (0, 21, 42, 63))
    t0 = time.time()
    for proto in ("mvcc", "sundial"):
        rows = {
            plane: api.execute(
                api.plan(
                    api.ExperimentSpec(
                        protocol=proto, workload="smallbank", configs=configs,
                        kernel_plane=plane, **kw,
                    )
                )
            ).rows
            for plane in (ops.JNP, kernel_plane)
        }
        for a, b in zip(rows[ops.JNP], rows[kernel_plane]):
            for k in _PARITY_COUNTERS:
                assert np.array_equal(np.asarray(a[k]), np.asarray(b[k])), (
                    f"{proto}: kernel plane {kernel_plane!r} diverged from jnp on {k!r}"
                )
        print(f"perf gate ok: {proto} counters on the {kernel_plane} plane == jnp")
    wall = time.time() - t0
    assert wall < budget_s, f"kernel plane gate took {wall:.1f}s (budget {budget_s:.0f}s)"


def main(budget_s: float, bucket_budget_s: float, shard_budget_s: float, kernel_budget_s: float) -> None:
    gate_hybrid_enumeration(budget_s)
    gate_bucketed_coroutines(bucket_budget_s)
    gate_node_sharded_tick(shard_budget_s)
    gate_kernel_plane(kernel_budget_s)


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--budget", type=float, default=300.0, help="2^6 enumeration budget (s)")
    ap.add_argument(
        "--bucket-budget", type=float, default=240.0, help="bucketed co-routine sweep budget (s)"
    )
    ap.add_argument(
        "--shard-budget", type=float, default=240.0, help="node-sharded tick gate budget (s)"
    )
    ap.add_argument(
        "--kernel-budget", type=float, default=600.0, help="kernel plane parity gate budget (s)"
    )
    add_device_args(ap)
    args = ap.parse_args()
    configure_devices(args, error=ap.error)
    configure_compile_cache()
    main(args.budget, args.bucket_budget, args.shard_budget, args.kernel_budget)
