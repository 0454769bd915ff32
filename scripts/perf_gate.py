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
  5. kernel plane (DESIGN.md §9): a roofline-style ticks/sec gate.  The
     same sweep runs on the jnp plane and on the Pallas plane (interpret
     mode when no accelerator is attached), warm-cache timed.  The jnp
     plane must clear ``--min-ticks-per-sec`` and the kernel plane must
     stay within ``--kernel-slowdown``x of it — interpret-mode emulation
     is slow, but a constant-factor regression (e.g. the dispatch layer
     re-tracing per tick) blows even that generous ratio.  Counter parity
     between the planes is re-checked here so the perf numbers are known
     to come from equivalent programs.

With ``--bench-out PATH`` the measured numbers are written as a
machine-readable ``BENCH_<rev>.json`` for the bench-smoke artifact trail.

Run from a fresh interpreter (the compile-cache assertions count programs
compiled in THIS process).
"""
import argparse
import json
import os
import subprocess
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
    return {"wall_s": round(wall, 3), "compiles": delta, "budget_s": budget_s}


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
    return {"wall_s": round(wall, 3), "compiles": delta, "budget_s": budget_s}


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
    return {"wall_s": round(wall, 3), "compiles": delta, "budget_s": budget_s}


_PARITY_COUNTERS = ("commits", "aborts", "abort_rate", "throughput_mtps", "avg_round_trips")


def gate_kernel_plane(budget_s: float, slowdown: float, min_tps: float) -> dict:
    """Roofline-style ticks/sec gate for the kernel plane (DESIGN.md §9)."""
    import numpy as np

    from repro import api
    from repro.kernels import ops

    kernel_plane = ops.PALLAS if ops.default_plane() == ops.PALLAS else ops.PALLAS_INTERPRET
    kw = dict(n_nodes=2, coroutines=12, records_per_node=1024, ticks=96, warmup=8)
    configs = tuple({"hybrid": c} for c in (0, 21, 42, 63))
    t0 = time.time()
    result = {"kernel_plane": kernel_plane, "protocols": {}}
    for proto in ("mvcc", "sundial"):
        timed, rows = {}, {}
        for plane in (ops.JNP, kernel_plane):
            pl = api.plan(
                api.ExperimentSpec(
                    protocol=proto, workload="smallbank", configs=configs,
                    kernel_plane=plane, **kw,
                )
            )
            rows[plane] = api.execute(pl).rows  # cold: compile + run
            t1 = time.time()
            api.execute(pl)  # warm-cache timed pass
            wall = time.time() - t1
            timed[plane] = kw["ticks"] * len(configs) / max(wall, 1e-9)
        for a, b in zip(rows[ops.JNP], rows[kernel_plane]):
            for k in _PARITY_COUNTERS:
                assert np.array_equal(np.asarray(a[k]), np.asarray(b[k])), (
                    f"{proto}: kernel plane {kernel_plane!r} diverged from jnp on {k!r} — "
                    "the ticks/sec numbers below would compare inequivalent programs"
                )
        jnp_tps, ker_tps = timed[ops.JNP], timed[kernel_plane]
        assert jnp_tps >= min_tps, (
            f"{proto}: jnp plane ran {jnp_tps:.1f} ticks/s (floor {min_tps:.0f})"
        )
        assert ker_tps >= jnp_tps / slowdown, (
            f"{proto}: {kernel_plane} plane ran {ker_tps:.1f} ticks/s vs jnp {jnp_tps:.1f} — "
            f"worse than the {slowdown:.0f}x roofline ratio"
        )
        result["protocols"][proto] = {
            "jnp_ticks_per_s": round(jnp_tps, 2),
            "kernel_ticks_per_s": round(ker_tps, 2),
            "slowdown_x": round(jnp_tps / max(ker_tps, 1e-9), 2),
        }
        print(
            f"perf gate ok: {proto} kernel plane {kernel_plane} = {ker_tps:.1f} ticks/s "
            f"(jnp {jnp_tps:.1f}, ratio {jnp_tps / max(ker_tps, 1e-9):.1f}x <= {slowdown:.0f}x)"
        )
    wall = time.time() - t0
    assert wall < budget_s, f"kernel plane gate took {wall:.1f}s (budget {budget_s:.0f}s)"
    result.update(wall_s=round(wall, 3), budget_s=budget_s)
    return result


def _rev() -> str:
    rev = os.environ.get("GITHUB_SHA")
    if rev:
        return rev
    try:
        return subprocess.run(
            ["git", "rev-parse", "HEAD"], cwd=_ROOT, capture_output=True, text=True, check=True
        ).stdout.strip()
    except (OSError, subprocess.CalledProcessError):
        return "unknown"


def _write_bench(path: str, gates: dict) -> None:
    payload = {"rev": _rev(), "generated_unix": int(time.time()), "gates": gates}
    with open(path, "w") as fh:
        json.dump(payload, fh, indent=2, sort_keys=True)
        fh.write("\n")
    print(f"bench report written: {path}")


def main(
    budget_s: float,
    bucket_budget_s: float,
    shard_budget_s: float,
    kernel_budget_s: float,
    kernel_slowdown: float,
    min_tps: float,
    bench_out: str | None = None,
) -> None:
    gates = {
        "hybrid_enumeration": gate_hybrid_enumeration(budget_s),
        "bucketed_coroutines": gate_bucketed_coroutines(bucket_budget_s),
        "node_sharded_tick": gate_node_sharded_tick(shard_budget_s),
        "kernel_plane": gate_kernel_plane(kernel_budget_s, kernel_slowdown, min_tps),
    }
    if bench_out:
        _write_bench(bench_out, gates)


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
        "--kernel-budget", type=float, default=600.0, help="kernel plane gate budget (s)"
    )
    ap.add_argument(
        "--kernel-slowdown",
        type=float,
        default=200.0,
        help="max allowed kernel-plane slowdown vs jnp (x); generous for interpret mode on CPU",
    )
    ap.add_argument(
        "--min-ticks-per-sec",
        type=float,
        default=5.0,
        help="jnp-plane warm-cache ticks/sec floor (roofline anchor)",
    )
    ap.add_argument(
        "--bench-out", default=None, help="write machine-readable BENCH_<rev>.json here"
    )
    add_device_args(ap)
    args = ap.parse_args()
    configure_devices(args, error=ap.error)
    configure_compile_cache()
    main(
        args.budget,
        args.bucket_budget,
        args.shard_budget,
        args.kernel_budget,
        args.kernel_slowdown,
        args.min_ticks_per_sec,
        args.bench_out,
    )
