"""The benchmark's cells cut to a size a CPU test run holds: the same
deployment files and traffic mixes, on a 2-node cluster of 256 records per
node with 8 co-routines, 4 configurations per call, 8 + 2 ticks; and a
node-layout deployment of that size (``node_tiny``), 4 nodes over 4
devices, one NOWAIT configuration per call."""
import json
import os

from bench import harness

BENCH = json.load(open(os.path.join(harness.ROOT, "BENCHMARK.json")))
CELLS = [w["name"] for w in BENCH["workloads"]]


def tiny(name: str):
    c = harness.load_cell(name, BENCH)
    dep = dict(c.deployment, n_nodes=2, coroutines=8, records_per_node=256)
    tr = dict(c.traffic, ticks=8, warmup=2)
    if tr["codes"] == "all":
        tr.update(codes="cycle", configs_per_call=4)
    return c, dep, tr


NODE_CHIPS = 4


def node_tiny():
    """(limits, deployment, traffic) of a tiny YCSB deployment whose 4 nodes
    lie over 4 devices (``node_shards``)."""
    c = harness.load_cell("ycsb-mvcc-single", BENCH)
    dep = dict(c.deployment, n_nodes=NODE_CHIPS, coroutines=8, records_per_node=256,
               node_shards=NODE_CHIPS)
    harness.check_layout(dep, NODE_CHIPS)
    tr = {"protocol": "nowait", "codes": "cycle", "configs_per_call": 1, "ticks": 8, "warmup": 2}
    return c.limits, dep, tr


def run_tiny(name: str, seed: int = 2**31 + 77, sample_rows: int = 8):
    """One warm call and one window call of the cell through the harness on
    the CPU (no chip check, the backend's own kernel plane), then the
    reference comparison: returns (numbers, correct, run)."""
    c, dep, tr = tiny(name)
    return run_small(c.limits, dep, tr, seed, sample_rows)


def run_small(limits, dep, tr, seed: int = 2**31 + 77, sample_rows: int = 8):
    import time

    import jax

    from bench import correct
    from bench.compile_log import CompileLog
    from repro import api

    run = harness.run_cell(
        api, dep, tr, seed=seed, seconds=0.0, trace=False, device_kind="cpu",
        log=CompileLog(jax.monitoring), t_start=time.perf_counter(), expect_plane=None,
    )
    numbers = correct.check(dep, tr, run.calls, sample_rows, seed)
    return numbers, run.failed == 0 and correct.judge(numbers, limits), run
