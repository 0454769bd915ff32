"""The benchmark's cells cut to a size a CPU test run holds: the same
deployment files and traffic mixes, 256 records per node, 8 co-routines,
8 + 2 ticks, and 4 configurations per call where a call holds a whole grid.

A cell on one chip (``CELLS_ONE``) is cut to a 2-node cluster and checked
in the test process.  A cell whose deployment states ``node_shards``
(``CELLS_NODE``, the node layout) keeps one whole node a device, as many
nodes as shards; the test process keeps one device, so its checks run in
``node_cell.py``'s child, whose CPU backend has four (``test_bench_node.py``).
``node_tiny`` is a node deployment of that size built from the MVCC cell's
YCSB file, one NOWAIT configuration per call.

The benchmark read is ``BENCHMARK.json`` at ``harness.ROOT``, read once, as
this module is imported.
"""
from bench import harness

BENCH = harness.load_benchmark()
CELLS = [w["name"] for w in BENCH["workloads"]]


def layouts(bench: dict):
    """(the cells on one chip, the cells whose deployment states
    ``node_shards``) of a ``BENCHMARK.json``, each in its order."""
    names = [w["name"] for w in bench["workloads"]]
    node = [n for n in names if "node_shards" in harness.load_cell(n, bench).deployment]
    return [n for n in names if n not in node], node


CELLS_ONE, CELLS_NODE = layouts(BENCH)


def cut(deployment: dict, chips: int) -> dict:
    """A deployment at the tests' size: 2 nodes on one chip, or where it
    states ``node_shards`` (the cell's ``chips``) one whole node a device."""
    dep = dict(deployment, n_nodes=deployment.get("node_shards", 2), coroutines=8,
               records_per_node=256)
    harness.check_layout(dep, chips)
    return dep


def tiny(name: str):
    c = harness.load_cell(name, BENCH)
    dep = cut(c.deployment, c.chips)
    tr = dict(c.traffic, ticks=8, warmup=2)
    if tr["codes"] == "all":
        tr.update(codes="cycle", configs_per_call=4)
    return c, dep, tr


NODE_CHIPS = 4


def node_tiny():
    """(limits, deployment, traffic) of a tiny YCSB deployment whose 4 nodes
    lie over 4 devices (``node_shards``)."""
    c = harness.load_cell("ycsb-mvcc-single", BENCH)
    dep = cut(dict(c.deployment, node_shards=NODE_CHIPS), NODE_CHIPS)
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


def coded(rows, knobs):
    """Reference rows with the hybrid coding written as the program's rows give it."""
    for row, k in zip(rows, knobs):
        row["hybrid"] = "".join(str((k["hybrid"] >> i) & 1) for i in range(6))
    return rows


def control(name: str):
    """The compared numbers of the precision control: the reference computed
    with bfloat16 latency accumulators, put in the program's place, against
    the reference, on four codings of the tiny cell."""
    from bench import correct, reference

    _, dep, tr = tiny(name)
    knobs = [{"hybrid": code, "seed": 1000 + code} for code in (0, 21, 42, 63)]
    call = {"protocol": tr["protocol"], "ticks": tr["ticks"], "warmup": tr["warmup"]}
    rows = coded(reference.rows(dep, call, knobs, fdt="bfloat16"), knobs)
    return correct.compare(rows, reference.rows(dep, call, knobs), [k["hybrid"] for k in knobs])


def assert_resolves(c: harness.Cell) -> None:
    """A cell's files, plug-ins and metrics resolve by name."""
    from bench import reference, traffic

    # the reference has a generator for the workload and a tick for the protocol
    assert callable(reference.workload(c.deployment["workload"]))
    assert callable(reference.protocol(c.traffic["protocol"]).tick)
    traffic.validate(c.traffic)
    assert set(c.limits) == {"int_mismatch", "float_gap"} and c.sample_rows >= 1
    names = {m["name"] for m in c.end_to_end}
    assert "setup_s" in names and len(names) >= 2
    run = harness.Run(c.deployment, c.traffic, "cpu", setup_s=1.5, window_s=2.0, config_ticks=10)
    got = harness.end_to_end(run, c.end_to_end)
    assert set(got) == names and got["setup_s"]["value"] == 1.5
    assert sorted(v["value"] for k, v in got.items() if k != "setup_s") == [5.0] * (len(names) - 1)
    assert c.per_layer, "every cell reports a per-layer metric"
    for m in c.per_layer:
        assert callable(harness.metric_reader(m["name"]))
        assert m["moves"] in names, (m["name"], m["moves"])


def assert_node_cell(got: dict) -> None:
    """The checks of a one-chip cell, as ``node_cell.py`` reports them for a
    node cell: its spec lays the nodes over the cell's chips, the tiny run
    plans the node layout and matches the reference, and neither the
    precision control nor a run with a fault planted does."""
    from bench import correct

    chips, limits = got["chips"], got["limits"]
    assert got["spec"] == {"node_shards": chips, "devices": "auto"}, got["spec"]
    sound = got["sound"]
    assert sound["node_shards"] == [chips], sound
    assert sound["numbers"] == {"int_mismatch": 0.0, "float_gap": 0.0}
    assert sound["correct"] and sound["attempted"] == 1 and sound["failed"] == 0
    numbers = got["control"]
    assert numbers["int_mismatch"] == 0
    assert numbers["float_gap"] > limits["float_gap"]
    assert not correct.judge(numbers, limits)
    assert got["faults"], "a node cell is run with faults planted"
    for fault, run in got["faults"].items():
        assert not run["correct"], (fault, run)
