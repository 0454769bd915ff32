"""The benchmark's layout: BENCHMARK.json, its files by name, the
reference's plug-ins, the calls' specs, the traffic generator, and the
refusal to run without a TPU."""
import dataclasses
import json
import os
import re
import subprocess
import sys

import pytest

from bench import harness, reference, traffic

from cells import BENCH, CELLS, CELLS_ONE, assert_resolves

ROOT = harness.ROOT
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


@pytest.mark.parametrize("cell", CELLS)
def test_cell_resolves_by_name(cell):
    assert_resolves(harness.load_cell(cell, BENCH))


@pytest.mark.parametrize("kind", ["protocol", "workload"])
def test_reference_refuses_an_unknown_name(kind):
    with pytest.raises(ValueError, match=f"no {kind} 'tpcc_new_order'"):
        reference.plugin(kind, "tpcc_new_order")
    with pytest.raises(ValueError):
        reference.plugin(kind, "../engine")


def test_a_plugin_is_one_new_file(tmp_path, monkeypatch):
    """A protocol and a workload enter the reference as a file each, found
    by name, with no edit to a file that exists: here NOWAIT and YCSB under
    new names, from a directory added to the package's search path."""
    (tmp_path / "protocol_nowait_copy.py").write_text(
        "from bench.reference.protocol_nowait import PROTOCOL  # noqa: F401\n")
    (tmp_path / "workload_ycsb_copy.py").write_text(
        "from bench.reference.workload_ycsb import make  # noqa: F401\n")
    monkeypatch.setattr(reference, "__path__", reference.__path__ + [str(tmp_path)])
    for mod in ("protocol_nowait_copy", "workload_ycsb_copy"):
        monkeypatch.delitem(sys.modules, f"bench.reference.{mod}", raising=False)
    assert "nowait_copy" in reference.known("protocol")
    dep = dict(harness.load_cell("ycsb-mvcc-single", BENCH).deployment, n_nodes=2, coroutines=4,
               records_per_node=128)
    knobs = [{"hybrid": 21, "seed": 3}]
    call = {"protocol": "nowait", "ticks": 4, "warmup": 1}
    want = reference.rows(dep, call, knobs)
    got = reference.rows(dict(dep, workload="ycsb_copy"), dict(call, protocol="nowait_copy"), knobs)
    assert got == want and want[0]["commits"] >= 0


def _spec_as_it_was(api, dep, call):
    """The spec every cell's call had before deployments could name a layout."""
    knobs = {"exec_ticks": dep["exec_ticks"]}
    if "hot_prob" in dep:
        knobs["hot_prob"] = dep["hot_prob"]
    return api.ExperimentSpec(
        protocol=call.protocol, workload=dep["workload"],
        configs=[dict(k, **knobs) for k in call.knobs], n_nodes=dep["n_nodes"],
        coroutines=dep["coroutines"], records_per_node=dep["records_per_node"],
        ticks=call.ticks, warmup=call.warmup, mvcc_slots=dep["mvcc_slots"], kernel_plane="auto",
    )


@pytest.mark.parametrize("cell", CELLS_ONE)
def test_spec_for_is_unchanged(cell):
    from repro import api

    c = harness.load_cell(cell, BENCH)
    assert "node_shards" not in c.deployment
    call = next(traffic.calls(c.traffic, 2**31 + 9))
    got = harness.spec_for(api, c.deployment, call)
    assert got == _spec_as_it_was(api, c.deployment, call)
    assert got.devices is None and got.node_shards is None


def test_spec_for_node_shards():
    from repro import api

    dep = dict(harness.load_cell("ycsb-mvcc-single", BENCH).deployment, node_shards=4)
    call = next(traffic.calls({"protocol": "nowait", "codes": "cycle", "configs_per_call": 1,
                               "ticks": 8, "warmup": 2}, 1))
    got = harness.spec_for(api, dep, call)
    assert (got.node_shards, got.devices) == (4, "auto")
    assert got == dataclasses.replace(_spec_as_it_was(api, dep, call), node_shards=4,
                                      devices="auto")


@pytest.mark.parametrize("shards, chips, ok", [(4, 4, True), (2, 2, True), (4, 1, False),
                                               (2, 4, False), (3, 3, False)])
def test_check_layout(shards, chips, ok):
    """``node_shards`` equals the cell's chips and divides the 8 nodes."""
    dep = dict(harness.load_cell("ycsb-mvcc-single", BENCH).deployment, node_shards=shards)
    if ok:
        harness.check_layout(dep, chips)
    else:
        with pytest.raises(ValueError, match="node_shards"):
            harness.check_layout(dep, chips)


def test_memory_peak_is_the_fullest_device():
    """Each chip reads the larger of its allocator's peak and its largest
    program; the cell reads its fullest chip."""
    from bench import run as bench_run

    assert bench_run.memory_peaks([7], [None]) == (7, [7])
    assert bench_run.memory_peaks([5, 9, 2, 9], [None] * 4) == (9, [5, 9, 2, 9])
    assert bench_run.memory_peaks([20, 19, 19, 19], [1300, 1290, 1290, 1290]) == (
        1300, [1300, 1290, 1290, 1290])
    assert bench_run.memory_peaks([50], [40]) == (50, [50])
    assert bench_run.memory_peaks([None], [None]) == (None, [None])


def test_program_bytes_records_each_program(monkeypatch):
    """Every program compiled after the wrap is recorded on its device, at its
    compiled arguments, outputs and temporaries less aliased outputs."""
    import jax
    import jax.numpy as jnp
    from jax._src import compiler

    from bench import run as bench_run

    programs = bench_run.ProgramBytes()
    monkeypatch.setattr(compiler, "compile_or_get_cached",
                        programs.wrap(compiler.compile_or_get_cached))
    fn = jax.jit(lambda x: (x * 3).cumsum() + 1)
    x = jnp.arange(4099, dtype=jnp.float32)
    fn(x).block_until_ready()
    m = fn.lower(x).compile().memory_analysis()
    want = (m.argument_size_in_bytes + m.output_size_in_bytes + m.temp_size_in_bytes
            - m.alias_size_in_bytes)
    dev = jax.devices()[0]
    assert programs.by_device[dev.id] >= want >= 2 * 4099 * 4
    allocator = (dev.memory_stats() or {}).get("peak_bytes_in_use")
    assert bench_run.memory_peaks([allocator], [programs.by_device[dev.id]])[0] >= want


def test_names_units_and_keys():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs", "workloads",
                          "end_to_end", "per_layer"}
    metrics = BENCH["end_to_end"] + BENCH["per_layer"]
    for entry in BENCH["configs"] + BENCH["workloads"] + metrics:
        assert NAME.match(entry["name"]), entry["name"]
    for m in metrics:
        assert UNIT.match(m["unit"]), m["unit"]
        assert m["better"] in ("lower", "higher")
    for c in BENCH["configs"]:
        assert os.path.isfile(os.path.join(ROOT, c["file"]))
        assert all(NAME.match(k) for k in c["reduced"])
    layers = {m["name"]: m["layer"] for m in BENCH["per_layer"]}
    assert all(m["moves"] in {e["name"] for e in BENCH["end_to_end"]} for m in BENCH["per_layer"])
    assert len(set(layers.values())) >= 4
    for w in BENCH["workloads"]:
        assert len(w["why"]) <= 200 and w["chips"] in (1, 4)
    assert len(json.dumps(BENCH)) < 64 * 1024


def test_traffic_is_a_function_of_the_seed():
    tr = {"protocol": "nowait", "codes": "cycle", "configs_per_call": 1, "ticks": 8, "warmup": 2}
    big = 2**31 + 4321

    def first(seed, n=70):
        gen = traffic.calls(tr, seed)
        return [next(gen) for _ in range(n)]

    a, b, c = first(big), first(big), first(big + 1)
    assert a == b and a != c
    # a call stream never repeats an engine seed, and cycles every coding
    assert len({call.knobs[0]["seed"] for call in a}) == len(a)
    assert {call.knobs[0]["hybrid"] for call in a[:64]} == set(range(64))
    grid = next(traffic.calls(dict(tr, codes="all", configs_per_call=64), big))
    assert [k["hybrid"] for k in grid.knobs] == list(range(64))
    assert grid.config_ticks == 64 * 10


def test_run_refuses_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, os.path.join(ROOT, "bench", "run.py"), "--workload", CELLS[0],
         "--seed", "5", "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, env=env, timeout=300,
    )
    assert p.returncode != 0
    assert "no TPU" in p.stderr
    assert p.stdout.strip() == ""


SPLIT = [m["name"] for m in BENCH["per_layer"] if "." in m["name"]]


@pytest.mark.parametrize("name", SPLIT)
def test_split_metric_reads_as_its_base(name):
    """``<base>.<part>`` has no reader file of its own and reads as ``<base>``."""
    from types import SimpleNamespace

    assert not os.path.isfile(os.path.join(ROOT, "bench", "metrics", f"{name}.py"))
    run = harness.Run({}, {}, "cpu", window_compiles=2, plan_ms=[1.0, 3.0, 2.0])
    run.trace = SimpleNamespace(busy_s=0.5, window_s=0.8, pallas_s=0.2)
    run.traced_call = traffic.Call(0, "nowait", 8, 2, [{"hybrid": 0, "seed": 1}])
    got = harness.metric_reader(name)(run)
    assert got is not None and got == harness.metric_reader(name.split(".")[0])(run)


@pytest.mark.parametrize("call_s, want", [(3.96, 2), (0.504, 9), (0.229, 16), (40.0, 2),
                                          (0.0, 16)])
def test_in_flight_follows_the_first_call(call_s, want):
    """The one call waited for and about four seconds of calls behind it."""
    assert harness.in_flight(call_s) == want


def test_window_keeps_calls_in_flight():
    """After its first call, which runs alone, the window keeps
    ``in_flight`` calls running; when time is up it sends nothing more,
    waits for every call sent, and counts each, in the order sent."""
    import threading
    import time
    from types import SimpleNamespace

    from bench import correct

    lock = threading.Lock()
    state = {"now": 0, "most": 0}

    def execute(pl):
        with lock:
            state["now"] += 1
            state["most"] = max(state["most"], state["now"])
        time.sleep(0.02)
        with lock:
            state["now"] -= 1
        row = dict.fromkeys(correct.INT_FIELDS + harness.FLOAT_FIELDS, 1)
        return SimpleNamespace(rows=[dict(row, stage_us_per_commit=[1.0]) for _ in pl.spec.configs])

    api = SimpleNamespace(ExperimentSpec=lambda **kw: SimpleNamespace(**kw),
                          plan=lambda spec: SimpleNamespace(kernel_plane="pallas", spec=spec),
                          execute=execute)
    log = SimpleNamespace(snapshot=lambda: (0, 0.0))
    tr = {"protocol": "nowait", "codes": "cycle", "configs_per_call": 1, "ticks": 8, "warmup": 2}
    dep = harness.load_cell("ycsb-mvcc-single", BENCH).deployment
    t0 = time.perf_counter()
    run = harness.run_cell(api, dep, tr, seed=2**31 + 5, seconds=0.5, trace=False,
                           device_kind="cpu", log=log, t_start=t0)
    assert time.perf_counter() - t0 >= run.window_s >= 0.5
    assert run.in_flight == harness.in_flight(run.call_s[0]) == harness.MAX_IN_FLIGHT
    assert state["most"] == run.in_flight and state["now"] == 0
    assert [call.index for call, _ in run.calls] == list(range(1, run.attempted + 1))
    assert run.failed == 0 and run.config_ticks == 10 * run.attempted
