"""The benchmark's layout: BENCHMARK.json, its files by name, the traffic
generator, and the refusal to run without a TPU."""
import json
import os
import re
import subprocess
import sys

import pytest

from bench import harness, traffic

ROOT = harness.ROOT
BENCH = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
CELLS = [w["name"] for w in BENCH["workloads"]]
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


@pytest.mark.parametrize("cell", CELLS)
def test_cell_resolves_by_name(cell):
    c = harness.load_cell(cell, BENCH)
    assert c.deployment["workload"] in ("smallbank", "ycsb")
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
