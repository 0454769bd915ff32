"""A deployment laid out over four devices (``node_shards``, the node
layout) through the harness, the reference with its store laid out
likewise, and each node cell of the benchmark (``CELLS_NODE``) under the
checks a cell on one chip gets.  The test process keeps one device, so
``node_cell.py`` runs in a child whose CPU backend has four (as
``tests/test_sharded.py`` does)."""
import ast
import glob
import json
import os
import shutil
import subprocess
import sys

import pytest

from bench import harness
from cells import BENCH, CELLS_NODE, CELLS_ONE, assert_node_cell, assert_resolves, layouts

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))


def run_child(*args):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    p = subprocess.run([sys.executable, os.path.join(HERE, "node_cell.py"), *args],
                       capture_output=True, text=True, env=env, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    return json.loads(p.stdout.strip().splitlines()[-1])


@pytest.fixture(scope="module")
def node_run():
    return run_child()


def test_node_shards_plans_the_node_layout(node_run):
    assert (node_run["layout"], node_run["n_devices"]) == ("node", 4)
    assert node_run["sound"]["node_shards"] == [4]


def test_node_deployment_is_correct(node_run):
    sound = node_run["sound"]
    assert sound["correct"] and sound["failed"] == 0
    assert sound["numbers"] == {"int_mismatch": 0.0, "float_gap": 0.0}


def test_program_bytes_on_every_device(node_run):
    """The node layout's program is recorded on each of the four devices."""
    assert len(node_run["program_bytes"]) == 4 and min(node_run["program_bytes"]) > 0


def test_dropped_exchange_is_not_correct(node_run):
    fault = node_run["exchange_dropped"]
    assert not fault["correct"] and fault["numbers"]["int_mismatch"] > 0, fault


def test_reference_store_laid_over_four_devices(node_run):
    """Batches of ``NODE_BATCH`` with the store over 4 devices give the one-device rows:
    counters bitwise, floats to float32 rounding (the partitioned sums run in
    another order); no array of the whole record axis is left in the program."""
    assert node_run["reference"]["int_mismatch"] == 0
    assert node_run["reference"]["float_gap"] < 1e-6
    assert min(node_run["reference_commits"]) > 0
    assert node_run["whole_record_arrays"] == 0 and node_run["local_record_arrays"] > 0


def test_reference_is_plain_jax():
    """The reference neither enters a mesh by hand nor imports the program."""
    for path in glob.glob(os.path.join(ROOT, "bench", "reference", "*.py")):
        tree = ast.parse(open(path).read())
        names = [a.name for n in ast.walk(tree) if isinstance(n, ast.Import) for a in n.names]
        names += [n.module for n in ast.walk(tree) if isinstance(n, ast.ImportFrom) and n.module]
        assert not [m for m in names if m.split(".")[0] == "repro"], path
        assert "shard_map" not in open(path).read(), path


@pytest.mark.parametrize("cell", CELLS_NODE)
def test_node_cell(node_run, cell):
    """A committed node cell passes the checks of a cell on one chip: spec,
    tiny run against the reference, precision control, planted faults."""
    assert_node_cell(node_run["cells"][cell])


NEW = "ycsb-node4-files"


def node_cell_files(root):
    """A copy of the benchmark at ``root`` with a node cell added as new
    files and list entries only: YCSB laid over 4 chips, one NOWAIT
    configuration per call."""
    shutil.copytree(harness.BENCH, root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", ".trace"))
    ycsb = harness.read_json(os.path.join(harness.BENCH, "configs", "ycsb-rcc.json"))
    files = {
        "configs/ycsb-node4.json": dict(ycsb, name="ycsb-node4", records_per_node=256,
                                        node_shards=4),
        "traffic/nowait-node4.json": {"protocol": "nowait", "codes": "cycle",
                                      "configs_per_call": 1, "ticks": 400, "warmup": 80},
        f"cells/{NEW}.json": {"sample_rows": 2, "limits": {"int_mismatch": 0, "float_gap": 1e-4}},
    }
    for path, data in files.items():
        (root / "bench" / path).write_text(json.dumps(data))
    bench = json.loads(json.dumps(BENCH))
    bench["configs"].append({"name": "ycsb-node4", "source": "https://arxiv.org/abs/2002.12664",
                             "file": "bench/configs/ycsb-node4.json",
                             "reduced": ["records_per_node"], "why": "YCSB over 4 chips"})
    bench["workloads"].append({"name": NEW, "config": "ycsb-node4", "traffic": "nowait-node4",
                               "chips": 4, "why": "the node layout, one NOWAIT config per call"})
    metrics = {m["name"]: m for m in bench["end_to_end"] + bench["per_layer"]}
    for name in ("config_ticks_per_s", "idle_share"):
        metrics[name]["workloads"].append(NEW)
    (root / "BENCHMARK.json").write_text(json.dumps(bench))


def test_node_cell_enters_as_files(tmp_path, monkeypatch):
    """A ``node_shards: 4`` cell added as files to a copy of the benchmark
    lands in ``CELLS_NODE`` and passes every check ``tests/bench`` applies to
    a cell of that list, with no committed file edited."""
    node_cell_files(tmp_path)
    monkeypatch.setattr(harness, "ROOT", str(tmp_path))
    monkeypatch.setattr(harness, "BENCH", str(tmp_path / "bench"))
    bench = harness.load_benchmark()
    assert layouts(bench) == (CELLS_ONE, CELLS_NODE + [NEW])
    c = harness.load_cell(NEW, bench)
    assert_resolves(c)
    assert "setup_compile_s" not in {m["name"] for m in c.per_layer}
    got = run_child("--bench", str(tmp_path), NEW)
    assert set(got) == {"cells"}
    assert_node_cell(got["cells"][NEW])
