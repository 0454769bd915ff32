"""A deployment laid out over four devices (``node_shards``, the node
layout) through the harness, and the reference with its store laid out
likewise.  The test process keeps one device, so ``node_cell.py`` runs in a
child whose CPU backend has four (as ``tests/test_sharded.py`` does)."""
import ast
import glob
import json
import os
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))


@pytest.fixture(scope="module")
def node_run():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    p = subprocess.run([sys.executable, os.path.join(HERE, "node_cell.py")],
                       capture_output=True, text=True, env=env, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    return json.loads(p.stdout.strip().splitlines()[-1])


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
