"""Device-sharded sweep engine (sweep.run_grid_sharded) SPMD equivalence.

The main test process must keep seeing 1 device (tests/conftest.py), so the
4-fake-device equivalence run executes in a subprocess with
``XLA_FLAGS=--xla_force_host_platform_device_count=4`` — the same forcing
the CI ``spmd-test`` job applies process-wide.  Direct (non-subprocess)
variants below run only when the current process already sees >= 2 devices
(i.e. inside that CI job or on real multi-device hosts).

Equivalence contract (pinned here and documented in DESIGN.md §6):
every integer / ratio metric (commits, aborts, abort_rate,
throughput_mtps, avg_round_trips) is BITWISE-equal to the single-device
``run_grid``; ``avg_latency_us`` and ``stage_us_per_commit`` involve
float32 cross-slot accumulations whose lowering may differ between the
partitioned and unpartitioned programs, and are pinned to 1e-6 relative.
"""
import os
import subprocess
import sys

import jax
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

BITWISE = ("commits", "aborts", "abort_rate", "throughput_mtps", "avg_round_trips")
ULP = ("avg_latency_us", "stage_us_per_commit")


def assert_rows_equal(ref, got):
    assert len(ref) == len(got)
    for r, s in zip(ref, got):
        for k in BITWISE:
            assert np.array_equal(np.asarray(r[k]), np.asarray(s[k])), (k, r["hybrid"])
        for k in ULP:
            np.testing.assert_allclose(np.asarray(s[k]), np.asarray(r[k]), rtol=1e-6, err_msg=k)


_CODE = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import numpy as np, jax
from repro.core import sweep
from repro.core.sweep import all_hybrid_codes, run_grid, run_grid_sharded

assert len(jax.devices()) == 4, jax.devices()
KW = dict(n_nodes=2, coroutines=8, records_per_node=128, ticks=48, warmup=8)
BITWISE = ("commits", "aborts", "abort_rate", "throughput_mtps", "avg_round_trips")
ULP = ("avg_latency_us", "stage_us_per_commit")

def check(ref, got):
    for r, s in zip(ref, got):
        for k in BITWISE:
            assert np.array_equal(np.asarray(r[k]), np.asarray(s[k])), (k, r["hybrid"])
        for k in ULP:
            np.testing.assert_allclose(np.asarray(s[k]), np.asarray(r[k]), rtol=1e-6, err_msg=k)

# the paper's 2^6 hybrid enumeration, 64 configs over 4 devices
cfgs = [{"hybrid": c} for c in all_hybrid_codes()]
ref = run_grid("occ", "smallbank", cfgs, **KW)
sh = run_grid_sharded("occ", "smallbank", cfgs, **KW)
assert sh[0]["n_devices"] == 4 and all(r["commits"] > 0 for r in sh)
check(ref, sh)

# non-divisible grid: 6 configs on 4 devices (remainder-padded, pad dropped)
cfgs6 = [{"hybrid": c, "seed": i} for i, c in enumerate((0, 1, 5, 21, 42, 63))]
check(run_grid("occ", "smallbank", cfgs6, **KW),
      run_grid_sharded("occ", "smallbank", cfgs6, **KW))

# sharding composes with bucketed static-axis padding
cfgb = [{"hybrid": 21, "coroutines": 5}, {"hybrid": 42, "coroutines": 8},
        {"hybrid": 63, "coroutines": 7}]
ref_b = run_grid("occ", "smallbank", cfgb, **KW)
sh_b = run_grid_sharded("occ", "smallbank", cfgb, **KW)
assert sh_b[0]["n_buckets"] == 1
check(ref_b, sh_b)
print("SPMD SWEEP OK")
"""


@pytest.mark.slow  # ~1.5 min; the CI spmd-test job covers the same ground
# on every PR via the in-process variants below, this subprocess version
# keeps single-device checkouts honest nightly
@pytest.mark.skipif(
    len(jax.devices()) >= 2,
    reason="redundant when the process already sees multiple devices: the "
    "direct variants below cover the same equivalence in-process",
)
def test_sharded_grid_matches_single_device():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(ROOT, "src")
    env.pop("XLA_FLAGS", None)
    out = subprocess.run(
        [sys.executable, "-c", _CODE], capture_output=True, text=True, env=env, timeout=540
    )
    assert out.returncode == 0, out.stderr[-3000:]
    assert "SPMD SWEEP OK" in out.stdout


multi_device = pytest.mark.skipif(
    len(jax.devices()) < 2, reason="needs >=2 devices (CI spmd-test job forces 4 fake hosts)"
)


@multi_device
def test_sharded_direct_hybrid_grid():
    """Direct in-process variant for the 4-fake-device CI job."""
    from repro.core.sweep import all_hybrid_codes, run_grid, run_grid_sharded

    kw = dict(n_nodes=2, coroutines=8, records_per_node=128, ticks=48, warmup=8)
    cfgs = [{"hybrid": c} for c in all_hybrid_codes()]
    ref = run_grid("occ", "smallbank", cfgs, **kw)
    sh = run_grid_sharded("occ", "smallbank", cfgs, **kw)
    assert sh[0]["n_devices"] == len(jax.devices())
    assert_rows_equal(ref, sh)


@multi_device
def test_sharded_direct_bucketed_composition():
    """Sharding composes with bucketed static-axis padding."""
    from repro.core.sweep import run_grid, run_grid_sharded

    kw = dict(n_nodes=2, coroutines=8, records_per_node=128, ticks=48, warmup=8)
    cfgs = [
        {"hybrid": 21, "coroutines": 5},
        {"hybrid": 42, "coroutines": 8},
        {"hybrid": 63, "coroutines": 7},
    ]
    ref = run_grid("occ", "smallbank", cfgs, **kw)
    sh = run_grid_sharded("occ", "smallbank", cfgs, **kw)
    assert sh[0]["n_buckets"] == 1
    assert_rows_equal(ref, sh)


@multi_device
def test_sharded_direct_non_divisible():
    from repro.core.sweep import run_grid, run_grid_sharded

    kw = dict(n_nodes=2, coroutines=8, records_per_node=128, ticks=48, warmup=8)
    n_dev = len(jax.devices())
    cfgs = [{"hybrid": c, "seed": c} for c in range(n_dev + 1)]  # never divides (n_dev >= 2)
    assert_rows_equal(
        run_grid("nowait", "smallbank", cfgs, **kw),
        run_grid_sharded("nowait", "smallbank", cfgs, **kw),
    )


def test_sharded_single_device_is_run_grid():
    """With one device the sharded entry point must not recompile or pad —
    it IS run_grid (same compiled program, same counters)."""
    from repro.core import sweep
    from repro.core.sweep import run_grid, run_grid_sharded

    if len(jax.devices()) != 1:
        pytest.skip("single-device contract")
    kw = dict(n_nodes=2, coroutines=8, records_per_node=128, ticks=48, warmup=8)
    cfgs = [{"hybrid": 21}, {"hybrid": 42}]
    ref = run_grid("nowait", "smallbank", cfgs, **kw)
    before = sweep.sharded_compile_cache_size()
    sh = run_grid_sharded("nowait", "smallbank", cfgs, **kw)
    after = sweep.sharded_compile_cache_size()
    assert after == before  # never touched the sharded entry point
    for r, s in zip(ref, sh):
        assert r["commits"] == s["commits"] and r["aborts"] == s["aborts"]
        assert s["n_devices"] == 1
