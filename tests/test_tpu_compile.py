"""The engine's Pallas kernels compile for a TPU v5e at paper-scale shapes.

Nothing runs: each kernel is lowered and compiled for one chip of a
described (not attached) ``v5e:2x2`` topology, which raises what the chip's
compiler would raise (unsupported layouts, VMEM overuse).  Shapes come from
the api's paper-scale defaults: 4 nodes x 60 co-routines x 10 YCSB ops =
2,400 requests against 4 x 65,536 = 262,144 records, and TPC-C's 15 ops =
3,600 lock requests; and from the benchmark's 8-node cells: 524,288
records, 4,800 YCSB keys per MVCC read, 960 SmallBank keys per lock read
in each of a grid's 64 configurations.  The topology is described inside a fixture (never at
import: only one process may load the TPU library), and the persistent
compile cache is off around the compiles, since a compile for a described
chip can be written to it but never read back.
"""

import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.api import ExperimentSpec
from repro.kernels.lock_arbiter import lock_arbiter
from repro.kernels.multi_read import multi_read
from repro.kernels.mvcc_version_select import mvcc_version_select
from repro.workloads import make_workload

SPEC = ExperimentSpec(protocol="mvcc", workload="ycsb")
N_RECORDS = SPEC.n_nodes * SPEC.records_per_node
YCSB = make_workload("ycsb", N_RECORDS)
SLOTS = SPEC.n_nodes * SPEC.coroutines
CELL_RECORDS, CELL_SLOTS = 8 * 65536, 8 * 60  # the benchmark's 8-node cells


def _requests(workload: str) -> int:
    return SLOTS * make_workload(workload, N_RECORDS).max_ops


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        try:
            topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
        except Exception as e:
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        was = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        yield SingleDeviceSharding(topo.devices[0])
        jax.config.update("jax_enable_compilation_cache", was)
        compilation_cache.reset_cache()


def _compile(fn, one_chip, *shapes):
    args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip) for s, dt in shapes]
    hlo = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in hlo
    return hlo


def _table_sized_ops(hlo: str, n: int):
    """Instructions of the compiled program that produce n or more
    elements, other than parameters and bitcasts: a pass over the table."""
    found = []
    for line in hlo.splitlines():
        m = re.match(r"\s*(?:ROOT )?%\S+ = (\(?\S+) ([\w-]+)\(", line)
        if not m or m.group(2) in ("parameter", "bitcast", "get-tuple-element", "tuple"):
            continue
        for dims in re.findall(r"\[([\d,]+)\]", m.group(1)):
            if eval("*".join(dims.split(","))) >= n:
                found.append(line.strip()[:160])
    return found


def _compile_read(one_chip, n_records, m, words, batch=None):
    """Compile one multi_read over arrays of ``words`` trailing shapes
    (vmapped over ``batch`` configurations if given); no op may touch as
    many elements as the table."""
    lead = (n_records,) if batch is None else (batch, n_records)
    keys = (m,) if batch is None else (batch, m)
    read = lambda k, *a: multi_read(a, k, interpret=False)  # noqa: E731
    hlo = _compile(
        read if batch is None else jax.vmap(read), one_chip,
        (keys, jnp.int32), *[(lead + w, jnp.int32) for w in words],
    )
    assert hlo.count("multi_read") and not _table_sized_ops(hlo, n_records), _table_sized_ops(
        hlo, n_records
    )


@pytest.mark.parametrize(
    "words", [[(), ()], [(YCSB.rw,), ()]], ids=["lock_words", "ycsb_data_ver"]
)
def test_multi_read_compiles_for_v5e(one_chip, words):
    m = _requests("ycsb")
    assert m == 2400 and N_RECORDS == 262144
    _compile_read(one_chip, N_RECORDS, m, words)


@pytest.mark.parametrize(
    "words",
    [[(4,), (4,)], [(), ()], [(4,), (4,), ()], [(), (), ()]],
    ids=["wts", "rts", "wts_ver", "lock_rts"],
)
def test_multi_read_compiles_for_v5e_mvcc_cell(one_chip, words):
    """MVCC's four reads in the ycsb-mvcc-single cell (4 version slots)."""
    _compile_read(one_chip, CELL_RECORDS, CELL_SLOTS * YCSB.max_ops, words)


def test_multi_read_compiles_for_v5e_grid_cell(one_chip):
    """The NOWAIT grid cell's lock-word read, vmapped over 64 configs: one
    kernel over (64, 524,288) tables at (64, 960) keys."""
    m = CELL_SLOTS * make_workload("smallbank", CELL_RECORDS).max_ops
    assert m == 960
    _compile_read(one_chip, CELL_RECORDS, m, [(), ()], batch=64)


def test_mvcc_version_select_compiles_for_v5e(one_chip):
    m, s = _requests("ycsb"), SPEC.mvcc_slots
    _compile(
        lambda *a: mvcc_version_select(*a, interpret=False), one_chip,
        *[((m, s), jnp.int32)] * 2, *[((m,), jnp.int32)] * 4,
    )


@pytest.mark.parametrize("workload,m", [("ycsb", 2400), ("tpcc", 3600)])
def test_lock_arbiter_compiles_for_v5e(one_chip, workload, m):
    assert _requests(workload) == m
    _compile(
        lambda *a: lock_arbiter(*a, interpret=False), one_chip,
        *[((1, m), jnp.int32)] * 3, ((1, m), jnp.bool_),
    )
