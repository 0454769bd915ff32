"""Shared benchmark helpers around the ``repro.api`` front door.

Two layers live here:

  * **Process set-up**, shared by ``chip_smoke.py``, ``benchmarks/run.py``,
    ``scripts/dev_smoke.py`` and ``scripts/perf_gate.py``: the persistent
    compile cache (``configure_compile_cache``) and the device/topology CLI
    flags (``add_device_args`` / ``configure_devices``), the one place
    ``--devices`` / ``--node-shards`` / fake-host XLA_FLAGS forcing is
    parsed.  Forcing fake
    host devices must happen BEFORE jax is imported, so this module keeps
    its import surface jax-free — every heavy import below is local to the
    function that needs it.
  * **Cell helpers**: ``run_cell`` is the sequential reference path (its own
    jit per cell, used by the batched-vs-sequential equivalence tests);
    ``cherry_pick_hybrid`` builds the paper §5.1 per-stage hybrid through
    ``repro.api``.

Benchmark modules take their grids straight from ``repro.api``
(``ExperimentSpec`` → ``plan`` → ``execute``); the legacy sweep entry
points are deprecated shims, banned here by scripts/check_api_boundary.py.
"""
from __future__ import annotations

import os
import sys
import time
from typing import Dict, Optional, Tuple

PROTO_LIST = ("nowait", "waitdie", "occ", "mvcc", "sundial")  # slot-engine protocols

# set by configure_devices (--node-shards): benchmarks that support it run
# their single-config cells with the simulated n_nodes axis SPMD on the
# first N devices (the api 'node' layout); None = dense engine
NODE_SHARDS: Optional[int] = None


def add_device_args(ap) -> None:
    """Install the shared ``--node-shards`` / ``--devices`` flags on a parser."""
    ap.add_argument(
        "--node-shards",
        type=int,
        default=0,
        help="shard the simulated n_nodes axis over this many devices "
        "(the repro.api 'node' layout); forces fake host devices when "
        "needed.  Honored by surfaces with single-config cells "
        "(stage_latency); grid surfaces keep config-axis sharding over "
        "the same devices",
    )
    ap.add_argument(
        "--devices",
        type=int,
        default=0,
        help="force this many (fake) host devices for config-axis sharding "
        "(repro.api picks them up via devices='auto')",
    )


def configure_devices(args, *, error=None) -> int:
    """Apply the shared device flags; MUST run before jax is imported.

    Appends ``--xla_force_host_platform_device_count`` to ``XLA_FLAGS`` when
    more than one device is requested and records ``--node-shards`` in
    :data:`NODE_SHARDS` for single-config surfaces.  ``error`` is the
    parser's ``.error`` (or any callable raising); defaults to SystemExit.
    Returns the forced device count (0/1 = no forcing).
    """
    global NODE_SHARDS

    def fail(msg: str):
        if error is not None:
            error(msg)
        raise SystemExit(f"error: {msg}")

    n_dev = max(args.node_shards, args.devices)
    if n_dev > 1:
        if "jax" in sys.modules:
            fail("--node-shards/--devices must be set before jax is imported")
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + f" --xla_force_host_platform_device_count={n_dev}"
        ).strip()
    NODE_SHARDS = args.node_shards or None
    return n_dev


# JAX's persistent compilation cache lives here unless JAX_COMPILATION_CACHE_DIR
# says otherwise.  A fixed path: the directory is part of the cache key, so a
# path built from a temp name, pid or time would never hit.
COMPILE_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))), ".jax_cache"
)


def configure_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; call before the first
    compile.  ``JAX_COMPILATION_CACHE_DIR``, when set, is read by JAX itself
    and no other directory is set; otherwise the cache goes to
    :data:`COMPILE_CACHE_DIR`.  Returns the directory in use."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax

    jax.config.update("jax_compilation_cache_dir", COMPILE_CACHE_DIR)
    return COMPILE_CACHE_DIR


def split_knobs(kw: Dict) -> Tuple[Dict, Dict]:
    """Split run_cell-style kwargs into (per-run knobs, static grid kwargs)."""
    from repro.api import KNOB_KEYS

    knobs = {k: kw[k] for k in KNOB_KEYS if k in kw and kw[k] is not None}
    static = {k: v for k, v in kw.items() if k not in KNOB_KEYS}
    return knobs, static


def run_cell(
    protocol: str,
    workload: str,
    hybrid,
    *,
    n_nodes: int = 4,
    coroutines: int = 60,
    records_per_node: int = 65536,  # paper-scale: 0.1% hot area >> the 16-record floor
    ticks: int = 400,
    warmup: int = 80,
    exec_ticks: Optional[int] = None,
    hot_prob: Optional[float] = None,
    qp_pressure: float = 0.0,
    history_cap: int = 0,
    seed: int = 0,
    tcp: bool = False,
    merge_stages: bool = False,
):
    """One (protocol, workload, hybrid, knobs) cell under its own jit — the
    sequential reference path the batched sweep is pinned against.

    Returns ``(metrics, state, store)`` for tick-driven protocols;
    epoch-driven registry entries (``entry.tick is None``, e.g. CALVIN) own
    their run loop through hooks and return ``(metrics, None, None)``.
    """
    import jax

    from repro.api import normalize_hybrid
    from repro.core.costmodel import CostModel
    from repro.core.engine import EngineConfig, run
    from repro.core.registry import get_protocol
    from repro.workloads import make_workload

    entry = get_protocol(protocol)
    hybrid = normalize_hybrid(hybrid)
    cm = CostModel.tcp() if tcp else CostModel(qp_pressure=qp_pressure)
    kw = {}
    if hot_prob is not None:
        kw["hot_prob"] = hot_prob
    if exec_ticks is not None:
        kw["exec_ticks"] = exec_ticks
    n_records = n_nodes * records_per_node
    wl = make_workload(workload, n_records, **kw)
    ec = EngineConfig(
        protocol=protocol,
        n_nodes=n_nodes,
        coroutines=coroutines,
        records_per_node=records_per_node,
        rw=wl.rw,
        max_ops=wl.max_ops,
        hybrid=hybrid,
        merge_stages=merge_stages,
        exec_ticks=wl.exec_ticks,  # keep handler starvation in sync with the workload
        history_cap=history_cap,
        seed=seed,
    )
    t0 = time.time()
    if entry.tick is None:  # epoch-driven protocols own their run loop
        m = jax.jit(
            lambda: entry.hooks.grid_run(
                entry, ec, cm, wl, ticks=ticks, warmup=warmup, ticks_active=None
            )
        )()
        st = store = None
    else:
        st, store, m = jax.jit(lambda: run(entry.tick, ec, cm, wl, ticks, warmup=warmup))()
    m = {k: (v.tolist() if hasattr(v, "tolist") else v) for k, v in m.items()}
    m["wall_s"] = round(time.time() - t0, 2)
    m["protocol"], m["workload"], m["hybrid"] = protocol, workload, "".join(map(str, hybrid))
    return m, st, store


def stage_breakdown(m: Dict) -> Dict[str, float]:
    from repro.core.costmodel import STAGE_NAMES

    return dict(zip(STAGE_NAMES, m["stage_us_per_commit"]))


def cherry_pick_hybrid(protocol: str, workload: str, **kw):
    """Paper §5.1: pick the lower-latency primitive per stage from the pure
    RPC and pure one-sided stage breakdowns (both run in one planned grid)."""
    from repro import api
    from repro.core.costmodel import N_HYBRID_STAGES, ONE_SIDED, RPC

    knobs, static = split_knobs(kw)
    m_rpc, m_os = api.run(
        api.ExperimentSpec(
            protocol=protocol,
            workload=workload,
            configs=(
                dict(knobs, hybrid=(RPC,) * N_HYBRID_STAGES),
                dict(knobs, hybrid=(ONE_SIDED,) * N_HYBRID_STAGES),
            ),
            **static,
        )
    ).rows
    code = tuple(
        RPC if m_rpc["stage_us_per_commit"][s] <= m_os["stage_us_per_commit"][s] else ONE_SIDED
        for s in range(N_HYBRID_STAGES)
    )
    return code, m_rpc, m_os
