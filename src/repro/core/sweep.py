"""Batched sweep engine: a grid of engine configurations as ONE program.

This is the ENGINE ROOM of the ``repro.api`` front door (DESIGN.md §8):
``api.plan`` consumes :func:`plan_buckets`, ``api.execute`` dispatches to
the jitted entry points below, and protocols are resolved through
``repro.core.registry`` (epoch-driven protocols bring their own RunHooks —
no protocol-name branches here).  The historical entry points
(:func:`run_grid`, :func:`run_grid_sharded`, :func:`run_cell_sharded`)
survive as thin deprecation shims that delegate to ``plan``/``execute``.

The paper's central experiment is an unbiased sweep over {protocol} x
{2^6 hybrid stage codings} x workload knobs.  Running each cell through a
fresh ``jax.jit`` costs one XLA compilation per cell — the exhaustive
hybrid enumeration alone is 64 compiles.  This module splits a run's
parameters into

  * a static :class:`GridSpec` (shapes + protocol + tick counts): one
    compilation per distinct spec, cached on the jitted entry point; and
  * traced :class:`RunKnobs` (hybrid coding as an int32[N_HYBRID_STAGES]
    array, seed, exec_ticks, hot_prob, qp_pressure): vmapped, so a whole
    grid of knob settings shares the single compiled ``lax.scan``.

``run_grid`` is the public API: it stacks the per-config knobs, runs
``vmap(run)`` under one jit, and unstacks the metrics into per-config
dicts shaped like ``benchmarks.common.run_cell``'s output.

Two scale-out layers sit on top (DESIGN.md §6):

  * **Bucketed static-axis padding**: configs may sweep the two static
    shape axes (``coroutines``, ``records_per_node``).  ``plan_buckets``
    groups configs into power-of-two shape buckets, pads each bucket to
    its max shape, and threads the per-config ACTIVE extents through as
    traced knobs (``EngineConfig.active_*``) — one XLA compile per bucket
    instead of one per distinct shape, with padded slots/records provably
    inert (bitwise-equal counters to the unpadded run).
  * **Device sharding**: ``run_grid_sharded`` splits the config axis over
    a 1-D ``grid`` mesh (``planes.shard_map``).  Grids that don't divide the
    device count are remainder-padded (the pad rows replicate the last
    config and are dropped on output), so any grid size works on any
    device count — real devices or ``--xla_force_host_platform_device_count``
    fake hosts — with output bitwise-equal to the single-device path.
"""
from __future__ import annotations

import functools
import itertools
import warnings
from typing import Any, Dict, Iterable, List, NamedTuple, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec

from repro.core import registry
from repro.core.costmodel import N_HYBRID_STAGES, RPC, CostModel
from repro.core.engine import EngineConfig
from repro.workloads import make_workload

# Per-workload knob defaults, mirroring each factory's signature; resolved
# at grid-construction (Python) time so an unspecified knob reproduces the
# sequential run_cell exactly.
WL_EXEC_TICKS = {"smallbank": 1, "ycsb": 3, "tpcc": 5}
YCSB_HOT_PROB = 0.10

KNOB_KEYS = ("hybrid", "seed", "exec_ticks", "hot_prob", "qp_pressure")

# static shape axes that plan_buckets can turn into traced active-extent
# knobs (per-config values in run_grid's ``configs`` dicts).  ``ticks`` is
# the scan-length axis: padded to the bucket max and early-exited per
# config (dead ticks freeze the carry and touch no counter), so a ticks
# sweep compiles once per bucket instead of once per distinct length.
STATIC_AXES = ("coroutines", "records_per_node", "ticks")


class GridSpec(NamedTuple):
    """Static shape/compile params — one XLA compilation per distinct value."""

    protocol: str
    workload: str
    n_nodes: int = 4
    coroutines: int = 60
    records_per_node: int = 65536
    ticks: int = 400
    warmup: int = 80
    history_cap: int = 0
    mvcc_slots: int = 4
    doorbell: bool = True
    tcp: bool = False
    merge_stages: bool = False  # cross-stage doorbell merging (rounds.py §4.2)
    kernel_plane: str = "jnp"  # fused hot-path backend (kernels/ops.py, DESIGN.md §9)


class RunKnobs(NamedTuple):
    """Traced per-run knobs; in ``run_grid`` every leaf has a leading grid axis.

    ``coroutines_active`` / ``records_active`` are the bucket-padding active
    extents (int32[...]) — None (an empty pytree leaf) when the matching
    static axis is unpadded, which keeps the legacy knob-only grids on the
    exact pre-bucketing program (pinned golden counters cannot drift).
    """

    hybrid: Any  # int32[..., N_HYBRID_STAGES]
    seed: Any  # int32[...]
    exec_ticks: Any  # int32[...]
    hot_prob: Any  # float32[...]
    qp_pressure: Any  # float32[...]
    coroutines_active: Any = None  # int32[...] live co-routines per node
    records_active: Any = None  # int32[...] live records per node
    ticks_active: Any = None  # int32[...] live measured ticks (tick bucketing)


def normalize_hybrid(code) -> Tuple[int, ...]:
    """Hybrid coding as a stage tuple; ints are bitmasks (bit i = stage i)."""
    if isinstance(code, (int, np.integer)):
        return tuple((int(code) >> i) & 1 for i in range(N_HYBRID_STAGES))
    code = tuple(int(b) for b in code)
    if len(code) != N_HYBRID_STAGES:
        raise ValueError(f"hybrid coding needs {N_HYBRID_STAGES} stages, got {code}")
    return code


def all_hybrid_codes() -> List[Tuple[int, ...]]:
    """All 2^N_HYBRID_STAGES stage codings (the paper's exhaustive sweep)."""
    return [normalize_hybrid(i) for i in range(2**N_HYBRID_STAGES)]


def grid_product(**axes: Sequence) -> List[Dict]:
    """Cartesian product of named knob axes -> list of config dicts."""
    names = list(axes)
    return [dict(zip(names, vals)) for vals in itertools.product(*(axes[n] for n in names))]


def make_knobs(workload: str, configs: Iterable[Dict]) -> RunKnobs:
    """Stack per-config knob dicts into a batched RunKnobs pytree.

    Each config may set any of ``hybrid`` (tuple or int bitmask), ``seed``,
    ``exec_ticks``, ``hot_prob``, ``qp_pressure``; omitted knobs take the
    workload's defaults.
    """
    configs = list(configs)
    if not configs:
        raise ValueError("empty config grid: pass at least one knob dict")
    rows = []
    for c in configs:
        c = dict(c)
        hy = normalize_hybrid(c.pop("hybrid", (RPC,) * N_HYBRID_STAGES))
        seed = int(c.pop("seed", 0))
        et = c.pop("exec_ticks", None)
        et = WL_EXEC_TICKS.get(workload, 1) if et is None else int(et)
        hp = c.pop("hot_prob", None)
        if hp is not None and workload != "ycsb":
            raise TypeError(f"hot_prob is a ycsb-only knob; workload={workload!r}")
        hp = YCSB_HOT_PROB if hp is None else float(hp)
        qp = float(c.pop("qp_pressure", 0.0))
        if c:
            raise TypeError(f"unknown knob(s): {sorted(c)}; valid: {KNOB_KEYS}")
        rows.append((hy, seed, et, hp, qp))
    hy, seed, et, hp, qp = zip(*rows)
    return RunKnobs(
        hybrid=jnp.asarray(np.array(hy, np.int32)),
        seed=jnp.asarray(np.array(seed, np.int32)),
        exec_ticks=jnp.asarray(np.array(et, np.int32)),
        hot_prob=jnp.asarray(np.array(hp, np.float32)),
        qp_pressure=jnp.asarray(np.array(qp, np.float32)),
    )


def _run_one(spec: GridSpec, kn: RunKnobs, shard=None) -> Dict:
    """One engine run with traced knobs (vmapped over the grid axis).

    ``shard`` (a ``planes.NodeShard``) runs the engine node-sharded: only
    meaningful inside a ``shard_map`` over that mesh axis (the 2-D
    ``config × node`` grid dispatch below).
    """
    cm = CostModel.tcp() if spec.tcp else CostModel(qp_pressure=kn.qp_pressure)
    # bucket padding: the workload draws over the LOGICAL (active) record
    # space; the engine owns the padded physical layout
    rpn = spec.records_per_node if kn.records_active is None else kn.records_active
    n_records = spec.n_nodes * rpn
    wkw: Dict[str, Any] = {"exec_ticks": kn.exec_ticks}
    if spec.workload == "ycsb":
        wkw["hot_prob"] = kn.hot_prob
    wl = make_workload(spec.workload, n_records, **wkw)
    ec = EngineConfig(
        protocol=spec.protocol,
        n_nodes=spec.n_nodes,
        coroutines=spec.coroutines,
        records_per_node=spec.records_per_node,
        active_coroutines=kn.coroutines_active,
        active_records_per_node=kn.records_active,
        rw=wl.rw,
        max_ops=wl.max_ops,
        hybrid=kn.hybrid,
        doorbell=spec.doorbell,
        merge_stages=spec.merge_stages,
        exec_ticks=kn.exec_ticks,
        history_cap=spec.history_cap,
        mvcc_slots=spec.mvcc_slots,
        seed=kn.seed,
        kernel_plane=spec.kernel_plane,
        shard=shard,
    )
    entry = registry.get_protocol(spec.protocol)
    # epoch-vs-tick dispatch lives in the registry entry's hooks, not in
    # name comparisons here: a new protocol brings its own runner if needed
    return entry.hooks.grid_run(
        entry, ec, cm, wl,
        ticks=spec.ticks, warmup=spec.warmup, ticks_active=kn.ticks_active,
    )


@functools.partial(jax.jit, static_argnums=0)
def _run_grid_jit(spec: GridSpec, knobs: RunKnobs) -> Dict:
    return jax.vmap(functools.partial(_run_one, spec))(knobs)


def compile_cache_size() -> int:
    """Number of distinct programs compiled for run_grid so far."""
    return _run_grid_jit._cache_size()


def sharded_compile_cache_size() -> int:
    """Programs compiled by the config-axis (1-D ``grid`` mesh) runners."""
    return sum(fn._cache_size() for (_, _, ns), fn in _GRID_RUNNERS.items() if ns is None)


def grid2d_compile_count() -> int:
    """Programs compiled by the 2-D ``config × node`` runners so far."""
    return sum(fn._cache_size() for (_, _, ns), fn in _GRID_RUNNERS.items() if ns is not None)


def _warn_legacy(name: str) -> None:
    warnings.warn(
        f"repro.core.sweep.{name} is deprecated: use repro.api "
        "(ExperimentSpec -> plan -> execute; see DESIGN.md §8) — this shim "
        "delegates to it",
        DeprecationWarning,
        stacklevel=3,
    )


# ---------------------------------------------------------------------------
# Bucketing planner: static shape axes -> (padded spec, traced active knobs)
# ---------------------------------------------------------------------------


class BucketPlan(NamedTuple):
    """One shape bucket: configs that share a padded (coroutines,
    records_per_node, ticks) shape and therefore one XLA compilation.

    ``coroutines`` / ``records_per_node`` / ``ticks`` are the PADDED shapes
    baked into the bucket's GridSpec; the matching ``*_active`` field
    carries each config's true extent (None when every config already
    matches the padded shape — that axis then stays off the padding
    machinery).  Padded coroutine slots / record rows are physically inert;
    padded TICKS freeze the scan carry (early-exit masks), so in all three
    cases counters are bitwise-equal to the unpadded run.
    """

    indices: Tuple[int, ...]  # positions in the caller's config list
    coroutines: int
    records_per_node: int
    knob_configs: Tuple[Dict, ...]  # static axes stripped
    coroutines_active: Optional[Tuple[int, ...]]
    records_active: Optional[Tuple[int, ...]]
    ticks: Optional[int] = None  # None = every config uses the grid default
    ticks_active: Optional[Tuple[int, ...]] = None


def _pow2_ceil(v: int) -> int:
    return 1 << (int(v) - 1).bit_length()


def plan_buckets(
    configs: Sequence[Dict],
    *,
    coroutines: int,
    records_per_node: int,
    ticks: Optional[int] = None,
) -> List[BucketPlan]:
    """Group configs into shape buckets (one compile each).

    Each config may set the static axes in :data:`STATIC_AXES`; omitted
    axes take the grid-level default.  Bucket key = power-of-two ceiling of
    each axis (so nearby shapes share a program); bucket shape = max actual
    value inside the bucket (no padding beyond what the bucket needs).
    """
    groups: Dict[Tuple[int, int, int], List[Tuple[int, int, int, int, Dict]]] = {}
    for i, cfg in enumerate(configs):
        cfg = dict(cfg)
        c = int(cfg.pop("coroutines", coroutines))
        r = int(cfg.pop("records_per_node", records_per_node))
        has_t = "ticks" in cfg
        t = cfg.pop("ticks", ticks)
        t = 0 if t is None else int(t)  # 0 = axis unset (grid default applies)
        if c < 1 or r < 1:
            raise ValueError(f"config {i}: coroutines/records_per_node must be >= 1, got {c}/{r}")
        if has_t and t < 1:
            raise ValueError(f"config {i}: ticks must be >= 1, got {t}")
        groups.setdefault((_pow2_ceil(c), _pow2_ceil(r), _pow2_ceil(t) if t else 0), []).append(
            (i, c, r, t, cfg)
        )
    buckets = []
    for key in sorted(groups):
        rows = groups[key]
        pad_c = max(c for _, c, _, _, _ in rows)
        pad_r = max(r for _, _, r, _, _ in rows)
        pad_t = max(t for _, _, _, t, _ in rows)
        buckets.append(
            BucketPlan(
                indices=tuple(i for i, _, _, _, _ in rows),
                coroutines=pad_c,
                records_per_node=pad_r,
                knob_configs=tuple(cfg for _, _, _, _, cfg in rows),
                coroutines_active=(
                    None if all(c == pad_c for _, c, _, _, _ in rows)
                    else tuple(c for _, c, _, _, _ in rows)
                ),
                records_active=(
                    None if all(r == pad_r for _, _, r, _, _ in rows)
                    else tuple(r for _, _, r, _, _ in rows)
                ),
                ticks=pad_t or None,
                ticks_active=(
                    None if all(t == pad_t for _, _, _, t, _ in rows)
                    else tuple(t for _, _, _, t, _ in rows)
                ),
            )
        )
    return buckets


# (GridSpec, device-key, node_shards or None) -> jitted mesh grid runner
_GRID_RUNNERS: Dict[Tuple[GridSpec, Tuple[str, ...], Optional[int]], Any] = {}


def _grid_runner(spec: GridSpec, devices: Sequence, node_shards: Optional[int]):
    """One jitted ``shard_map`` of the vmapped grid over a device mesh.

    ``node_shards=None``: a 1-D ``grid`` mesh, each device running its
    slice of the configs dense.  Otherwise a 2-D ``config × node`` mesh on
    which each config's simulation also runs node-sharded.  The grid enters
    the mesh through ``shard_map`` rather than jit's automatic
    partitioning, which cannot partition the Pallas kernels.
    """
    key = (spec, tuple(str(d) for d in devices), node_shards)
    fn = _GRID_RUNNERS.get(key)
    if fn is not None:
        return fn
    from repro.core import planes

    if node_shards is None:
        mesh, shard = Mesh(np.asarray(list(devices)), ("grid",)), None
    else:
        n_cfg = len(devices) // node_shards
        mesh = Mesh(np.asarray(list(devices)).reshape(n_cfg, node_shards), ("grid", "node"))
        shard = planes.NodeShard(axis="node", n_shards=node_shards)
    body = jax.vmap(functools.partial(_run_one, spec, shard=shard))
    runner = jax.jit(
        planes.shard_map(body, mesh=mesh, in_specs=(PartitionSpec("grid"),),
                         out_specs=PartitionSpec("grid"))
    )
    _GRID_RUNNERS[key] = runner
    return runner


def _run_sharded(
    spec: GridSpec, knobs: RunKnobs, devices, node_shards: Optional[int] = None
) -> Dict:
    """Dispatch one bucket's grid with the config axis sharded over devices.

    Pads the grid to a multiple of the config-axis size by replicating the
    last config; the outputs stay on the devices, pad rows included, and
    the caller keeps the first rows, one per config.  With ``node_shards``
    the mesh is 2-D ``config × node``: each config's SIMULATION additionally runs node-sharded over the
    ``node`` axis (every plane exchange inside the vmapped engine batches
    over the local configs), the same engine program
    :func:`~repro.core.engine.run_sharded` runs on a 1-D node mesh.
    """
    if node_shards is not None:
        if not registry.get_protocol(spec.protocol).caps.batch_node_shardable:
            # e.g. calvin: the wave executor iterates a per-config traced wave
            # count — configs cannot batch around its node collectives
            raise ValueError(
                f"protocol {spec.protocol!r} cannot run on a 2-D config × node mesh: "
                "its registry entry sets Caps(batch_node_shardable=False); shard the "
                "config axis only (node_shards=None)"
            )
        if spec.n_nodes % node_shards:
            raise ValueError(
                f"node_shards={node_shards} must divide n_nodes={spec.n_nodes}"
            )
    n_cfg = len(devices) // (node_shards or 1)
    size = int(np.asarray(knobs.seed).shape[0])
    pad = (-size) % n_cfg
    if pad:
        knobs = jax.tree_util.tree_map(
            lambda x: jnp.concatenate([x, jnp.repeat(x[-1:], pad, axis=0)], axis=0), knobs
        )
    return _grid_runner(spec, devices, node_shards)(knobs)


def _legacy_grid(
    protocol: str,
    workload: str,
    configs: Iterable[Dict],
    *,
    devices: Optional[Sequence] = None,
    node_shards: Optional[int] = None,
    **kw,
) -> List[Dict]:
    """Map the historical run_grid signature onto ``repro.api.plan/execute``.

    Layout resolution reproduces the old in-module dispatch exactly:
    ``node_shards>1`` -> 2-D ``config x node`` mesh; ``len(devices)>1`` ->
    config-axis sharding; one explicit device -> dense with placement;
    otherwise dense.
    """
    from repro import api

    devices = list(devices) if devices is not None else None
    node_shards = node_shards if node_shards and node_shards > 1 else None
    if node_shards is not None:
        # historical contract: devices must be passed explicitly (the planner
        # would otherwise auto-resolve to all of jax.devices())
        n_dev = len(devices) if devices is not None else 1
        if n_dev % node_shards:
            raise ValueError(
                f"node_shards={node_shards} must divide the device count ({n_dev})"
            )
        layout = api.CONFIG_NODE
    elif devices is not None and len(devices) > 1:
        layout = api.CONFIG
    else:
        layout = api.DENSE
    spec = api.ExperimentSpec(
        protocol=protocol,
        workload=workload,
        configs=tuple(dict(c) for c in configs),
        devices=tuple(devices) if devices is not None else None,
        node_shards=node_shards,
        layout=layout,
        **kw,
    )
    return api.execute(api.plan(spec)).rows


def run_grid(
    protocol: str,
    workload: str,
    configs: Iterable[Dict],
    *,
    devices: Optional[Sequence] = None,
    node_shards: Optional[int] = None,
    **kw,
) -> List[Dict]:
    """DEPRECATED shim: use :mod:`repro.api` (``plan``/``execute``).

    Delegates to the planner with the historical layout rules, so counters
    are bitwise-identical to the old in-module dispatch (pinned by
    tests/test_api.py) and the row schema is unchanged.  Emits one
    :class:`DeprecationWarning`.
    """
    _warn_legacy("run_grid")
    return _legacy_grid(
        protocol, workload, configs, devices=devices, node_shards=node_shards, **kw
    )


def run_grid_sharded(
    protocol: str,
    workload: str,
    configs: Iterable[Dict],
    *,
    devices: Optional[Sequence] = None,
    **kw,
) -> List[Dict]:
    """DEPRECATED shim: use :mod:`repro.api` with ``devices="auto"``.

    ``devices`` defaults to all of :func:`jax.devices`; on a single device
    this degenerates to the dense program (same compiled entry point, zero
    overhead) — the planner keeps that contract.
    """
    _warn_legacy("run_grid_sharded")
    devices = list(devices) if devices is not None else list(jax.devices())
    return _legacy_grid(protocol, workload, configs, devices=devices, **kw)


# ---------------------------------------------------------------------------
# Node-sharded single-config runs (DESIGN.md §7): the SIMULATION axis on the
# device mesh — paper-scale single configs instead of many small configs.
# ---------------------------------------------------------------------------

# (GridSpec, device-key) -> jitted runner.  Knobs stay traced, so a whole
# family of configs (hybrids, seeds, exec_ticks, ...) shares ONE compiled
# SPMD program per mesh shape — the perf gate asserts this.
_NODE_RUNNERS: Dict[Tuple[GridSpec, Tuple[str, ...]], Any] = {}


def _node_runner(spec: GridSpec, devices: Sequence):
    key = (spec, tuple(str(d) for d in devices))
    fn = _NODE_RUNNERS.get(key)
    if fn is not None:
        return fn
    devs = list(devices)

    entry = registry.get_protocol(spec.protocol)

    @jax.jit
    def runner(kn: RunKnobs) -> Dict:
        cm = CostModel.tcp() if spec.tcp else CostModel(qp_pressure=kn.qp_pressure)
        wkw: Dict[str, Any] = {"exec_ticks": kn.exec_ticks}
        if spec.workload == "ycsb":
            wkw["hot_prob"] = kn.hot_prob
        wl = make_workload(spec.workload, spec.n_nodes * spec.records_per_node, **wkw)
        ec = EngineConfig(
            protocol=spec.protocol,
            n_nodes=spec.n_nodes,
            coroutines=spec.coroutines,
            records_per_node=spec.records_per_node,
            rw=wl.rw,
            max_ops=wl.max_ops,
            hybrid=kn.hybrid,
            doorbell=spec.doorbell,
            merge_stages=spec.merge_stages,
            exec_ticks=kn.exec_ticks,
            history_cap=spec.history_cap,
            mvcc_slots=spec.mvcc_slots,
            seed=kn.seed,
            kernel_plane=spec.kernel_plane,
        )
        return entry.hooks.node_run(
            entry, ec, cm, wl, ticks=spec.ticks, warmup=spec.warmup, devices=devs
        )

    _NODE_RUNNERS[key] = runner
    return runner


def node_sharded_compile_count() -> int:
    """Programs compiled by the node-sharded runners so far: one per
    (GridSpec, mesh) pair when the knob tracing holds, regardless of how
    many configs ran."""
    return sum(fn._cache_size() for fn in _NODE_RUNNERS.values())


def run_cell_sharded(
    protocol: str,
    workload: str,
    config: Optional[Dict] = None,
    *,
    node_shards: Optional[int] = None,
    devices: Optional[Sequence] = None,
    **kw,
) -> Dict:
    """DEPRECATED shim: use :mod:`repro.api` with ``layout="node"``.

    One engine run with the simulated ``n_nodes`` axis SPMD on the mesh.
    ``devices`` picks the mesh explicitly; ``node_shards`` takes the first N
    of ``jax.devices()`` (their count must divide ``n_nodes``).  The jitted
    program is cached per (GridSpec, mesh) with every knob traced, so
    sweeping hybrids or seeds at a fixed mesh costs one compilation —
    ``api.ExecutionPlan.expected_compiles`` accounts for it.
    """
    _warn_legacy("run_cell_sharded")
    from repro import api

    spec = api.ExperimentSpec(
        protocol=protocol,
        workload=workload,
        configs=(dict(config or {}),),
        devices=tuple(devices) if devices is not None else None,
        node_shards=node_shards,
        layout=api.NODE,
        **kw,
    )
    return api.execute(api.plan(spec)).rows[0]
