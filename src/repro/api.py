"""One front door for RCC experiments: ``plan(spec)`` → ``execute(plan)``.

The repo's engine grew four dispatch layers (dense vmapped grids, a
config-sharded device mesh, node-sharded single configs, and the 2-D
``config × node`` composition) plus a shape-bucketing planner — and every
benchmark hand-rolled the choice between them.  This module owns that
choice declaratively:

    from repro.api import ExperimentSpec, plan, execute

    spec = ExperimentSpec(
        protocol="sundial", workload="smallbank",
        configs=[{"hybrid": c} for c in all_hybrid_codes()],
        ticks=96, coroutines=12, records_per_node=4096, warmup=8,
    )
    pl = plan(spec)        # buckets, mesh layout, compile accounting
    print(pl.summary())    # human-readable: what compiles, on which mesh
    rows = execute(pl).rows

The planner owns what callers used to hand-roll: power-of-two shape
bucketing (``sweep.plan_buckets``), config-axis vs node-axis vs 2-D
``config × node`` mesh selection, remainder padding, per-protocol
capability constraints (e.g. CALVIN grids stay config-axis only —
``Caps.batch_node_shardable=False`` from the protocol registry), and the
expected-compile accounting that scripts/perf_gate.py asserts against.
Protocols come from :mod:`repro.core.registry` — a new protocol is one
module plus one ``register_protocol`` call and every surface above picks
it up by name.

Devices: ``ExperimentSpec.devices`` is ``None`` (single-device dense run,
no placement), ``"auto"`` (all of ``jax.devices()`` — real accelerators or
``--xla_force_host_platform_device_count`` fake hosts), or an explicit
device sequence.  Layout auto-selection can be overridden with
``ExperimentSpec.layout``.

The legacy entry points (``sweep.run_grid`` / ``run_grid_sharded`` /
``run_cell_sharded``) are deprecation shims over this module, so their
counters are bitwise-identical to the ``plan/execute`` path by
construction — and pinned by tests/test_api.py anyway.
"""
from __future__ import annotations

import functools
import time
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Tuple, Union

import jax
import numpy as np

from repro.core import registry
from repro.core import sweep as _sweep
from repro.kernels import ops as _kernel_ops
from repro.core.costmodel import N_HYBRID_STAGES, RPC
from repro.core.sweep import (  # noqa: F401  (public planner helpers, re-exported)
    KNOB_KEYS,
    STATIC_AXES,
    BucketPlan,
    GridSpec,
    all_hybrid_codes,
    grid_product,
    make_knobs,
    normalize_hybrid,
    plan_buckets,
)

AUTO = "auto"

# mesh layouts the planner can select (ExperimentSpec.layout overrides)
DENSE = "dense"  # one device, vmap over the config axis
CONFIG = "config"  # config axis sharded over a 1-D `grid` mesh
NODE = "node"  # ONE config, simulated n_nodes axis SPMD over a `node` mesh
CONFIG_NODE = "config_node"  # 2-D `config × node` mesh (DESIGN.md §7)
LAYOUTS = (DENSE, CONFIG, NODE, CONFIG_NODE)


@dataclass(frozen=True)
class ExperimentSpec:
    """Declarative description of one experiment sweep.

    ``configs`` is a sequence of per-run dicts mixing traced knobs
    (``hybrid``, ``seed``, ``exec_ticks``, ``hot_prob``, ``qp_pressure``)
    with static shape axes (:data:`STATIC_AXES`: ``coroutines``,
    ``records_per_node``, ``ticks``) — the planner buckets the static axes,
    the executor vmaps the knobs.  Everything else is grid-level defaults.
    """

    protocol: str
    workload: str
    configs: Tuple[Dict, ...] = ({},)
    n_nodes: int = 4
    coroutines: int = 60
    records_per_node: int = 65536
    ticks: int = 400
    warmup: int = 80
    history_cap: int = 0
    mvcc_slots: int = 4
    doorbell: bool = True
    tcp: bool = False
    merge_stages: bool = False
    # kernel plane for the fused hot paths (DESIGN.md §9): "auto" resolves
    # per backend at plan time (Pallas on TPU/GPU, jnp on CPU); "jnp",
    # "pallas", "pallas_interpret" pin it.  Counters are bitwise-equal
    # across planes (the kernel-parity CI contract).
    kernel_plane: str = "auto"
    # topology: None = single-device dense; "auto" = all jax.devices();
    # or an explicit device sequence.  node_shards sizes the `node` mesh axis.
    devices: Union[None, str, Tuple[Any, ...]] = None
    node_shards: Optional[int] = None
    layout: Optional[str] = None  # override planner auto-selection

    def __post_init__(self):
        object.__setattr__(self, "configs", tuple(dict(c) for c in self.configs))
        if isinstance(self.devices, (list, tuple)):
            object.__setattr__(self, "devices", tuple(self.devices))


@dataclass(frozen=True)
class PlannedBucket:
    """One shape bucket of the plan: a padded GridSpec (= one XLA program)
    plus the per-config active extents that make the padding inert."""

    index: int
    grid_spec: GridSpec
    bucket: BucketPlan

    def describe(self) -> str:
        b, g = self.bucket, self.grid_spec
        axes = []
        for name, padded, active in (
            ("coroutines", g.coroutines, b.coroutines_active),
            ("records_per_node", g.records_per_node, b.records_active),
            ("ticks", g.ticks, b.ticks_active),
        ):
            if active is None:
                axes.append(f"{name}={padded}")
            else:
                axes.append(f"{name}={padded} (active {min(active)}..{max(active)})")
        return (
            f"bucket {self.index}: {len(b.indices)} config(s), "
            + ", ".join(axes)
            + " -> 1 compile"
        )


@dataclass(frozen=True)
class ExecutionPlan:
    """What :func:`execute` will run: buckets, mesh layout, compile budget."""

    spec: ExperimentSpec
    layout: str
    devices: Optional[Tuple[Any, ...]]  # None = default single device
    node_shards: Optional[int]
    buckets: Tuple[PlannedBucket, ...]
    expected_compiles: int  # cold-cache upper bound; cache hits only lower it
    cache: str = "grid"  # which jit cache the programs land in (compile_stats key)
    kernel_plane: str = "jnp"  # resolved hot-path backend (spec "auto" -> concrete)

    @property
    def n_configs(self) -> int:
        return len(self.spec.configs)

    @property
    def n_devices(self) -> int:
        return len(self.devices) if self.devices is not None else 1

    def mesh_shape(self) -> str:
        if self.layout == DENSE:
            return "1 device (dense vmap)"
        if self.layout == CONFIG:
            return f"1-D grid mesh, {self.n_devices} device(s) on the config axis"
        if self.layout == NODE:
            return f"1-D node mesh, {self.n_devices} device(s) on the n_nodes axis"
        n_cfg = self.n_devices // (self.node_shards or 1)
        return (
            f"2-D config × node mesh, {self.n_devices} device(s) as "
            f"{n_cfg} config-shard(s) × {self.node_shards} node-shard(s)"
        )

    def summary(self) -> str:
        """Human-readable plan: which bucket compiles what, on which mesh."""
        s = self.spec
        lines = [
            f"ExperimentSpec: protocol={s.protocol} workload={s.workload} "
            f"configs={self.n_configs}",
            f"layout: {self.layout} — {self.mesh_shape()}",
        ]
        lines += [pb.describe() for pb in self.buckets]
        lines.append(
            f"kernel plane: {self.kernel_plane} — "
            f"{_kernel_ops.describe_plane(self.kernel_plane)}"
        )
        lines.append(
            f"expected compiles (cold {self.cache!r} cache): {self.expected_compiles}"
        )
        return "\n".join(lines)


@dataclass(frozen=True)
class Results:
    """Executed plan: one metrics dict per config, in ``spec.configs`` order."""

    rows: List[Dict] = field(default_factory=list)
    plan: Optional[ExecutionPlan] = None
    wall_s: float = 0.0

    @property
    def row(self) -> Dict:
        if len(self.rows) != 1:
            raise ValueError(f"Results.row: plan produced {len(self.rows)} rows, not 1")
        return self.rows[0]


def _resolve_devices(spec: ExperimentSpec, *, need: bool) -> Optional[Tuple[Any, ...]]:
    if spec.devices is None:
        return tuple(jax.devices()) if need else None
    if isinstance(spec.devices, str):
        if spec.devices != AUTO:
            raise ValueError(
                f"ExperimentSpec.devices={spec.devices!r}: pass None, 'auto', "
                "or an explicit device sequence"
            )
        return tuple(jax.devices())
    return tuple(spec.devices)


@functools.partial(jax.profiler.annotate_function, name="repro.plan")
def plan(spec: ExperimentSpec) -> ExecutionPlan:
    """Resolve an :class:`ExperimentSpec` into an executable plan.

    Raises at plan time — before anything compiles — on unknown protocols
    (registry lookup), capability violations (e.g. a 2-D ``config × node``
    mesh for a protocol registered with ``Caps(batch_node_shardable=False)``),
    and topology mismatches (device counts that don't divide).
    """
    entry = registry.get_protocol(spec.protocol)
    if not spec.configs:
        raise ValueError("ExperimentSpec.configs is empty: pass at least one knob dict")
    if spec.layout is not None and spec.layout not in LAYOUTS:
        raise ValueError(f"ExperimentSpec.layout={spec.layout!r}: valid layouts {LAYOUTS}")
    # resolve the kernel plane before anything compiles so the plan reports
    # (and the whole run uses) one concrete backend
    kernel_plane = _kernel_ops.resolve_plane(spec.kernel_plane)

    # node_shards <= 0 means "no node sharding" (CLI flags default to 0)
    node_shards = spec.node_shards if spec.node_shards and spec.node_shards >= 1 else None
    layout = spec.layout
    if layout is None:
        if node_shards is not None and len(spec.configs) == 1:
            layout = NODE
        elif node_shards is not None and node_shards >= 2:
            layout = CONFIG_NODE
        else:
            # node_shards in (None, 1) with a multi-config grid degenerates
            # to no node sharding: pick dense/config from the device count
            node_shards = None
            devices = _resolve_devices(spec, need=False)
            layout = CONFIG if devices is not None and len(devices) > 1 else DENSE

    # capability gates come first: a protocol that cannot run a layout should
    # say so before any device-count arithmetic confuses the message
    if layout in (NODE, CONFIG_NODE) and not entry.caps.node_shardable:
        raise ValueError(
            f"protocol {spec.protocol!r} is not node-shardable: its registry entry "
            "sets Caps(node_shardable=False); run it dense or config-sharded, or "
            "re-register via repro.core.registry.register_protocol(...)"
        )
    if layout == CONFIG_NODE and not entry.caps.batch_node_shardable:
        raise ValueError(
            f"protocol {spec.protocol!r} cannot run on a 2-D config × node mesh: "
            "its registry entry sets Caps(batch_node_shardable=False) (configs "
            "cannot batch around its node collectives).  Shard the config axis "
            "only (layout='config'), or node-shard a single config "
            "(layout='node'), or re-register the protocol with different "
            "capabilities via repro.core.registry.register_protocol(...)"
        )

    if layout == NODE:
        return _plan_node(spec, node_shards, kernel_plane)

    devices = _resolve_devices(spec, need=layout in (CONFIG, CONFIG_NODE))
    if layout == DENSE and devices is not None and len(devices) > 1:
        raise ValueError(
            f"layout='dense' places at most one device, got {len(devices)}; "
            "use layout='config' (or devices='auto') to shard the config axis"
        )
    if layout == CONFIG and len(devices) < 2 and spec.layout == CONFIG:
        # explicit request for a config mesh on one device is fine — it just
        # degenerates to the dense program (run_grid_sharded's contract)
        layout = DENSE
    if layout == CONFIG_NODE:
        if not node_shards or node_shards < 2:
            raise ValueError(
                f"layout='config_node' needs node_shards >= 2, got {node_shards}"
            )
        if len(devices) % node_shards:
            raise ValueError(
                f"node_shards={node_shards} must divide the device count ({len(devices)})"
            )
        if spec.n_nodes % node_shards:
            raise ValueError(
                f"node_shards={node_shards} must divide n_nodes={spec.n_nodes}"
            )
    else:
        node_shards = None

    buckets = plan_buckets(
        list(spec.configs),
        coroutines=spec.coroutines,
        records_per_node=spec.records_per_node,
        ticks=spec.ticks,
    )
    planned = tuple(
        PlannedBucket(
            index=i,
            grid_spec=GridSpec(
                protocol=spec.protocol,
                workload=spec.workload,
                n_nodes=spec.n_nodes,
                coroutines=b.coroutines,
                records_per_node=b.records_per_node,
                ticks=b.ticks if b.ticks is not None else spec.ticks,
                warmup=spec.warmup,
                history_cap=spec.history_cap,
                mvcc_slots=spec.mvcc_slots,
                doorbell=spec.doorbell,
                tcp=spec.tcp,
                merge_stages=spec.merge_stages,
                kernel_plane=kernel_plane,
            ),
            bucket=b,
        )
        for i, b in enumerate(buckets)
    )
    cache = {DENSE: "grid", CONFIG: "grid_sharded", CONFIG_NODE: "grid2d"}[layout]
    return ExecutionPlan(
        spec=spec,
        layout=layout,
        devices=devices,
        node_shards=node_shards,
        buckets=planned,
        expected_compiles=len(planned),
        cache=cache,
        kernel_plane=kernel_plane,
    )


def _plan_node(
    spec: ExperimentSpec, node_shards: Optional[int], kernel_plane: str
) -> ExecutionPlan:
    """The single-config node-sharded layout (legacy ``run_cell_sharded``)."""
    if len(spec.configs) != 1:
        raise ValueError(
            f"layout='node' runs ONE config with the n_nodes axis on the mesh, "
            f"got {len(spec.configs)} configs; use layout='config_node' to also "
            "shard the config axis"
        )
    bad_axes = sorted(set(spec.configs[0]) & set(STATIC_AXES))
    if bad_axes:
        raise ValueError(
            f"layout='node' does not bucket static axes; move {bad_axes} to the "
            "ExperimentSpec grid defaults or use a dense/config layout"
        )
    if spec.devices is None or spec.devices == AUTO:
        devices = tuple(jax.devices())
        if node_shards is not None:
            if node_shards > len(devices):
                raise ValueError(
                    f"node_shards={node_shards} > visible devices ({len(devices)}); "
                    "set XLA_FLAGS=--xla_force_host_platform_device_count or --devices"
                )
            devices = devices[:node_shards]
    else:
        devices = tuple(spec.devices)
        if node_shards is not None and node_shards != len(devices):
            raise ValueError(
                f"node_shards={node_shards} conflicts with len(devices)={len(devices)}; "
                "pass one or the other"
            )
    if spec.n_nodes % len(devices):
        raise ValueError(
            f"node mesh: {len(devices)} device(s) must divide n_nodes={spec.n_nodes} "
            "(shards own whole simulated nodes)"
        )
    gs = GridSpec(
        protocol=spec.protocol,
        workload=spec.workload,
        n_nodes=spec.n_nodes,
        coroutines=spec.coroutines,
        records_per_node=spec.records_per_node,
        ticks=spec.ticks,
        warmup=spec.warmup,
        history_cap=spec.history_cap,
        mvcc_slots=spec.mvcc_slots,
        doorbell=spec.doorbell,
        tcp=spec.tcp,
        merge_stages=spec.merge_stages,
        kernel_plane=kernel_plane,
    )
    bucket = BucketPlan(
        indices=(0,),
        coroutines=spec.coroutines,
        records_per_node=spec.records_per_node,
        knob_configs=(dict(spec.configs[0]),),
        coroutines_active=None,
        records_active=None,
    )
    return ExecutionPlan(
        spec=spec,
        layout=NODE,
        devices=devices,
        node_shards=len(devices),
        buckets=(PlannedBucket(index=0, grid_spec=gs, bucket=bucket),),
        expected_compiles=1,
        cache="node",
        kernel_plane=kernel_plane,
    )


def execute(pl: ExecutionPlan) -> Results:
    """Run an :class:`ExecutionPlan`; returns :class:`Results`.

    Row schema matches the historical ``sweep.run_grid`` output (metrics from
    ``engine.summarize`` plus ``wall_s`` / ``grid_size`` / ``n_buckets`` /
    ``bucket`` / ``n_devices`` / ``n_node_shards`` / ``protocol`` /
    ``workload`` / ``hybrid`` / resolved static axes), so existing consumers
    and golden tests see identical dicts.  ``wall_s`` is the host time of
    the whole call, the interval of its ``repro.execute`` profiler span.

    Profiler spans inside ``repro.execute``: ``.knobs`` (the knob arrays),
    ``.dispatch`` (the jitted call, up to its return), ``.fetch`` (copying
    the outputs to the host, which waits for the device) and ``.rows``.
    """
    if pl.layout == NODE:
        row = _execute_node(pl)
        return Results(rows=[row], plan=pl, wall_s=row["wall_s"])
    return _execute_grid(pl)


@functools.partial(jax.profiler.annotate_function, name="repro.execute")
def _execute_grid(pl: ExecutionPlan) -> Results:
    t0 = time.perf_counter()
    spec = pl.spec
    import jax.numpy as jnp

    rows: List[Optional[Dict]] = [None] * len(spec.configs)
    for pb in pl.buckets:
        b, gs = pb.bucket, pb.grid_spec
        with jax.profiler.TraceAnnotation("repro.execute.knobs"):
            knobs = make_knobs(spec.workload, b.knob_configs)
            if b.coroutines_active is not None:
                knobs = knobs._replace(
                    coroutines_active=jnp.asarray(np.array(b.coroutines_active, np.int32))
                )
            if b.records_active is not None:
                knobs = knobs._replace(
                    records_active=jnp.asarray(np.array(b.records_active, np.int32))
                )
            if b.ticks_active is not None:
                knobs = knobs._replace(
                    ticks_active=jnp.asarray(np.array(b.ticks_active, np.int32))
                )
            if pl.layout == DENSE and pl.devices is not None:
                # honor an explicit single-device placement
                knobs = jax.device_put(knobs, pl.devices[0])
        with jax.profiler.TraceAnnotation("repro.execute.dispatch"):
            if pl.layout in (CONFIG, CONFIG_NODE):
                out = _sweep._run_sharded(gs, knobs, list(pl.devices), pl.node_shards)
            else:
                out = _sweep._run_grid_jit(gs, knobs)
        with jax.profiler.TraceAnnotation("repro.execute.fetch"):
            # a sharded grid is padded to the mesh: its pad rows go here
            out = {k: np.asarray(v)[: len(b.indices)] for k, v in out.items()}
        with jax.profiler.TraceAnnotation("repro.execute.rows"):
            hy = np.asarray(knobs.hybrid)
            for g, idx in enumerate(b.indices):
                m = {k: v[g].tolist() for k, v in out.items()}
                m["grid_size"] = len(spec.configs)
                m["n_buckets"] = len(pl.buckets)
                m["bucket"] = pb.index
                m["n_devices"] = pl.n_devices
                m["n_node_shards"] = pl.node_shards or 1
                m["protocol"], m["workload"] = spec.protocol, spec.workload
                m["hybrid"] = "".join(str(int(bit)) for bit in hy[g])
                m["coroutines"] = (
                    b.coroutines if b.coroutines_active is None else b.coroutines_active[g]
                )
                m["records_per_node"] = (
                    b.records_per_node if b.records_active is None else b.records_active[g]
                )
                m["ticks"] = gs.ticks if b.ticks_active is None else b.ticks_active[g]
                rows[idx] = m
    wall = round(time.perf_counter() - t0, 4)
    for m in rows:
        m["wall_s"] = wall
    return Results(rows=rows, plan=pl, wall_s=wall)  # type: ignore[arg-type]


@functools.partial(jax.profiler.annotate_function, name="repro.execute")
def _execute_node(pl: ExecutionPlan) -> Dict:
    t0 = time.perf_counter()
    spec = pl.spec
    pb = pl.buckets[0]
    with jax.profiler.TraceAnnotation("repro.execute.knobs"):
        knobs = make_knobs(spec.workload, pb.bucket.knob_configs)
        knobs = jax.tree_util.tree_map(lambda x: x[0], knobs)
    with jax.profiler.TraceAnnotation("repro.execute.dispatch"):
        out = _sweep._node_runner(pb.grid_spec, list(pl.devices))(knobs)
    with jax.profiler.TraceAnnotation("repro.execute.fetch"):
        out = {k: np.asarray(v) for k, v in out.items()}
    with jax.profiler.TraceAnnotation("repro.execute.rows"):
        m = {k: v.tolist() for k, v in out.items()}
        m["protocol"], m["workload"] = spec.protocol, spec.workload
        m["n_node_shards"] = len(pl.devices)
        hy = np.asarray(
            normalize_hybrid(pb.bucket.knob_configs[0].get("hybrid", (RPC,) * N_HYBRID_STAGES))
        )
        m["hybrid"] = "".join(str(int(b)) for b in hy)
    m["wall_s"] = round(time.perf_counter() - t0, 4)
    return m


def run(spec: ExperimentSpec) -> Results:
    """``execute(plan(spec))`` — the one-call front door."""
    return execute(plan(spec))


def compile_stats() -> Dict[str, int]:
    """Programs compiled so far per jit cache.  Keys match
    :attr:`ExecutionPlan.cache`; perf_gate asserts the measured deltas
    against ``ExecutionPlan.expected_compiles``."""
    return {
        "grid": _sweep.compile_cache_size(),
        "grid_sharded": _sweep.sharded_compile_cache_size(),
        "grid2d": _sweep.grid2d_compile_count(),
        "node": _sweep.node_sharded_compile_count(),
    }


__all__ = [
    "AUTO",
    "DENSE",
    "CONFIG",
    "NODE",
    "CONFIG_NODE",
    "ExperimentSpec",
    "ExecutionPlan",
    "PlannedBucket",
    "Results",
    "plan",
    "execute",
    "run",
    "compile_stats",
    "all_hybrid_codes",
    "grid_product",
    "normalize_hybrid",
]
