"""The plain reference the benchmark's ``correct`` compares against.

A dense simulator of the configurations the benchmark runs, kept here so
that no change to the program moves it.  It imports nothing of the program
and takes nothing the program made: each row is recomputed from the
deployment file and the call's knobs (hybrid coding, seed).

Plug-ins, found by name.  A protocol is a module ``protocol_<name>.py`` of
this package whose ``PROTOCOL`` is an :class:`engine.Protocol`: its tick and
its store's layout (``init_store``; every array's leading axis is the
record).  A workload is a module ``workload_<name>.py`` whose
``make(cfg, n_records, exec_ticks, hot_prob)`` returns an
:class:`engine.Workload` (the transaction generator and the write set),
``cfg`` being the deployment file's numbers.  A protocol or a workload
enters the reference as one such new file, built from ``protocols.py``'s
stage machine and ``engine.py``'s store access; a name without a file
raises.

Batching and placement.  :func:`rows` runs the sampled configurations as
one vmapped, jitted program, all at once on one device.  For a deployment
that states ``node_shards`` it runs them ``NODE_BATCH`` at a time, and lays
the store's record axis out over the first ``node_shards`` devices: a
sharding constraint (a ``NamedSharding`` on a one-axis mesh) on the store at
every tick and on the per-record scratch of the lock arbitration, in plain
``jax.jit``, so that XLA partitions the same gathers and scatters and one
configuration's store need not fit one device.

``fdt`` is the dtype of the latency accumulators: float32 is the stated
precision, bfloat16 the precision control.
"""
from __future__ import annotations

import functools
import importlib
import pkgutil
import re
from typing import Callable, Dict, List, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from bench.reference import engine

PLUGIN_NAME = re.compile(r"^[A-Za-z0-9_]+$")
RECORDS_AXIS = "records"
# Configurations per program for a store laid over chips: at 8 nodes x 20M
# records two hold about 1.3 GB a chip of a TPU v5e's 16 GB.
NODE_BATCH = 2


def known(kind: str) -> List[str]:
    """The names of this package's ``<kind>_<name>.py`` plug-ins."""
    prefix = f"{kind}_"
    return sorted(m.name[len(prefix):] for m in pkgutil.iter_modules(__path__)
                  if m.name.startswith(prefix))


def plugin(kind: str, name: str):
    """The module ``<kind>_<name>`` of this package; ValueError if there is none."""
    module = f"{__name__}.{kind}_{name}"
    if PLUGIN_NAME.match(name):
        try:
            return importlib.import_module(module)
        except ModuleNotFoundError as e:
            if e.name != module:
                raise
    raise ValueError(
        f"the reference has no {kind} {name!r}: add bench/reference/{kind}_{name}.py "
        f"(it has {known(kind)})"
    )


def protocol(name: str) -> engine.Protocol:
    return plugin("protocol", name).PROTOCOL


def workload(name: str) -> Callable[..., engine.Workload]:
    return plugin("workload", name).make


class Shape(NamedTuple):
    """Everything static about one reference program."""

    protocol: str
    workload: str
    n_nodes: int
    coroutines: int
    records_per_node: int
    ticks: int
    warmup: int
    mvcc_slots: int
    deployment: tuple  # sorted (key, value) items of the workload's parameters
    fdt: str
    shards: int = 0  # devices the store's record axis is laid out over; 0: one device


def _one(shape: Shape, proto: engine.Protocol, make_workload, hybrid, seed, exec_ticks,
         hot_prob) -> Dict:
    cfg = dict(shape.deployment)
    n_records = shape.n_nodes * shape.records_per_node
    wl = make_workload(cfg, n_records, exec_ticks, hot_prob)
    ec = engine.EngineConfig(
        n_nodes=shape.n_nodes, coroutines=shape.coroutines,
        records_per_node=shape.records_per_node, rw=wl.rw, max_ops=wl.max_ops,
        hybrid=hybrid, exec_ticks=exec_ticks, seed=seed, mvcc_slots=shape.mvcc_slots,
        fdt=jnp.dtype(shape.fdt), **_placement(shape.shards),
    )
    return engine.run(proto, ec, engine.CostModel(), wl, shape.ticks, shape.warmup)


def _placement(shards: int) -> Dict:
    """``EngineConfig.place`` for a store over ``shards`` devices."""
    if not shards:
        return {}
    devices = jax.devices()
    if len(devices) < shards:
        raise ValueError(f"the reference lays the store over {shards} devices; JAX sees "
                         f"{len(devices)}")
    mesh = jax.sharding.Mesh(np.array(devices[:shards]), (RECORDS_AXIS,))
    by_record = jax.sharding.NamedSharding(mesh, jax.sharding.PartitionSpec(RECORDS_AXIS))

    return {"place": lambda a: jax.lax.with_sharding_constraint(a, by_record)}


@functools.lru_cache(maxsize=None)
def program(shape: Shape):
    """The jitted reference program of one shape, over a batch of configurations;
    its plug-ins are looked up here, so an unknown name raises before tracing."""
    one = functools.partial(_one, shape, protocol(shape.protocol), workload(shape.workload))
    return jax.jit(jax.vmap(one))


def shape_for(deployment: dict, call: dict, fdt: str = "float32") -> Shape:
    return Shape(
        protocol=call["protocol"], workload=deployment["workload"],
        n_nodes=deployment["n_nodes"], coroutines=deployment["coroutines"],
        records_per_node=deployment["records_per_node"], ticks=call["ticks"],
        warmup=call["warmup"], mvcc_slots=deployment["mvcc_slots"],
        deployment=tuple(sorted(
            (k, v) for k, v in deployment.items() if isinstance(v, (int, float))
        )),
        fdt=fdt,
        shards=deployment.get("node_shards", 0),
    )


def inputs(deployment: dict, knobs: List[dict]) -> tuple:
    """The program's arguments for ``knobs``: hybrid bits, seed, exec ticks, hot share."""
    hybrid = np.array([[(k["hybrid"] >> i) & 1 for i in range(engine.N_HYBRID_STAGES)]
                       for k in knobs], np.int32)
    seed = np.array([k["seed"] for k in knobs], np.int32)
    exec_ticks = np.full(len(knobs), deployment["exec_ticks"], np.int32)
    hot_prob = np.full(len(knobs), deployment.get("hot_prob", 0.0), np.float32)
    return hybrid, seed, exec_ticks, hot_prob


def rows(deployment: dict, call: dict, knobs: List[dict], fdt: str = "float32") -> List[Dict]:
    """Reference rows for ``knobs`` (each ``{"hybrid": code, "seed": s}``)
    under one deployment and one call shape (protocol, ticks, warm-up).  In
    batches (a deployment laid over chips), the last batch is filled up with
    copies of the last configuration, so that one program serves every batch."""
    fn = program(shape_for(deployment, call, fdt))
    batch = NODE_BATCH if deployment.get("node_shards") else len(knobs)
    padded = knobs + knobs[-1:] * (-len(knobs) % batch)
    got: List[Dict] = []
    for i in range(0, len(padded), batch):
        out = fn(*inputs(deployment, padded[i:i + batch]))
        out = {k: np.asarray(v) for k, v in out.items()}
        got += [{k: v[j].tolist() for k, v in out.items()} for j in range(batch)]
    return got[:len(knobs)]
