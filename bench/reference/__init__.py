"""The plain reference the benchmark's ``correct`` compares against.

A dense, single-device simulator of the configurations the benchmark runs
(NOWAIT and MVCC on the slot engine; SmallBank and YCSB),
kept here so that no change to the program moves it.  It imports nothing of
the program and takes nothing the program made: each row is recomputed
from the deployment file and the call's knobs (hybrid coding, seed).

:func:`rows` runs a batch of configurations as one vmapped program and
returns one metrics dict per configuration, in the front door's row
schema.  ``fdt`` is the dtype of the latency accumulators: float32 is the
stated precision, bfloat16 the precision control.
"""
from __future__ import annotations

import functools
from typing import Dict, List, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from bench.reference import engine, protocols, workloads


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


def _one(shape: Shape, hybrid, seed, exec_ticks, hot_prob) -> Dict:
    cfg = dict(shape.deployment)
    n_records = shape.n_nodes * shape.records_per_node
    if shape.workload == "smallbank":
        wl = workloads.smallbank(cfg, n_records, exec_ticks)
    else:
        wl = workloads.ycsb(cfg, n_records, exec_ticks, hot_prob)
    ec = engine.EngineConfig(
        protocol=shape.protocol, n_nodes=shape.n_nodes, coroutines=shape.coroutines,
        records_per_node=shape.records_per_node, rw=wl.rw, max_ops=wl.max_ops,
        hybrid=hybrid, exec_ticks=exec_ticks, seed=seed, mvcc_slots=shape.mvcc_slots,
        fdt=jnp.dtype(shape.fdt),
    )
    return engine.run(protocols.TICKS[shape.protocol], ec, engine.CostModel(), wl, shape.ticks,
                      shape.warmup)


@functools.lru_cache(maxsize=None)
def _program(shape: Shape):
    return jax.jit(jax.vmap(functools.partial(_one, shape)))


def rows(deployment: dict, call: dict, knobs: List[dict], fdt: str = "float32") -> List[Dict]:
    """Reference rows for ``knobs`` (each ``{"hybrid": code, "seed": s}``)
    under one deployment and one call shape (protocol, ticks, warm-up)."""
    shape = Shape(
        protocol=call["protocol"], workload=deployment["workload"],
        n_nodes=deployment["n_nodes"], coroutines=deployment["coroutines"],
        records_per_node=deployment["records_per_node"], ticks=call["ticks"],
        warmup=call["warmup"], mvcc_slots=deployment["mvcc_slots"],
        deployment=tuple(sorted(
            (k, v) for k, v in deployment.items() if isinstance(v, (int, float))
        )),
        fdt=fdt,
    )
    hybrid = np.array([[(k["hybrid"] >> i) & 1 for i in range(engine.N_HYBRID_STAGES)]
                       for k in knobs], np.int32)
    seed = np.array([k["seed"] for k in knobs], np.int32)
    exec_ticks = np.full(len(knobs), deployment["exec_ticks"], np.int32)
    hot_prob = np.full(len(knobs), deployment.get("hot_prob", 0.0), np.float32)
    out = _program(shape)(hybrid, seed, exec_ticks, hot_prob)
    out = {k: np.asarray(v) for k, v in out.items()}
    return [{k: v[i].tolist() for k, v in out.items()} for i in range(len(knobs))]
