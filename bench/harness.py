"""One benchmark run of one cell: set-up, the measured window, the check.

Everything here is driven by data: :func:`load_cell` finds a cell in
``BENCHMARK.json`` and its deployment (``bench/configs/<config>.json``),
traffic mix (``bench/traffic/<traffic>.json``) and limits
(``bench/cells/<cell>.json``) by name; per-layer metrics are read by
``bench/metrics/<metric>.py``, each a ``read(run)`` that returns a number,
or None where it finds nothing to read.

Each call of the window is what a user sends: ``repro.api.plan`` of an
``ExperimentSpec`` with ``kernel_plane="auto"``, then ``repro.api.execute``.
A deployment that states ``node_shards`` lays the simulated nodes over that
many chips, the cell's ``chips``: a call of one configuration then plans
as the node layout (``api.NODE``).  Without it a call runs on one chip.

The window keeps calls in flight, each on a thread of its own, so that the
chip is fed while the host handles a call's rows or stands still: about
:data:`AHEAD_S` seconds of calls behind the one it waits for.  Its rate is
all the work of the calls sent over the time until the last has returned.
"""
from __future__ import annotations

import importlib.util
import json
import math
import os
import shutil
import sys
import time
import traceback
from collections import deque
from concurrent.futures import ThreadPoolExecutor
from contextlib import nullcontext
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

from bench import correct, traffic as traffic_mod

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
TRACE_DIR = os.path.join(BENCH, ".trace")
FLOAT_FIELDS = ("throughput_mtps", "avg_latency_us", "abort_rate", "avg_round_trips")
AHEAD_S = 4.0  # seconds of calls the window sends ahead of the one it waits for
MAX_IN_FLIGHT = 16  # calls in flight at most, each on a thread of its own


def in_flight(call_s: float) -> int:
    """Calls kept in flight when one call takes ``call_s`` seconds: the one
    waited for and about :data:`AHEAD_S` of calls behind it, at least one."""
    ahead = round(AHEAD_S / call_s) if call_s > 0 else MAX_IN_FLIGHT
    return min(MAX_IN_FLIGHT, 1 + max(1, ahead))


def read_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_benchmark() -> dict:
    return read_json(os.path.join(ROOT, "BENCHMARK.json"))


@dataclass(frozen=True)
class Cell:
    name: str
    chips: int
    deployment: dict
    traffic: dict
    limits: dict
    sample_rows: int
    end_to_end: List[dict]
    per_layer: List[dict]


def load_cell(name: str, bench: Optional[dict] = None) -> Cell:
    bench = bench or load_benchmark()
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; have {sorted(cells)}")
    w = cells[name]
    spec = read_json(os.path.join(BENCH, "cells", f"{name}.json"))
    deployment = read_json(os.path.join(BENCH, "configs", f"{w['config']}.json"))
    check_layout(deployment, w["chips"])

    def applies(m: dict) -> bool:
        return name in m.get("workloads", [name])

    return Cell(
        name=name,
        chips=w["chips"],
        deployment=deployment,
        traffic=read_json(os.path.join(BENCH, "traffic", f"{w['traffic']}.json")),
        limits=spec["limits"],
        sample_rows=spec["sample_rows"],
        end_to_end=[m for m in bench["end_to_end"] if applies(m)],
        per_layer=[m for m in bench["per_layer"] if applies(m)],
    )


def check_layout(deployment: dict, chips: int) -> None:
    """A deployment's ``node_shards`` must be the cell's chips and divide its
    nodes (each chip holds whole simulated nodes)."""
    shards = deployment.get("node_shards")
    if shards is None:
        return
    if shards != chips or deployment["n_nodes"] % shards:
        raise ValueError(
            f"deployment {deployment.get('name')!r}: node_shards={shards} must equal the "
            f"cell's chips ({chips}) and divide n_nodes={deployment['n_nodes']}"
        )


def metric_reader(name: str):
    """``bench/metrics/<name>.py``'s ``read`` function.  A metric split by
    the cells it is read in (``<base>.<part>``) shares its base's reader
    unless it has a file of its own."""
    path = os.path.join(BENCH, "metrics", f"{name}.py")
    if not os.path.isfile(path):
        path = os.path.join(BENCH, "metrics", f"{name.split('.')[0]}.py")
    spec = importlib.util.spec_from_file_location(f"bench.metrics.{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


@dataclass
class Run:
    """What one run measured; per-layer readers take their numbers from it."""

    deployment: dict
    traffic: dict
    device_kind: str
    setup_s: float = 0.0
    setup_compile_s: float = 0.0
    window_s: float = 0.0
    window_compiles: int = 0
    config_ticks: int = 0
    plan_ms: List[float] = field(default_factory=list)
    warm_call_s: float = 0.0
    call_s: List[float] = field(default_factory=list)  # host seconds of each window call
    calls: List[Tuple[traffic_mod.Call, Optional[List[Dict]]]] = field(default_factory=list)
    in_flight: int = 1  # window calls in flight after the first
    attempted: int = 0
    failed: int = 0
    traced_call: Optional[traffic_mod.Call] = None
    trace: object = None  # bench.trace.Summary of the traced call

    @property
    def config_ticks_per_s(self) -> float:
        return self.config_ticks / self.window_s


def spec_for(api, deployment: dict, call: traffic_mod.Call, kernel_plane: str = "auto"):
    """The ``ExperimentSpec`` of one call; ``node_shards`` (checked against the
    cell's chips by :func:`check_layout`) places it over that many devices."""
    knobs = {"exec_ticks": deployment["exec_ticks"]}
    if "hot_prob" in deployment:
        knobs["hot_prob"] = deployment["hot_prob"]
    layout = {}
    if "node_shards" in deployment:
        layout = {"node_shards": deployment["node_shards"], "devices": "auto"}
    return api.ExperimentSpec(
        protocol=call.protocol,
        workload=deployment["workload"],
        configs=[dict(k, **knobs) for k in call.knobs],
        n_nodes=deployment["n_nodes"],
        coroutines=deployment["coroutines"],
        records_per_node=deployment["records_per_node"],
        ticks=call.ticks,
        warmup=call.warmup,
        mvcc_slots=deployment["mvcc_slots"],
        kernel_plane=kernel_plane,
        **layout,
    )


def well_formed(rows, call: traffic_mod.Call) -> bool:
    """One row per configuration, every compared number present and finite."""
    if not isinstance(rows, list) or len(rows) != len(call.knobs):
        return False
    for r in rows:
        vals = [r.get(k) for k in correct.INT_FIELDS + FLOAT_FIELDS] + list(
            r.get("stage_us_per_commit") or [None]
        )
        if not all(isinstance(v, (int, float)) and math.isfinite(v) for v in vals):
            return False
    return True


def run_cell(
    api,
    deployment: dict,
    traffic: dict,
    *,
    seed: int,
    seconds: float,
    trace: bool,
    device_kind: str,
    log,
    t_start: float,
    expect_plane: Optional[str] = "pallas",
    kernel_plane: str = "auto",
) -> Run:
    """Warm up, run the window of calls, trace its first if asked.

    ``t_start`` is the process's start on ``time.perf_counter``; set-up is
    everything from there to the first timed call.  ``expect_plane`` is the
    kernel plane ``kernel_plane`` must resolve to (None: no check)."""
    import jax

    run = Run(deployment=deployment, traffic=traffic, device_kind=device_kind)
    stream = traffic_mod.calls(traffic, seed)

    def one_call(call, span=nullcontext):
        """(rows, plan ms, ok) of one call; a call that raises is counted
        as failed and the window goes on."""
        try:
            with span("bench.plan"):
                t0 = time.perf_counter()
                pl = api.plan(spec_for(api, deployment, call, kernel_plane))
                plan_ms = (time.perf_counter() - t0) * 1e3
            if expect_plane is not None and pl.kernel_plane != expect_plane:
                raise RuntimeError(
                    f"kernel_plane {kernel_plane!r} resolved to {pl.kernel_plane!r}, "
                    f"not {expect_plane!r}"
                )
            with span("bench.execute"):
                res = api.execute(pl)
            with span("bench.rows"):
                rows = list(res.rows)
                ok = well_formed(rows, call)
            return rows, plan_ms, ok
        except Exception:
            traceback.print_exc(file=sys.stderr)
            return None, None, False

    def timed_call(call, span=nullcontext):
        t0 = time.perf_counter()
        return (*one_call(call, span), time.perf_counter() - t0)

    def record(call, rows, plan_ms, ok, secs):
        run.call_s.append(secs)
        run.attempted += 1
        run.calls.append((call, rows))
        if ok:
            run.plan_ms.append(plan_ms)
            run.config_ticks += call.config_ticks
        else:
            run.failed += 1

    warm = next(stream)
    t0 = time.perf_counter()
    if not one_call(warm)[2]:
        raise RuntimeError("the warm-up call failed or returned malformed rows")
    run.warm_call_s = time.perf_counter() - t0
    c0, s0 = log.snapshot()
    run.setup_compile_s = s0

    if trace:
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
    t_win = time.perf_counter()
    run.setup_s = t_win - t_start
    # The window's first call runs alone: it is the traced call, and its
    # time sets how many calls the rest of the window keeps in flight.
    first = next(stream)
    if trace:
        jax.profiler.start_trace(TRACE_DIR)
        with jax.profiler.TraceAnnotation("bench.call"):
            out = timed_call(first, jax.profiler.TraceAnnotation)
        jax.profiler.stop_trace()
        run.traced_call = first
    else:
        out = timed_call(first)
    record(first, *out)
    run.in_flight = in_flight(run.call_s[0])
    # The rest: each call is sent while those before it still run, so the
    # device waits on no host work between calls.  When time is up nothing
    # more is sent; the window ends when every call sent has returned.
    with ThreadPoolExecutor(max_workers=run.in_flight) as pool:
        sent = deque()
        while True:
            while len(sent) < run.in_flight and time.perf_counter() - t_win < seconds:
                call = next(stream)
                sent.append((call, pool.submit(timed_call, call)))
            if not sent:
                break
            call, fut = sent.popleft()
            record(call, *fut.result())
    run.window_s = time.perf_counter() - t_win
    c1, _ = log.snapshot()
    run.window_compiles = c1 - c0
    if trace:
        from bench import trace as trace_mod

        run.trace = trace_mod.reduce(trace_mod.load(TRACE_DIR))
    return run


def end_to_end(run: Run, metrics: List[dict]) -> Dict[str, dict]:
    """The cell's end-to-end metrics; ``<base>.<part>`` is ``<base>`` read in
    the cells listed for it."""
    values = {"config_ticks_per_s": run.config_ticks_per_s, "setup_s": run.setup_s}
    return {m["name"]: {"value": values[m["name"].split(".")[0]], "unit": m["unit"]}
            for m in metrics}


def per_layer(run: Run, metrics: List[dict]) -> Dict[str, dict]:
    out = {}
    for m in metrics:
        value = metric_reader(m["name"])(run)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out
