"""The node layout on four devices, for ``test_bench_node.py``.

    XLA_FLAGS=--xla_force_host_platform_device_count=4 JAX_PLATFORMS=cpu \\
        python3 tests/bench/node_cell.py

Prints one JSON line: how a tiny deployment with ``node_shards`` plans, its
numbers compared for ``correct`` through the harness, the largest program
recorded on each device, the same with the
exchange between devices left out (``faults.exchange_dropped``), and the
reference with its store laid over the four devices against the reference
on one device.
"""
import json
import os
import re
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path[:0] = [HERE, ROOT, os.path.join(ROOT, "src")]


def judged(result):
    numbers, ok, run = result
    rows = [r for _, rs in run.calls for r in rs or []]
    return {"numbers": numbers, "correct": ok, "failed": run.failed,
            "node_shards": sorted({r.get("n_node_shards") for r in rows})}


def record_arrays(text: str, n_configs: int, n_records: int) -> int:
    """Arrays of ``n_configs`` x ``n_records`` (x ...) words in compiled HLO text."""
    return len(re.findall(rf"\[{n_configs},{n_records}[,\]]", text))


def main() -> None:
    import jax
    import pytest

    import faults
    from bench import correct, harness, reference, traffic
    from cells import NODE_CHIPS, node_tiny, run_small
    from repro import api

    if len(jax.devices()) != NODE_CHIPS:
        raise SystemExit(f"node_cell: needs {NODE_CHIPS} devices, JAX sees {len(jax.devices())}")
    limits, dep, tr = node_tiny()
    from jax._src import compiler

    from bench import run as bench_run

    programs = bench_run.ProgramBytes()
    compiler.compile_or_get_cached = programs.wrap(compiler.compile_or_get_cached)
    pl = api.plan(harness.spec_for(api, dep, next(traffic.calls(tr, 5))))
    out = {"layout": pl.layout, "n_devices": pl.n_devices}
    out["sound"] = judged(run_small(limits, dep, tr))
    out["program_bytes"] = [programs.by_device.get(d.id) for d in jax.devices()]
    with pytest.MonkeyPatch.context() as mp:
        faults.exchange_dropped(mp)
        out["exchange_dropped"] = judged(run_small(limits, dep, tr))

    call = {"protocol": tr["protocol"], "ticks": tr["ticks"], "warmup": tr["warmup"]}
    knobs = [{"hybrid": code, "seed": 11 + code} for code in (0, 21, 42)]
    one_device = {k: v for k, v in dep.items() if k != "node_shards"}
    laid_out = reference.rows(dep, call, knobs)
    for row, k in zip(laid_out, knobs):
        row["hybrid"] = "".join(str((k["hybrid"] >> i) & 1) for i in range(6))
    dense = reference.rows(one_device, call, knobs)
    out["reference"] = correct.compare(laid_out, dense, [k["hybrid"] for k in knobs])
    out["reference_commits"] = [r["commits"] for r in dense]
    text = reference.program(reference.shape_for(dep, call)).lower(
        *reference.inputs(dep, knobs[:2])).compile().as_text()
    n_records = dep["n_nodes"] * dep["records_per_node"]
    out["whole_record_arrays"] = record_arrays(text, 2, n_records)
    out["local_record_arrays"] = record_arrays(text, 2, n_records // NODE_CHIPS)
    print(json.dumps(out))


if __name__ == "__main__":
    main()
