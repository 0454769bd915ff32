"""The node layout on four devices, for ``test_bench_node.py``.

    XLA_FLAGS=--xla_force_host_platform_device_count=4 JAX_PLATFORMS=cpu \\
        python3 tests/bench/node_cell.py [--bench <root>] [<cell> ...]

Prints one JSON line.  Its ``cells`` holds, for each node cell named (by
default every cell of ``CELLS_NODE``), the checks a cell on one chip gets
in the test process: its spec, its tiny run through the harness compared
for ``correct``, and the bfloat16 control.  A node cell's checks run here
because the test process keeps one device.  ``--bench`` reads the
``BENCHMARK.json`` and ``bench/`` files of another checkout root instead
of this one.

With no cell named, the line also holds how a tiny deployment with
``node_shards`` plans, its numbers compared for ``correct`` through the
harness, the largest program recorded on each device, the same with the
exchange between devices left out (``faults.exchange_dropped``), and the
reference with its store laid over the four devices against the reference
on one device.
"""
import argparse
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
    return {"numbers": numbers, "correct": ok, "failed": run.failed, "attempted": run.attempted,
            "node_shards": sorted({r.get("n_node_shards") for r in rows})}


def record_arrays(text: str, n_configs: int, n_records: int) -> int:
    """Arrays of ``n_configs`` x ``n_records`` (x ...) words in compiled HLO text."""
    return len(re.findall(rf"\[{n_configs},{n_records}[,\]]", text))


def node_cell(name: str) -> dict:
    """A node cell's spec, tiny run, precision control, and its tiny run
    with each of :data:`faults.NODE_FAULTS` planted."""
    import pytest

    import faults
    from bench import harness, traffic
    from cells import control, run_tiny, tiny
    from repro import api

    c = tiny(name)[0]
    spec = harness.spec_for(api, c.deployment, next(traffic.calls(c.traffic, 2**31 + 9)))
    out = {"chips": c.chips, "limits": c.limits,
           "spec": {"node_shards": spec.node_shards, "devices": spec.devices},
           "sound": judged(run_tiny(name)), "control": control(name), "faults": {}}
    for fault in faults.NODE_FAULTS:
        with pytest.MonkeyPatch.context() as mp:
            fault(mp)
            out["faults"][fault.__name__] = judged(run_tiny(name))
    return out


def layout() -> dict:
    import jax
    import pytest

    import faults
    from bench import correct, harness, reference, traffic
    from cells import NODE_CHIPS, coded, node_tiny, run_small
    from repro import api

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
    laid_out = coded(reference.rows(dep, call, knobs), knobs)
    dense = reference.rows(one_device, call, knobs)
    out["reference"] = correct.compare(laid_out, dense, [k["hybrid"] for k in knobs])
    out["reference_commits"] = [r["commits"] for r in dense]
    text = reference.program(reference.shape_for(dep, call)).lower(
        *reference.inputs(dep, knobs[:2])).compile().as_text()
    n_records = dep["n_nodes"] * dep["records_per_node"]
    out["whole_record_arrays"] = record_arrays(text, 2, n_records)
    out["local_record_arrays"] = record_arrays(text, 2, n_records // NODE_CHIPS)
    return out


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--bench", help="checkout root whose BENCHMARK.json and bench/ files to read")
    ap.add_argument("cells", nargs="*", help="node cells to check (default: CELLS_NODE)")
    args = ap.parse_args(argv)

    import jax

    from bench import harness

    if args.bench:  # before cells.py reads the benchmark
        harness.ROOT = os.path.abspath(args.bench)
        harness.BENCH = os.path.join(harness.ROOT, "bench")
    from cells import CELLS_NODE, NODE_CHIPS

    if len(jax.devices()) != NODE_CHIPS:
        raise SystemExit(f"node_cell: needs {NODE_CHIPS} devices, JAX sees {len(jax.devices())}")
    out = {} if args.cells else layout()
    out["cells"] = {name: node_cell(name) for name in args.cells or CELLS_NODE}
    print(json.dumps(out))


if __name__ == "__main__":
    main()
