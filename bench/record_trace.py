"""Record the small chip trace that ``tests/bench`` reduces.

    python3 bench/record_trace.py <out.xplane.pb>

Runs one traced window call of NOWAIT on SmallBank (four configurations,
a 4-node cluster of 8 co-routines and 1,024 records per node, 4 + 1 ticks)
through the harness on the chip, copies the trace to ``<out>`` and prints
what the reduction reads from it, with the device planes' op names and
metadata for a reader to check the op matching by hand.  The test data
``tests/bench/data/nowait.xplane.pb.gz`` is such a trace, gzipped.
"""
import os
import shutil
import sys
import time

T_START = time.perf_counter()
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from bench import harness, trace  # noqa: E402
from bench.compile_log import CompileLog  # noqa: E402

TINY = {"n_nodes": 4, "coroutines": 8, "records_per_node": 1024}
TRAFFIC = {"protocol": "nowait", "codes": "cycle", "configs_per_call": 4, "ticks": 4, "warmup": 1}


def main(out: str) -> None:
    import glob

    import jax
    from jax.profiler import ProfileData

    if jax.devices()[0].platform != "tpu":
        raise SystemExit("record_trace: JAX found no TPU")
    sys.path.insert(0, os.path.join(ROOT, "src"))
    from repro import api

    dep = dict(harness.read_json(os.path.join(harness.BENCH, "configs", "smallbank-rcc.json")), **TINY)
    run = harness.run_cell(api, dep, TRAFFIC, seed=1, seconds=0.0, trace=True,
                           device_kind=jax.devices()[0].device_kind,
                           log=CompileLog(jax.monitoring), t_start=T_START)
    src = sorted(glob.glob(os.path.join(harness.TRACE_DIR, "**", "*.xplane.pb"), recursive=True))[-1]
    shutil.copyfile(src, out)
    s = run.trace
    print(f"{out} ({os.path.getsize(out)} bytes): window {s.window_s} busy {s.busy_s} "
          f"pallas {s.pallas_s} gaps {s.idle_gaps[:3]}")
    for plane in ProfileData.from_file(out).planes:
        print(f"plane {plane.name}: { {ln.name: len(list(ln.events)) for ln in plane.lines} }")
        if plane.name.startswith(trace.DEVICE_PREFIX):
            seen = set()
            for ln in plane.lines:
                for e in ln.events:
                    if (ln.name, e.name) not in seen and len(seen) < 60:
                        seen.add((ln.name, e.name))
                        print(f"  [{ln.name}] {e.name} {e.duration_ns} {dict(e.stats)}")


if __name__ == "__main__":
    main(sys.argv[1])
