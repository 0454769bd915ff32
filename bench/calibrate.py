"""Readings that the limits of ``correct`` are set from, on the chip.

    python3 bench/calibrate.py --workload <cell> --seeds 12 --control-seeds 3 --base-seed <n>

In one process, for each of ``--seeds`` seeds: the cell's first window
calls through the front door (as many as a run of ``run_seconds`` samples
rows from), and the compared numbers of those rows against the reference.  Then, for
``--control-seeds`` seeds, the same numbers for the control: the
reference computed with bfloat16 latency accumulators put in the program's
place.  Prints one JSON line per reading and the largest of each kind.
"""
import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from bench import correct, harness, reference, traffic  # noqa: E402
from bench.compile_log import use_compile_cache  # noqa: E402


def window_calls(api, cell, seed, seconds, n_calls=None):
    """The first window calls of ``seed``: as many as a run's window of
    ``seconds`` makes before it has ``sample_rows`` rows (or ``n_calls``);
    with ``api`` None the calls are not made (rows None)."""
    stream = traffic.calls(cell.traffic, seed)
    next(stream)  # the warm call
    out = []
    t0 = time.perf_counter()
    while True:
        call = next(stream)
        spec = api and api.plan(harness.spec_for(api, cell.deployment, call))
        out.append((call, api and api.execute(spec).rows))
        if n_calls is not None:
            if len(out) >= n_calls:
                return out
        elif (sum(len(c.knobs) for c, _ in out) >= cell.sample_rows
              or time.perf_counter() - t0 >= seconds):
            return out


def control(cell, calls, seed):
    """The compared numbers with the bfloat16 reference in the program's place."""
    flat = [(call, i) for call, _ in calls for i in range(len(call.knobs))]
    picks = [flat[p] for p in correct.sample_positions(len(flat), cell.sample_rows, seed)]
    knobs = [call.knobs[i] for call, i in picks]
    first = picks[0][0]
    shape = {"protocol": first.protocol, "ticks": first.ticks, "warmup": first.warmup}
    rows = reference.rows(cell.deployment, shape, knobs, fdt="bfloat16")
    for row, k in zip(rows, knobs):
        row["hybrid"] = "".join(str((k["hybrid"] >> b) & 1) for b in range(6))
    return correct.compare(rows, reference.rows(cell.deployment, shape, knobs),
                           [k["hybrid"] for k in knobs])


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--control-seeds", type=int, default=3)
    ap.add_argument("--base-seed", type=int, default=2**31 + 1000)
    args = ap.parse_args()

    import jax

    if jax.devices()[0].platform != "tpu":
        raise SystemExit("calibrate: JAX found no TPU")
    use_compile_cache(ROOT)
    sys.path.insert(0, os.path.join(ROOT, "src"))
    from repro import api

    cell = harness.load_cell(args.workload)
    seconds = harness.load_benchmark()["run_seconds"]
    worst = {"program": {}, "control": {}}
    n_calls = 0
    warm = next(traffic.calls(cell.traffic, args.base_seed))  # compile before any timing
    api.execute(api.plan(harness.spec_for(api, cell.deployment, warm)))
    for i in range(args.seeds + args.control_seeds):
        kind = "program" if i < args.seeds else "control"
        seed = args.base_seed + 7919 * i
        t0 = time.perf_counter()
        if kind == "program":
            calls = window_calls(api, cell, seed, seconds)
            n_calls = max(n_calls, len(calls))
            numbers = correct.check(cell.deployment, cell.traffic, calls, cell.sample_rows, seed)
            numbers["min_commits"] = min(r["commits"] for _, rows in calls for r in rows)
        else:
            numbers = control(cell, window_calls(None, cell, seed, seconds, n_calls), seed)
        for k in correct.NUMBERS:
            worst[kind][k] = max(worst[kind].get(k, 0.0), numbers[k])
        print(json.dumps({"kind": kind, "seed": seed, **numbers,
                          "seconds": time.perf_counter() - t0}), flush=True)
    print(json.dumps({"workload": args.workload, "worst": worst, "limits": cell.limits}))


if __name__ == "__main__":
    main()
