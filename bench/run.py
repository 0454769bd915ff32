"""Benchmark entry point: one run of one cell on the chip.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Set-up (JAX start, the program's import, one warm call of the cell's own
program, loaded from the persistent compile cache after the first run in
a checkout) counts as ``setup_s``.  The window then sends whole calls,
several in flight, until ``--seconds`` have passed, and ends when the last
call sent returns (``harness.run_cell``).  With ``--trace 1`` the first
window call, which runs alone, is profiled and the line carries the
per-layer metrics instead of the end-to-end ones.  After the window, a sample of the rows is checked
against the plain reference (``bench/reference``) and ``correct`` set.

The last line of stdout is one JSON object; the numbers compared for
``correct`` also end standard error, each beside its limit.  Its
``device.memory_peak_bytes`` is the peak of the fullest of the cell's chips,
read before the reference runs; ``memory_peak_bytes_per_device`` lists each
chip's.  A chip's peak is the larger of its allocator's peak and the
largest program that ran there (:class:`ProgramBytes`).  Without a TPU, or
with fewer chips than the cell asks for, the run exits non-zero and prints
no result.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from typing import Dict  # noqa: E402

import numpy as np  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from bench import correct, harness  # noqa: E402
from bench.compile_log import CompileLog, use_compile_cache  # noqa: E402


def require_chips(jax, chips: int):
    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise SystemExit(
            f"bench: JAX found no TPU (platform {devices[0].platform!r}); the benchmark runs "
            "on the chip only"
        )
    if len(devices) < chips:
        raise SystemExit(f"bench: the cell needs {chips} chip(s), JAX sees {len(devices)}")
    return devices[0]


class ProgramBytes:
    """Each device's largest compiled program, in bytes: its arguments,
    outputs and temporaries less the outputs that alias an argument, as XLA's
    compiled memory stats give them for one device.  The TPU runtime's
    allocator peak leaves an executable's temporaries out, and the program
    builds its store inside one, so that peak alone misses the store.

    :meth:`wrap` wraps JAX's ``compile_or_get_cached``, which every program
    passes through once, compiled or loaded from the persistent cache."""

    def __init__(self):
        self.by_device: Dict[int, int] = {}

    def wrap(self, compile_or_get_cached):
        def recording(backend, computation, devices, *args, **kwargs):
            exe = compile_or_get_cached(backend, computation, devices, *args, **kwargs)
            self.note(exe, devices)
            return exe

        return recording

    def note(self, exe, devices) -> None:
        try:
            m = exe.get_compiled_memory_stats()
        except (AttributeError, NotImplementedError, RuntimeError):  # no stats: the allocator's stands
            return
        size = (m.argument_size_in_bytes + m.output_size_in_bytes + m.temp_size_in_bytes
                - m.alias_size_in_bytes)
        for d in np.asarray(devices).flat:
            self.by_device[d.id] = max(self.by_device.get(d.id, 0), size)


def memory_peaks(allocator, program):
    """(the fullest chip's peak, each chip's peak) in bytes, from each chip's
    allocator peak and largest program; None where neither is known."""
    peaks = [max((b for b in pair if b is not None), default=None)
             for pair in zip(allocator, program)]
    return max((p for p in peaks if p is not None), default=None), peaks


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    cell = harness.load_cell(args.workload)
    import jax

    dev = require_chips(jax, cell.chips)
    print(f"# device: {dev.platform} {dev.device_kind} x{len(jax.devices())}", file=sys.stderr)
    print(f"# compile cache: {use_compile_cache(ROOT)}", file=sys.stderr)
    log = CompileLog(jax.monitoring)
    from jax._src import compiler  # no public hook sees every program run

    programs = ProgramBytes()
    compiler.compile_or_get_cached = programs.wrap(compiler.compile_or_get_cached)
    sys.path.insert(0, os.path.join(ROOT, "src"))
    from repro import api

    run = harness.run_cell(
        api, cell.deployment, cell.traffic, seed=args.seed, seconds=args.seconds,
        trace=bool(args.trace), device_kind=dev.device_kind, log=log, t_start=T_START,
    )
    chips = jax.devices()[:cell.chips]
    allocator = [(d.memory_stats() or {}).get("peak_bytes_in_use") for d in chips]
    program = [programs.by_device.get(d.id) for d in chips]
    peak, peaks = memory_peaks(allocator, program)
    print(
        f"# window: {run.attempted} call(s), {run.failed} failed, {run.config_ticks} "
        f"config-ticks in {run.window_s:.3f} s; set-up {run.setup_s:.3f} s "
        f"({run.setup_compile_s:.3f} s compiling), {run.window_compiles} compile(s) in the window",
        file=sys.stderr,
    )
    print(f"# memory a chip (bytes): allocator peak {allocator}, largest program {program}",
          file=sys.stderr)
    calls = sorted(run.call_s)
    print(
        f"# call seconds: warm {run.warm_call_s:.4f}; window min {calls[0]:.4f}, median "
        f"{calls[len(calls) // 2]:.4f}, max {calls[-1]:.4f}; first {run.call_s[0]:.4f}, "
        f"then {run.in_flight} in flight",
        file=sys.stderr,
    )

    t_check = time.perf_counter()
    numbers = correct.check(cell.deployment, cell.traffic, run.calls, cell.sample_rows, args.seed)
    ok = run.failed == 0 and correct.judge(numbers, cell.limits)
    print(f"# reference check: {time.perf_counter() - t_check:.3f} s", file=sys.stderr)

    device = {"platform": dev.platform, "kind": dev.device_kind, "count": len(jax.devices()),
              "memory_peak_bytes": peak, "memory_peak_bytes_per_device": peaks}
    if args.trace:
        metrics = harness.per_layer(run, cell.per_layer)
        device["busy_s"] = run.trace.busy_s
        device["window_s"] = run.trace.window_s
    else:
        metrics = harness.end_to_end(run, cell.end_to_end)
    result = {
        "correct": ok,
        "attempted": run.attempted,
        "failed": run.failed,
        "metrics": metrics,
        "device": device,
    }
    if args.trace:
        result["breakdown"] = {
            "device_ops": [[n, s] for n, s in run.trace.top_ops(10)],
            "idle_gaps": [[n, s] for n, s in run.trace.idle_gaps[:10]],
        }
    checks = {k: {"value": numbers[k], "limit": cell.limits[k]} for k in correct.NUMBERS}
    checks["failed_calls"] = {"value": run.failed, "limit": 0}
    result["checks"] = checks
    for k, v in checks.items():
        print(f"check {k}: {v['value']!r} (limit {v['limit']!r})", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
