"""Benchmark entry point: one module per paper figure/table.

Default mode keeps sizes CI-friendly (single CPU core); ``--full`` runs the
paper-scale sweeps.  Output: CSV lines prefixed by figure id.
"""
from __future__ import annotations

import argparse
import os
import sys
import time

# allow `python benchmarks/run.py` from a repo checkout: put the repo root
# (for the benchmarks package) and src/ (for repro, when not pip-installed)
# on sys.path
_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for _p in (_ROOT, os.path.join(_ROOT, "src")):
    if _p not in sys.path:
        sys.path.insert(0, _p)


# name -> module (import path under benchmarks/); single source for the
# dispatch order, --only validation, and the help text
BENCHMARKS = {
    "stage_latency": "stage_latency",
    "overall": "overall",
    "coroutines": "coroutines",
    "contention": "contention",
    "computation": "computation",
    "qp_scaling": "qp_scaling",
    "hybrid": "hybrid_search",
    "mvcc_slots": "mvcc_slots",
}


def main() -> None:
    from benchmarks import common  # jax-free import surface (see common.py)

    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--only", default="all", help="comma list: " + ",".join(BENCHMARKS))
    common.add_device_args(ap)
    args = ap.parse_args()
    want = None if args.only == "all" else set(args.only.split(","))
    if want and not want <= set(BENCHMARKS):
        ap.error(
            f"unknown benchmark(s): {sorted(want - set(BENCHMARKS))}; known: {sorted(BENCHMARKS)}"
        )

    # shared --node-shards/--devices handling (fake-host XLA_FLAGS forcing
    # must precede the first jax import, which the benchmark modules do)
    common.configure_devices(args, error=ap.error)
    common.configure_compile_cache()

    import importlib

    modules = [
        (name, importlib.import_module(f"benchmarks.{modname}"))
        for name, modname in BENCHMARKS.items()
    ]
    t0 = time.time()
    for name, mod in modules:
        if want and name not in want:
            continue
        print(f"# === {name} ({time.time()-t0:.0f}s elapsed) ===", flush=True)
        mod.main(full=args.full)
    print(f"# all benchmarks done in {time.time()-t0:.0f}s")


if __name__ == "__main__":
    main()
