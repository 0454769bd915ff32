"""Chip smoke: drive ``repro.api`` once on a TPU at the paper-scale deployment.

    python chip_smoke.py                # one chip: phases (a)-(c) below
    python chip_smoke.py --four-chips   # four chips: the sharded layouts only

One chip, all through ``ExperimentSpec -> plan -> execute`` with the api's
paper-scale defaults (4 nodes x 60 co-routines, 65,536 records per node,
400 ticks after 80 warm-up) and ``kernel_plane="auto"``, which must resolve
to compiled Pallas:

  (a) the golden grid of ``tests/data/stage_graph_golden.json``; its integer
      counters must equal the pinned ones;
  (b) the 2^6 hybrid grid of every registered protocol on SmallBank;
  (c) YCSB with four hybrid codes on the Pallas and on the jnp plane, for
      one protocol per kernel path (nowait: lock arbiter + multi-read, mvcc:
      version select, sundial); commits and aborts must match bitwise.

``--four-chips`` runs only (i) each protocol's SmallBank config on a
4-shard node mesh against the dense run on one chip and (ii) the 2^6 nowait
grid with its config axis over the four chips against the dense grid.
Both keep the paper-scale widths but run 100 ticks after 20 warm-up
instead of 400 after 80.

Every row must commit and keep its abort rate in [0, 1].  The script exits
non-zero on any mismatch and when JAX finds no TPU; nothing falls back to
the CPU or to interpret mode.  Per-phase times are set-up and
informational, not metrics.  The last line of stdout is one JSON object
naming the device.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
GOLDEN = os.path.join(ROOT, "tests", "data", "stage_graph_golden.json")
YCSB_CODES = (0, 63, 0b010101, 0b101010)  # pure RPC, pure one-sided, two mixed
KERNEL_PATH_PROTOCOLS = ("nowait", "mvcc", "sundial")
NODE_CODE = 0b010101  # mixed coding: RPC and one-sided stages both cross the node mesh
FOUR_CHIP_DEPTH = dict(ticks=100, warmup=20)  # depth cut; widths stay at paper scale


def require(ok: bool, msg: str) -> None:
    if not ok:
        raise SystemExit(f"chip_smoke FAILED: {msg}")


def phase(name: str, log, fn):
    print(f"# === phase {name}", flush=True)
    c0, s0 = log.snapshot()
    t0 = time.time()
    fn()
    wall = time.time() - t0
    c1, s1 = log.snapshot()
    print(
        f"# phase {name} (set-up, informational): {c1 - c0} compile(s), "
        f"compile {s1 - s0:.1f}s, wall {wall:.1f}s, run (wall - compile) {wall - (s1 - s0):.1f}s",
        flush=True,
    )


def check_rows(tag: str, rows) -> None:
    for r in rows:
        require(r["commits"] > 0, f"{tag} hybrid {r['hybrid']}: no commits ({r})")
        require(0.0 <= r["abort_rate"] <= 1.0, f"{tag} hybrid {r['hybrid']}: abort_rate {r}")


def counters(rows):
    return [(int(r["commits"]), int(r["aborts"])) for r in rows]


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument(
        "--four-chips", action="store_true",
        help="run only the node-layout and config-axis checks on four chips",
    )
    args = ap.parse_args()

    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise SystemExit(
            f"chip_smoke: JAX found no TPU (devices()[0].platform={dev.platform!r}); "
            "this script runs on the chip only"
        )
    n_chips = len(jax.devices())
    require(not args.four_chips or n_chips == 4, f"--four-chips needs 4 devices, JAX sees {n_chips}")

    for p in (ROOT, os.path.join(ROOT, "src")):
        if p not in sys.path:
            sys.path.insert(0, p)
    from bench.compile_log import CompileLog
    from benchmarks.common import configure_compile_cache
    from repro import api
    from repro.core import registry

    print(f"# device: {dev.platform} {dev.device_kind}, {n_chips} visible", flush=True)
    print(f"# compile cache: {configure_compile_cache()}", flush=True)
    log = CompileLog(jax.monitoring)

    def run(tag: str, **spec_kw):
        pl = api.plan(api.ExperimentSpec(**spec_kw))
        print(f"# plan [{tag}]\n" + "\n".join("#   " + s for s in pl.summary().splitlines()),
              flush=True)
        if spec_kw.get("kernel_plane", "auto") == "auto":
            require(pl.kernel_plane == "pallas",
                    f"{tag}: kernel_plane 'auto' resolved to {pl.kernel_plane!r}, not 'pallas'")
        return api.execute(pl).rows

    def golden():
        with open(GOLDEN) as f:
            g = json.load(f)
        for workload, cell in g["cells"].items():
            for proto in cell["protocols"]:
                rows = run(f"golden {proto}/{workload}", protocol=proto, workload=workload,
                           configs=[{"hybrid": c} for c in cell["codes"]], **g["kw"])
                for r in rows:
                    key = f"{proto}/{workload}/{r['hybrid']}"
                    want = g["counters"][key]
                    got = {"commits": int(r["commits"]), "aborts": int(r["aborts"])}
                    require(got == want, f"golden {key}: got {got}, pinned {want}")
        print("# golden counters match", flush=True)

    def smallbank_grids():
        for proto in registry.protocol_names():
            rows = run(f"{proto}/smallbank 2^6", protocol=proto, workload="smallbank",
                       configs=[{"hybrid": c} for c in api.all_hybrid_codes()])
            check_rows(f"{proto}/smallbank", rows)
            print(f"# {proto}/smallbank: 64 rows, commits {min(r['commits'] for r in rows)}"
                  f"..{max(r['commits'] for r in rows)}", flush=True)

    def ycsb_planes():
        for proto in KERNEL_PATH_PROTOCOLS:
            cfgs = [{"hybrid": c} for c in YCSB_CODES]
            pal = run(f"{proto}/ycsb pallas", protocol=proto, workload="ycsb", configs=cfgs)
            ref = run(f"{proto}/ycsb jnp", protocol=proto, workload="ycsb", configs=cfgs,
                      kernel_plane="jnp")
            check_rows(f"{proto}/ycsb", pal)
            require(counters(pal) == counters(ref),
                    f"{proto}/ycsb: pallas {counters(pal)} != jnp {counters(ref)}")
            print(f"# {proto}/ycsb pallas == jnp: {counters(pal)}", flush=True)

    def node_layout():
        devices = tuple(jax.devices())
        cfgs = ({"hybrid": NODE_CODE},)
        for proto in registry.protocol_names():
            if not registry.get_protocol(proto).caps.node_shardable:
                print(f"# {proto}: node layout not admitted, skipped", flush=True)
                continue
            dense = run(f"{proto}/smallbank dense", protocol=proto, workload="smallbank",
                        configs=cfgs, **FOUR_CHIP_DEPTH)
            node = run(f"{proto}/smallbank node x4", protocol=proto, workload="smallbank",
                       configs=cfgs, devices=devices, node_shards=4, layout=api.NODE,
                       **FOUR_CHIP_DEPTH)
            check_rows(f"{proto}/smallbank node", node)
            require(counters(node) == counters(dense),
                    f"{proto}: node x4 {counters(node)} != dense {counters(dense)}")
            print(f"# {proto}/smallbank node x4 == dense: {counters(node)}", flush=True)

    def config_axis():
        cfgs = [{"hybrid": c} for c in api.all_hybrid_codes()]
        dense = run("nowait/smallbank 2^6 dense", protocol="nowait", workload="smallbank",
                    configs=cfgs, **FOUR_CHIP_DEPTH)
        sharded = run("nowait/smallbank 2^6 config x4", protocol="nowait",
                      workload="smallbank", configs=cfgs, devices="auto", **FOUR_CHIP_DEPTH)
        require(all(r["n_devices"] == 4 for r in sharded), "config layout did not use 4 devices")
        check_rows("nowait/smallbank config", sharded)
        require(counters(sharded) == counters(dense), "nowait 2^6: config x4 != dense")
        print("# nowait/smallbank 2^6 config x4 == dense", flush=True)

    t0 = time.time()
    if args.four_chips:
        phase("(i) node layout x4 vs dense", log, node_layout)
        phase("(ii) config axis x4 vs dense", log, config_axis)
    else:
        phase("(a) golden grid", log, golden)
        phase("(b) smallbank 2^6 x protocols", log, smallbank_grids)
        phase("(c) ycsb pallas vs jnp", log, ycsb_planes)
    n, secs = log.snapshot()
    print(f"# total (set-up, informational): {n} compile(s), compile {secs:.1f}s, "
          f"wall {time.time() - t0:.1f}s", flush=True)
    print(json.dumps({
        "ok": True,
        "device": {"platform": dev.platform, "kind": dev.device_kind, "count": n_chips},
    }))


if __name__ == "__main__":
    main()
