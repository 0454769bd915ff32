"""Reference engine: one dense, unpadded simulator run.

The same bulk-synchronous semantics as the simulator under test (one tick =
one network round; RPC requests queue on the destination handler CPU,
one-sided verbs on its RNIC), written out with plain ``jax.numpy`` gathers
and scatters: no kernel plane, no node-sharded transport, no shape-bucket
padding, no history recording.  It imports nothing of the program.  The
store may be laid out over several devices (``EngineConfig.place``); XLA then
partitions the same gathers and scatters, and the semantics do not change.

``EngineConfig.fdt`` is the dtype of the latency accumulators (per-txn
latency, latency and round-trip sums, per-stage time).  float32 is the
configuration's stated precision; the precision control runs bfloat16.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Callable, Dict, NamedTuple

import jax
import jax.numpy as jnp

# ---------------------------------------------------------------------------
# Cost model (per-unit costs of the two communication planes)
# ---------------------------------------------------------------------------

RPC = 0
ONE_SIDED = 1
ST_FETCH, ST_LOCK, ST_VALIDATE, ST_LOG, ST_COMMIT, ST_RELEASE, ST_EXEC, ST_WAIT = range(8)
N_HYBRID_STAGES = 6
N_STAGES = 8


@dataclass(frozen=True)
class CostModel:
    tick_us: float = 2.0
    rpc_rtt_us: float = 2.2
    os_rtt_us: float = 1.8
    handler_us: float = 0.20
    handler_cap: int = 64
    nic_cap: int = 512
    mmio_us: float = 0.15
    byte_us: float = 0.00008
    n_backups: int = 3
    qp_pressure: Any = 0.0

    def nic_eff_cap(self):
        return self.nic_cap / (1.0 + self.qp_pressure)


@dataclass(frozen=True)
class WireCost:
    base: float = 0.0
    words: float = 0.0
    n_verbs: int = 1
    replicated: bool = False

    def bytes_for(self, rw: int, n_backups: int = 1) -> float:
        b = self.base + self.words * 4.0 * rw
        return b * (n_backups if self.replicated else 1)


# The wire costs shared by the protocols; each protocol's plug-in holds its
# own table of a WireCost per canonical stage.
LOG_WIRE = WireCost(base=8.0, words=1.0, replicated=True)
RELEASE_WIRE = WireCost(base=8.0)


def queue_delay_us(cm: CostModel, is_rpc, dest_load):
    rpc_delay = cm.handler_us * jnp.maximum(dest_load - 1, 0.0) / 2.0 + cm.handler_us
    nic_unit = 1.0 / jnp.maximum(jnp.asarray(cm.nic_eff_cap(), jnp.float32), 1e-6) * cm.tick_us
    nic_delay = nic_unit * jnp.maximum(dest_load - 1, 0.0) / 2.0
    return jnp.where(is_rpc, rpc_delay, nic_delay)


def round_latency_us(cm: CostModel, is_rpc, dest_load, msg_bytes, n_verbs=1, doorbell=True):
    base = jnp.where(is_rpc, cm.rpc_rtt_us, cm.os_rtt_us)
    mmio = jnp.where(is_rpc, cm.mmio_us, cm.mmio_us * (1 if doorbell else n_verbs))
    return base + mmio + msg_bytes * cm.byte_us + queue_delay_us(cm, is_rpc, dest_load)


# ---------------------------------------------------------------------------
# Timestamps and arbitration
# ---------------------------------------------------------------------------


class TS(NamedTuple):
    hi: Any
    lo: Any


def ts_lt(a: TS, b: TS):
    return (a.hi < b.hi) | ((a.hi == b.hi) & (a.lo < b.lo))


def ts_eq(a: TS, b: TS):
    return (a.hi == b.hi) & (a.lo == b.lo)


def ts_is_zero(a: TS):
    return (a.hi == 0) & (a.lo == 0)


def hash_prio(x, salt):
    """Deterministic pseudo-random priority (models arrival order)."""
    x = (x.astype(jnp.uint32) * jnp.uint32(2654435761)) ^ jnp.uint32(salt)
    x = x ^ (x >> 16)
    return (x & jnp.uint32(0x7FFFFFFF)).astype(jnp.int32)


def cas_winner(keys, prio_hi, prio_lo, active, n_records, place):
    """Per-key lexicographic-min (prio_hi, prio_lo) among active requests;
    ``place`` lays out the per-record scratch as the store is laid out."""
    big = jnp.int32(2**31 - 1)
    best_hi = place(jnp.full((n_records,), big, jnp.int32)).at[keys].min(
        jnp.where(active, prio_hi, big), mode="drop"
    )
    hi_ok = active & (prio_hi == best_hi[keys])
    best_lo = place(jnp.full((n_records,), big, jnp.int32)).at[keys].min(
        jnp.where(hi_ok, prio_lo, big), mode="drop"
    )
    return hi_ok & (prio_lo == best_lo[keys])


# ---------------------------------------------------------------------------
# Configuration, workload contract, store and per-slot state
# ---------------------------------------------------------------------------


class Workload(NamedTuple):
    name: str
    rw: int
    max_ops: int
    init_value: int
    gen: Callable  # gen(key, node, slot) -> (keys (K,), is_w (K,), valid (K,))
    execute: Callable  # execute(keys, is_w, valid, rvals (K, RW)) -> wvals (K, RW)
    exec_ticks: Any = 1


@dataclass(frozen=True)
class EngineConfig:
    n_nodes: int
    coroutines: int
    records_per_node: int
    rw: int
    max_ops: int
    hybrid: Any  # int32[N_HYBRID_STAGES], traced
    exec_ticks: Any
    seed: Any
    mvcc_slots: int = 4
    doorbell: bool = True
    fdt: Any = jnp.float32
    # lays out an array whose leading axis is the record: the identity on one
    # device, a sharding constraint over a device mesh
    place: Callable = lambda a: a

    @property
    def n_slots(self) -> int:
        return self.n_nodes * self.coroutines

    @property
    def n_records(self) -> int:
        return self.n_nodes * self.records_per_node


def lock_store(n_records: int) -> Dict:
    """The words every protocol's store has: the lock timestamp and a version."""
    return {k: jnp.zeros((n_records,), jnp.int32) for k in ("lock_hi", "lock_lo", "ver")}


def init_state(ec: EngineConfig) -> Dict:
    N, K, RW = ec.n_slots, ec.max_ops, ec.rw

    def z(*s):
        return jnp.zeros(s, jnp.int32)

    def zb(*s):
        return jnp.zeros(s, bool)

    def zf(*s):
        return jnp.zeros(s, ec.fdt)

    return {
        "keys": z(N, K), "is_w": zb(N, K), "valid": zb(N, K),
        "rvals": z(N, K, RW), "wvals": z(N, K, RW),
        "stage": jnp.full((N,), -1, jnp.int32), "substep": z(N),
        "ts_hi": z(N), "ts_lo": z(N), "clock": z(N),
        "locked": zb(N, K), "served": zb(N, K),
        "ver_seen": z(N, K), "wts_seen_hi": z(N, K), "wts_seen_lo": z(N, K),
        "exec_left": z(N), "lat_us": zf(N), "rounds": z(N), "txn_no": z(N),
        "n_commit": z(N), "n_abort": z(N), "lat_sum": zf(N), "rt_sum": zf(N),
        "stage_us": zf(N_STAGES),
    }


def slot_ids(ec: EngineConfig):
    sid = jnp.arange(ec.n_slots, dtype=jnp.int32)
    return sid, sid // ec.coroutines


def regen_txns(ec: EngineConfig, wl: Workload, st: Dict, mask) -> Dict:
    """Fresh transactions (and fresh timestamps) for the slots in ``mask``."""
    sid, node = slot_ids(ec)
    key0 = jax.random.PRNGKey(ec.seed)

    def gen_one(s, n, t_no):
        return wl.gen(jax.random.fold_in(jax.random.fold_in(key0, s), t_no), n, s)

    keys, is_w, valid = jax.vmap(gen_one)(sid, node, st["txn_no"])
    st = dict(st)
    m2 = mask[:, None]
    st["keys"] = jnp.where(m2, keys, st["keys"])
    st["is_w"] = jnp.where(m2, is_w, st["is_w"])
    st["valid"] = jnp.where(m2, valid, st["valid"])
    st["txn_no"] = jnp.where(mask, st["txn_no"] + 1, st["txn_no"])
    st["locked"] = jnp.where(m2, False, st["locked"])
    st["served"] = jnp.where(m2, False, st["served"])
    st["substep"] = jnp.where(mask, 0, st["substep"])
    st["rounds"] = jnp.where(mask, 0, st["rounds"])
    st["lat_us"] = jnp.where(mask, 0.0, st["lat_us"]).astype(ec.fdt)
    clock = st["clock"] + mask.astype(jnp.int32)
    st["ts_hi"] = jnp.where(mask, clock, st["ts_hi"])
    st["ts_lo"] = jnp.where(mask, sid + 1, st["ts_lo"])
    st["clock"] = clock
    return st


# ---------------------------------------------------------------------------
# Per-tick service capacity and latency accounting
# ---------------------------------------------------------------------------


def service_ops(ec: EngineConfig, cm: CostModel, st: Dict, op_mask, is_rpc, salt):
    """Which requested ops are served this tick under per-node capacities.

    Requests are ranked per (destination node, plane) by a hashed arrival
    priority; the first ``cap`` of each group are served.  Returns
    (served (N, K), same-plane load at each op's destination (N, K))."""
    N, K = op_mask.shape
    active = op_mask.reshape(-1)
    dest = jnp.clip(st["keys"].reshape(-1) // ec.records_per_node, 0, ec.n_nodes - 1)
    rpc_f = jnp.broadcast_to(is_rpc, op_mask.shape).reshape(-1)
    _, node = slot_ids(ec)
    exec_load = jnp.zeros((ec.n_nodes,), jnp.int32).at[node].add(
        (st["exec_left"] > 0).astype(jnp.int32)
    )
    rpc_cap = jnp.maximum(cm.handler_cap - exec_load * jnp.maximum(1, ec.exec_ticks), 1)
    nic_cap = jnp.broadcast_to(
        jnp.asarray(cm.nic_eff_cap(), jnp.float32).astype(jnp.int32), (ec.n_nodes,)
    )
    sid, _ = slot_ids(ec)
    op_ix = sid[:, None] * K + jnp.arange(K, dtype=jnp.int32)[None, :]
    prio = hash_prio(op_ix.reshape(-1) + st["ts_lo"].repeat(K), salt)
    group = dest * 2 + rpc_f.astype(jnp.int32)
    order = jnp.argsort(jnp.where(active, group * (2**20) + (prio & (2**20 - 1)), 2**30))
    g_sorted = group[order]
    first = jnp.concatenate([jnp.ones(1, bool), g_sorted[1:] != g_sorted[:-1]])
    pos = jnp.arange(N * K)
    seg_start = jax.lax.associative_scan(jnp.maximum, jnp.where(first, pos, 0))
    rank = jnp.zeros(N * K, jnp.int32).at[order].set((pos - seg_start).astype(jnp.int32))
    served = active & (rank < jnp.where(rpc_f, rpc_cap[dest], nic_cap[dest]))
    load = jnp.zeros((ec.n_nodes, 2), jnp.int32).at[dest, rpc_f.astype(jnp.int32)].add(
        active.astype(jnp.int32)
    )
    op_load = load[dest, rpc_f.astype(jnp.int32)].astype(jnp.float32)
    return served.reshape(N, K), op_load.reshape(N, K)


def base_time(ec: EngineConfig, cm: CostModel, st: Dict, canon_stage) -> Dict:
    """Every active transaction spends one tick in its canonical stage."""
    st = dict(st)
    active = canon_stage >= 0
    st["lat_us"] = st["lat_us"] + jnp.where(active, cm.tick_us, 0.0).astype(ec.fdt)
    st["stage_us"] = st["stage_us"].at[jnp.where(active, canon_stage, N_STAGES)].add(
        jnp.where(active, cm.tick_us, 0.0).astype(ec.fdt), mode="drop"
    )
    return st


def account_round(ec, cm, st, stage_id, op_mask, op_load, primitive, bytes_per_op, n_verbs=1):
    """A round's latency beyond the tick base: RTT delta, MMIO, wire, queueing."""
    per_op = round_latency_us(
        cm, jnp.asarray(primitive == RPC), op_load, bytes_per_op,
        n_verbs=n_verbs, doorbell=ec.doorbell,
    ) - cm.tick_us
    per_txn = jnp.where(op_mask, per_op, -jnp.inf).max(axis=1)
    txn_mask = op_mask.any(axis=1)
    per_txn = jnp.where(txn_mask, per_txn, 0.0).astype(ec.fdt)
    st = dict(st)
    st["lat_us"] = st["lat_us"] + per_txn
    st["rounds"] = st["rounds"] + txn_mask.astype(jnp.int32)
    st["stage_us"] = st["stage_us"].at[stage_id].add(per_txn.sum())
    return st


# ---------------------------------------------------------------------------
# Store access
# ---------------------------------------------------------------------------


def gather(arr, keys):
    """arr (R, ...) at keys (N, K) -> (N, K, ...)."""
    return arr[keys.reshape(-1)].reshape(keys.shape + arr.shape[1:])


def gather2(arr, keys, sel):
    """(row, slot) gather from an (R, S, ...) array."""
    return arr[keys.reshape(-1), sel.reshape(-1)].reshape(keys.shape + arr.shape[2:])


def scatter(arr, idx, vals, op="set"):
    """Row scatter; ``idx`` >= R is dropped."""
    return arr.at[idx].add(vals, mode="drop") if op == "add" else arr.at[idx].set(vals, mode="drop")


def scatter2(arr, idx, sel, vals):
    return arr.at[idx, sel].set(vals, mode="drop")


def scatter_ts_max(ec: EngineConfig, hi_arr, lo_arr, idx, ch, cl, active):
    """Lexicographic scatter-max of (ch, cl) into a stored timestamp pair."""
    r = ec.n_records
    cand_hi = ec.place(jnp.full((r,), -(2**31), jnp.int32)).at[idx].max(
        jnp.where(active, ch, -(2**31)), mode="drop"
    )
    at_max = active & (ch == cand_hi[jnp.clip(idx, 0, r - 1)])
    cand_lo = ec.place(jnp.full((r,), -(2**31), jnp.int32)).at[idx].max(
        jnp.where(at_max, cl, -(2**31)), mode="drop"
    )
    upd = (hi_arr < cand_hi) | ((hi_arr == cand_hi) & (lo_arr < cand_lo))
    return jnp.where(upd, cand_hi, hi_arr), jnp.where(upd, cand_lo, lo_arr)


def try_lock(ec: EngineConfig, store, st, op_mask, prio_hi, prio_lo):
    """Arbitrated CAS on lock words: a CAS wins iff the lock is free (or
    already this txn's) and it is the key's arbitration winner this round."""
    N, K = op_mask.shape
    keys_f = st["keys"].reshape(-1)
    win = cas_winner(keys_f, prio_hi.reshape(-1), prio_lo.reshape(-1), op_mask.reshape(-1),
                     ec.n_records, ec.place)
    lock = TS(gather(store["lock_hi"], st["keys"]), gather(store["lock_lo"], st["keys"]))
    mine = ts_eq(lock, TS(st["ts_hi"][:, None], st["ts_lo"][:, None]))
    won = win.reshape(N, K) & (ts_is_zero(lock) | mine) & op_mask
    wf = won.reshape(-1)
    idx_w = jnp.where(wf, keys_f, ec.n_records)
    store = dict(store)
    store["lock_hi"] = scatter(store["lock_hi"], idx_w, jnp.where(wf, jnp.repeat(st["ts_hi"], K), 0))
    store["lock_lo"] = scatter(store["lock_lo"], idx_w, jnp.where(wf, jnp.repeat(st["ts_lo"], K), 0))
    return won, store


def release_locks(ec: EngineConfig, store, st, rel_mask):
    m = (rel_mask & st["locked"]).reshape(-1)
    idx = jnp.where(m, st["keys"].reshape(-1), ec.n_records)
    store = dict(store)
    store["lock_hi"] = scatter(store["lock_hi"], idx, 0)
    store["lock_lo"] = scatter(store["lock_lo"], idx, 0)
    return store


def finish_commit(st: Dict, mask) -> Dict:
    st = dict(st)
    st["n_commit"] = st["n_commit"] + mask.astype(jnp.int32)
    st["lat_sum"] = st["lat_sum"] + jnp.where(mask, st["lat_us"], 0.0).astype(st["lat_sum"].dtype)
    st["rt_sum"] = st["rt_sum"] + jnp.where(mask, st["rounds"], 0).astype(st["rt_sum"].dtype)
    return st


def finish_abort(st: Dict, mask) -> Dict:
    st = dict(st)
    st["n_abort"] = st["n_abort"] + mask.astype(jnp.int32)
    return st


# ---------------------------------------------------------------------------
# Run loop and metrics
# ---------------------------------------------------------------------------


class Protocol(NamedTuple):
    """A protocol as a reference plug-in gives it (``protocol_<name>.py``)."""

    tick: Callable  # tick(ec, cm, wl, st, store, t) -> (st, store)
    init_store: Callable  # init_store(n_records, rw, init_value, n_versions) -> {name: (R, ...)}


def run(proto: Protocol, ec: EngineConfig, cm: CostModel, wl: Workload, n_ticks: int,
        warmup: int) -> Dict:
    """Warm up, reset the counters, run ``n_ticks`` measured ticks, summarize.
    Every tick's store is laid out by ``ec.place``."""

    def place(store):
        return {k: ec.place(v) for k, v in store.items()}

    store = place(proto.init_store(ec.n_records, ec.rw, wl.init_value, ec.mvcc_slots))
    st = init_state(ec)

    def body(carry, t):
        st, store = proto.tick(ec, cm, wl, *carry, t)
        return (st, place(store)), None

    if warmup:
        (st, store), _ = jax.lax.scan(body, (st, store), jnp.arange(warmup))
        for k in ("n_commit", "n_abort", "lat_sum", "rt_sum", "stage_us"):
            st[k] = jnp.zeros_like(st[k])
    (st, store), _ = jax.lax.scan(body, (st, store), jnp.arange(warmup, warmup + n_ticks))
    commits = st["n_commit"].sum()
    aborts = st["n_abort"].sum()
    return {
        "commits": commits,
        "aborts": aborts,
        "throughput_mtps": commits / (n_ticks * cm.tick_us),
        "avg_latency_us": st["lat_sum"].sum() / jnp.maximum(commits, 1),
        "abort_rate": aborts / jnp.maximum(commits + aborts, 1),
        "avg_round_trips": st["rt_sum"].sum() / jnp.maximum(commits, 1),
        "stage_us_per_commit": st["stage_us"] / jnp.maximum(commits, 1),
    }

