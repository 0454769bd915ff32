"""Bulk-synchronous vectorized transaction engine.

Execution model (paper §3.2 mapped to lockstep SPMD, see DESIGN.md §2):
one engine *tick* = one network round.  Every node runs C co-routine slots;
each slot drives one transaction through its protocol's stage machine.  A
stage occupies >= 1 tick depending on the primitive (one-sided CAS->READ is
2 rounds unless doorbell-batched; RPC is 1 round + remote-CPU queueing).

Capacity semantics (what creates the paper's effects):
  * RPC requests queue on the destination handler CPU: a node services at
    most `handler_cap - exec_load` RPC requests per tick (local co-routines
    busy in their execution phase starve the handler — Fig. 9), excess
    requests are deferred a tick.
  * one-sided verbs queue on the RNIC (`nic_cap`, degraded by QP pressure
    for emulated large clusters — Fig. 10).

All state lives in dense arrays; a tick is one jitted function; runs are
`lax.scan`s — the whole simulator is differentiable-by-accident and fast.
"""
from __future__ import annotations

import dataclasses
from dataclasses import dataclass
from typing import Any, Callable, Dict, NamedTuple, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import costmodel as cmod
from repro.core import planes
from repro.core.arbiter import hash_prio
from repro.kernels import ops as kops
from repro.core.costmodel import N_STAGES, RPC, CostModel
from repro.core.planes import NodeShard
from repro.core.store import init_store
from repro.core.timestamps import TS, ts_eq, ts_is_zero


@dataclass(frozen=True)
class EngineConfig:
    """Engine configuration, split into two kinds of fields.

    *Static shape params* (protocol, n_nodes, coroutines, records_per_node,
    rw, max_ops, doorbell, history_cap, mvcc_slots) determine array shapes
    and compiled program structure; they must be concrete Python values and
    every distinct combination costs one XLA compilation.

    *Per-run knobs* (hybrid, exec_ticks, seed) may hold traced jnp scalars /
    arrays: no protocol code is allowed to Python-branch on them, so a whole
    grid of knob settings can share one compiled program via
    `repro.core.sweep.run_grid` (vmap over configs).  `hybrid` is either a
    Python tuple (sequential path — XLA folds the selects) or an
    int32[N_HYBRID_STAGES] array (batched path — `lax.select` at runtime).

    *Bucketed padding* (DESIGN.md §6): `active_coroutines` /
    `active_records_per_node` turn the two static shape axes into traced
    knobs.  The arrays are sized for the padded shapes (`coroutines`,
    `records_per_node`) while only the first `active_*` coroutine slots per
    node run transactions and only the first `active_records_per_node`
    record offsets per node are addressable; padded slots stay at stage -1
    forever and padded records are never generated, so neither leaks into
    commit/abort/latency/byte counters.  Every identity-derived value
    (RNG streams, timestamps, arbitration priorities) uses LOGICAL ids —
    `logical_ids` / `op_index` below — so a padded run is bitwise-equal to
    the same config run unpadded.  `None` (the default) means "axis not
    padded": the logical ids fold to the physical ones at trace time.

    *Kernel plane* (DESIGN.md §9): `kernel_plane` selects the backend for
    the three fused hot paths — lock arbitration, the MVCC version pick,
    and the doorbell-batched multi-read ("jnp" reference gather/scatter,
    "pallas" compiled kernels, "pallas_interpret" for CPU CI).  Static, so
    it is part of the compiled program identity; every plane keeps integer
    counters bitwise-equal to "jnp" (the kernel-parity CI contract).

    *Node sharding* (DESIGN.md §7): `shard` is None for the dense
    single-device engine, or a :class:`~repro.core.planes.NodeShard` when
    the tick runs SPMD under `shard_map` (see :func:`run_sharded`).  Store
    arrays are then LOCAL shards (each mesh shard owns whole simulated
    nodes' record rows) and every store access in the engine and the
    protocol effect hooks routes through the plane primitives below
    (`read_rows` / `write_rows` / `arb_winner` / ...), which lower to the
    dense gather/scatter when `shard` is None and to owner-local work plus
    one collective exchange per round when sharded.
    """

    protocol: str
    n_nodes: int = 4
    coroutines: int = 10  # per node (paper default: 10 threads x co-routines)
    records_per_node: int = 16384
    # traced active extents for bucket-padded sweeps (None = unpadded axis)
    active_coroutines: Any = None
    active_records_per_node: Any = None
    rw: int = 2  # record words (YCSB 64B = 16)
    max_ops: int = 4  # K
    hybrid: Tuple[int, ...] = (RPC,) * N_STAGES  # primitive per stage (traceable)
    doorbell: bool = True
    # cross-stage doorbell merging (paper §4.2, rounds.fuse_log_commit):
    # static opt-in — off by default so counters stay bitwise reproducible
    # against the pre-merge stage machines
    merge_stages: bool = False
    exec_ticks: int = 1  # execution-phase ticks (YCSB computation knob, traceable)
    history_cap: int = 0  # >0: record commit history for serializability checks
    mvcc_slots: int = 4  # MVCC static version slots (paper: 4; ablation knob)
    seed: int = 0  # traceable
    # kernel plane for the fused hot paths (static; see kernels/ops.py)
    kernel_plane: str = "jnp"
    # node-sharded SPMD execution (None = dense single-device engine)
    shard: Optional[NodeShard] = None

    @property
    def n_slots(self) -> int:
        return self.n_nodes * self.coroutines

    @property
    def n_records(self) -> int:
        return self.n_nodes * self.records_per_node

    @property
    def records_local(self) -> int:
        """Store rows owned by one mesh shard (= n_records when dense)."""
        return self.n_records // (self.shard.n_shards if self.shard else 1)


class Workload(NamedTuple):
    name: str
    rw: int
    max_ops: int
    init_value: int
    # gen(key, slot_node, slot_id) -> (keys (K,), is_w (K,), valid (K,))
    gen: Callable
    # execute(keys, is_w, valid, rvals (K,RW)) -> wvals (K,RW)
    execute: Callable
    exec_ticks: int = 1


# ---------------------------------------------------------------------------
# State
# ---------------------------------------------------------------------------


def init_state(ec: EngineConfig, wl: Workload) -> Dict:
    N, K, RW = ec.n_slots, ec.max_ops, wl.rw
    def z(*s):
        return jnp.zeros(s, jnp.int32)

    def zb(*s):
        return jnp.zeros(s, bool)

    def zf(*s):
        return jnp.zeros(s, jnp.float32)

    st = {
        "keys": z(N, K),
        "is_w": zb(N, K),
        "valid": zb(N, K),
        "rvals": z(N, K, RW),
        "wvals": z(N, K, RW),
        "stage": jnp.full((N,), -1, jnp.int32),  # -1 => fresh slot
        "substep": z(N),
        "ts_hi": z(N),
        "ts_lo": z(N),
        "clock": z(N),
        "locked": zb(N, K),
        "served": zb(N, K),
        "seq_seen": z(N, K),
        "ver_seen": z(N, K),
        "wts_seen_hi": z(N, K),  # sundial: wts at fetch time
        "wts_seen_lo": z(N, K),
        "commit_hi": z(N),  # sundial: commit_tts lease
        "commit_lo": z(N),
        "exec_left": z(N),
        "lat_us": zf(N),
        "rounds": z(N),
        "txn_no": z(N),
        "n_commit": z(N),
        "n_abort": z(N),
        "lat_sum": zf(N),
        "rt_sum": zf(N),
        "stage_us": zf(N_STAGES),
        "wait_us": zf(1),
        "tick": z(1),
    }
    if ec.history_cap:
        H = ec.history_cap
        st["h_idx"] = z(1)
        st["h_keys"] = z(H, K)
        st["h_ver_r"] = z(H, K)
        st["h_ver_w"] = z(H, K)
        st["h_isw"] = zb(H, K)
        st["h_valid"] = zb(H, K)
        st["h_ts_hi"] = z(H)
        st["h_ts_lo"] = z(H)
    return st


def slot_ids(ec: EngineConfig):
    sid = jnp.arange(ec.n_slots, dtype=jnp.int32)
    return sid, sid // ec.coroutines  # (slot, node)


def logical_ids(ec: EngineConfig):
    """(logical slot id, node, alive mask) under bucket padding.

    The logical id is the slot's identity in the UNPADDED system
    (node * active_coroutines + coroutine); every id-derived quantity (RNG
    folds, timestamp lo words, arbitration priorities) must use it so a
    padded run stays bitwise-equal to its unpadded reference.  ``alive`` is
    None when the coroutine axis is unpadded (the physical ids already are
    the logical ids and no slot is dead).
    """
    sid, node = slot_ids(ec)
    if ec.active_coroutines is None:
        return sid, node, None
    c = sid % ec.coroutines
    act = jnp.asarray(ec.active_coroutines, jnp.int32)
    return node * act + c, node, c < act


def alive_mask(ec: EngineConfig):
    """(n_slots,) bool of live slots, or None when nothing is padded."""
    return logical_ids(ec)[2]


def op_index(ec: EngineConfig, k: int):
    """(n_slots, k) logical flat op index: ``lsid * k + op``.

    Identity basis for hashed arbitration priorities (twopl/occ lock
    stages); equals ``arange(n_slots * k)`` when the coroutine axis is
    unpadded and stays padding-invariant otherwise.
    """
    lsid, _, _ = logical_ids(ec)
    return lsid[:, None] * k + jnp.arange(k, dtype=jnp.int32)[None, :]


def physical_keys(ec: EngineConfig, keys):
    """Map workload-generated LOGICAL keys onto the padded store layout.

    Logical key k (over n_nodes * active_records_per_node records) keeps
    its owning node and per-node offset: node k // aR gets physical row
    ``node * records_per_node + k % aR``.  Identity when the record axis is
    unpadded.  Monotone, so per-key orderings (arbitration, version chains,
    CALVIN waves) are preserved bitwise.
    """
    if ec.active_records_per_node is None:
        return keys
    a_r = jnp.asarray(ec.active_records_per_node, jnp.int32)
    return (keys // a_r) * ec.records_per_node + keys % a_r


def regen_txns(ec: EngineConfig, wl: Workload, st: Dict, mask, *, new_ts=True) -> Dict:
    """Generate fresh transactions for slots in `mask`.

    All identity flows through LOGICAL slot ids so bucket-padded runs match
    their unpadded references bitwise; dead (padded) slots never regenerate.
    """
    lsid, node, alive = logical_ids(ec)
    if alive is not None:
        mask = mask & alive
    key0 = jax.random.PRNGKey(ec.seed)

    def gen_one(s, n, t_no):
        k = jax.random.fold_in(jax.random.fold_in(key0, s), t_no)
        return wl.gen(k, n, s)

    keys, is_w, valid = jax.vmap(gen_one)(lsid, node, st["txn_no"])
    keys = physical_keys(ec, keys)
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
    st["lat_us"] = jnp.where(mask, 0.0, st["lat_us"])
    if new_ts:
        clock = st["clock"] + mask.astype(jnp.int32)
        # lo encodes the unique LOGICAL slot id (padding-invariant)
        ts = TS(jnp.asarray(clock, jnp.int32), jnp.asarray(lsid + 1, jnp.int32))
        st["ts_hi"] = jnp.where(mask, ts.hi, st["ts_hi"])
        st["ts_lo"] = jnp.where(mask, ts.lo, st["ts_lo"])
        st["clock"] = clock
    return st


def txn_ts(st) -> TS:
    return TS(st["ts_hi"], st["ts_lo"])


# ---------------------------------------------------------------------------
# Per-tick service-capacity model
# ---------------------------------------------------------------------------


def service_ops(ec: EngineConfig, cm: CostModel, st: Dict, op_mask, primitive_is_rpc, salt):
    """Which requested ops get served this tick, given per-node capacities.

    op_mask (N,K) bool: ops wanting a round this tick.  Returns
    (served (N,K), dest_load (N,K) fp32 — same-plane load at each op's dest).

    Node-sharded: the per-(dest, plane) ranking is the DESTINATION's job —
    each shard ranks only the requests arriving at its nodes (its handler
    CPU / RNIC queue) and the served bits combine in one reply exchange.
    Owned groups rank identically to the dense global sort (segment ranks
    are per-group), so the outcome is bitwise-equal.
    """
    with jax.named_scope("service"):
        N, K = op_mask.shape
        keys_f = st["keys"].reshape(-1)
        active = op_mask.reshape(-1)
        dest = jnp.clip(keys_f // ec.records_per_node, 0, ec.n_nodes - 1)
        is_rpc_f = jnp.broadcast_to(primitive_is_rpc, op_mask.shape).reshape(-1)

        # execution-phase co-routines starve their node's RPC handler (Fig. 9)
        _, node, _ = logical_ids(ec)
        exec_load = jnp.zeros((ec.n_nodes,), jnp.int32).at[node].add(
            (st["exec_left"] > 0).astype(jnp.int32)
        )
        rpc_cap = jnp.maximum(cm.handler_cap - exec_load * jnp.maximum(1, ec.exec_ticks), 1)
        nic_eff = jnp.asarray(cm.nic_eff_cap(), jnp.float32).astype(jnp.int32)
        nic_cap = jnp.broadcast_to(nic_eff, (ec.n_nodes,))

        # destination-side view: when sharded, a shard only ranks the requests
        # targeting the nodes it owns (the rest sort to the inactive tail)
        if ec.shard is None:
            arrived = active
        else:
            nodes_per_shard = ec.n_nodes // ec.shard.n_shards
            my_node = (dest // nodes_per_shard) == jax.lax.axis_index(ec.shard.axis)
            arrived = active & my_node

        # rank requests within (dest, plane) by hashed priority (arrival order);
        # the LOGICAL op index keeps the draws padding-invariant
        prio = hash_prio(op_index(ec, K).reshape(-1) + st["ts_lo"].repeat(K), salt)
        group = dest * 2 + is_rpc_f.astype(jnp.int32)
        sort_key = jnp.where(arrived, group * (2**20) + (prio & (2**20 - 1)), 2**30)
        order = jnp.argsort(sort_key)
        # rank within group via cumulative count in sorted order
        g_sorted = group[order]
        first = jnp.concatenate([jnp.ones(1, bool), g_sorted[1:] != g_sorted[:-1]])
        idx_in_sorted = jnp.arange(N * K)
        seg_start = jnp.where(first, idx_in_sorted, 0)
        seg_start = jax.lax.associative_scan(jnp.maximum, seg_start)
        rank_sorted = idx_in_sorted - seg_start
        rank = jnp.zeros(N * K, jnp.int32).at[order].set(rank_sorted.astype(jnp.int32))

        cap = jnp.where(is_rpc_f, rpc_cap[dest], nic_cap[dest])
        served = arrived & (rank < cap)
        if ec.shard is not None:
            # served-bit reply exchange back to the coordinators
            with jax.named_scope("exchange"):
                served = jax.lax.psum(served.astype(jnp.int32), ec.shard.axis) > 0

        # same-plane per-dest load (for queue-delay accounting; (n_nodes, 2) is
        # coordinator bookkeeping over the replicated request set — no exchange)
        load = jnp.zeros((ec.n_nodes, 2), jnp.int32).at[dest, is_rpc_f.astype(jnp.int32)].add(
            active.astype(jnp.int32)
        )
        op_load = load[dest, is_rpc_f.astype(jnp.int32)].astype(jnp.float32)
        return served.reshape(N, K), op_load.reshape(N, K)


def base_time(ec: EngineConfig, cm: CostModel, st: Dict, canon_stage) -> Dict:
    """Per-tick base time: every active txn spends tick_us in its stage.

    canon_stage (N,) int32: canonical cost-stage id of each active txn
    (negative => inactive).  Round extras (queue delay, wire, MMIO, plane
    RTT delta) are added separately by account_round.
    """
    st = dict(st)
    active = canon_stage >= 0
    st["lat_us"] = st["lat_us"] + jnp.where(active, cm.tick_us, 0.0)
    st["stage_us"] = st["stage_us"].at[jnp.where(active, canon_stage, N_STAGES)].add(
        jnp.where(active, cm.tick_us, 0.0), mode="drop"
    )
    return st


def account_round(
    ec: EngineConfig,
    cm: CostModel,
    st: Dict,
    stage_id: int,
    op_mask,
    op_load,
    primitive: int,
    bytes_per_op: float,
    n_verbs: int = 1,
) -> Dict:
    """Attribute one round's *extras* (beyond the tick base) per txn.

    extras = (plane RTT - tick) + MMIO + wire bytes + destination queueing.
    Also counts the network round for the round-trip metric (Fig. 5).
    """
    is_rpc = jnp.asarray(primitive == RPC)
    per_op = cmod.round_latency_us(
        cm, is_rpc, op_load, bytes_per_op, n_verbs=n_verbs, doorbell=ec.doorbell
    ) - cm.tick_us
    per_op = jnp.where(op_mask, per_op, -jnp.inf)
    per_txn = per_op.max(axis=1)  # outstanding requests overlap within a round
    txn_mask = op_mask.any(axis=1)
    per_txn = jnp.where(txn_mask, per_txn, 0.0)
    st = dict(st)
    st["lat_us"] = st["lat_us"] + per_txn
    st["rounds"] = st["rounds"] + txn_mask.astype(jnp.int32)
    st["stage_us"] = st["stage_us"].at[stage_id].add(per_txn.sum())
    return st


# ---------------------------------------------------------------------------
# Store access helpers (the two communication planes differ only in cost and
# round structure; raw memory semantics are identical — DESIGN.md §2).
# Every helper routes through the planes.py transport when the config is
# node-sharded (DESIGN.md §7): the store array is then a LOCAL shard and the
# remote access becomes owner-local work plus one collective exchange.
# ---------------------------------------------------------------------------


def gather_rows(arr, keys):
    """arr (R, ...) at keys (N,K) -> (N,K,...) (dense, whole-store view)."""
    return arr[keys.reshape(-1)].reshape(keys.shape + arr.shape[1:])


def read_rows(ec: EngineConfig, arr, keys):
    """Plane-routed row gather: one-sided READ round when node-sharded."""
    with jax.named_scope("gather"):
        if ec.shard is None:
            return gather_rows(arr, keys)
        return planes.node_read(ec.shard, arr, keys)


def read_rows_many(ec: EngineConfig, arrs: Sequence, keys) -> Tuple:
    """Gather several store arrays at the same keys.

    Dense: independent gathers (jnp plane) or ONE multi-read kernel
    dispatch (Pallas planes).  Sharded: ONE doorbell-batched exchange
    (planes.node_read_batch) — dependent metadata reads of a round ride a
    single collective, mirroring §4.2's doorbell batching.
    """
    with jax.named_scope("gather"):
        if ec.shard is None:
            if kops.is_pallas(ec.kernel_plane):
                return kops.gather_many(arrs, keys, plane=ec.kernel_plane)
            return tuple(gather_rows(a, keys) for a in arrs)
        return planes.node_read_batch(ec.shard, arrs, keys, kernel_plane=ec.kernel_plane)


def read_rows2(ec: EngineConfig, arr, keys, sel):
    """(row, slot) gather from a (R, S, ...) store array (MVCC versions)."""
    with jax.named_scope("gather"):
        if ec.shard is None:
            flat = arr[keys.reshape(-1), sel.reshape(-1)]
            return flat.reshape(keys.shape + arr.shape[2:])
        return planes.node_read2(ec.shard, arr, keys, sel)


def write_rows(ec: EngineConfig, arr, idx, vals, *, op: str = "set"):
    """Plane-routed row scatter.  ``idx`` (M,) global rows with the dense
    drop sentinel (>= n_records) for masked-off requests."""
    if ec.shard is None:
        if op == "add":
            return arr.at[idx].add(vals, mode="drop")
        return arr.at[idx].set(vals, mode="drop")
    return planes.node_write(ec.shard, arr, idx, vals, op=op)


def write_rows2(ec: EngineConfig, arr, idx, sel, vals, *, op: str = "set"):
    """(row, slot) scatter into a (R, S, ...) store array."""
    if ec.shard is None:
        if op == "add":
            return arr.at[idx, sel].add(vals, mode="drop")
        return arr.at[idx, sel].set(vals, mode="drop")
    return planes.node_write2(ec.shard, arr, idx, sel, vals, op=op)


def arb_winner(ec: EngineConfig, keys, prio_hi, prio_lo, active):
    """Per-key CAS arbitration (the RNIC's serialization of one round).

    Dense: global scatter-min (jnp plane) or the all-pairs arbitration
    kernel (Pallas planes) — same lexicographic-min winners bitwise.
    Sharded: each owner arbitrates its rows' contest locally and the
    won-bits combine in one exchange — bitwise the same winners (a key's
    contest happens entirely at its owner).
    """
    with jax.named_scope("arbitrate"):
        if ec.shard is None:
            return kops.cas_arbitrate(
                keys, prio_hi, prio_lo, active, ec.n_records, plane=ec.kernel_plane
            )
        return planes.node_cas_winner(
            ec.shard, ec.records_local, keys, prio_hi, prio_lo, active,
            kernel_plane=ec.kernel_plane,
        )


def scatter_ts_max(ec: EngineConfig, hi_arr, lo_arr, idx, ch, cl, active):
    """Lexicographic scatter-max of (ch, cl) timestamps into a store TS pair
    (MVCC rts bump, SUNDIAL lease renewal).  Owner-local when sharded: the
    candidate reduction runs over the local rows only."""
    if ec.shard is None:
        r, li, act = ec.n_records, idx, active
    else:
        r = ec.records_local
        li = planes.local_ix_drop(ec.shard, r, idx)
        act = active & (li < r)
    cand_hi = jnp.full((r,), -(2**31), jnp.int32).at[li].max(
        jnp.where(act, ch, -(2**31)), mode="drop"
    )
    at_max = act & (ch == cand_hi[jnp.clip(li, 0, r - 1)])
    cand_lo = jnp.full((r,), -(2**31), jnp.int32).at[li].max(
        jnp.where(at_max, cl, -(2**31)), mode="drop"
    )
    upd = (hi_arr < cand_hi) | ((hi_arr == cand_hi) & (lo_arr < cand_lo))
    return jnp.where(upd, cand_hi, hi_arr), jnp.where(upd, cand_lo, lo_arr)


def try_lock(ec: EngineConfig, store, st, op_mask, prio_hi, prio_lo, *, reentrant_ts=None):
    """Arbitrated CAS on lock words for ops in op_mask.

    Returns (won (N,K), store').  A CAS wins iff the lock is free (or held by
    this txn) and it is the per-key arbitration winner this round.  Sharded:
    the owner arbitrates + applies the CAS on its rows; the won-bits and the
    returned lock words are one batched reply exchange (os_cas semantics).
    """
    N, K = op_mask.shape
    keys_f = st["keys"].reshape(-1)
    active = op_mask.reshape(-1)
    win = arb_winner(ec, keys_f, prio_hi.reshape(-1), prio_lo.reshape(-1), active)
    lock_hi, lock_lo = read_rows_many(ec, (store["lock_hi"], store["lock_lo"]), st["keys"])
    lock = TS(lock_hi, lock_lo)
    mine = ts_eq(lock, TS(st["ts_hi"][:, None], st["ts_lo"][:, None]))
    free = ts_is_zero(lock) | mine
    won = win.reshape(N, K) & free & op_mask
    wf = won.reshape(-1)
    ts = txn_ts(st)
    new_hi = jnp.repeat(ts.hi, K)
    new_lo = jnp.repeat(ts.lo, K)
    store = dict(store)
    idx_w = jnp.where(wf, keys_f, ec.n_records)
    store["lock_hi"] = write_rows(ec, store["lock_hi"], idx_w, jnp.where(wf, new_hi, 0))
    store["lock_lo"] = write_rows(ec, store["lock_lo"], idx_w, jnp.where(wf, new_lo, 0))
    return won, store


def release_locks(ec: EngineConfig, store, st, rel_mask):
    """Zero lock words this txn holds for ops in rel_mask."""
    keys_f = st["keys"].reshape(-1)
    m = (rel_mask & st["locked"]).reshape(-1)
    store = dict(store)
    idx = jnp.where(m, keys_f, ec.n_records)
    store["lock_hi"] = write_rows(ec, store["lock_hi"], idx, 0)
    store["lock_lo"] = write_rows(ec, store["lock_lo"], idx, 0)
    return store


def finish_commit(ec: EngineConfig, cm: CostModel, st: Dict, mask) -> Dict:
    st = dict(st)
    st["n_commit"] = st["n_commit"] + mask.astype(jnp.int32)
    st["lat_sum"] = st["lat_sum"] + jnp.where(mask, st["lat_us"], 0.0)
    st["rt_sum"] = st["rt_sum"] + jnp.where(mask, st["rounds"].astype(jnp.float32), 0.0)
    if ec.history_cap:
        H = ec.history_cap
        offs = jnp.cumsum(mask.astype(jnp.int32)) - 1
        row = jnp.where(mask, st["h_idx"][0] + offs, H)  # drop when full
        row = jnp.where(row < H, row, H)
        st["h_keys"] = st["h_keys"].at[row].set(st["keys"], mode="drop")
        st["h_ver_r"] = st["h_ver_r"].at[row].set(st["ver_seen"], mode="drop")
        ver_w = st["ver_seen"] + st["is_w"].astype(jnp.int32)
        st["h_ver_w"] = st["h_ver_w"].at[row].set(ver_w, mode="drop")
        st["h_isw"] = st["h_isw"].at[row].set(st["is_w"], mode="drop")
        st["h_valid"] = st["h_valid"].at[row].set(st["valid"], mode="drop")
        st["h_ts_hi"] = st["h_ts_hi"].at[row].set(st["ts_hi"], mode="drop")
        st["h_ts_lo"] = st["h_ts_lo"].at[row].set(st["ts_lo"], mode="drop")
        st["h_idx"] = st["h_idx"] + mask.sum()[None].astype(jnp.int32)
    return st


def finish_abort(st: Dict, mask) -> Dict:
    st = dict(st)
    st["n_abort"] = st["n_abort"] + mask.astype(jnp.int32)
    return st


# ---------------------------------------------------------------------------
# Run loop + metrics
# ---------------------------------------------------------------------------


def run(
    protocol_tick,
    ec: EngineConfig,
    cm: CostModel,
    wl: Workload,
    n_ticks: int,
    warmup: int = 0,
    *,
    ticks_active=None,
):
    """Run the engine; returns (final_state, final_store, metrics dict).

    ``ticks_active`` (traced int32, None = unpadded) supports tick-axis
    bucketing (sweep.plan_buckets): the scan runs the padded ``n_ticks``
    shape but every tick past ``warmup + ticks_active`` freezes the whole
    carry — dead ticks touch no counter, no store word, no RNG draw — so
    the result is bitwise-equal to a run of exactly ``ticks_active`` ticks
    and a whole ticks sweep shares one compiled program.
    """
    from repro.core.registry import protocol_family

    # store layout is keyed by the registry FAMILY, so registered variants
    # (family="occ", ...) inherit the right metadata words
    with jax.named_scope("init"):
        store = init_store(
            protocol_family(ec.protocol), ec.records_local, wl.rw, wl.init_value,
            n_versions=ec.mvcc_slots,
        )
        st = init_state(ec, wl)

    def tick(carry, t):
        st0, store0 = carry
        st, store = protocol_tick(ec, cm, wl, st0, store0, t)
        st = dict(st)
        st["tick"] = st["tick"] + 1
        if ticks_active is not None:
            live = t < warmup + jnp.asarray(ticks_active, jnp.int32)

            def frz(new, old):
                return jnp.where(live, new, old)

            st = jax.tree_util.tree_map(frz, st, st0)
            store = jax.tree_util.tree_map(frz, store, store0)
        return (st, store), None

    if warmup:
        (st, store), _ = jax.lax.scan(tick, (st, store), jnp.arange(warmup))
        # reset counters after warmup
        for k in ("n_commit", "n_abort", "lat_sum", "rt_sum"):
            st[k] = jnp.zeros_like(st[k])
        st["stage_us"] = jnp.zeros_like(st["stage_us"])
    (st, store), _ = jax.lax.scan(tick, (st, store), jnp.arange(warmup, warmup + n_ticks))
    n_eff = n_ticks if ticks_active is None else ticks_active
    with jax.named_scope("summarize"):
        return st, store, summarize(ec, cm, st, n_eff)


def run_sharded(
    protocol_tick,
    ec: EngineConfig,
    cm: CostModel,
    wl: Workload,
    n_ticks: int,
    warmup: int = 0,
    *,
    devices: Optional[Sequence] = None,
    axis: str = "node",
):
    """:func:`run` with the simulated cluster laid out SPMD on a device mesh.

    The store (record data, locks, versions — the O(records) memory and
    compute) is sharded over a 1-D ``node`` mesh axis, whole simulated
    nodes per shard; the per-slot coordinator state is sequencer-replicated
    (O(slots·K) ints).  The protocol tick runs unchanged inside
    ``shard_map``: every store access routes through the planes.py
    transport (os_read / os_cas / capacity-ranking rounds as collectives),
    so integer commit/abort/round counters are bitwise-equal to the dense
    engine and the wire traffic is structurally honest — one exchange per
    network round.

    ``devices`` defaults to all of ``jax.devices()``; their count must
    divide ``ec.n_nodes`` so shards own whole nodes.  Returns the same
    (state, GLOBAL store, metrics) triple as :func:`run`.
    """
    from jax.sharding import PartitionSpec as P

    mesh, ec_sh = node_mesh_config(ec, devices, axis)

    def body():
        return run(protocol_tick, ec_sh, cm, wl, n_ticks, warmup=warmup)

    return planes.shard_map(
        body, mesh=mesh, in_specs=(), out_specs=(P(), P(axis), P())
    )()


def node_mesh_config(ec: EngineConfig, devices: Optional[Sequence], axis: str):
    """Validate + build the 1-D node mesh and the sharded config.

    Shared by :func:`run_sharded` and CALVIN's epoch runner so the
    device-list defaulting, the whole-nodes-per-shard divisibility check,
    and the ``EngineConfig.shard`` wiring live in one place.
    """
    if ec.shard is not None:
        raise ValueError("node mesh: config already node-sharded")
    devices = list(devices) if devices is not None else list(jax.devices())
    n_shards = len(devices)
    if ec.n_nodes % n_shards:
        raise ValueError(
            f"node mesh: {n_shards} device(s) must divide n_nodes={ec.n_nodes} "
            "(shards own whole simulated nodes)"
        )
    from jax.sharding import Mesh

    mesh = Mesh(np.asarray(devices), (axis,))
    ec_sh = dataclasses.replace(ec, shard=NodeShard(axis=axis, n_shards=n_shards))
    return mesh, ec_sh


def summarize(ec: EngineConfig, cm: CostModel, st: Dict, n_ticks: int) -> Dict:
    commits = st["n_commit"].sum()
    aborts = st["n_abort"].sum()
    sim_us = n_ticks * cm.tick_us
    return {
        "commits": commits,
        "aborts": aborts,
        "throughput_mtps": commits / sim_us,  # million txns/sec (txns per us)
        "avg_latency_us": st["lat_sum"].sum() / jnp.maximum(commits, 1),
        "abort_rate": aborts / jnp.maximum(commits + aborts, 1),
        "avg_round_trips": st["rt_sum"].sum() / jnp.maximum(commits, 1),
        "stage_us_per_commit": st["stage_us"] / jnp.maximum(commits, 1),
    }
