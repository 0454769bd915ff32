"""Reference stage machines: NOWAIT and MVCC on the reference engine.

A protocol is a table of stages processed in reverse pipeline order each
tick, so a transaction advances at most one network stage per tick.  Each
serviced round runs: want-mask -> capacity service -> the stage's effect ->
latency accounting -> served bookkeeping -> stage transition.  Stages are
never merged across doorbells (the default of the system under test).
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Callable, Dict, NamedTuple, Optional

import jax
import jax.numpy as jnp

from bench.reference import engine as eng
from bench.reference.engine import (
    RPC, ST_COMMIT, ST_EXEC, ST_FETCH, ST_LOCK, ST_LOG, ST_RELEASE, ST_VALIDATE, TS,
    ts_eq, ts_is_zero, ts_lt,
)

FRESH = -1
ROUND, LOG, EXEC = "round", "log", "exec"
_MIN = jnp.int32(-(2**31))


class StageOut(NamedTuple):
    st: Dict
    store: Dict
    fail: Optional[jnp.ndarray] = None
    served_acc: Optional[jnp.ndarray] = None
    outstanding: Optional[jnp.ndarray] = None


@dataclass(frozen=True)
class Stage:
    stage: int
    canon: int
    kind: str = ROUND
    ops: Optional[Callable] = None
    effect: Optional[Callable] = None
    next_stage: int = FRESH
    done: str = "advance"  # "advance" | "commit" | "abort"
    retry_stage: Optional[int] = None
    abrel_stage: Optional[int] = None
    new_ts: bool = False
    start_exec: bool = False
    salt_off: int = 0
    ro_commit: bool = False


# ---- shared op masks and effects ----------------------------------------


def ops_valid(st):
    return st["valid"] & ~st["served"]


def ops_write_set(st):
    return st["valid"] & st["is_w"] & ~st["served"]


def ops_read_set(st):
    return st["valid"] & ~st["is_w"] & ~st["served"]


def ops_locked(st):
    return st["locked"] & ~st["served"]


def ops_lock_pending(write_only: bool):
    def ops(st):
        base = st["valid"] & st["is_w"] if write_only else st["valid"]
        return base & ~st["locked"] & ~st["served"]

    return ops


def release_effect(ec, cm, wl, st, store, in_s, served, salt):
    store = eng.release_locks(ec, store, st, served)
    st = dict(st)
    st["locked"] = st["locked"] & ~served
    return StageOut(st, store)


def writeback_commit_effect(ec, cm, wl, st, store, in_s, served, salt):
    """Write back the write set, bump versions, release this txn's locks."""
    keys_f = st["keys"].reshape(-1)
    idx_w = jnp.where((served & st["is_w"]).reshape(-1), keys_f, ec.n_records)
    store = dict(store)
    store["data"] = eng.scatter(store["data"], idx_w, st["wvals"].reshape(-1, wl.rw))
    store["ver"] = eng.scatter(store["ver"], idx_w, 1, op="add")
    idx_r = jnp.where((served & st["locked"]).reshape(-1), keys_f, ec.n_records)
    store["lock_hi"] = eng.scatter(store["lock_hi"], idx_r, 0)
    store["lock_lo"] = eng.scatter(store["lock_lo"], idx_r, 0)
    st = dict(st)
    st["locked"] = st["locked"] & ~served
    return StageOut(st, store)


def abort_to_retry(st: Dict, fail, spec: Stage) -> Dict:
    """Failing txns go to abort-release when they hold locks, else retry now."""
    has_locks = st["locked"].any(1)
    st = dict(st)
    st["stage"] = jnp.where(fail, jnp.where(has_locks, spec.abrel_stage, spec.retry_stage), st["stage"])
    insta = fail & ~has_locks
    st = eng.finish_abort(st, insta)
    if spec.new_ts:
        st["clock"] = jnp.where(insta, st["clock"] + 1, st["clock"])
        st["ts_hi"] = jnp.where(insta, st["clock"], st["ts_hi"])
    st["lat_us"] = jnp.where(insta, 0.0, st["lat_us"]).astype(st["lat_us"].dtype)
    st["rounds"] = jnp.where(insta, 0, st["rounds"])
    return st


# ---- one serviced round of a stage ----------------------------------------


def stage_round(ec, cm, wl, st, store, spec: Stage, salt):
    prim = ec.hybrid[spec.canon]
    in_s = st["stage"] == spec.stage
    want = in_s[:, None] & spec.ops(st)
    served, load = eng.service_ops(ec, cm, st, want, prim == RPC, salt)
    out = spec.effect(ec, cm, wl, st, store, in_s, served, salt)
    st, store = dict(out.st), out.store
    wc = eng.WIRE_COSTS[ec.protocol][spec.canon]
    st = eng.account_round(
        ec, cm, st, spec.canon, served, load, prim, wc.bytes_for(wl.rw, cm.n_backups), wc.n_verbs
    )
    st = dict(st)
    st["served"] = st["served"] | (served if out.served_acc is None else out.served_acc)

    if spec.done == "abort":
        done = in_s & ~st["locked"].any(1)
        st = eng.finish_abort(st, done)
        if spec.new_ts:
            st["clock"] = jnp.where(done, st["clock"] + 1, st["clock"])
            st["ts_hi"] = jnp.where(done, st["clock"], st["ts_hi"])
        st["stage"] = jnp.where(done, spec.next_stage, st["stage"])
        st["served"] = jnp.where(done[:, None], False, st["served"])
        st["lat_us"] = jnp.where(done, 0.0, st["lat_us"]).astype(st["lat_us"].dtype)
        st["rounds"] = jnp.where(done, 0, st["rounds"])
        return st, store

    outstanding = out.outstanding
    if outstanding is None:
        outstanding = in_s[:, None] & spec.ops(st)
    done = in_s & ~outstanding.any(1)

    if spec.done == "commit":
        st = eng.finish_commit(st, done)
        st["stage"] = jnp.where(done, FRESH, st["stage"])
        st["served"] = jnp.where(done[:, None], False, st["served"])
        return st, store

    exit_mask = done
    if out.fail is not None:
        done = done & ~out.fail
        exit_mask = done | out.fail
        st = abort_to_retry(st, out.fail, spec)
    if spec.ro_commit:
        has_ws = (st["valid"] & st["is_w"]).any(1)
        ro_done = done & ~has_ws
        st = eng.finish_commit(st, ro_done)
        st["stage"] = jnp.where(ro_done, FRESH, st["stage"])
        done = done & has_ws
    st["stage"] = jnp.where(done, spec.next_stage, st["stage"])
    if spec.start_exec:
        st["exec_left"] = jnp.where(done, wl.exec_ticks, st["exec_left"])
    st["served"] = jnp.where(exit_mask[:, None], False, st["served"])
    st["substep"] = jnp.where(exit_mask, 0, st["substep"])
    return st, store


def log_round(ec, cm, wl, st, spec: Stage):
    """Fire-and-forget log to the backups: no service arbitration."""
    prim = ec.hybrid[spec.canon]
    in_g = st["stage"] == spec.stage
    ops = in_g[:, None] & st["is_w"] & st["valid"]
    load = jnp.full(ops.shape, float(cm.n_backups), jnp.float32)
    wc = eng.WIRE_COSTS[ec.protocol][spec.canon]
    st = eng.account_round(
        ec, cm, st, spec.canon, ops, load, prim, wc.bytes_for(wl.rw, cm.n_backups), wc.n_verbs
    )
    st = dict(st)
    st["stage"] = jnp.where(in_g, spec.next_stage, st["stage"])
    st["served"] = jnp.where(in_g[:, None], False, st["served"])
    return st


def exec_stage(ec, wl, st, spec: Stage):
    """Local execution: burn ``exec_left`` ticks, then compute the writes."""
    in_e = st["stage"] == spec.stage
    st = dict(st)
    st["exec_left"] = jnp.where(in_e, jnp.maximum(st["exec_left"] - 1, 0), st["exec_left"])
    done_e = in_e & (st["exec_left"] == 0)
    wv = jax.vmap(wl.execute)(st["keys"], st["is_w"], st["valid"], st["rvals"])
    st["wvals"] = jnp.where(done_e[:, None, None], wv, st["wvals"])
    st["stage"] = jnp.where(done_e, spec.next_stage, st["stage"])
    return st


def make_tick(specs, start_stage: int, salt_mult: int):
    canon_map = {s.stage: s.canon for s in specs}

    def tick(ec, cm, wl, st, store, t):
        salt = t * salt_mult
        fresh = st["stage"] < 0
        st = eng.regen_txns(ec, wl, st, fresh)
        st["stage"] = jnp.where(fresh, start_stage, st["stage"])
        canon = jnp.full_like(st["stage"], -1)
        for ps in range(len(canon_map)):
            canon = jnp.where(st["stage"] == ps, canon_map[ps], canon)
        st = eng.base_time(ec, cm, st, canon)
        for spec in specs:
            if spec.kind == ROUND:
                st, store = stage_round(ec, cm, wl, st, store, spec, salt + spec.salt_off)
            elif spec.kind == LOG:
                st = log_round(ec, cm, wl, st, spec)
            else:
                st = exec_stage(ec, wl, st, spec)
        return st, store

    return tick


# ---- NOWAIT ---------------------------------------------------------------

N_LOCK, N_EXEC, N_LOG, N_COMMIT, N_ABREL = range(5)


def _nowait_lock(ec, cm, wl, st, store, in_l, served, salt):
    """Arbitrated CAS + fetch under the lock; any lost CAS aborts the txn.
    RPC lock requests park on the owner (``served`` accumulates); one-sided
    requests re-post every tick."""
    is_rpc = jnp.asarray(ec.hybrid[ST_LOCK] == RPC)
    st = dict(st)
    pend = in_l[:, None] & st["valid"] & ~st["locked"]
    acc = served & is_rpc
    contenders = jnp.where(is_rpc, pend & (st["served"] | acc), served)
    K = contenders.shape[1]
    sid, _ = eng.slot_ids(ec)
    base = sid[:, None] * K + jnp.arange(K, dtype=jnp.int32)[None, :]
    prio_hi = eng.hash_prio(base + st["ts_lo"][:, None], salt + 1)
    won, store = eng.try_lock(ec, store, st, contenders, prio_hi, base)
    st["locked"] = st["locked"] | won
    got = eng.gather(store["data"], st["keys"])
    ver = eng.gather(store["ver"], st["keys"])
    st["rvals"] = jnp.where(won[:, :, None], got, st["rvals"])
    st["ver_seen"] = jnp.where(won, ver, st["ver_seen"])
    abort_now = in_l & (contenders & ~won).any(1)
    return StageOut(st, store, fail=abort_now, served_acc=acc,
                    outstanding=st["valid"] & ~st["locked"])


NOWAIT = (
    Stage(N_COMMIT, ST_COMMIT, ops=ops_valid, effect=writeback_commit_effect, done="commit",
          salt_off=1),
    Stage(N_ABREL, ST_RELEASE, ops=ops_locked, effect=release_effect, done="abort",
          next_stage=N_LOCK, salt_off=2),
    Stage(N_LOG, ST_LOG, kind=LOG, next_stage=N_COMMIT),
    Stage(N_EXEC, ST_EXEC, kind=EXEC, next_stage=N_LOG),
    Stage(N_LOCK, ST_LOCK, ops=ops_lock_pending(False), effect=_nowait_lock, next_stage=N_EXEC,
          start_exec=True, retry_stage=N_LOCK, abrel_stage=N_ABREL, salt_off=3),
)

# ---- MVCC -----------------------------------------------------------------

M_READ, M_RTS, M_LOCKW, M_EXEC, M_LOG, M_COMMIT, M_ABREL = range(7)


def _vts(store, keys) -> TS:
    return TS(eng.gather(store["wts_hi"], keys), eng.gather(store["wts_lo"], keys))


def _lex_lt(ah, al, bh, bl):
    return (ah < bh) | ((ah == bh) & (al < bl))


def _best_version(wts: TS, ctts: TS):
    """Cond R1: the slot with the largest committed wts strictly below ctts."""
    cand = _lex_lt(wts.hi, wts.lo, ctts.hi[..., None], ctts.lo[..., None]) & ~ts_is_zero(wts)
    best_h = jnp.where(cand, wts.hi, _MIN).max(-1, keepdims=True)
    is_h = cand & (wts.hi == best_h)
    best_l = jnp.where(is_h, jnp.where(cand, wts.lo, _MIN), _MIN).max(-1, keepdims=True)
    winner = is_h & (wts.lo == best_l)
    return cand.any(-1), jnp.argmax(winner, axis=-1).astype(jnp.int32)


def _pick(wts: TS, ctts: TS, lock: Optional[TS] = None):
    found, slot = _best_version(wts, ctts)
    r2 = None if lock is None else ts_is_zero(lock) | ts_lt(ctts, lock)
    return found, slot, r2


def _max_wts(wts: TS) -> TS:
    bh = wts.hi.max(-1, keepdims=True)
    bl = jnp.where(wts.hi == bh, wts.lo, _MIN).max(-1)
    return TS(bh[..., 0], bl)


def _oldest_slot(wts: TS):
    bh = wts.hi.min(-1, keepdims=True)
    is_h = wts.hi == bh
    bl = jnp.where(is_h, wts.lo, jnp.int32(2**31 - 1)).min(-1, keepdims=True)
    return jnp.argmax(is_h & (wts.lo == bl), axis=-1).astype(jnp.int32)


def _check_w1(store, st, ops):
    """Cond W1: ctts above every version's wts and above rts."""
    mx = _max_wts(_vts(store, st["keys"]))
    rts = TS(eng.gather(store["rts_hi"], st["keys"]), eng.gather(store["rts_lo"], st["keys"]))
    me = TS(st["ts_hi"][:, None], st["ts_lo"][:, None])
    ok = _lex_lt(mx.hi, mx.lo, me.hi, me.lo) & _lex_lt(rts.hi, rts.lo, me.hi, me.lo)
    return ok | ~ops


def _mvcc_commit(ec, cm, wl, st, store, in_c, served, salt):
    """Overwrite the oldest version slot and its record, then unlock."""
    st = dict(st)
    oldest = _oldest_slot(_vts(store, st["keys"]))
    ver = eng.gather(store["ver"], st["keys"])
    K = st["keys"].shape[1]
    keys_f = st["keys"].reshape(-1)
    idx_k = jnp.where(served.reshape(-1), keys_f, ec.n_records)
    idx_s = oldest.reshape(-1)
    store = dict(store)
    store["wts_hi"] = eng.scatter2(store["wts_hi"], idx_k, idx_s, jnp.repeat(st["ts_hi"], K))
    store["wts_lo"] = eng.scatter2(store["wts_lo"], idx_k, idx_s, jnp.repeat(st["ts_lo"], K))
    store["vdata"] = eng.scatter2(store["vdata"], idx_k, idx_s, st["wvals"].reshape(-1, wl.rw))
    store["vver"] = eng.scatter2(store["vver"], idx_k, idx_s, (ver + 1).reshape(-1))
    store["ver"] = eng.scatter(store["ver"], idx_k, 1, op="add")
    idx_r = jnp.where((served & st["locked"]).reshape(-1), keys_f, ec.n_records)
    store["lock_hi"] = eng.scatter(store["lock_hi"], idx_r, 0)
    store["lock_lo"] = eng.scatter(store["lock_lo"], idx_r, 0)
    st["locked"] = st["locked"] & ~served
    return StageOut(st, store)


def _mvcc_lock(ec, cm, wl, st, store, in_l, served, salt):
    """CAS tts + READ, then re-check W1 under the lock (double-read)."""
    st = dict(st)
    won, store = eng.try_lock(
        ec, store, st, served,
        jnp.broadcast_to(st["ts_hi"][:, None], served.shape),
        jnp.broadcast_to(st["ts_lo"][:, None], served.shape),
    )
    st["locked"] = st["locked"] | won
    found, slot, _ = _pick(_vts(store, st["keys"]), TS(st["ts_hi"][:, None], st["ts_lo"][:, None]))
    st["rvals"] = jnp.where(won[:, :, None], eng.gather2(store["vdata"], st["keys"], slot), st["rvals"])
    st["ver_seen"] = jnp.where(won, eng.gather2(store["vver"], st["keys"], slot), st["ver_seen"])
    w1_ok = _check_w1(store, st, won)
    fail = in_l & ((served & ~won).any(1) | (won & ~w1_ok).any(1) | (won & ~found).any(1))
    return StageOut(st, store, fail=fail, served_acc=jnp.zeros_like(served),
                    outstanding=st["valid"] & st["is_w"] & ~st["locked"])


def _mvcc_rts(ec, cm, wl, st, store, in_t, served, salt):
    """rts CAS-max, valid only while the read version is still the newest
    below ctts and Cond R2 still holds."""
    st = dict(st)
    wts_now = _vts(store, st["keys"])
    ctts = TS(st["ts_hi"][:, None], st["ts_lo"][:, None])
    lock_now = TS(eng.gather(store["lock_hi"], st["keys"]), eng.gather(store["lock_lo"], st["keys"]))
    found_now, slot_now, r2_now = _pick(wts_now, ctts, lock_now)
    best_now = TS(
        jnp.take_along_axis(wts_now.hi, slot_now[..., None], axis=-1)[..., 0],
        jnp.take_along_axis(wts_now.lo, slot_now[..., None], axis=-1)[..., 0],
    )
    still_ok = found_now & ts_eq(best_now, TS(st["wts_seen_hi"], st["wts_seen_lo"])) & r2_now
    fail = in_t & (served & ~still_ok).any(1)
    served = served & still_ok
    K = st["keys"].shape[1]
    sf = served.reshape(-1)
    idx = jnp.where(sf, st["keys"].reshape(-1), ec.n_records)
    store = dict(store)
    store["rts_hi"], store["rts_lo"] = eng.scatter_ts_max(
        ec, store["rts_hi"], store["rts_lo"], idx,
        jnp.repeat(st["ts_hi"], K), jnp.repeat(st["ts_lo"], K), sf,
    )
    return StageOut(st, store, fail=fail, served_acc=served)


def _mvcc_read(ec, cm, wl, st, store, in_f, served, salt):
    """Atomic double-read, version pick, clock drift adjustment, W1 precheck."""
    st = dict(st)
    wts = _vts(store, st["keys"])
    lock = TS(eng.gather(store["lock_hi"], st["keys"]), eng.gather(store["lock_lo"], st["keys"]))
    rts_obs = eng.gather(store["rts_hi"], st["keys"])
    found, slot, r2 = _pick(wts, TS(st["ts_hi"][:, None], st["ts_lo"][:, None]), lock)
    rs_served = served & st["valid"] & ~st["is_w"]
    st["rvals"] = jnp.where(rs_served[:, :, None], eng.gather2(store["vdata"], st["keys"], slot),
                            st["rvals"])
    st["ver_seen"] = jnp.where(rs_served, eng.gather2(store["vver"], st["keys"], slot), st["ver_seen"])
    best_hi = jnp.take_along_axis(wts.hi, slot[..., None], axis=-1)[..., 0]
    best_lo = jnp.take_along_axis(wts.lo, slot[..., None], axis=-1)[..., 0]
    st["wts_seen_hi"] = jnp.where(rs_served, best_hi, st["wts_seen_hi"])
    st["wts_seen_lo"] = jnp.where(rs_served, best_lo, st["wts_seen_lo"])
    obs = jnp.maximum(
        jnp.where(served, wts.hi.max(-1), 0).max(1), jnp.where(served, rts_obs, 0).max(1)
    )
    st["clock"] = jnp.maximum(st["clock"], obs)
    w1 = _check_w1(store, st, served & st["is_w"])
    bad = (rs_served & ~(found & r2)).any(1) | (served & st["is_w"] & ~w1).any(1)
    return StageOut(st, store, fail=in_f & bad)


MVCC = (
    Stage(M_COMMIT, ST_COMMIT, ops=ops_write_set, effect=_mvcc_commit, done="commit", salt_off=1),
    Stage(M_ABREL, ST_RELEASE, ops=ops_locked, effect=release_effect, done="abort",
          next_stage=M_READ, new_ts=True, salt_off=2),
    Stage(M_LOG, ST_LOG, kind=LOG, next_stage=M_COMMIT),
    Stage(M_EXEC, ST_EXEC, kind=EXEC, next_stage=M_LOG),
    Stage(M_LOCKW, ST_LOCK, ops=ops_lock_pending(True), effect=_mvcc_lock, next_stage=M_EXEC,
          start_exec=True, retry_stage=M_READ, abrel_stage=M_ABREL, new_ts=True, salt_off=3),
    Stage(M_RTS, ST_VALIDATE, ops=ops_read_set, effect=_mvcc_rts, ro_commit=True,
          next_stage=M_LOCKW, retry_stage=M_READ, abrel_stage=M_ABREL, new_ts=True, salt_off=4),
    Stage(M_READ, ST_FETCH, ops=ops_valid, effect=_mvcc_read, next_stage=M_RTS,
          retry_stage=M_READ, abrel_stage=M_ABREL, new_ts=True, salt_off=5),
)

TICKS = {
    "nowait": make_tick(NOWAIT, N_LOCK, salt_mult=17),
    "mvcc": make_tick(MVCC, M_READ, salt_mult=37),
}
