"""Reference plug-in: MVCC over static version slots (RCC's MVCC): a read
picks the newest committed version below its timestamp, a write locks,
re-checks and overwrites the oldest slot."""
from __future__ import annotations

from typing import Optional

import jax.numpy as jnp

from bench.reference import engine as eng
from bench.reference.engine import (
    ST_COMMIT, ST_EXEC, ST_FETCH, ST_LOCK, ST_LOG, ST_RELEASE, ST_VALIDATE, TS, ts_eq, ts_is_zero,
    ts_lt,
)
from bench.reference.protocols import (
    EXEC, LOG, Stage, StageOut, make_tick, ops_lock_pending, ops_locked, ops_read_set, ops_valid,
    ops_write_set, release_effect,
)

_MIN = jnp.int32(-(2**31))

WIRE = {
    ST_FETCH: eng.WireCost(base=48.0, words=8.0, n_verbs=2),
    ST_LOCK: eng.WireCost(base=24.0, words=1.0, n_verbs=2),
    ST_VALIDATE: eng.WireCost(base=16.0),
    ST_LOG: eng.LOG_WIRE,
    ST_COMMIT: eng.WireCost(base=16.0, words=1.0, n_verbs=2),
    ST_RELEASE: eng.RELEASE_WIRE,
}


def init_store(n_records: int, rw: int, init_value: int, n_versions: int):
    """Lock words and a version per key, ``n_versions`` version slots (each a
    write timestamp, a record and its version) and a read timestamp."""
    def z(*s):
        return jnp.zeros(s, jnp.int32)

    store = eng.lock_store(n_records)
    store["wts_hi"] = z(n_records, n_versions)
    store["wts_lo"] = z(n_records, n_versions).at[:, 0].set(1)
    store["rts_hi"] = z(n_records)
    store["rts_lo"] = z(n_records)
    store["vdata"] = jnp.full((n_records, n_versions, rw), init_value, jnp.int32)
    store["vver"] = z(n_records, n_versions)
    return store


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

PROTOCOL = eng.Protocol(make_tick(MVCC, M_READ, salt_mult=37, wire=WIRE), init_store)
