"""Reference stage machines: what the protocols on the reference engine share.

A protocol (``protocol_<name>.py``) is a table of stages processed in
reverse pipeline order each tick, so a transaction advances at most one
network stage per tick.  Each
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
from bench.reference.engine import RPC

FRESH = -1
ROUND, LOG, EXEC = "round", "log", "exec"


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


def stage_round(ec, cm, wl, st, store, spec: Stage, salt, wire):
    prim = ec.hybrid[spec.canon]
    in_s = st["stage"] == spec.stage
    want = in_s[:, None] & spec.ops(st)
    served, load = eng.service_ops(ec, cm, st, want, prim == RPC, salt)
    out = spec.effect(ec, cm, wl, st, store, in_s, served, salt)
    st, store = dict(out.st), out.store
    wc = wire[spec.canon]
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


def log_round(ec, cm, wl, st, spec: Stage, wire):
    """Fire-and-forget log to the backups: no service arbitration."""
    prim = ec.hybrid[spec.canon]
    in_g = st["stage"] == spec.stage
    ops = in_g[:, None] & st["is_w"] & st["valid"]
    load = jnp.full(ops.shape, float(cm.n_backups), jnp.float32)
    wc = wire[spec.canon]
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


def make_tick(specs, start_stage: int, salt_mult: int, wire: Dict[int, eng.WireCost]):
    """One tick of a stage table; ``wire`` is the protocol's WireCost per
    canonical stage of its network rounds."""
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
                st, store = stage_round(ec, cm, wl, st, store, spec, salt + spec.salt_off, wire)
            elif spec.kind == LOG:
                st = log_round(ec, cm, wl, st, spec, wire)
            else:
                st = exec_stage(ec, wl, st, spec)
        return st, store

    return tick
