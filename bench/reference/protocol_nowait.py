"""Reference plug-in: NOWAIT, two-phase locking that aborts on any lost
lock CAS, with the record fetched under the lock."""
from __future__ import annotations

import jax.numpy as jnp

from bench.reference import engine as eng
from bench.reference.engine import RPC, ST_COMMIT, ST_EXEC, ST_LOCK, ST_LOG, ST_RELEASE
from bench.reference.protocols import (
    EXEC, LOG, Stage, StageOut, make_tick, ops_locked, ops_lock_pending, ops_valid,
    release_effect, writeback_commit_effect,
)

WIRE = {
    ST_LOCK: eng.WireCost(base=16.0, words=1.0, n_verbs=2),
    ST_LOG: eng.LOG_WIRE,
    ST_COMMIT: eng.WireCost(base=12.0, words=1.0, n_verbs=2),
    ST_RELEASE: eng.RELEASE_WIRE,
}


def init_store(n_records: int, rw: int, init_value: int, n_versions: int):
    """Lock words, a version and one record of ``rw`` words per key."""
    store = eng.lock_store(n_records)
    store["data"] = jnp.full((n_records, rw), init_value, jnp.int32)
    return store


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

PROTOCOL = eng.Protocol(make_tick(NOWAIT, N_LOCK, salt_mult=17, wire=WIRE), init_store)
