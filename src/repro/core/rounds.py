"""Declarative stage-graph runtime (DESIGN.md §3).

RCC's promise is a *common execution environment* in which the concurrency
control protocol is the only changeable component.  This module makes that
environment code instead of convention: a protocol is a table of
:class:`StageSpec` rows (canonical cost-stage id, op-mask fn, wire-cost
entry, effect hook, success/fail transitions) and :func:`make_tick` compiles
the table into the engine's per-tick function.  The full round lifecycle —

    want-mask -> service_ops -> effect hook -> account_round
              -> served bookkeeping -> stage transition

— lives in :func:`run_stage_round`, once, so the five engine protocols
differ only in their tables and small jnp effect hooks.

Cross-stage doorbell merging (paper §4.2, DESIGN.md §4) is a runtime pass
over the same tables: when a stage declares ``fuse_next`` and the merge
predicate holds (both stages coded one-sided, doorbell batching on,
``EngineConfig.merge_stages`` set), completed transactions skip the
intermediate stage and its wire bytes ride the absorbing stage's doorbell —
one MMIO, one RTT, one fewer engine tick.  The predicate is jnp-composable,
so a batched sweep (repro.core.sweep) fuses per-config inside one compiled
program.

Everything here must stay knob-traceable: no Python branching on hybrid
codings, seeds, or exec_ticks (see EngineConfig's static/traced split).
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Callable, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core import engine as eng
from repro.core.costmodel import (
    ONE_SIDED,
    RPC,
    ST_COMMIT,
    ST_LOG,
    ST_VALIDATE,
    STAGE_NAMES,
    CostModel,
    wire_cost,
)

FRESH = -1  # st["stage"] sentinel: slot regenerates a new txn next tick

# StageSpec.kind values
ROUND = "round"  # serviced network round (lock/fetch/validate/commit/release)
LOG = "log"  # fire-and-forget replicated log round (no service arbitration)
EXEC = "exec"  # local execution phase (no network)


class StageOut(NamedTuple):
    """What an effect hook hands back to the driver.

    ``fail``: (N,) txns aborting out of this stage (routed by
    :func:`abort_to_retry`).  ``served_acc``: override for what accumulates
    into ``st["served"]`` (default: everything served this round; a lock
    stage under one-sided coding accumulates nothing — it re-posts every
    tick).  ``outstanding``: override for the completion check (default:
    the stage's op mask re-evaluated after bookkeeping; lock stages
    complete on ``~locked``, not ``~served``).
    """

    st: Dict
    store: Dict
    fail: Optional[jnp.ndarray] = None
    served_acc: Optional[jnp.ndarray] = None
    outstanding: Optional[jnp.ndarray] = None


@dataclass(frozen=True)
class StageSpec:
    """One row of a protocol's stage table.

    ``stage`` is the protocol-local id stored in ``st["stage"]``; ``canon``
    is the canonical cost stage (ST_*) that picks the hybrid primitive, the
    latency bucket, and the :class:`~repro.core.costmodel.WireCost` row.
    ``ops(ec, wl, st) -> (N,K)`` is the want basis (the driver ANDs the
    in-stage mask); ``effect`` applies the stage's store/state mutation for
    the ops actually served.  ``done`` picks the completion rule:

      * ``"advance"``: all ops complete -> ``next_stage``; with
        ``ro_commit`` set, transactions with an empty write set instead
        commit here (the declarative read-only fast path — no lock/log/
        commit rounds); failures go through the shared abort path.
      * ``"commit"``: all ops complete -> finish_commit + slot regen.
      * ``"abort"``: all locks released -> finish_abort + retry at
        ``next_stage``.
    """

    stage: int
    canon: int
    kind: str = ROUND
    ops: Optional[Callable] = None
    effect: Optional[Callable] = None
    next_stage: int = FRESH
    done: str = "advance"
    retry_stage: Optional[int] = None  # fail: restart stage (no locks held)
    abrel_stage: Optional[int] = None  # fail: abort-release stage (locks held)
    new_ts: bool = False  # retry with a fresh (larger) timestamp
    start_exec: bool = False  # completion enters the execution phase
    salt_off: int = 0  # service_ops salt offset (pins arbitration RNG draws)
    # declarative read-only fast path: protocols whose read-set validation
    # doubles as the commit point (mvcc's rts round) set this instead of
    # forking the driver with a routing override — RO-fast-path protocols
    # are table entries, not code forks
    ro_commit: bool = False
    fuse_next: Optional[int] = None  # next_stage when doorbell merging fires
    fuse_absorbs: Optional[int] = None  # canon id whose bytes ride this doorbell


# ---------------------------------------------------------------------------
# Cross-stage doorbell merging (§4.2): the fusable-pair merge table
# ---------------------------------------------------------------------------

# Protocol -> ordered (absorber, absorbed) canonical stage pairs.  A pair
# fires when both stages are coded one-sided, doorbell batching is on, and
# the config opts in via ``merge_stages``; the FIRST firing pair for an
# absorbed stage claims it (an earlier absorber shadows later ones), so at
# most one doorbell carries the absorbed bytes.  Write-heavy OCC registers
# VALIDATE→LOG (the validation CAS round and the log WRITEs post as one
# doorbell batch) ahead of the family-default LOG→COMMIT fusion.
MERGE_TABLE: Dict[str, Tuple[Tuple[int, int], ...]] = {
    "default": ((ST_COMMIT, ST_LOG),),
    "occ": ((ST_VALIDATE, ST_LOG), (ST_COMMIT, ST_LOG)),
}


def merge_pairs(protocol: str) -> Tuple[Tuple[int, int], ...]:
    from repro.core.registry import protocol_family

    return MERGE_TABLE.get(protocol_family(protocol), MERGE_TABLE["default"])


def _pair_on(ec: eng.EngineConfig, absorber: int, absorbed: int):
    """Raw pair predicate (ignoring precedence).  jnp-composable: under a
    batched sweep the hybrid coding is traced and fusion resolves per grid
    row at runtime; off by default (``merge_stages``) so pre-merge counters
    stay bitwise reproducible."""
    if not (ec.merge_stages and ec.doorbell):
        return jnp.asarray(False)
    hy = ec.hybrid
    return (jnp.asarray(hy[absorber]) == ONE_SIDED) & (jnp.asarray(hy[absorbed]) == ONE_SIDED)


def fuse_log_commit(ec: eng.EngineConfig):
    """The family-default pair: LOG rides the COMMIT doorbell (legacy name)."""
    return _pair_on(ec, ST_COMMIT, ST_LOG)


def log_rides(ec: eng.EngineConfig, st: Dict):
    """Which doorbell carries each txn's LOG bytes: ``(absorbed, by_v, by_c)``.

    Resolved PER TRANSACTION: the VALIDATE→LOG pair can only carry a txn
    that actually posts a validate round (non-empty read set) — a
    write-only txn's log WRITEs fall through to the next registered pair
    (COMMIT), or to a plain LOG round when nothing absorbs them.  All masks
    broadcast against (N,) (scalars when only scalar pairs are registered,
    so non-occ protocols keep the original single-predicate program).
    """
    by_v = jnp.asarray(False)
    by_c = jnp.asarray(False)
    for a, b in merge_pairs(ec.protocol):
        if b != ST_LOG:
            continue
        if a == ST_VALIDATE:
            has_rs = (st["valid"] & ~st["is_w"]).any(1)
            by_v = by_v | (_pair_on(ec, a, b) & has_rs)
        elif a == ST_COMMIT:
            by_c = by_c | _pair_on(ec, a, b)
    by_c = by_c & ~by_v  # first registered pair claims the stage
    return by_v | by_c, by_v, by_c


def _resolve_next(ec: eng.EngineConfig, spec: StageSpec, st: Dict):
    # fuse_next routes past the LOG stage for txns whose log bytes have a
    # doorbell to ride (per-txn under the occ VALIDATE→LOG pair)
    if spec.fuse_next is None:
        return spec.next_stage
    absorbed, _, _ = log_rides(ec, st)
    return jnp.where(absorbed, spec.fuse_next, spec.next_stage)


def _stage_wire(ec: eng.EngineConfig, cm: CostModel, wl, spec: StageSpec, st: Dict):
    """(bytes, n_verbs) for one round, with absorbed-stage bytes when fused.

    Absorbed LOG bytes apply per op and only where a write set exists: on a
    COMMIT doorbell they ride the WRITE ops (a read-only txn's commit round
    releases locks but ships no log message); on a VALIDATE doorbell they
    ride the read-set ops of txns that also carry writes.  Bytes may then
    be (N,K), which broadcasts through account_round's wire term.
    """
    wc = wire_cost(ec.protocol, spec.canon)
    nb = wc.bytes_for(wl.rw, cm.n_backups)
    if spec.fuse_absorbs is not None and ec.merge_stages and ec.doorbell:
        extra = wire_cost(ec.protocol, spec.fuse_absorbs).bytes_for(wl.rw, cm.n_backups)
        _, by_v, by_c = log_rides(ec, st)
        if spec.canon == ST_VALIDATE:
            has_ws = (st["valid"] & st["is_w"]).any(1)
            on = jnp.asarray(by_v & has_ws)[:, None] & st["valid"] & ~st["is_w"]
        else:
            on = jnp.asarray(by_c)
            on = (on[:, None] if on.ndim else on) & st["is_w"]
        nb = nb + jnp.where(on, extra, 0.0)
    return nb, wc.n_verbs


# ---------------------------------------------------------------------------
# Shared effect building blocks
# ---------------------------------------------------------------------------


def apply_commit(ec: eng.EngineConfig, store: Dict, st: Dict, eff, *, bump_seq: bool = False) -> Dict:
    """Write back wvals + release this txn's locks for served commit ops.

    The single write-back used by the 2PL family and OCC (``bump_seq``
    additionally advances OCC's validation sequence word).
    """
    keys_f = st["keys"].reshape(-1)
    w_eff = (eff & st["is_w"]).reshape(-1)
    idx_w = jnp.where(w_eff, keys_f, ec.n_records)
    store = dict(store)
    store["data"] = eng.write_rows(
        ec, store["data"], idx_w, st["wvals"].reshape(-1, st["wvals"].shape[-1])
    )
    store["ver"] = eng.write_rows(ec, store["ver"], idx_w, 1, op="add")
    if bump_seq:
        store["seq"] = eng.write_rows(ec, store["seq"], idx_w, 1, op="add")
    rel = (eff & st["locked"]).reshape(-1)
    idx_r = jnp.where(rel, keys_f, ec.n_records)
    store["lock_hi"] = eng.write_rows(ec, store["lock_hi"], idx_r, 0)
    store["lock_lo"] = eng.write_rows(ec, store["lock_lo"], idx_r, 0)
    return store


def writeback_commit_effect(*, bump_seq: bool = False) -> Callable:
    """COMMIT effect hook for protocols using the plain write-back."""

    def effect(ec, cm, wl, st, store, in_s, served, salt):
        store = apply_commit(ec, store, st, served, bump_seq=bump_seq)
        st = dict(st)
        st["locked"] = st["locked"] & ~served
        return StageOut(st, store)

    return effect


def release_effect(ec, cm, wl, st, store, in_s, served, salt) -> StageOut:
    """ABORT-RELEASE effect: zero the lock words this txn still holds."""
    store = eng.release_locks(ec, store, st, served)
    st = dict(st)
    st["locked"] = st["locked"] & ~served
    return StageOut(st, store)


def ops_valid(ec, wl, st):
    """All valid ops not yet served (fetch/commit-style stages)."""
    return st["valid"] & ~st["served"]


def ops_write_set(ec, wl, st):
    """Write-set ops not yet served (occ/sundial/mvcc commit)."""
    return st["valid"] & st["is_w"] & ~st["served"]


def ops_read_set(ec, wl, st):
    """Read-set ops not yet served (validate stages)."""
    return st["valid"] & ~st["is_w"] & ~st["served"]


def ops_locked(ec, wl, st):
    """Held locks not yet released (abort-release stages)."""
    return st["locked"] & ~st["served"]


def ops_lock_pending(write_only: bool) -> Callable:
    """Lock-stage want basis: unlocked (write-set) ops.  One-sided lock
    requests re-post every tick, so ``served`` does NOT mask the basis."""

    def ops(ec, wl, st):
        base = st["valid"] & st["is_w"] if write_only else st["valid"]
        # ~served only bites under RPC park-the-waiter semantics (twopl);
        # one-sided lock stages never accumulate served, so it is vacuous
        return base & ~st["locked"] & ~st["served"]

    return ops


def abort_to_retry(st: Dict, fail, spec: StageSpec) -> Dict:
    """Route failing txns: ABREL when holding locks, else immediate retry.

    Immediate retries count the abort and zero the latency/round counters;
    ``spec.new_ts`` additionally takes a fresh (larger) timestamp (mvcc /
    sundial retry rule — 2PL keeps the original so WAITDIE requesters age).
    """
    has_locks = st["locked"].any(1)
    st = dict(st)
    st["stage"] = jnp.where(
        fail, jnp.where(has_locks, spec.abrel_stage, spec.retry_stage), st["stage"]
    )
    insta = fail & ~has_locks
    st = eng.finish_abort(st, insta)
    st = dict(st)
    if spec.new_ts:
        st["clock"] = jnp.where(insta, st["clock"] + 1, st["clock"])
        st["ts_hi"] = jnp.where(insta, st["clock"], st["ts_hi"])
    st["lat_us"] = jnp.where(insta, 0.0, st["lat_us"])
    st["rounds"] = jnp.where(insta, 0, st["rounds"])
    return st


# ---------------------------------------------------------------------------
# The driver
# ---------------------------------------------------------------------------


def run_stage_round(
    ec: eng.EngineConfig, cm: CostModel, wl, st: Dict, store: Dict, spec: StageSpec, salt
) -> Tuple[Dict, Dict]:
    """One serviced network round for ``spec``: the full lifecycle."""
    prim = ec.hybrid[spec.canon]
    in_s = st["stage"] == spec.stage
    want = in_s[:, None] & spec.ops(ec, wl, st)
    served, load = eng.service_ops(ec, cm, st, want, prim == RPC, salt)
    out = spec.effect(ec, cm, wl, st, store, in_s, served, salt)
    st, store = dict(out.st), out.store
    nbytes, n_verbs = _stage_wire(ec, cm, wl, spec, st)
    st = eng.account_round(ec, cm, st, spec.canon, served, load, prim, nbytes, n_verbs=n_verbs)
    st = dict(st)
    acc = served if out.served_acc is None else out.served_acc
    st["served"] = st["served"] | acc

    if spec.done == "abort":
        done = in_s & ~st["locked"].any(1)
        st = eng.finish_abort(st, done)
        st = dict(st)
        if spec.new_ts:
            st["clock"] = jnp.where(done, st["clock"] + 1, st["clock"])
            st["ts_hi"] = jnp.where(done, st["clock"], st["ts_hi"])
        st["stage"] = jnp.where(done, spec.next_stage, st["stage"])
        st["served"] = jnp.where(done[:, None], False, st["served"])
        st["lat_us"] = jnp.where(done, 0.0, st["lat_us"])
        st["rounds"] = jnp.where(done, 0, st["rounds"])
        return st, store

    outstanding = out.outstanding
    if outstanding is None:
        outstanding = in_s[:, None] & spec.ops(ec, wl, st)
    done = in_s & ~outstanding.any(1)

    if spec.done == "commit":
        st = eng.finish_commit(ec, cm, st, done)
        st = dict(st)
        st["stage"] = jnp.where(done, FRESH, st["stage"])
        st["served"] = jnp.where(done[:, None], False, st["served"])
        return st, store

    # "advance"
    fail = out.fail
    exit_mask = done
    if fail is not None:
        done = done & ~fail
        exit_mask = done | fail
        st = abort_to_retry(st, fail, spec)
    if spec.ro_commit:
        # declarative read-only fast path: txns with an empty write set
        # commit on completing this stage (no lock/log/commit rounds)
        has_ws = (st["valid"] & st["is_w"]).any(1)
        ro_done = done & ~has_ws
        st = eng.finish_commit(ec, cm, st, ro_done)
        st = dict(st)
        st["stage"] = jnp.where(ro_done, FRESH, st["stage"])
        done = done & has_ws
    st["stage"] = jnp.where(done, _resolve_next(ec, spec, st), st["stage"])
    if spec.start_exec:
        st["exec_left"] = jnp.where(done, wl.exec_ticks, st["exec_left"])
    st["served"] = jnp.where(exit_mask[:, None], False, st["served"])
    st["substep"] = jnp.where(exit_mask, 0, st["substep"])
    return st, store


def _log_round(ec: eng.EngineConfig, cm: CostModel, wl, st: Dict, spec: StageSpec) -> Dict:
    """Coordinator log to the replication group: one fire-and-forget round.

    No service arbitration (backups only append); read-only txns advance
    for free.  Txns whose LOG bytes found a doorbell to ride
    (:func:`log_rides`) are routed PAST this stage per transaction; the
    ones with no ride — e.g. occ write-only txns when only the
    VALIDATE→LOG pair fires — still land here and pay the real round, so
    this stage is live even with merging on.
    """
    prim = ec.hybrid[spec.canon]
    in_g = st["stage"] == spec.stage
    ops = in_g[:, None] & st["is_w"] & st["valid"]
    load = jnp.full(ops.shape, float(cm.n_backups), jnp.float32)
    nbytes, n_verbs = _stage_wire(ec, cm, wl, spec, st)
    st = eng.account_round(ec, cm, st, spec.canon, ops, load, prim, nbytes, n_verbs=n_verbs)
    st = dict(st)
    st["stage"] = jnp.where(in_g, spec.next_stage, st["stage"])
    st["served"] = jnp.where(in_g[:, None], False, st["served"])
    return st


def _exec_stage(ec: eng.EngineConfig, wl, st: Dict, spec: StageSpec) -> Dict:
    """Local execution phase: burn exec_left ticks, then run the workload's
    execute fn and advance (possibly straight past a fused LOG stage)."""
    in_e = st["stage"] == spec.stage
    st = dict(st)
    st["exec_left"] = jnp.where(in_e, jnp.maximum(st["exec_left"] - 1, 0), st["exec_left"])
    done_e = in_e & (st["exec_left"] == 0)
    wv = jax.vmap(wl.execute)(st["keys"], st["is_w"], st["valid"], st["rvals"])
    st["wvals"] = jnp.where(done_e[:, None, None], wv, st["wvals"])
    st["stage"] = jnp.where(done_e, _resolve_next(ec, spec, st), st["stage"])
    return st


def canon_table(specs: Tuple[StageSpec, ...]) -> Tuple[int, ...]:
    """Protocol-stage -> canonical-stage map derived from a stage table."""
    by_stage = {s.stage: s.canon for s in specs}
    return tuple(by_stage[i] for i in range(len(by_stage)))


def canon_of(stage, canon_map: Tuple[int, ...]):
    """Map st["stage"] values to canonical cost stages (-1 = inactive)."""
    canon = jnp.full_like(stage, -1)
    for ps, c in enumerate(canon_map):
        canon = jnp.where(stage == ps, c, canon)
    return canon


def begin_tick(
    ec: eng.EngineConfig,
    cm: CostModel,
    wl,
    st: Dict,
    canon_map: Tuple[int, ...],
    start_stage: int,
    fresh_hook: Optional[Callable] = None,
) -> Dict:
    """Regenerate fresh slots and charge every active txn its tick base.

    Bucket-padded (dead) slots stay at stage -1 forever: they are excluded
    from ``fresh``, so they never generate transactions, never enter any
    stage mask, and never touch a counter (DESIGN.md §6).
    """
    fresh = st["stage"] < 0
    alive = eng.alive_mask(ec)
    if alive is not None:
        fresh = fresh & alive
    st = eng.regen_txns(ec, wl, st, fresh, new_ts=True)
    st = dict(st)
    st["stage"] = jnp.where(fresh, start_stage, st["stage"])
    if fresh_hook is not None:
        st = fresh_hook(st, fresh)
    return eng.base_time(ec, cm, st, canon_of(st["stage"], canon_map))


def make_tick(
    *,
    specs: Tuple[StageSpec, ...],
    start_stage: int,
    salt_mult: int,
    fresh_hook: Optional[Callable] = None,
) -> Callable:
    """Compile a stage table into the engine's per-tick function.

    ``specs`` are processed in the given order — reverse pipeline order, so
    a transaction advances at most one network stage per tick (the engine's
    bulk-synchronous contract).  ``salt_mult`` namespaces each protocol's
    arbitration RNG stream.
    """
    canon_map = canon_table(specs)

    def tick(ec: eng.EngineConfig, cm: CostModel, wl, st: Dict, store: Dict, t):
        salt = t * salt_mult
        with jax.named_scope("begin_tick"):
            st = begin_tick(ec, cm, wl, st, canon_map, start_stage, fresh_hook)
        for spec in specs:
            with jax.named_scope(f"stage_{STAGE_NAMES[spec.canon]}"):
                if spec.kind == ROUND:
                    st, store = run_stage_round(ec, cm, wl, st, store, spec, salt + spec.salt_off)
                elif spec.kind == LOG:
                    st = _log_round(ec, cm, wl, st, spec)
                else:
                    st = _exec_stage(ec, wl, st, spec)
        return st, store

    return tick
