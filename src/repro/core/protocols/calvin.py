"""CALVIN (paper §4.6): deterministic, epoch-based, shared-nothing.

Per epoch: (1) sequencing layer — every node broadcasts its local batch of
transactions to all other nodes (RPC batch, or one-sided: two doorbell-
batched WRITEs into pre-agreed per-(epoch, sender) ring buffers — value
then valid-flag); (2) RS/WS forwarding — passive participants send RS
records to active participants, actives exchange WS records; (3) local
deterministic execution in the agreed global order (lock-free: conflicting
transactions execute in dependency waves).  No aborts by construction.

Epoch synchronization is why co-routines do not help CALVIN (paper Fig. 7):
the epoch barrier serializes sequencer rounds regardless of overlap.
"""
from __future__ import annotations


import jax
import jax.numpy as jnp

from repro.core import costmodel as cmod
from repro.core import engine as eng
from repro.core import registry
from repro.core.costmodel import ONE_SIDED, RPC, CostModel
from repro.core.engine import EngineConfig, Workload
from repro.core.store import init_store

tick = None  # CALVIN uses the epoch runner below, not the slot engine
STAGES_USED = ("sequence", "forward", "execute")


def _epoch_txns(ec: EngineConfig, wl: Workload, epoch, key0):
    """Generate this epoch's global batch in deterministic order.

    Identity flows through LOGICAL slot ids and generated keys are remapped
    onto the padded store layout, so bucket-padded runs (sweep.py) stay
    bitwise-equal to unpadded ones; dead (padded) slots get valid=False.
    """
    lsid, node, alive = eng.logical_ids(ec)

    def gen_one(s, n):
        k = jax.random.fold_in(jax.random.fold_in(key0, s), epoch)
        return wl.gen(k, n, s)

    keys, is_w, valid = jax.vmap(gen_one)(lsid, node)
    keys = eng.physical_keys(ec, keys)
    if alive is not None:
        valid = valid & alive[:, None]
    return keys, is_w, valid, node


def _waves(ec: EngineConfig, keys, is_w, valid):
    """Dependency wave per txn: readers wait for earlier writers; writers
    wait for all earlier accesses (deterministic lock schedule)."""
    N, K = keys.shape
    M = N * K
    kf = keys.reshape(-1)
    order = jnp.repeat(jnp.arange(N, dtype=jnp.int32), K)
    wf = (is_w & valid).reshape(-1)
    af = valid.reshape(-1)
    sort_key = jnp.where(af, kf * (M + 1) + order, jnp.int32(2**30))
    perm = jnp.argsort(sort_key)
    k_s = kf[perm]
    w_s = wf[perm].astype(jnp.int32)
    a_s = af[perm].astype(jnp.int32)
    first = jnp.concatenate([jnp.ones(1, bool), k_s[1:] != k_s[:-1]])
    # exclusive prefix counts within key segments
    cw = jnp.cumsum(w_s) - w_s
    ca = jnp.cumsum(a_s) - a_s
    seg_cw0 = jnp.where(first, cw, 0)
    seg_ca0 = jnp.where(first, ca, 0)
    seg_cw0 = jax.lax.associative_scan(jnp.maximum, seg_cw0)
    seg_ca0 = jax.lax.associative_scan(jnp.maximum, seg_ca0)
    earlier_writers = cw - seg_cw0
    earlier_access = ca - seg_ca0
    wave_s = jnp.where(w_s > 0, earlier_access, earlier_writers)
    wave_f = jnp.zeros(M, jnp.int32).at[perm].set(wave_s.astype(jnp.int32))
    wave_f = jnp.where(af, wave_f, 0)
    return wave_f.reshape(N, K).max(1)  # txn wave


def run_epochs(
    ec: EngineConfig, cm: CostModel, wl: Workload, n_epochs: int, *, epochs_active=None
):
    """Returns metrics matching engine.summarize's schema.

    ``epochs_active`` (traced, None = unpadded) is the tick-bucketing mask:
    epochs past it execute zero waves, freeze the store, and contribute
    zero to every stat, so a padded run is bitwise-equal to a run of
    exactly ``epochs_active`` epochs.  When ``ec.shard`` is set the store
    lives node-sharded and the wave executor's gathers/scatters route
    through the plane primitives (one collective per wave round).
    """
    key0 = jax.random.PRNGKey(ec.seed)
    store = init_store("nowait", ec.records_local, wl.rw, wl.init_value)
    # traceable under the batched sweep: no Python branching on the plane
    one_sided = jnp.asarray(ec.hybrid[0] == ONE_SIDED)
    is_rpc = jnp.logical_not(one_sided)
    K = wl.max_ops
    # live co-routines per node / batch size under bucket padding (traced)
    act_c = ec.coroutines if ec.active_coroutines is None else ec.active_coroutines
    n_live = jnp.asarray(ec.n_nodes * act_c, jnp.int32)

    def epoch_body(carry, epoch):
        store, = carry
        live = (
            jnp.asarray(True)
            if epochs_active is None
            else epoch < jnp.asarray(epochs_active, jnp.int32)
        )
        keys, is_w, valid, node = _epoch_txns(ec, wl, epoch, key0)
        wave = _waves(ec, keys, is_w, valid)
        n_waves = jnp.where(live, wave.max() + 1, 0)

        # ---- execute waves sequentially (deterministic order) ----------
        def wave_body(w, sd):
            rvals = eng.read_rows(ec, sd["data"], keys)
            wv = jax.vmap(wl.execute)(keys, is_w, valid, rvals)
            active = (wave == w)[:, None] & is_w & valid
            af = active.reshape(-1)
            idx = jnp.where(af, keys.reshape(-1), ec.n_records)
            sd = dict(sd)
            sd["data"] = eng.write_rows(ec, sd["data"], idx, wv.reshape(-1, wl.rw))
            sd["ver"] = eng.write_rows(ec, sd["ver"], idx, 1, op="add")
            return sd

        store = jax.lax.fori_loop(0, n_waves, wave_body, store)

        # ---- epoch cost model -------------------------------------------
        # sequencing: each node ships its C txn descriptors to n-1 peers
        # (message shapes from the central wire-cost table, DESIGN.md §5)
        desc_bytes = act_c * cmod.CALVIN_WIRE["sequence"].bytes_for(wl.rw, n_ops=K)
        # n_verbs=2 models the one-sided value+valid-flag WRITE pair; the RPC
        # branch of round_latency_us never reads n_verbs, so passing 2
        # unconditionally keeps the expression traceable.
        bcast = cmod.round_latency_us(
            cm, is_rpc, float(ec.n_nodes - 1), desc_bytes * (ec.n_nodes - 1),
            n_verbs=2, doorbell=ec.doorbell,
        )
        # RS/WS forwarding: ops whose owner differs from an active participant
        owner = keys // ec.records_per_node
        remote = valid & (owner != node[:, None])
        fwd_ops = remote.sum()
        fwd_bytes = fwd_ops * cmod.CALVIN_WIRE["forward"].bytes_for(wl.rw)
        fwd = cmod.round_latency_us(
            cm, is_rpc, fwd_ops / max(ec.n_nodes, 1), fwd_bytes / max(ec.n_nodes, 1),
            n_verbs=2, doorbell=ec.doorbell,
        )
        exec_us = n_waves.astype(jnp.float32) * wl.exec_ticks * cm.tick_us
        barrier = cm.tick_us  # epoch sync barrier across sequencers
        epoch_us = bcast + fwd + exec_us + barrier
        stats = {
            "commits": jnp.where(live, n_live, 0),
            "epoch_us": jnp.where(live, epoch_us, 0.0),
            "rounds": jnp.where(
                live, jnp.where(one_sided, jnp.float32(4), jnp.float32(2)), 0.0
            ),
            "waves": n_waves,
        }
        return (store,), stats

    (store,), stats = jax.lax.scan(epoch_body, (store,), jnp.arange(n_epochs))
    n_eff = n_epochs if epochs_active is None else jnp.asarray(epochs_active, jnp.int32)
    total_us = stats["epoch_us"].sum()
    commits = stats["commits"].sum()
    metrics = {
        "commits": commits,
        "aborts": jnp.int32(0),
        "throughput_mtps": commits / total_us,
        # txns commit at epoch end; dead (padded) epochs contribute zero
        "avg_latency_us": stats["epoch_us"].sum() / n_eff,
        "abort_rate": jnp.float32(0.0),
        "avg_round_trips": stats["rounds"].sum() / n_eff,
        "avg_waves": stats["waves"].sum() / n_eff,
        "stage_us_per_commit": jnp.zeros((cmod.N_STAGES,), jnp.float32),
    }
    return store, metrics


def run_epochs_sharded(
    ec: EngineConfig,
    cm: CostModel,
    wl: Workload,
    n_epochs: int,
    *,
    devices=None,
    axis: str = "node",
    epochs_active=None,
):
    """:func:`run_epochs` SPMD on a ``node`` device mesh (DESIGN.md §7).

    CALVIN's shared-nothing layout maps directly: the partitioned store is
    sharded by owner, sequencing/forwarding cost is sequencer-replicated
    bookkeeping, and each dependency wave's record exchange is one plane
    round (read collective + owner-local writes).  Bitwise-equal commit
    counters vs the dense :func:`run_epochs`.
    """
    from jax.sharding import PartitionSpec as P

    from repro.core import planes

    mesh, ec_sh = eng.node_mesh_config(ec, devices, axis)

    def body():
        return run_epochs(ec_sh, cm, wl, n_epochs, epochs_active=epochs_active)

    return planes.shard_map(
        body, mesh=mesh, in_specs=(), out_specs=(P(axis), P())
    )()


# ---------------------------------------------------------------------------
# Registry entry: CALVIN is epoch-driven, so it owns its run hooks instead of
# a slot-engine tick.  ``ticks`` from the front door map onto epochs at the
# historical ratio (one epoch per 8 ticks, floor 8) so grid specs stay
# comparable across protocols.
# ---------------------------------------------------------------------------


def epochs_for_ticks(ticks: int) -> int:
    return max(int(ticks) // 8, 8)


def _grid_run(entry, ec, cm, wl, *, ticks, warmup, ticks_active):
    ep_act = (
        None
        if ticks_active is None
        else jnp.maximum(jnp.asarray(ticks_active, jnp.int32) // 8, 8)
    )
    _, m = run_epochs(ec, cm, wl, epochs_for_ticks(ticks), epochs_active=ep_act)
    return m


def _node_run(entry, ec, cm, wl, *, ticks, warmup, devices):
    _, m = run_epochs_sharded(ec, cm, wl, epochs_for_ticks(ticks), devices=devices)
    return m


registry.register_protocol(
    "calvin",
    tick=None,
    stages=STAGES_USED,
    hooks=registry.RunHooks(grid_run=_grid_run, node_run=_node_run),
    capabilities=registry.Caps(
        # the wave executor's per-config traced wave count cannot batch
        # around the node collectives: single-config node meshes only
        node_shardable=True,
        batch_node_shardable=False,
        deterministic=True,
        tick_driven=False,
    ),
)
