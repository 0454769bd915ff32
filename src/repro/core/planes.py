"""SPMD communication planes: the production mapping of RCC's two
primitive families onto mesh collectives (DESIGN.md §2, §7).

The engine (engine.py) simulates the cluster on one device for benchmarks;
THIS module is the distribution plane: the same tuple-store service
expressed with shard_map + jax.lax collectives over a `node` mesh axis.
Two layers live here:

  * the **request-routed planes** (`make_planes`): requests packed into
    per-destination buffers and exchanged with `all_to_all` — the
    standalone proof that one engine round maps onto one fabric exchange.
  * the **engine transport** (`NodeShard` + the `node_*` primitives):
    what `engine.run_sharded` actually runs on.  The store lives sharded
    (each mesh shard owns its nodes' record rows — data, locks, versions);
    the tiny per-slot coordinator state is sequencer-replicated, so every
    request set is known mesh-wide and a round needs exactly ONE reply
    exchange: the owner shard does the gather / arbitrated CAS / capacity
    ranking on its local rows (the RNIC's / handler CPU's job) and replies
    combine with a `psum` whose every addend is zero except the owner's —
    bytes on the wire = bytes in the collective.  `node_read_batch` is the
    doorbell-batched multi-op round (§4.2): several metadata words for the
    same key set ride one exchange.

One-sided plane (`os_read` / `os_cas`): requests are address-only; the
owner shard performs raw gathers / arbitrated CAS (the RNIC's job — zero
protocol logic) and payloads return via the same all_to_all.  Two-sided
plane (`rpc_call`): the owner runs a vectorized *handler* on the delivered
requests (the remote CPU's job).  Both planes use one all_to_all exchange
per round = one network round, matching the engine's tick semantics.
"""
from __future__ import annotations

from typing import Callable, NamedTuple, Sequence, Tuple

import jax
import jax.numpy as jnp

from jax.sharding import Mesh, PartitionSpec as P

from repro.core.arbiter import scatter_min_winner
from repro.kernels import ops as kops


def shard_map(f, *, mesh: Mesh, in_specs, out_specs):
    """``jax.shard_map`` with replication checking off: the one place every
    SPMD body of this repo (engine, CALVIN, 2-D grid, routed planes) enters
    a mesh.  The engine's replicated outputs are psum'd or sequencer-
    replicated by construction, which the checker cannot see through a
    scan carry."""
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs, out_specs=out_specs, check_vma=False)


def _psum(x, axis: str):
    """A round's reply exchange (one ``psum``), named ``exchange`` in traces."""
    with jax.named_scope("exchange"):
        return jax.lax.psum(x, axis)


def _all_to_all(x, axis: str):
    """One routed fabric exchange, named ``exchange`` in traces."""
    with jax.named_scope("exchange"):
        return jax.lax.all_to_all(x, axis, 0, 0, tiled=True)


# ---------------------------------------------------------------------------
# Engine transport: node-sharded store primitives (DESIGN.md §7)
# ---------------------------------------------------------------------------


class NodeShard(NamedTuple):
    """Mesh placement of the simulated cluster (EngineConfig.shard).

    ``axis`` is the mesh axis name the store's record rows are sharded
    over; ``n_shards`` its size.  Simulated nodes map onto shards in
    contiguous blocks (n_nodes % n_shards == 0), so a shard owns whole
    nodes' record ranges and the dense engine's key -> owner arithmetic
    is preserved.  A None shard on EngineConfig means the dense
    single-device engine — every primitive below then degenerates to the
    plain gather/scatter it replaces, keeping one code path.
    """

    axis: str
    n_shards: int


def _local_ix(shard: NodeShard, r_local: int, keys):
    """Global row ids -> (local row ids clipped in range, ownership mask).

    The read-side form: gather from the clipped index, mask the value.
    """
    off = jax.lax.axis_index(shard.axis).astype(jnp.int32) * r_local
    li = keys.astype(jnp.int32) - off
    mine = (li >= 0) & (li < r_local)
    return jnp.clip(li, 0, r_local - 1), mine


def local_ix_drop(shard: NodeShard, r_local: int, idx):
    """Global row ids -> local row ids with non-owned rows at the drop
    sentinel ``r_local`` (the write-side form: scatter with mode="drop").
    The caller's own drop sentinel (>= global rows) lands out of every
    shard's range and stays dropped."""
    off = jax.lax.axis_index(shard.axis).astype(jnp.int32) * r_local
    li = idx.astype(jnp.int32) - off
    return jnp.where((li < 0) | (li >= r_local), r_local, li)


def node_read(shard: NodeShard, arr, keys):
    """One-sided READ round: gather global rows of a node-sharded array.

    ``arr`` is the LOCAL shard (r_local, ...); ``keys`` (...,) global row
    ids (replicated).  The owner does the DMA gather on its rows; replies
    combine in one psum exchange (all other shards contribute zeros).
    """
    kf = keys.reshape(-1)
    li, mine = _local_ix(shard, arr.shape[0], kf)
    vals = arr[li]
    vals = jnp.where(mine.reshape((-1,) + (1,) * (arr.ndim - 1)), vals, 0)
    out = _psum(vals, shard.axis)
    return out.reshape(keys.shape + arr.shape[1:])


def node_read_batch(shard: NodeShard, arrs: Sequence, keys, *, kernel_plane: str = "jnp") -> Tuple:
    """Doorbell-batched multi-op READ: several arrays, same keys, ONE
    exchange.  The per-array replies are flattened along a feature axis,
    psum'd together, and split back — the collective analogue of posting
    dependent reads in a single doorbell (§4.2).  On a Pallas kernel plane
    the owner's local gather is the multi-read kernel, one DMA per key and
    array (the RNIC's DMA engine); the exchange structure is identical."""
    kf = keys.reshape(-1)
    li, mine = _local_ix(shard, arrs[0].shape[0], kf)
    vals = kops.gather_many(arrs, li, plane=kernel_plane)
    flat = [jnp.where(mine[:, None], v.reshape(kf.shape[0], -1), 0) for v in vals]
    out = _psum(jnp.concatenate(flat, axis=1), shard.axis)
    outs, pos = [], 0
    for a, f in zip(arrs, flat):
        w = f.shape[1]
        outs.append(out[:, pos : pos + w].reshape(keys.shape + a.shape[1:]))
        pos += w
    return tuple(outs)


def node_read2(shard: NodeShard, arr, keys, sel):
    """READ of (row, slot) pairs from a (r_local, S, ...) sharded array
    (MVCC version-slot fetch).  One exchange."""
    kf, sf = keys.reshape(-1), sel.reshape(-1)
    li, mine = _local_ix(shard, arr.shape[0], kf)
    vals = arr[li, sf]
    vals = jnp.where(mine.reshape((-1,) + (1,) * (arr.ndim - 2)), vals, 0)
    out = _psum(vals, shard.axis)
    return out.reshape(keys.shape + arr.shape[2:])


def node_write(shard: NodeShard, arr, idx, vals, *, op: str = "set"):
    """One-sided WRITE round: scatter into global rows of a sharded array.

    ``idx`` (M,) global row ids with the caller's drop sentinel >= the
    global row count for masked-off requests (the dense convention).  The
    request set is sequencer-replicated, so the owner applies its rows'
    updates locally and NO reply exchange is needed (write acks carry no
    payload).  ``op`` in {"set", "add"}.
    """
    li = local_ix_drop(shard, arr.shape[0], idx)
    if op == "add":
        return arr.at[li].add(vals, mode="drop")
    return arr.at[li].set(vals, mode="drop")


def node_write2(shard: NodeShard, arr, idx, sel, vals, *, op: str = "set"):
    """WRITE of (row, slot) pairs into a (r_local, S, ...) sharded array."""
    li = local_ix_drop(shard, arr.shape[0], idx)
    if op == "add":
        return arr.at[li, sel].add(vals, mode="drop")
    return arr.at[li, sel].set(vals, mode="drop")


def node_cas_winner(shard: NodeShard, r_local: int, keys, prio_hi, prio_lo, active,
                    *, kernel_plane: str = "jnp"):
    """One-sided CAS arbitration round: per-key (prio_hi, prio_lo) minimum.

    The owner shard arbitrates the requests that target its rows — its
    memory controller serializes the CASes, exactly `scatter_min_winner`
    over the local range (or the all-pairs arbitration kernel on a Pallas
    plane: same lexicographic-min winners bitwise) — and the won-bits
    combine in one psum exchange.  Bitwise-equal to the dense global
    arbitration: every key's contest happens entirely at its owner with
    the same priorities.
    """
    li, mine = _local_ix(shard, r_local, keys)
    win_l = kops.cas_arbitrate(li, prio_hi, prio_lo, active & mine, r_local, plane=kernel_plane)
    return _psum(win_l.astype(jnp.int32), shard.axis) > 0


def _route(requests, dest, n_nodes, cap):
    """Pack per-node request buffers (n_nodes, cap, ...) by destination.

    requests (M, W) int32; dest (M,); entries beyond cap are dropped (the
    caller sizes cap = M for losslessness).
    """
    onehot = jax.nn.one_hot(dest, n_nodes, dtype=jnp.int32)  # (M, n)
    pos = jnp.cumsum(onehot, axis=0) - onehot  # rank within destination
    slot = (pos * onehot).sum(-1)
    keep = slot < cap
    # dropped requests scatter to an out-of-bounds destination (discarded by
    # mode="drop") instead of aliasing into slot cap-1 and clobbering the
    # request legitimately routed there
    dest_k = jnp.where(keep, dest, n_nodes)
    slot_k = jnp.where(keep, slot, 0)
    buf = jnp.zeros((n_nodes, cap, requests.shape[1]), requests.dtype)
    buf = buf.at[dest_k, slot_k].set(requests, mode="drop")
    valid = jnp.zeros((n_nodes, cap), bool).at[dest_k, slot_k].set(True, mode="drop")
    return buf, valid, slot


def make_planes(mesh: Mesh, axis: str, records_per_node: int, rw: int, cap: int = 0):
    """Returns jittable (os_read, os_cas, rpc_call) over a node-sharded store.

    ``cap`` bounds the per-destination request buffer (0 = size it for
    losslessness, i.e. the per-shard request count).  With a finite cap,
    requests beyond it are DROPPED by the routing fabric: their replies
    come back zero / not-won, never another request's payload (the reply
    un-route masks by the routing validity, mirroring an RNIC dropping
    work requests when the send queue overflows).
    """
    n_nodes = mesh.shape[axis]

    def os_read(store_data, keys):
        """One-sided READ: keys (n_local,) global keys per node shard.

        store_data sharded (node, R_local, rw); returns values for each key
        (zeros for requests dropped by a finite ``cap``).  The owner does
        NO protocol logic — just the DMA gather.
        """

        def body(data_l, keys_l):
            m = keys_l.shape[0]
            c = cap or m
            dest = keys_l // records_per_node
            req = jnp.stack([keys_l % records_per_node, jnp.arange(m, dtype=jnp.int32)], 1)
            buf, _, slot = _route(req, dest, n_nodes, c)
            inbox = _all_to_all(buf, axis)  # (n*c, 2)
            inbox = inbox.reshape(n_nodes, c, 2)
            # RNIC DMA: raw gather, no handler logic
            vals = data_l[jnp.clip(inbox[..., 0], 0, data_l.shape[0] - 1)]
            back = _all_to_all(vals.reshape(n_nodes * c, rw), axis)
            back = back.reshape(n_nodes, c, rw)
            # un-route: value for local request i sits at (dest[i], slot-in-dest);
            # dropped requests (slot >= c) must NOT alias slot c-1
            keep = slot < c
            out = back[dest, jnp.minimum(slot, c - 1)]
            return jnp.where(keep[:, None], out, 0)

        return shard_map(
            body, mesh=mesh, in_specs=(P(axis, None), P(axis)), out_specs=P(axis, None)
        )(store_data, keys)

    def os_cas(lock_words, keys, new_vals):
        """One-sided CAS (expect-free): arbitrated at the owner's memory
        controller; returns won-mask.  lock_words sharded (node, R_local)."""

        def body(lock_l, keys_l, new_l):
            m = keys_l.shape[0]
            c = cap or m
            dest = keys_l // records_per_node
            req = jnp.stack(
                [keys_l % records_per_node, new_l, jnp.arange(m, dtype=jnp.int32)], 1
            )
            buf, valid, slot = _route(req, dest, n_nodes, c)
            inbox = _all_to_all(buf, axis).reshape(n_nodes, c, 3)
            vwin = _all_to_all(valid.astype(jnp.int32), axis)
            v = vwin.reshape(n_nodes * c) > 0
            addr = inbox.reshape(-1, 3)[:, 0]
            newv = inbox.reshape(-1, 3)[:, 1]
            win = scatter_min_winner(
                addr, jnp.zeros_like(addr), jnp.arange(addr.shape[0], dtype=jnp.int32), v, lock_l.shape[0]
            )
            free = lock_l[jnp.clip(addr, 0, lock_l.shape[0] - 1)] == 0
            ok = win & free & v
            lock_l = lock_l.at[jnp.where(ok, addr, lock_l.shape[0])].set(
                jnp.where(ok, newv, 0), mode="drop"
            )
            okb = _all_to_all(ok.reshape(n_nodes, c).astype(jnp.int32), axis).reshape(n_nodes, c)
            # dropped requests never won (and must not alias slot c-1's result)
            keep = slot < c
            return lock_l, (okb[dest, jnp.minimum(slot, c - 1)] > 0) & keep

        return shard_map(
            body,
            mesh=mesh,
            in_specs=(P(axis), P(axis), P(axis)),
            out_specs=(P(axis), P(axis)),
        )(lock_words, keys, new_vals)

    def rpc_call(store_data, keys, handler: Callable):
        """Two-sided RPC: requests routed to owners; the OWNER's CPU runs
        `handler(data_local, addrs) -> (data_local', replies)`."""

        def body(data_l, keys_l):
            m = keys_l.shape[0]
            c = cap or m
            dest = keys_l // records_per_node
            req = jnp.stack([keys_l % records_per_node, jnp.arange(m, dtype=jnp.int32)], 1)
            buf, valid, slot = _route(req, dest, n_nodes, c)
            inbox = _all_to_all(buf, axis).reshape(n_nodes, c, 2)
            vmask = _all_to_all(valid.astype(jnp.int32), axis)
            data_l, replies = handler(data_l, inbox[..., 0].reshape(-1), vmask.reshape(-1) > 0)
            back = _all_to_all(replies.reshape(n_nodes * c, -1), axis).reshape(n_nodes, c, -1)
            # dropped requests get a zero reply, not another request's payload
            keep = slot < c
            return data_l, jnp.where(keep[:, None], back[dest, jnp.minimum(slot, c - 1)], 0)

        return shard_map(
            body,
            mesh=mesh,
            in_specs=(P(axis, None), P(axis)),
            out_specs=(P(axis, None), P(axis, None)),
        )(store_data, keys)

    return os_read, os_cas, rpc_call
