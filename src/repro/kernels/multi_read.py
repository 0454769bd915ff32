"""Doorbell-batched multi-read — Pallas TPU kernel.

One RDMA doorbell posts several dependent READs for the same key set
(paper §4.2); the engine's analogue is ``read_rows_many`` /
``planes.node_read_batch``: several store arrays gathered at one batch of
row ids.  This kernel is that gather as the RNIC does it: one DMA per key
and array, straight from the store in HBM, so its cost grows with the keys
read and never with the table.

The store arrays keep the layout XLA gives them, records on lanes: a
(R, w) array is stored feature-major, w words over R lanes, and a (R,)
array is one row of R lanes.  The kernel views each array that way — a
bitcast, no copy — and for a key k copies the 128-lane column block that
holds k (all w words of it) into VMEM; a whole block of keys' copies are
in flight on one DMA semaphore.  Once they have all landed, an exact
int32 lane select keeps lane k % 128 of each word.

A copy moves bits, and the select is an int32 ``where`` over a sum in
which every other term is the constant 0, so the result is bitwise
``table[keys]`` — no rounding of counters above 2^24, as an f32 matmul
would have.  That is what keeps the kernel plane bitwise-equal to the jnp
gather plane.

Under ``jax.vmap`` (the sweep's config axis) the kernel is batched by a
rule of its own: the config axis becomes a grid axis of ONE
``pallas_call``, and the keys stay blocked SMEM inputs, not scalar
prefetch (whose batching rule loops over the batch).

``interpret=None`` (the default) defers to backend detection in
``repro.kernels.ops`` — compiled on TPU/GPU, interpret mode on CPU CI.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128
MAX_BLOCK_M = 512  # keys per grid step: bounds the copies in flight and the VMEM tiles


def _kernel(keys_smem, keys_vmem, *refs, n: int, n_rows: int, block_m: int):
    srcs, outs, bufs, sem = refs[:n], refs[n : 2 * n], refs[2 * n : 3 * n], refs[3 * n]
    b = pl.program_id(0)

    def window(src, buf, k):
        """The HBM window one copy reads for key k, shaped like buf[i]."""
        col = pl.multiple_of(k // LANES * LANES, LANES)
        if len(src.shape) == 3:  # (B, w, R): all w words of the key's column block
            return src.at[b, :, pl.ds(col, LANES)]
        t = buf.shape[1]  # (B, R): the t-row tile that holds this batch row
        top = pl.multiple_of(b // t * t, t)
        return src.at[pl.ds(top, t), pl.ds(col, LANES)]

    def copies(i):
        k = jnp.clip(keys_smem[0, i], 0, n_rows - 1)  # a DMA out of range faults
        for src, buf in zip(srcs, bufs):
            yield pltpu.make_async_copy(window(src, buf, k), buf.at[i], sem)

    def start(j, carry):  # eight keys per trip: block_m is a multiple of 8
        for u in range(8):
            for cp in copies(j * 8 + u):
                cp.start()
        return carry

    jax.lax.fori_loop(0, block_m // 8, start, 0)
    # The semaphore counts the data that lands, so one wait sized as a whole
    # buffer waits for all of the block's copies into it: a wait per copy
    # would cost as much again as issuing it.
    for buf in bufs:
        pltpu.make_async_copy(buf, buf, sem).wait()

    keys = keys_vmem[...]  # (bm, 1)
    hit = jax.lax.broadcasted_iota(jnp.int32, (block_m, LANES), 1) == (
        jnp.clip(keys, 0, n_rows - 1) % LANES
    )

    def lane(words):  # (bm, 128) -> (bm, 1): each key's own lane, exactly
        return jnp.where(hit, words, 0).sum(axis=1, keepdims=True)

    for src, buf, out in zip(srcs, bufs, outs):
        if len(src.shape) == 3:
            cols = [lane(buf[:, c, :]) for c in range(buf.shape[1])]
        else:
            t, r = buf.shape[1], b % buf.shape[1]
            cols = [lane(sum(jnp.where(r == j, buf[:, j, :], 0) for j in range(t)))]
        for c, word in enumerate(cols):
            out[:, c : c + 1] = jnp.where(keys >= 0, word, 0)


def _block_m(m: int) -> int:
    """Keys per grid step, from the key count: at most MAX_BLOCK_M, in
    blocks as even as a multiple of 8 allows."""
    return pl.cdiv(pl.cdiv(m, pl.cdiv(m, MAX_BLOCK_M)), 8) * 8


def _row_tile(b: int) -> int:
    """Rows of the HBM tile of a (B, R) int32 array: one DMA reads whole
    tiles along the batch rows (8, or the power of two that holds B)."""
    return min(8, 1 << (b - 1).bit_length())


def _lane_view(a):
    """(B, R, ...) -> the array as stored, records on lanes: (B, R) for
    one word per record, else (B, w, R).  A (B, R) array whose B is not a
    whole number of row tiles is padded to one (a copy; the cells' config
    batches are 1 or a multiple of 8)."""
    if a.ndim == 2:
        pad = (-a.shape[0]) % _row_tile(a.shape[0])
        return jnp.pad(a, ((0, pad), (0, 0))) if pad else a
    b, r = a.shape[:2]
    return a.reshape(b, r, -1).swapaxes(1, 2)


@functools.partial(jax.jit, static_argnames="interpret", inline=True)
def _pallas_read(arrs, keys, interpret: bool):
    """arrs: (B, R, ...) int32; keys (B, M) -> (B, M, w) int32 each.  Jitted
    so that reads of one shape trace the kernel once per program."""
    B, M = keys.shape
    R = arrs[0].shape[1]
    views = [_lane_view(a) for a in arrs]
    pad_r = (-R) % LANES  # real stores hold a multiple of 128 records
    if pad_r:
        views = [jnp.pad(v, [(0, 0)] * (v.ndim - 1) + [(0, pad_r)]) for v in views]
    if not interpret:  # keep XLA from staging a table in VMEM for the call: a copy
        views = [pltpu.with_memory_space_constraint(v, pltpu.HBM) for v in views]
    widths = [1 if v.ndim == 2 else v.shape[1] for v in views]
    rows = [_row_tile(v.shape[0]) if v.ndim == 2 else v.shape[1] for v in views]
    bm = _block_m(M)
    nb = pl.cdiv(M, bm)
    keys = jnp.pad(keys, ((0, 0), (0, nb * bm - M)), constant_values=-1)
    outs = pl.pallas_call(
        functools.partial(_kernel, n=len(arrs), n_rows=R, block_m=bm),
        grid=(B, nb),
        in_specs=[
            pl.BlockSpec((None, None, 1, bm), lambda b, i: (b, i, 0, 0), memory_space=pltpu.SMEM),
            pl.BlockSpec((None, bm, 1), lambda b, i: (b, i, 0)),
        ]
        + [pl.BlockSpec(memory_space=pltpu.HBM)] * len(arrs),
        out_specs=[pl.BlockSpec((None, bm, w), lambda b, i: (b, i, 0)) for w in widths],
        out_shape=[jax.ShapeDtypeStruct((B, nb * bm, w), jnp.int32) for w in widths],
        scratch_shapes=[pltpu.VMEM((bm, r, LANES), jnp.int32) for r in rows]
        + [pltpu.SemaphoreType.DMA(())],
        interpret=interpret,
        name="multi_read",
    )(keys.reshape(B, nb, 1, bm), keys[:, :, None], *views)
    return tuple(o[:, :M] for o in outs)


@functools.lru_cache(maxsize=None)
def _batched_read(interpret: bool):
    """``_pallas_read`` with a vmap rule that folds each new batch axis into
    the grid's leading axis (one ``pallas_call`` however deep the vmap)."""

    @jax.custom_batching.custom_vmap
    def read(arrs, keys):
        return _pallas_read(arrs, keys, interpret)

    @read.def_vmap
    def _rule(axis_size, in_batched, arrs, keys):
        def fold(x, batched):  # (N, B, ...) -> (N * B, ...)
            x = x if batched else jnp.broadcast_to(x, (axis_size,) + x.shape)
            return x.reshape((-1,) + x.shape[2:])

        arrs_b, keys_b = in_batched
        outs = read(tuple(map(fold, arrs, arrs_b)), fold(keys, keys_b))
        outs = tuple(o.reshape((axis_size, -1) + o.shape[1:]) for o in outs)
        return outs, (True,) * len(outs)

    return read


def multi_read(arrs, keys, *, interpret=None):
    """Gather rows of several store arrays at the same keys.

    arrs: int32 arrays (R, ...) with the same R; keys: int32 of any shape,
    in [0, R) or negative.  Returns one array per store array, shaped
    ``keys.shape + arr.shape[1:]``: ``arr[keys]``, with zeros where a key
    is negative (padding)."""
    if interpret is None:
        from repro.kernels import ops

        interpret = ops.default_interpret()
    R = arrs[0].shape[0]
    if any(a.shape[0] != R or a.dtype != jnp.int32 for a in arrs):
        raise ValueError(f"multi_read takes int32 arrays of {R} rows: {[a.shape for a in arrs]}")
    outs = _batched_read(bool(interpret))(
        tuple(a[None] for a in arrs), keys.reshape(1, -1).astype(jnp.int32)
    )
    return tuple(o[0].reshape(keys.shape + a.shape[1:]) for o, a in zip(outs, arrs))
