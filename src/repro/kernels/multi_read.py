"""Doorbell-batched multi-read — Pallas TPU kernel.

One RDMA doorbell posts several dependent READs for the same key set
(paper §4.2); the engine's analogue is ``read_rows_many`` /
``planes.node_read_batch``: several store arrays packed along a feature
axis and gathered at one batch of row ids.  This kernel fuses that gather:
the packed table streams through VMEM feature-major (packed columns on
sublanes, records on lanes) one record block at a time, while each key
block (keys on sublanes) builds a 2-D hit mask against the block's record
ids and accumulates, column by column in a static loop, the one matching
word per key.

The accumulation is an EXACT int32 select-and-sum (each key matches
exactly one table row, every other contribution is the int32 constant 0)
— never a matmul, whose f32 MXU path would silently round counters above
2^24.  That exactness is what keeps the kernel plane bitwise-equal to the
jnp gather plane.

``interpret=None`` (the default) defers to backend detection in
``repro.kernels.ops`` — compiled on TPU/GPU, interpret mode on CPU CI.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _kernel(keys_ref, table_ref, out_ref, *, block_r: int):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _zero():
        out_ref[...] = jnp.zeros_like(out_ref)

    rel = keys_ref[...] - j * block_r  # (bm, 1): key's offset into this record block
    hit = rel == jax.lax.broadcasted_iota(jnp.int32, (rel.shape[0], block_r), 1)
    for a in range(table_ref.shape[0]):  # static loop over packed columns
        row = table_ref[a : a + 1, :]  # (1, br)
        out_ref[:, a : a + 1] += jnp.where(hit, row, 0).sum(axis=1, keepdims=True)


def multi_read(table, keys, *, block_m: int = 256, block_r: int = 2048, interpret=None):
    """Gather packed rows: table (R, A) int32, keys (M,) int32 in [0, R)
    -> (M, A) int32 == table[keys].  Negative keys (padding) return zeros."""
    if interpret is None:
        from repro.kernels import ops

        interpret = ops.default_interpret()
    M = keys.shape[0]
    R, A = table.shape
    block_m = min(block_m, pl.cdiv(M, 8) * 8)
    block_r = min(block_r, pl.cdiv(R, 128) * 128)
    pad_m = (-M) % block_m
    pad_r = (-R) % block_r
    keys = jnp.pad(keys, ((0, pad_m),), constant_values=-1)[:, None]  # (Mp, 1)
    table_t = jnp.pad(table.T, ((0, 0), (0, pad_r)))  # (A, Rp)
    Mp, Rp = M + pad_m, R + pad_r
    out = pl.pallas_call(
        lambda kr, tr, orf: _kernel(kr, tr, orf, block_r=block_r),
        grid=(Mp // block_m, Rp // block_r),
        in_specs=[
            pl.BlockSpec((block_m, 1), lambda i, j: (i, 0)),
            pl.BlockSpec((A, block_r), lambda i, j: (0, j)),
        ],
        out_specs=pl.BlockSpec((block_m, A), lambda i, j: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((Mp, A), jnp.int32),
        interpret=interpret,
        name="multi_read",
    )(keys, table_t)
    return out[:M]
