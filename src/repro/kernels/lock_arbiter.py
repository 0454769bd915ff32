"""Lock-CAS arbitration — Pallas TPU kernel.

Models the owning node's RNIC serializing concurrent CAS verbs: within each
owner group, request i wins iff no active request j on the same key has a
lexicographically smaller (prio_hi, prio_lo).  The all-pairs compare is
tiled over a (G, i-block, j-block) grid: each step compares one block of
contenders j (sublanes) against one block of requests i (lanes) and ORs a
lane-dense "beaten" flag, so no tile grows with the batch — the
TPU-native replacement for the GPU-style atomic-CAS loop.

Semantics are EXACTLY ``repro.core.arbiter.scatter_min_winner``: pure
lexicographic minimum, no index tiebreak — engine callers guarantee unique
(prio_hi, prio_lo) pairs among active requests (timestamp pairs, or a
hashed hi word with the unique logical op index as the lo word), which is
what makes the winner unique and the kernel plane bitwise-interchangeable
with the jnp plane.

``interpret=None`` (the default) defers to backend detection in
``repro.kernels.ops`` — compiled on TPU/GPU, interpret mode on CPU CI.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _kernel(key_i_ref, hi_i_ref, lo_i_ref, key_j_ref, hi_j_ref, lo_j_ref, act_j_ref, beaten_ref):
    @pl.when(pl.program_id(2) == 0)
    def _zero():
        beaten_ref[...] = jnp.zeros_like(beaten_ref)

    key_i, hi_i, lo_i = key_i_ref[...], hi_i_ref[...], lo_i_ref[...]  # (1, b): requests
    key_j, hi_j, lo_j = key_j_ref[...], hi_j_ref[...], lo_j_ref[...]  # (b, 1): contenders
    beats = (
        (key_j == key_i)
        & (act_j_ref[...] != 0)
        & ((hi_j < hi_i) | ((hi_j == hi_i) & (lo_j < lo_i)))
    )  # (b, b)
    beaten_ref[...] = jnp.maximum(
        beaten_ref[...], beats.astype(jnp.int32).max(axis=0, keepdims=True)
    )


def lock_arbiter(keys, prio_hi, prio_lo, active, *, block_m: int = 512, interpret=None):
    """Per-owner arbitration. keys/prio_hi/prio_lo (G, M) int32, active
    (G, M) bool -> won (G, M) bool.  G = owner groups (nodes); M = max
    requests per owner.  A request wins iff it is the per-key lexicographic
    (prio_hi, prio_lo) minimum among active requests in its group (ties ->
    multiple winners, exactly as ``scatter_min_winner``)."""
    if interpret is None:
        from repro.kernels import ops

        interpret = ops.default_interpret()
    G, M = keys.shape
    block_m = min(block_m, pl.cdiv(M, 128) * 128)
    pad = (-M) % block_m
    act = active.astype(jnp.int32)
    keys, prio_hi, prio_lo, act = (
        jnp.pad(a, ((0, 0), (0, pad))) for a in (keys, prio_hi, prio_lo, act)
    )
    Mp = M + pad
    n = Mp // block_m
    rows = [a[:, None, :] for a in (keys, prio_hi, prio_lo)]  # (G, 1, Mp)
    cols = [a[:, :, None] for a in (keys, prio_hi, prio_lo, act)]  # (G, Mp, 1)
    row_spec = pl.BlockSpec((None, 1, block_m), lambda g, i, j: (g, 0, i))
    col_spec = pl.BlockSpec((None, block_m, 1), lambda g, i, j: (g, j, 0))
    beaten = pl.pallas_call(
        _kernel,
        grid=(G, n, n),
        in_specs=[row_spec] * 3 + [col_spec] * 4,
        out_specs=row_spec,
        out_shape=jax.ShapeDtypeStruct((G, 1, Mp), jnp.int32),
        interpret=interpret,
        name="lock_arbiter",
    )(*rows, *cols)
    return active & (beaten[:, 0, :M] == 0)
