"""MVCC version selection — Pallas TPU kernel.

RCC's per-op read hot loop (paper §4.4): for a batch of read requests,
pick the slot with the largest wts < ctts among the S static version slots
(Cond R1) and check Cond R2 (lock free or lock > ctts).  TPU-native
layout: the version slots ride the sublane axis and the requests tile the
lane axis (``block_m`` lanes per step), so every compare is a lane-dense
VPU op and the slot reductions run over sublanes — no gathers.  All
arithmetic is int32 (Mosaic lowers no bool argmax and no bool outputs);
the wrapper transposes in and casts out.  The slot count comes from the
input shape (``mvcc_slots`` is an EngineConfig ablation knob, not a
kernel constant).

``interpret=None`` (the default) defers to backend detection in
``repro.kernels.ops`` — compiled on TPU/GPU, interpret mode on CPU CI.
"""
from __future__ import annotations


import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

_MIN = -(2**31)


def _kernel(wts_hi_ref, wts_lo_ref, ctts_hi_ref, ctts_lo_ref, lk_hi_ref, lk_lo_ref,
            found_ref, slot_ref, ok_ref):
    wh, wl = wts_hi_ref[...], wts_lo_ref[...]  # (S, bm)
    ch, cl = ctts_hi_ref[...], ctts_lo_ref[...]  # (1, bm)
    lh, ll = lk_hi_ref[...], lk_lo_ref[...]  # (1, bm)
    # Cond R1: largest (wh, wl) < (ch, cl), excluding empty (0,0) slots
    lt = (wh < ch) | ((wh == ch) & (wl < cl))
    occupied = (wh != 0) | (wl != 0)
    cand = lt & occupied
    best_h = jnp.where(cand, wh, _MIN).max(axis=0, keepdims=True)
    at_h = cand & (wh == best_h)
    best_l = jnp.where(at_h, wl, _MIN).max(axis=0, keepdims=True)
    winner = at_h & (wl == best_l)
    # first winning slot (argmax semantics), S when there is none
    n_slots = wh.shape[0]
    iota = jax.lax.broadcasted_iota(jnp.int32, wh.shape, 0)
    first = jnp.where(winner, iota, n_slots).min(axis=0, keepdims=True)
    found = first < n_slots
    found_ref[...] = found.astype(jnp.int32)
    slot_ref[...] = jnp.where(found, first, 0)
    # Cond R2: lock free, or lock (writer tts) ordered after ctts
    free = (lh == 0) & (ll == 0)
    after = (ch < lh) | ((ch == lh) & (cl < ll))
    ok_ref[...] = (free | after).astype(jnp.int32)


def mvcc_version_select(wts_hi, wts_lo, ctts_hi, ctts_lo, lock_hi, lock_lo,
                        *, block_m: int = 512, interpret=None):
    """wts_* (M, S), the rest (M,) int32 -> (found (M,), slot (M,), r2_ok (M,))."""
    if interpret is None:
        from repro.kernels import ops

        interpret = ops.default_interpret()
    M, S = wts_hi.shape
    block_m = min(block_m, pl.cdiv(M, 128) * 128)
    pad = (-M) % block_m

    def lanes2(a):  # (M, S) -> (S, Mp)
        return jnp.pad(a.T, ((0, 0), (0, pad)))

    def lanes1(a):  # (M,) -> (1, Mp)
        return jnp.pad(a, ((0, pad),))[None]

    Mp = M + pad
    s2 = pl.BlockSpec((S, block_m), lambda i: (0, i))
    s1 = pl.BlockSpec((1, block_m), lambda i: (0, i))
    found, slot, ok = pl.pallas_call(
        _kernel,
        grid=(Mp // block_m,),
        in_specs=[s2, s2, s1, s1, s1, s1],
        out_specs=[s1, s1, s1],
        out_shape=[jax.ShapeDtypeStruct((1, Mp), jnp.int32)] * 3,
        interpret=interpret,
        name="mvcc_version_select",
    )(lanes2(wts_hi), lanes2(wts_lo), *map(lanes1, (ctts_hi, ctts_lo, lock_hi, lock_lo)))
    return found[0, :M] != 0, slot[0, :M], ok[0, :M] != 0
