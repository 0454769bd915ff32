"""Pure-jnp oracles for every Pallas kernel (tests assert against these)."""
from __future__ import annotations

import jax
import jax.numpy as jnp

_MIN = -(2**31)


def flash_attention_ref(q, k, v, *, causal=True):
    """q/k/v (B, H, S, Dh) -> (B, H, S, Dh) — naive O(S^2) fp32 softmax."""
    Dh = q.shape[-1]
    s = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32), k.astype(jnp.float32))
    s = s / jnp.sqrt(Dh)
    if causal:
        Sq, Sk = q.shape[2], k.shape[2]
        mask = jnp.arange(Sk)[None, :] <= jnp.arange(Sq)[:, None]
        s = jnp.where(mask[None, None], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", p.astype(v.dtype), v)


def mvcc_version_select_ref(wts_hi, wts_lo, ctts_hi, ctts_lo, lock_hi, lock_lo):
    ch, cl = ctts_hi[:, None], ctts_lo[:, None]
    lt = (wts_hi < ch) | ((wts_hi == ch) & (wts_lo < cl))
    occ = (wts_hi != 0) | (wts_lo != 0)
    cand = lt & occ
    bh = jnp.where(cand, wts_hi, _MIN).max(1, keepdims=True)
    at_h = cand & (wts_hi == bh)
    bl = jnp.where(at_h, wts_lo, _MIN).max(1, keepdims=True)
    winner = at_h & (wts_lo == bl)
    found = cand.any(1)
    slot = jnp.argmax(winner, axis=1).astype(jnp.int32)
    free = (lock_hi == 0) & (lock_lo == 0)
    after = (ctts_hi < lock_hi) | ((ctts_hi == lock_hi) & (ctts_lo < lock_lo))
    return found, slot, free | after


def lock_arbiter_ref(keys, prio_hi, prio_lo, active):
    """(G, M) -> won (G, M): per-group per-key lexicographic
    (prio_hi, prio_lo) minimum wins — ``scatter_min_winner`` semantics, no
    index tiebreak (callers guarantee unique pairs for winner uniqueness)."""
    same = keys[:, :, None] == keys[:, None, :]
    hi_j, hi_i = prio_hi[:, None, :], prio_hi[:, :, None]
    lo_j, lo_i = prio_lo[:, None, :], prio_lo[:, :, None]
    beats = same & active[:, None, :] & ((hi_j < hi_i) | ((hi_j == hi_i) & (lo_j < lo_i)))
    return active & ~beats.any(-1)


def multi_read_ref(table, keys):
    """table (R, ...), keys (...) -> keys.shape + table.shape[1:];
    negative (padding) keys gather 0."""
    out = table[jnp.clip(keys, 0, table.shape[0] - 1)]
    return jnp.where((keys >= 0).reshape(keys.shape + (1,) * (table.ndim - 1)), out, 0)
