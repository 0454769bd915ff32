"""GQA attention: train/prefill (scan-flash, local-window, bidirectional),
and decode with a sequence-sharded KV cache (flash-decoding style lse-combine).

Three execution tiers:
  * naive O(S^2) reference           — tests / tiny shapes (`naive_attention`)
  * scan-flash (pure XLA, online softmax over KV chunks) — production CPU/XLA path
  * Pallas TPU kernel (kernels/flash_attention.py)        — TPU target, opt-in
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import PartitionSpec as P

from repro.configs.base import ArchConfig
from repro.sharding import AxisRules, dense_init, zeros_init


# ---------------------------------------------------------------------------
# Params
# ---------------------------------------------------------------------------


def init_attn(key, cfg: ArchConfig, dtype=jnp.float32, cross: bool = False):
    D, H, KV, Dh = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    p = {
        "wq": dense_init(key, "wq", (D, H * Dh), P("embed", "heads"), dtype),
        "wk": dense_init(key, "wk", (D, KV * Dh), P("embed", "kv_heads"), dtype),
        "wv": dense_init(key, "wv", (D, KV * Dh), P("embed", "kv_heads"), dtype),
        "wo": dense_init(key, "wo", (H * Dh, D), P("heads", "embed"), dtype),
    }
    if cfg.qkv_bias:
        p["bq"] = zeros_init("bq", (H * Dh,), P("heads"), dtype)
        p["bk"] = zeros_init("bk", (KV * Dh,), P("kv_heads"), dtype)
        p["bv"] = zeros_init("bv", (KV * Dh,), P("kv_heads"), dtype)
    if cfg.mlp_bias:
        p["bo"] = zeros_init("bo", (D,), P("embed"), dtype)
    return p


def _project_qkv(params, cfg: ArchConfig, x, kv_x=None):
    """x (B,S,D) -> q (B,S,H,Dh), k/v (B,S_kv,KV,Dh)."""
    B, S, D = x.shape
    H, KV, Dh = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    kv_x = x if kv_x is None else kv_x
    q = jnp.einsum("bsd,de->bse", x, params["wq"].astype(x.dtype))
    k = jnp.einsum("bsd,de->bse", kv_x, params["wk"].astype(x.dtype))
    v = jnp.einsum("bsd,de->bse", kv_x, params["wv"].astype(x.dtype))
    if "bq" in params:
        q = q + params["bq"].astype(x.dtype)
        k = k + params["bk"].astype(x.dtype)
        v = v + params["bv"].astype(x.dtype)
    q = q.reshape(B, S, H, Dh)
    k = k.reshape(B, kv_x.shape[1], KV, Dh)
    v = v.reshape(B, kv_x.shape[1], KV, Dh)
    return q, k, v


def _out_proj(params, x_attn, dtype):
    """(B,S,H,Dh) -> (B,S,D)."""
    B, S, H, Dh = x_attn.shape
    out = jnp.einsum("bse,ed->bsd", x_attn.reshape(B, S, H * Dh), params["wo"].astype(dtype))
    if "bo" in params:
        out = out + params["bo"].astype(dtype)
    return out


def repeat_kv(k: jnp.ndarray, n_rep: int) -> jnp.ndarray:
    """(B,S,KV,Dh) -> (B,S,KV*n_rep,Dh)."""
    if n_rep == 1:
        return k
    B, S, KV, Dh = k.shape
    return jnp.broadcast_to(k[:, :, :, None, :], (B, S, KV, n_rep, Dh)).reshape(
        B, S, KV * n_rep, Dh
    )


# ---------------------------------------------------------------------------
# Naive reference (tests / tiny)
# ---------------------------------------------------------------------------


def naive_attention(q, k, v, causal: bool, window: int = 0, q_offset: int = 0):
    """q (B,Sq,H,Dh), k/v (B,Sk,H,Dh) -> (B,Sq,H,Dh). fp32 softmax."""
    Dh = q.shape[-1]
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32) / jnp.sqrt(Dh)
    Sq, Sk = q.shape[1], k.shape[1]
    qpos = jnp.arange(Sq)[:, None] + q_offset
    kpos = jnp.arange(Sk)[None, :]
    mask = jnp.ones((Sq, Sk), bool)
    if causal:
        mask &= kpos <= qpos
    if window:
        mask &= kpos > qpos - window
    s = jnp.where(mask[None, None], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", p.astype(v.dtype), v)


# ---------------------------------------------------------------------------
# Scan-flash (online softmax over KV chunks) — pure XLA production path
# ---------------------------------------------------------------------------


def flash_attention_xla(
    q, k, v, *, causal: bool, window: int = 0, chunk: int = 1024, q_offset: int = 0
):
    """Memory-bounded attention: scan over KV chunks with online softmax.

    q (B,Sq,H,Dh), k/v (B,Sk,H,Dh) with H already GQA-expanded.
    """
    B, Sq, H, Dh = q.shape
    Sk = k.shape[1]
    chunk = min(chunk, Sk)
    n_chunks = -(-Sk // chunk)
    pad = n_chunks * chunk - Sk
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
    kc = k.reshape(B, n_chunks, chunk, H, Dh).transpose(1, 0, 3, 2, 4)  # (n,B,H,C,Dh)
    vc = v.reshape(B, n_chunks, chunk, H, Dh).transpose(1, 0, 3, 2, 4)
    qT = q.transpose(0, 2, 1, 3)  # (B,H,Sq,Dh)
    scale = 1.0 / jnp.sqrt(Dh).astype(jnp.float32)
    qpos = jnp.arange(Sq) + q_offset

    def step(carry, inp):
        m, l, acc = carry
        j, k_j, v_j = inp
        s = jnp.einsum("bhqd,bhcd->bhqc", qT, k_j).astype(jnp.float32) * scale
        kpos = j * chunk + jnp.arange(chunk)
        valid = kpos[None, :] < Sk
        if causal:
            valid &= kpos[None, :] <= qpos[:, None]
        if window:
            valid &= kpos[None, :] > qpos[:, None] - window
        s = jnp.where(valid[None, None], s, -1e30)
        m_new = jnp.maximum(m, s.max(-1))
        p = jnp.exp(s - m_new[..., None])
        corr = jnp.exp(m - m_new)
        l = l * corr + p.sum(-1)
        acc = acc * corr[..., None] + jnp.einsum(
            "bhqc,bhcd->bhqd", p.astype(v_j.dtype), v_j
        ).astype(jnp.float32)
        return (m_new, l, acc), None

    init = (
        jnp.full((B, H, Sq), -1e30, jnp.float32),
        jnp.zeros((B, H, Sq), jnp.float32),
        jnp.zeros((B, H, Sq, Dh), jnp.float32),
    )
    (m, l, acc), _ = jax.lax.scan(step, init, (jnp.arange(n_chunks), kc, vc))
    out = acc / jnp.maximum(l, 1e-30)[..., None]
    return out.transpose(0, 2, 1, 3).astype(q.dtype)  # (B,Sq,H,Dh)


# ---------------------------------------------------------------------------
# Local (sliding-window) attention via chunking — exact for window <= chunk
# ---------------------------------------------------------------------------


def local_attention_xla(q, k, v, *, window: int, causal: bool = True):
    """Chunked sliding-window attention. q/k/v (B,S,H,Dh), H pre-expanded.

    Each query chunk of size W attends to [its own chunk, previous chunk],
    masked to the exact window — O(S * 2W) memory/compute.
    """
    B, S, H, Dh = q.shape
    W = window
    if S <= W:
        return naive_attention(q, k, v, causal=causal, window=W)
    n = -(-S // W)
    pad = n * W - S
    if pad:
        q = jnp.pad(q, ((0, 0), (0, pad), (0, 0), (0, 0)))
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
    qc = q.reshape(B, n, W, H, Dh)
    kc = k.reshape(B, n, W, H, Dh)
    vc = v.reshape(B, n, W, H, Dh)
    k_prev = jnp.concatenate([jnp.zeros_like(kc[:, :1]), kc[:, :-1]], axis=1)
    v_prev = jnp.concatenate([jnp.zeros_like(vc[:, :1]), vc[:, :-1]], axis=1)
    k2 = jnp.concatenate([k_prev, kc], axis=2)  # (B,n,2W,H,Dh)
    v2 = jnp.concatenate([v_prev, vc], axis=2)
    s = jnp.einsum("bnqhd,bnkhd->bnhqk", qc, k2).astype(jnp.float32) / jnp.sqrt(Dh)
    qpos = jnp.arange(W)[:, None] + W  # position within [prev, cur] frame
    kpos = jnp.arange(2 * W)[None, :]
    mask = (kpos <= qpos) if causal else jnp.ones((W, 2 * W), bool)
    mask &= kpos > qpos - W
    # first chunk has no previous chunk
    first = jnp.arange(n)[:, None, None] > 0
    mask_n = mask[None] & (first | (kpos[None] >= W))
    s = jnp.where(mask_n[None, :, None, :, :], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bnhqk,bnkhd->bnqhd", p.astype(v2.dtype), v2)
    out = out.reshape(B, n * W, H, Dh)
    return out[:, :S]


# ---------------------------------------------------------------------------
# Decode attention with sequence-sharded KV cache (flash-decoding)
# ---------------------------------------------------------------------------


def _gqa_partials(q, k_cache, v_cache, valid):
    """GQA partial attention without head expansion.

    q (B,KV,rep,Dh); k/v_cache (B,C,KV,Dh); valid (C,) bool.
    Returns fp32 (num (B,KV,rep,Dh), den (B,KV,rep), m (B,KV,rep)).
    """
    Dh = q.shape[-1]
    s = jnp.einsum("bkrd,bckd->bkrc", q, k_cache).astype(jnp.float32) / jnp.sqrt(Dh)
    s = jnp.where(valid[None, None, None, :], s, -1e30)
    m = s.max(-1)
    p = jnp.exp(s - m[..., None])
    den = p.sum(-1)
    num = jnp.einsum("bkrc,bckd->bkrd", p.astype(v_cache.dtype), v_cache).astype(jnp.float32)
    return num, den, m


def decode_attn_cached(
    cfg: ArchConfig,
    shd: AxisRules,
    q,  # (B, H, Dh) — rope already applied
    k_new,  # (B, KV, Dh) or None (cross-attention / no write)
    v_new,
    k_cache,  # (B, S, KV, Dh)
    v_cache,
    cache_len,  # scalar int32: #valid entries BEFORE this step
    *,
    ring: bool = False,  # ring buffer (sliding-window) cache
):
    """One-token attention against a (possibly sequence-sharded) KV cache.

    Writes (k_new, v_new) at cache_len (mod S for ring), attends over valid
    entries, lse-combining partials across the `model` axis when the cache's
    sequence dim is sharded (flash-decoding).  Returns (out (B,H,Dh), k_cache,
    v_cache).
    """
    B, S, KV, Dh = k_cache.shape
    H = q.shape[1]
    rep = H // KV
    qg = q.reshape(B, KV, rep, Dh)

    kv_axes = shd.resolve(P("kv_seq"), (S,)) if shd.mesh is not None else P(None)
    sharded = kv_axes[0] is not None

    def write(kc, vc, kn, vn, slot, mine):
        upd_k = jax.lax.dynamic_update_slice(kc, kn[:, None], (0, slot, 0, 0))
        upd_v = jax.lax.dynamic_update_slice(vc, vn[:, None], (0, slot, 0, 0))
        kc = jnp.where(mine, upd_k, kc)
        vc = jnp.where(mine, upd_v, vc)
        return kc, vc

    if not sharded:
        if k_new is not None:
            slot = jnp.mod(cache_len, S) if ring else jnp.clip(cache_len, 0, S - 1)
            k_cache, v_cache = write(k_cache, v_cache, k_new, v_new, slot, True)
        n_valid = cache_len + (0 if k_new is None else 1)
        if ring:
            valid = jnp.arange(S) < jnp.minimum(n_valid, S)
        else:
            valid = jnp.arange(S) < n_valid
        num, den, m = _gqa_partials(qg, k_cache, v_cache, valid)
        out = num / jnp.maximum(den, 1e-30)[..., None]
        return out.reshape(B, H, Dh).astype(q.dtype), k_cache, v_cache

    # --- sequence-sharded cache: shard_map over the model axis -------------
    batch_ax = shd.resolve(P("batch"), (B,))[0]
    cache_spec = P(batch_ax, kv_axes[0], None, None)
    rep_spec_q = P(batch_ax, None, None)
    mesh_axis = kv_axes[0] if isinstance(kv_axes[0], str) else kv_axes[0][0]

    def body(qg_l, kn, vn, kc, vc, clen):
        s_local = kc.shape[1]
        idx = jax.lax.axis_index(mesh_axis)
        off = idx * s_local
        if kn is not None:
            tgt = (jnp.mod(clen, S) if ring else clen) - off
            mine = (tgt >= 0) & (tgt < s_local)
            slot = jnp.clip(tgt, 0, s_local - 1)
            kc, vc = write(kc, vc, kn, vn, slot, mine)
        n_valid = clen + (0 if kn is None else 1)
        pos = jnp.arange(s_local) + off
        if ring:
            valid = pos < jnp.minimum(n_valid, S)
        else:
            valid = pos < n_valid
        num, den, m = _gqa_partials(qg_l, kc, vc, valid)
        g_m = jax.lax.pmax(m, mesh_axis)
        corr = jnp.exp(m - g_m)
        num = jax.lax.psum(num * corr[..., None], mesh_axis)
        den = jax.lax.psum(den * corr, mesh_axis)
        out = num / jnp.maximum(den, 1e-30)[..., None]
        return out, kc, vc

    has_new = k_new is not None
    in_specs = (
        P(batch_ax, None, None, None),  # qg
        rep_spec_q if has_new else None,
        rep_spec_q if has_new else None,
        cache_spec,
        cache_spec,
        P(),
    )
    out_specs = (P(batch_ax, None, None, None), cache_spec, cache_spec)
    if not has_new:
        def body2(qg_l, kc, vc, clen):
            return body(qg_l, None, None, kc, vc, clen)

        out, k_cache, v_cache = shard_map(
            body2,
            mesh=shd.mesh,
            in_specs=(P(batch_ax, None, None, None), cache_spec, cache_spec, P()),
            out_specs=out_specs,
        )(qg, k_cache, v_cache, cache_len)
    else:
        out, k_cache, v_cache = shard_map(
            body,
            mesh=shd.mesh,
            in_specs=in_specs,
            out_specs=out_specs,
        )(qg, k_new, v_new, k_cache, v_cache, cache_len)
    return out.reshape(B, H, Dh).astype(q.dtype), k_cache, v_cache


def decode_attention_local(q, k_cache, v_cache, cache_len, *, pos_offset=0):
    """Partial attention over a local cache chunk; returns (num, denom, max).

    q (B,H,Dh); k/v_cache (B,C,H,Dh) — H pre-expanded.  Entries at global
    position >= cache_len are masked.  Returns fp32 partials for lse-combine.
    """
    Dh = q.shape[-1]
    s = jnp.einsum("bhd,bchd->bhc", q, k_cache).astype(jnp.float32) / jnp.sqrt(Dh)
    pos = jnp.arange(k_cache.shape[1]) + pos_offset
    s = jnp.where((pos < cache_len)[None, None, :], s, -1e30)
    m = s.max(-1)  # (B,H)
    p = jnp.exp(s - m[..., None])
    den = p.sum(-1)
    num = jnp.einsum("bhc,bchd->bhd", p.astype(v_cache.dtype), v_cache).astype(jnp.float32)
    return num, den, m


def combine_partials(num, den, m, axis_name: Optional[str]):
    """lse-weighted combine of partial attention across a mesh axis."""
    if axis_name is None:
        return num / jnp.maximum(den, 1e-30)[..., None]
    g_m = jax.lax.pmax(m, axis_name)
    corr = jnp.exp(m - g_m)
    num = jax.lax.psum(num * corr[..., None], axis_name)
    den = jax.lax.psum(den * corr, axis_name)
    return num / jnp.maximum(den, 1e-30)[..., None]
