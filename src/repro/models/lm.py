"""Unified LM stack covering all assigned architecture families.

One parameterized decoder (plus optional encoder) built from block kinds:
  "attn"   — GQA attention (+ optional sliding window) + FFN/MoE
  "ssm"    — Mamba-1 selective SSM (no separate FFN)
  "rglru"  — RG-LRU recurrent block + FFN

Forward entry points:
  lm_loss(...)          train-time causal LM loss over the full sequence
  lm_prefill(...)       full forward building a KV/state cache, returns last logits
  lm_decode_step(...)   one-token decode against the cache (seq-sharded KV)
"""
from __future__ import annotations

from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
from jax import shard_map

from repro import flags
from jax.sharding import PartitionSpec as P

from repro.configs.base import ArchConfig
from repro.layers import attention as attn_lib
from repro.layers.common import apply_mrope, apply_norm, apply_rope, init_norm, sinusoidal_positions
from repro.layers.mlp import apply_mlp, init_mlp
from repro.layers.moe import apply_moe, init_moe
from repro.layers.rglru import apply_rglru, init_rglru
from repro.layers.ssm import apply_ssm, init_ssm
from repro.sharding import AxisRules, Param, dense_init, name_key, unzip_params


# ---------------------------------------------------------------------------
# Init
# ---------------------------------------------------------------------------


def _init_layer(key, cfg: ArchConfig, kind: str, dtype):
    if kind == "ssm":
        return {"norm": init_norm(cfg.norm, cfg.d_model, dtype), "ssm": init_ssm(key, cfg, dtype)}
    if kind == "rglru":
        return {
            "norm1": init_norm(cfg.norm, cfg.d_model, dtype),
            "rglru": init_rglru(key, cfg, dtype),
            "norm2": init_norm(cfg.norm, cfg.d_model, dtype),
            "mlp": init_mlp(key, cfg, dtype),
        }
    # attention block
    ffn = init_moe(key, cfg, dtype) if cfg.is_moe else init_mlp(key, cfg, dtype)
    ffn_name = "moe" if cfg.is_moe else "mlp"
    if cfg.parallel_block:
        return {
            "norm": init_norm(cfg.norm, cfg.d_model, dtype),
            "attn": attn_lib.init_attn(key, cfg, dtype),
            ffn_name: ffn,
        }
    return {
        "norm1": init_norm(cfg.norm, cfg.d_model, dtype),
        "attn": attn_lib.init_attn(key, cfg, dtype),
        "norm2": init_norm(cfg.norm, cfg.d_model, dtype),
        ffn_name: ffn,
    }


def _init_dec_layer(key, cfg: ArchConfig, dtype):
    """Whisper decoder layer: self-attn + cross-attn + FFN."""
    return {
        "norm1": init_norm(cfg.norm, cfg.d_model, dtype),
        "attn": attn_lib.init_attn(key, cfg, dtype),
        "norm_x": init_norm(cfg.norm, cfg.d_model, dtype),
        "xattn": attn_lib.init_attn(key, cfg, dtype, cross=True),
        "norm2": init_norm(cfg.norm, cfg.d_model, dtype),
        "mlp": init_mlp(key, cfg, dtype),
    }


def _stack_init(key, n: int, init_fn):
    """vmap a Param-returning init over n layers; prepend layer dim to specs."""
    keys = jax.random.split(key, n)
    captured = {}

    def vals_fn(k):
        vals, specs = unzip_params(init_fn(k))
        captured["specs"] = specs
        return vals

    jax.eval_shape(vals_fn, keys[0])  # capture specs without allocating
    values = jax.vmap(vals_fn)(keys)
    specs = jax.tree.map(lambda s: P(None, *tuple(s)), captured["specs"])
    return jax.tree.map(Param, values, specs)


def init_lm(key, cfg: ArchConfig, dtype=jnp.float32):
    V, D = cfg.vocab_size, cfg.d_model
    # embed table: vocab-sharded only (it is small per device already; an
    # extra fsdp axis on D would force gathers in the sharded lookup)
    params: Dict[str, Any] = {
        "embed": dense_init(key, "embed", (V, D), P("vocab", None), dtype, scale=0.02),
        "final_norm": init_norm(cfg.norm, D, dtype),
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = dense_init(key, "lm_head", (D, V), P(("embed", "fsdp"), "vocab"), dtype)

    kinds = cfg.layer_kinds()
    if cfg.is_hybrid:
        pat = cfg.block_pattern
        n_full = cfg.n_layers // len(pat)
        rem = cfg.n_layers % len(pat)
        params["groups"] = {
            f"g{j}_{k}": _stack_init(
                name_key(key, f"grp{j}"), n_full, lambda kk, kind=k: _init_layer(kk, cfg, kind, dtype)
            )
            for j, k in enumerate(pat)
        }
        params["tail"] = [
            _init_layer(name_key(key, f"tail{i}"), cfg, pat[i], dtype) for i in range(rem)
        ]
    else:
        kind = kinds[0]
        params["layers"] = _stack_init(
            name_key(key, "layers"), cfg.n_layers, lambda kk: _init_layer(kk, cfg, kind, dtype)
        )

    if cfg.encoder_decoder:
        params["enc_layers"] = _stack_init(
            name_key(key, "enc"), cfg.n_enc_layers, lambda kk: _init_layer(kk, cfg, "attn", dtype)
        )
        params["enc_norm"] = init_norm(cfg.norm, D, dtype)
        params["dec_layers"] = _stack_init(
            name_key(key, "dec"), cfg.n_layers, lambda kk: _init_dec_layer(kk, cfg, dtype)
        )
        # NOTE: whisper proper uses a learned decoder position table (448
        # entries); the assigned 32k/500k shapes exceed any learned table, so
        # we use sinusoidal decoder positions (documented deviation).
        del params["layers"]
    return params


# ---------------------------------------------------------------------------
# Block bodies (full-sequence)
# ---------------------------------------------------------------------------


def _rope(cfg: ArchConfig, x, positions):
    if cfg.mrope_sections is not None:
        return apply_mrope(x, positions, cfg.mrope_sections, cfg.rope_theta)
    return apply_rope(x, positions, cfg.rope_pct, cfg.rope_theta)


def _attn_full(lp, cfg: ArchConfig, shd: AxisRules, x, positions, *, causal=True, window=0, use_rope=True):
    q, k, v = attn_lib._project_qkv(lp, cfg, x)
    if use_rope:
        q = _rope(cfg, q, positions)
        k = _rope(cfg, k, positions)
    # Explicit layouts (perf: see EXPERIMENTS.md §Perf iteration 1): Q shards
    # on heads; K/V stay REPLICATED over `model` when kv_heads doesn't divide
    # it — without this, GSPMD shards K/V on head_dim and every attention
    # score einsum becomes a partial-sum + all-reduce of (B,H,S,chunk).
    q = shd.constrain(q, "batch", None, "heads", None)
    k = shd.constrain(k, "batch", None, "kv_heads", None)
    v = shd.constrain(v, "batch", None, "kv_heads", None)
    k = attn_lib.repeat_kv(k, cfg.n_rep)
    v = attn_lib.repeat_kv(v, cfg.n_rep)
    S = x.shape[1]
    if window and S > window:
        out = attn_lib.local_attention_xla(q, k, v, window=window, causal=causal)
    elif flags.USE_PALLAS_ATTENTION and not window and jax.default_backend() == "tpu":
        from repro.kernels.flash_attention import flash_attention as _fa

        out = _fa(
            q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3), v.transpose(0, 2, 1, 3),
            causal=causal, interpret=False,
        ).transpose(0, 2, 1, 3)
    elif S <= 512:
        out = attn_lib.naive_attention(q, k, v, causal=causal, window=window)
    else:
        out = attn_lib.flash_attention_xla(q, k, v, causal=causal, window=window)
    return attn_lib._out_proj(lp, out, x.dtype)


def _ffn(lp, cfg: ArchConfig, shd, x):
    if cfg.is_moe:
        return apply_moe(lp["moe"], cfg, shd, x)
    return apply_mlp(lp["mlp"], cfg, shd, x)


def _block_full(lp, cfg: ArchConfig, shd, kind: str, x, positions, *, causal=True):
    """One decoder block over a full sequence. x (B,S,D)."""
    if kind == "ssm":
        return x + apply_ssm(lp["ssm"], cfg, shd, apply_norm(cfg.norm, lp["norm"], x))
    if kind == "rglru":
        x = x + apply_rglru(lp["rglru"], cfg, shd, apply_norm(cfg.norm, lp["norm1"], x))
        return x + apply_mlp(lp["mlp"], cfg, shd, apply_norm(cfg.norm, lp["norm2"], x))
    window = cfg.local_window if (cfg.is_hybrid and kind == "attn") else 0
    if cfg.parallel_block:
        h = apply_norm(cfg.norm, lp["norm"], x)
        return x + _attn_full(lp["attn"], cfg, shd, h, positions, causal=causal, window=window) + _ffn(
            lp, cfg, shd, h
        )
    h = apply_norm(cfg.norm, lp["norm1"], x)
    x = x + _attn_full(lp["attn"], cfg, shd, h, positions, causal=causal, window=window)
    x = shd.constrain(x, "batch", "seq", None)
    return x + _ffn(lp, cfg, shd, apply_norm(cfg.norm, lp["norm2"], x))


def _remat(f, cfg: ArchConfig):
    if cfg.remat == "none":
        return f
    if cfg.remat == "save_attn":
        policy = jax.checkpoint_policies.checkpoint_dots_with_no_batch_dims
        return jax.checkpoint(f, policy=policy)
    return jax.checkpoint(f)


def _run_stack(params, cfg: ArchConfig, shd, x, positions, *, causal=True):
    """Scan the decoder stack over x (B,S,D)."""
    if cfg.is_hybrid:
        pat = cfg.block_pattern
        group_stacks = [params["groups"][f"g{j}_{k}"] for j, k in enumerate(pat)]

        def group_body(h, lps):
            for j, kind in enumerate(pat):
                h = _block_full(lps[j], cfg, shd, kind, h, positions, causal=causal)
            return h, None

        vals = [unzip_params(g)[0] if _has_params(g) else g for g in group_stacks]
        x, _ = flags.scan(_remat(group_body, cfg), x, tuple(vals))
        for i, lp in enumerate(params["tail"]):
            lpv = unzip_params(lp)[0] if _has_params(lp) else lp
            x = _block_full(lpv, cfg, shd, pat[i], x, positions, causal=causal)
        return x

    kind = cfg.layer_kinds()[0]

    def body(h, lp):
        return _block_full(lp, cfg, shd, kind, h, positions, causal=causal), None

    stacked = params["layers"]
    vals = unzip_params(stacked)[0] if _has_params(stacked) else stacked
    x, _ = flags.scan(_remat(body, cfg), x, vals)
    return x


def _has_params(tree) -> bool:
    found = [False]

    def chk(x):
        if isinstance(x, Param):
            found[0] = True
        return x

    jax.tree.map(chk, tree, is_leaf=lambda x: isinstance(x, Param))
    return found[0]


def strip_params(tree):
    """Param-leaved tree -> raw value tree (no-op if already raw)."""
    return unzip_params(tree)[0] if _has_params(tree) else tree


# ---------------------------------------------------------------------------
# Embedding / logits / loss
# ---------------------------------------------------------------------------


def embed_tokens(params, cfg: ArchConfig, shd, tokens):
    """Vocab-sharded lookup: local masked gather + psum over the vocab axis.

    Without this, GSPMD all-gathers the whole table per lookup (observed in
    the decode dry-runs — EXPERIMENTS.md §Perf iteration 2).
    """
    emb = params["embed"]
    if shd.mesh is not None:
        vocab_ax = shd.resolve(P("vocab"), (cfg.vocab_size,))[0]
        if vocab_ax is not None:
            batch_ax = shd.resolve(P("batch"), (tokens.shape[0],))[0]
            v_local = cfg.vocab_size // shd.axis_sizes[
                vocab_ax if isinstance(vocab_ax, str) else vocab_ax[0]
            ]
            ax_name = vocab_ax if isinstance(vocab_ax, str) else vocab_ax[0]

            def body(emb_l, tok_l):
                v0 = jax.lax.axis_index(ax_name) * v_local
                loc = tok_l - v0
                mine = (loc >= 0) & (loc < v_local)
                x = emb_l[jnp.clip(loc, 0, v_local - 1)]
                x = jnp.where(mine[..., None], x, 0)
                return jax.lax.psum(x, ax_name)

            x = shard_map(
                body,
                mesh=shd.mesh,
                in_specs=(P(ax_name, None), P(batch_ax, None)),
                out_specs=P(batch_ax, None, None),
            )(emb, tokens)
            return shd.constrain(x, "batch", "seq", None)
    x = jnp.take(emb, tokens, axis=0)
    return shd.constrain(x, "batch", "seq", None)


def logits_fn(params, cfg: ArchConfig, shd, x):
    x = apply_norm(cfg.norm, params["final_norm"], x)
    w = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
    logits = jnp.einsum("bsd,dv->bsv", x, w.astype(x.dtype))
    return shd.constrain(logits, "batch", "seq", "vocab")


def xent_loss(logits, labels, mask=None, shd: Optional[AxisRules] = None):
    """Streaming-safe cross-entropy with vocab possibly sharded."""
    lf = logits.astype(jnp.float32)
    m = lf.max(-1, keepdims=True)
    lse = jnp.log(jnp.sum(jnp.exp(lf - m), axis=-1)) + m[..., 0]
    onehot = jax.nn.one_hot(labels, lf.shape[-1], dtype=lf.dtype)
    if shd is not None:
        onehot = shd.constrain(onehot, "batch", "seq", "vocab")
    gold = jnp.sum(lf * onehot, axis=-1)
    nll = lse - gold
    if mask is None:
        return nll.mean()
    return (nll * mask).sum() / jnp.maximum(mask.sum(), 1.0)


# ---------------------------------------------------------------------------
# Whisper encoder (stub conv frontend: inputs are precomputed frame embeds)
# ---------------------------------------------------------------------------


def encode_audio(params, cfg: ArchConfig, shd, frames):
    """frames (B, T_enc, D) -> encoder states."""
    pos = sinusoidal_positions(frames.shape[1], cfg.d_model).astype(frames.dtype)
    x = frames + pos[None]
    positions = jnp.arange(frames.shape[1])[None]

    def body(h, lp):
        return _block_full(lp, cfg, shd, "attn", h, positions, causal=False), None

    x, _ = flags.scan(_remat(body, cfg), x, strip_params(params["enc_layers"]))
    return apply_norm(cfg.norm, params["enc_norm"], x)


def _dec_block_full(lp, cfg: ArchConfig, shd, x, enc, positions):
    h = apply_norm(cfg.norm, lp["norm1"], x)
    x = x + _attn_full(lp["attn"], cfg, shd, h, positions, causal=True, use_rope=False)
    h = apply_norm(cfg.norm, lp["norm_x"], x)
    q, k, v = attn_lib._project_qkv(lp["xattn"], cfg, h, kv_x=enc)
    k = attn_lib.repeat_kv(k, cfg.n_rep)
    v = attn_lib.repeat_kv(v, cfg.n_rep)
    out = attn_lib.flash_attention_xla(q, k, v, causal=False)
    x = x + attn_lib._out_proj(lp["xattn"], out, x.dtype)
    return x + apply_mlp(lp["mlp"], cfg, shd, apply_norm(cfg.norm, lp["norm2"], x))


def _run_decoder_encdec(params, cfg: ArchConfig, shd, x, enc, positions):
    S = x.shape[1]
    x = x + sinusoidal_positions(S, cfg.d_model).astype(x.dtype)[None]

    def body(h, lp):
        return _dec_block_full(lp, cfg, shd, h, enc, positions), None

    x, _ = flags.scan(_remat(body, cfg), x, strip_params(params["dec_layers"]))
    return x


# ---------------------------------------------------------------------------
# Public full-sequence entry points
# ---------------------------------------------------------------------------


def lm_hidden(params, cfg: ArchConfig, shd: AxisRules, batch: Dict[str, jnp.ndarray]):
    """Backbone forward -> final hidden states (B,S,D)."""
    tokens = batch["tokens"]
    B, S = tokens.shape
    positions = batch.get("positions")
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(S)[None], (B, S))
    x = embed_tokens(params, cfg, shd, tokens)
    if cfg.encoder_decoder:
        enc = encode_audio(params, cfg, shd, batch["frames"])
        x = _run_decoder_encdec(params, cfg, shd, x, enc, positions)
    else:
        x = _run_stack(params, cfg, shd, x, positions, causal=True)
    return x


def lm_apply(params, cfg: ArchConfig, shd: AxisRules, batch: Dict[str, jnp.ndarray]):
    """Full forward -> logits (B,S,V). batch: tokens (+positions/frames)."""
    return logits_fn(params, cfg, shd, lm_hidden(params, cfg, shd, batch))


def lm_loss(params, cfg: ArchConfig, shd: AxisRules, batch, loss_chunk: int = 1024) -> jnp.ndarray:
    """Causal LM loss with SEQUENCE-CHUNKED head+xent: the (B,S,V) logits
    tensor is never materialized (EXPERIMENTS.md §Perf iteration 3) — each
    chunk's logits are recomputed in the backward pass (checkpointed), which
    trades one extra lm_head matmul for ~B*S*V*8 bytes of peak temp."""
    labels = batch["labels"]
    x = lm_hidden(params, cfg, shd, batch)
    xs, ys = x[:, :-1], labels[:, 1:]
    B, S1, D = xs.shape
    chunk = min(loss_chunk, S1)
    n = -(-S1 // chunk)
    pad = n * chunk - S1
    mask = jnp.pad(jnp.ones((B, S1), jnp.float32), ((0, 0), (0, pad)))
    if pad:
        xs = jnp.pad(xs, ((0, 0), (0, pad), (0, 0)))
        ys = jnp.pad(ys, ((0, 0), (0, pad)))
    xs = xs.reshape(B, n, chunk, D).transpose(1, 0, 2, 3)
    ys = ys.reshape(B, n, chunk).transpose(1, 0, 2)
    mask = mask.reshape(B, n, chunk).transpose(1, 0, 2)
    head = {"final_norm": params["final_norm"], "embed": params["embed"]}
    if not cfg.tie_embeddings:
        head["lm_head"] = params["lm_head"]

    @jax.checkpoint
    def chunk_nll(head_p, xc, yc, mc):
        logits = logits_fn(head_p, cfg, shd, xc)
        lf = logits.astype(jnp.float32)
        m = lf.max(-1, keepdims=True)
        lse = jnp.log(jnp.sum(jnp.exp(lf - m), axis=-1)) + m[..., 0]
        onehot = shd.constrain(jax.nn.one_hot(yc, lf.shape[-1], dtype=lf.dtype), "batch", None, "vocab")
        gold = jnp.sum(lf * onehot, axis=-1)
        return ((lse - gold) * mc).sum()

    def body(acc, inp):
        xc, yc, mc = inp
        return acc + chunk_nll(head, xc, yc, mc), None

    total, _ = flags.scan(body, jnp.zeros((), jnp.float32), (xs, ys, mask))
    return total / jnp.maximum(mask.sum(), 1.0)
