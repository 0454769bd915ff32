"""Mixture-of-Experts with expert parallelism.

Design (TPU-native, see DESIGN.md §6): expert weights are sharded over the
`model` mesh axis; token activations are replicated over `model` (they are
batch-sharded over `data`/`pod`).  Each expert shard *locally selects* the
token assignments routed to its experts (zero-communication dispatch), runs
its experts, scatters weighted outputs back to token positions, and a single
psum over `model` combines partial outputs — the same collective cost as one
tensor-parallel FFN all-reduce.  Capacity-factor dropping bounds buffers.
"""
from __future__ import annotations


import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import PartitionSpec as P

from repro.configs.base import ArchConfig
from repro.layers.common import activation
from repro.sharding import AxisRules, dense_init


def init_moe(key, cfg: ArchConfig, dtype=jnp.float32):
    D, F, E = cfg.d_model, cfg.d_ff, cfg.n_experts
    p = {
        "wr": dense_init(key, "wr", (D, E), P("embed", None), jnp.float32),
        "wg": dense_init(key, "wg", (E, D, F), P("expert", "fsdp", None), dtype),
        "wu": dense_init(key, "wu", (E, D, F), P("expert", "fsdp", None), dtype),
        "wd": dense_init(key, "wd", (E, F, D), P("expert", "fsdp", None), dtype),
    }
    return p


def _capacity(cfg: ArchConfig, n_tokens: int, n_local_experts: int) -> int:
    c = int(n_tokens * cfg.top_k * cfg.capacity_factor / cfg.n_experts)
    return max(c, 4)


def _expert_ffn(cfg: ArchConfig, wg, wu, wd, buf):
    """buf (E_l, C, D) -> (E_l, C, D)."""
    dt = buf.dtype
    g = jnp.einsum("ecd,edf->ecf", buf, wg.astype(dt))
    u = jnp.einsum("ecd,edf->ecf", buf, wu.astype(dt))
    h = activation("silu", g) * u if cfg.mlp_act == "swiglu" else activation(cfg.mlp_act, g)
    return jnp.einsum("ecf,efd->ecd", h, wd.astype(dt))


def _route(cfg: ArchConfig, wr, x_flat):
    """x_flat (T,D) -> gates (T,k) fp32, expert ids (T,k) int32."""
    logits = jnp.einsum("td,de->te", x_flat.astype(jnp.float32), wr.astype(jnp.float32))
    probs = jax.nn.softmax(logits, axis=-1)
    gates, idx = jax.lax.top_k(probs, cfg.top_k)
    gates = gates / jnp.maximum(gates.sum(-1, keepdims=True), 1e-9)
    return gates, idx.astype(jnp.int32)


def _moe_local(cfg: ArchConfig, params_local, x, e0: jnp.ndarray, n_local: int):
    """Per-shard MoE body. x (B_l, S, D); processes experts [e0, e0+n_local)."""
    B, S, D = x.shape
    T = B * S
    x_flat = x.reshape(T, D)
    gates, idx = _route(cfg, params_local["wr"], x_flat)
    k = cfg.top_k
    C = _capacity(cfg, T, n_local)

    eid = idx.reshape(T * k)
    w = gates.reshape(T * k).astype(x.dtype)
    tid = jnp.repeat(jnp.arange(T, dtype=jnp.int32), k)

    le = eid - e0
    local = (le >= 0) & (le < n_local)
    le_safe = jnp.clip(le, 0, n_local - 1)
    onehot = jax.nn.one_hot(jnp.where(local, le_safe, n_local), n_local + 1, dtype=jnp.int32)
    pos = jnp.cumsum(onehot, axis=0) - 1  # running rank within each local expert
    pos_a = jnp.take_along_axis(pos, le_safe[:, None], axis=1)[:, 0]
    keep = local & (pos_a < C)
    dest = jnp.where(keep, le_safe * C + pos_a, n_local * C)  # overflow row

    buf = jnp.zeros((n_local * C + 1, D), x.dtype)
    buf = buf.at[dest].add(x_flat[tid] * keep.astype(x.dtype)[:, None])
    out = _expert_ffn(
        cfg,
        params_local["wg"],
        params_local["wu"],
        params_local["wd"],
        buf[: n_local * C].reshape(n_local, C, D),
    ).reshape(n_local * C, D)
    out = jnp.concatenate([out, jnp.zeros((1, D), out.dtype)], axis=0)

    contrib = out[dest] * (w * keep.astype(w.dtype))[:, None]
    y = jnp.zeros((T, D), x.dtype).at[tid].add(contrib)
    return y.reshape(B, S, D)


def apply_moe(params, cfg: ArchConfig, shd: AxisRules, x: jnp.ndarray) -> jnp.ndarray:
    """x (B,S,D) -> (B,S,D)."""
    if shd.mesh is None or "model" not in shd.axis_sizes or shd.axis_sizes["model"] == 1:
        return _moe_local(cfg, params, x, jnp.int32(0), cfg.n_experts)

    n_shards = shd.axis_sizes["model"]
    if cfg.n_experts % n_shards != 0:
        return _moe_local(cfg, params, x, jnp.int32(0), cfg.n_experts)
    n_local = cfg.n_experts // n_shards
    batch_spec = shd.resolve(P("batch"), (x.shape[0],))
    x_spec = P(batch_spec[0], None, None)
    # experts may be FSDP-sharded on the contraction dim; gather inside body
    fsdp_ax = shd.resolve(P("fsdp"), (cfg.d_model,))[0]
    w_spec = P("model", fsdp_ax, None)

    def body(wr, wg, wu, wd, x_l):
        m = jax.lax.axis_index("model")
        if fsdp_ax is not None:
            wg = jax.lax.all_gather(wg, fsdp_ax, axis=1, tiled=True)
            wu = jax.lax.all_gather(wu, fsdp_ax, axis=1, tiled=True)
            wd = jax.lax.all_gather(wd, fsdp_ax, axis=1, tiled=True)
        pl = {"wr": wr, "wg": wg, "wu": wu, "wd": wd}
        y = _moe_local(cfg, pl, x_l, m * n_local, n_local)
        return jax.lax.psum(y, "model")

    return shard_map(
        body,
        mesh=shd.mesh,
        in_specs=(P(), w_spec, w_spec, w_spec, x_spec),
        out_specs=x_spec,
    )(params["wr"], params["wg"], params["wu"], params["wd"], x)
