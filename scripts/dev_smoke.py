"""Dev smoke: protocol-engine matrix + reduced LM configs on 1 CPU device.

``--fast`` runs the protocol matrix through the batched sweep engine (one
compiled grid per protocol instead of one jit per (protocol, plane) cell)
and is what CI's quick job uses.
"""
import argparse
import os
import sys

# runnable as `python scripts/dev_smoke.py` from a checkout
_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for _p in (_ROOT, os.path.join(_ROOT, "src")):
    if _p not in sys.path:
        sys.path.insert(0, _p)

# device flags are parsed (benchmarks.common, jax-free) before any heavy
# import below pulls in jax — fake-host forcing must come first
B, S = 2, 64


def batch_for(cfg):
    import jax
    import jax.numpy as jnp

    key = jax.random.PRNGKey(0)
    b = {
        "tokens": jax.random.randint(key, (B, S), 0, cfg.vocab_size),
        "labels": jax.random.randint(key, (B, S), 0, cfg.vocab_size),
    }
    if cfg.encoder_decoder:
        b["frames"] = jax.random.normal(key, (B, cfg.enc_seq_len, cfg.d_model), jnp.float32)
    if cfg.mrope_sections is not None:
        b["positions"] = jnp.broadcast_to(jnp.arange(S)[None, None], (B, 3, S)).astype(jnp.int32)
    return b


def protocol_matrix(fast: bool) -> None:
    """Every REGISTERED protocol x {rpc, one-sided} commits transactions."""
    from repro.api import ExperimentSpec, run
    from repro.core.costmodel import ONE_SIDED, RPC
    from repro.core.registry import protocol_names

    kw = dict(n_nodes=2, coroutines=6, records_per_node=256, ticks=48, warmup=8)
    planes = [{"hybrid": (RPC,) * 6}, {"hybrid": (ONE_SIDED,) * 6}]
    for proto in protocol_names():
        if fast:
            # one compiled 2-config grid per protocol, planned by repro.api
            rows = run(
                ExperimentSpec(protocol=proto, workload="smallbank", configs=planes, **kw)
            ).rows
        else:
            # true sequential reference (static hybrid, one jit per cell)
            from benchmarks.common import run_cell

            rows = [run_cell(proto, "smallbank", p["hybrid"], **kw)[0] for p in planes]
        for impl, m in zip(("rpc", "one_sided"), rows):
            assert m["commits"] > 0, (proto, impl, m)
            assert m["abort_rate"] < 1.0, (proto, impl, m)
        print(
            f"    {proto}: ok (commits rpc={rows[0]['commits']} "
            f"one_sided={rows[1]['commits']})",
            flush=True,
        )
    print("protocol matrix ok", flush=True)


def main(arch_ids):
    import jax
    import jax.numpy as jnp

    from repro.configs import reduced_config
    from repro.models.decode import lm_decode_step, lm_prefill
    from repro.models.lm import init_lm, lm_apply
    from repro.sharding import AxisRules, unzip_params
    from repro.train.steps import build_train_step

    shd = AxisRules(None)
    for aid in arch_ids:
        cfg = reduced_config(aid)
        print(f"--- {aid}: {cfg.family} params={cfg.param_count():,}", flush=True)
        params = unzip_params(init_lm(jax.random.PRNGKey(1), cfg, jnp.float32))[0]
        batch = batch_for(cfg)
        logits = jax.jit(lambda p, b: lm_apply(p, cfg, shd, b))(params, batch)
        assert logits.shape == (B, S, cfg.vocab_size), logits.shape
        assert bool(jnp.isfinite(logits).all()), "NaN in logits"
        print("    forward ok", flush=True)

        train_step, opt = build_train_step(cfg, shd, "adamw")
        opt_state = opt.init(params)
        p2, o2, metrics = jax.jit(train_step)(params, opt_state, jnp.int32(0), batch)
        assert bool(jnp.isfinite(metrics["loss"])), metrics
        print(f"    train ok loss={float(metrics['loss']):.3f}", flush=True)

        pre_batch = dict(batch)
        pre_batch.pop("labels")
        logits1, cache = jax.jit(lambda p, b: lm_prefill(p, cfg, shd, b))(params, pre_batch)
        assert logits1.shape == (B, cfg.vocab_size)
        db = {"token": jnp.zeros((B,), jnp.int32)}
        if cfg.mrope_sections is not None:
            db["positions"] = jnp.full((B, 3), S, jnp.int32)
        logits2, cache2 = jax.jit(lambda p, c, b: lm_decode_step(p, cfg, shd, c, b))(
            params, cache, db
        )
        assert logits2.shape == (B, cfg.vocab_size)
        assert bool(jnp.isfinite(logits2).all())
        assert int(cache2["len"]) == S + 1
        print("    prefill+decode ok", flush=True)

        # consistency: prefill(4) logits == full[:,3]; decode(tok4) == full[:,4]
        Tp = 4
        fb = {"tokens": batch["tokens"][:, : Tp + 1]}
        if "frames" in batch:
            fb["frames"] = batch["frames"]
        if "positions" in batch:
            fb["positions"] = batch["positions"][:, :, : Tp + 1]
        full = lm_apply(params, cfg, shd, fb)
        pb = {"tokens": fb["tokens"][:, :Tp]}
        if "frames" in fb:
            pb["frames"] = fb["frames"]
        if "positions" in fb:
            pb["positions"] = fb["positions"][:, :, :Tp]
        lg_p, c = lm_prefill(params, cfg, shd, pb, pad_to=Tp + 4)
        err_p = float(jnp.abs(lg_p - full[:, Tp - 1]).max())
        dbt = {"token": fb["tokens"][:, Tp]}
        if cfg.mrope_sections is not None:
            dbt["positions"] = jnp.full((B, 3), Tp, jnp.int32)
        lg_d, c = lm_decode_step(params, cfg, shd, c, dbt)
        err_d = float(jnp.abs(lg_d - full[:, Tp]).max())
        print(f"    prefill-vs-forward={err_p:.2e} decode-vs-forward={err_d:.2e}", flush=True)
        assert err_p < 2e-2 and err_d < 2e-2, (err_p, err_d)
    print("ALL OK")


if __name__ == "__main__":
    from benchmarks.common import add_device_args, configure_compile_cache, configure_devices

    ap = argparse.ArgumentParser()
    ap.add_argument("arch_ids", nargs="*", help="LM arch ids (default: all)")
    ap.add_argument(
        "--fast", action="store_true", help="batched sweep for the protocol matrix"
    )
    ap.add_argument("--skip-lm", action="store_true", help="protocol matrix only")
    add_device_args(ap)
    args = ap.parse_args()
    configure_devices(args, error=ap.error)
    configure_compile_cache()
    print(f"--- protocol matrix ({'batched' if args.fast else 'sequential'})", flush=True)
    protocol_matrix(args.fast)
    if not args.skip_lm:
        from repro.configs import ARCH_IDS

        main(args.arch_ids or list(ARCH_IDS))
