"""Reference plug-in: H-Store SmallBank, parameterised by the deployment file.

The generator draws one transaction per (slot, transaction number) from a
folded PRNG key; ``execute`` computes the write set from the values read.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from bench.reference.engine import Workload


def make(cfg: dict, n_records: int, exec_ticks, hot_prob) -> Workload:
    """Six transaction types over one or two accounts; a share of accesses
    goes to a small set of hot accounts (``hot_prob`` is not a SmallBank knob)."""
    K = cfg["ops_per_txn"]
    n_hot = min(cfg["hot_accounts"], n_records)
    hot_frac = cfg["hot_access_frac"]

    def gen(key, node, slot):
        k1, k2, k3, k4, _ = jax.random.split(key, 5)
        ttype = jax.random.randint(k1, (), 0, 6)
        hot = jax.random.uniform(k2, (K,)) < hot_frac
        acct = jax.random.randint(k3, (K,), 0, n_records)
        acct_hot = jax.random.randint(k4, (K,), 0, n_hot)
        a = jnp.where(hot, acct_hot, acct)
        a = jnp.where(a[1] == a[0], (a + jnp.arange(K)) % n_records, a)
        two = (ttype == 0) | (ttype == 3)  # amalgamate, send-payment
        read_only = ttype == 1  # balance
        valid = jnp.stack([jnp.bool_(True), two])
        is_w = jnp.stack([~read_only, two & ~read_only])
        return a.astype(jnp.int32), is_w, valid

    def execute(keys, is_w, valid, rvals):
        w0 = rvals[0].at[0].add(jnp.where(valid[1], -1, 1))
        w1 = rvals[1].at[0].add(1)
        return jnp.stack([w0, w1])

    return Workload("smallbank", cfg["record_words"], K, cfg["init_balance"], gen, execute,
                    exec_ticks)
