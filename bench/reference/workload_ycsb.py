"""Reference plug-in: YCSB's one table, parameterised by the deployment file.

The generator draws one transaction per (slot, transaction number) from a
folded PRNG key; ``execute`` computes the write set from the values read.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from bench.reference.engine import Workload


def make(cfg: dict, n_records: int, exec_ticks, hot_prob) -> Workload:
    """YCSB: K distinct keys per transaction, a hot area hit with
    probability ``hot_prob``, each op a write with probability write_frac."""
    K = cfg["ops_per_txn"]
    n_hot = max(int(np.float32(n_records) * np.float32(cfg["hot_frac"])), 16)
    write_frac = cfg["write_frac"]

    def gen(key, node, slot):
        k1, k2, k3, k4 = jax.random.split(key, 4)
        hot = jax.random.uniform(k1, (K,)) < hot_prob
        cold = jax.random.randint(k2, (K,), n_hot, n_records)
        hot_keys = jax.random.randint(k3, (K,), 0, n_hot)
        keys = jnp.where(hot, hot_keys, cold).astype(jnp.int32)
        for r in range(4):  # nudge colliding keys apart, four passes
            for i in range(1, K):
                clash = (keys[:i] == keys[i]).any()
                nudged = (keys[i] + i * 131 + r * 37 + slot * 13 + 1) % n_records
                keys = keys.at[i].set(jnp.where(clash, nudged, keys[i]))
        is_w = jax.random.uniform(k4, (K,)) < write_frac
        return keys, is_w, jnp.ones((K,), bool)

    def execute(keys, is_w, valid, rvals):
        return rvals + 1

    return Workload("ycsb", cfg["record_words"], K, 0, gen, execute, exec_ticks)
