"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps (interpret mode).

The lock_arbiter / mvcc_version_select property tests follow the
tests/test_bucketed.py convention: Hypothesis when installed, a
derandomized seeded generator otherwise (the container CI image has no
hypothesis), so the properties are exercised either way.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ref
from repro.kernels.flash_attention import flash_attention
from repro.kernels.lock_arbiter import lock_arbiter
from repro.kernels.multi_read import multi_read
from repro.kernels.mvcc_version_select import mvcc_version_select

try:
    from hypothesis import given, settings, strategies as st

    HAVE_HYPOTHESIS = True
except ImportError:
    HAVE_HYPOTHESIS = False

KEY = jax.random.PRNGKey(42)


@pytest.mark.parametrize("B,H,S,Dh", [(1, 2, 128, 64), (2, 1, 192, 32), (1, 1, 320, 128)])
@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention(B, H, S, Dh, causal, dtype):
    k1, k2, k3 = jax.random.split(jax.random.fold_in(KEY, S * Dh + causal), 3)
    q = jax.random.normal(k1, (B, H, S, Dh), dtype)
    k = jax.random.normal(k2, (B, H, S, Dh), dtype)
    v = jax.random.normal(k3, (B, H, S, Dh), dtype)
    out = flash_attention(q, k, v, causal=causal, block_q=64, block_k=64)
    exp = ref.flash_attention_ref(q, k, v, causal=causal)
    tol = 1e-5 if dtype == jnp.float32 else 3e-2
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(exp, np.float32), atol=tol, rtol=tol
    )


@pytest.mark.parametrize("M,S", [(7, 4), (256, 4), (700, 4), (64, 2), (96, 6)])
def test_mvcc_version_select(M, S):
    ks = [jax.random.fold_in(KEY, M * 10 + S + i) for i in range(6)]
    wh = jax.random.randint(ks[0], (M, S), 0, 6)
    wl = jax.random.randint(ks[1], (M, S), 0, 4)
    ch = jax.random.randint(ks[2], (M,), 0, 7)
    cl = jax.random.randint(ks[3], (M,), 0, 4)
    lh = jax.random.randint(ks[4], (M,), 0, 3)
    ll = jax.random.randint(ks[5], (M,), 0, 2)
    f1, s1, o1 = mvcc_version_select(wh, wl, ch, cl, lh, ll, interpret=True)
    f2, s2, o2 = ref.mvcc_version_select_ref(wh, wl, ch, cl, lh, ll)
    assert bool((f1 == f2).all()) and bool((o1 == o2).all())
    assert bool((s1 == s2).all())  # unfound rows argmax to slot 0 in both


def _arbiter_case(G, M, nk, seed):
    """Random arbitration batch with UNIQUE (hi, lo) pairs per group (the
    engine's contract: ts pairs, or hashed hi + unique logical op index lo)."""
    rng = np.random.default_rng(seed)
    keys = rng.integers(0, nk, (G, M)).astype(np.int32)
    hi = rng.integers(0, 5, (G, M)).astype(np.int32)  # narrow: force lo tiebreaks
    lo = np.stack([rng.permutation(M) for _ in range(G)]).astype(np.int32)
    act = rng.random((G, M)) < 0.6
    return jnp.asarray(keys), jnp.asarray(hi), jnp.asarray(lo), jnp.asarray(act)


@pytest.mark.parametrize("G,M,nk", [(2, 32, 4), (4, 128, 11), (1, 256, 40)])
def test_lock_arbiter(G, M, nk):
    keys, hi, lo, act = _arbiter_case(G, M, nk, seed=G * M + nk)
    won = lock_arbiter(keys, hi, lo, act, interpret=True)
    exp = ref.lock_arbiter_ref(keys, hi, lo, act)
    assert bool((won == exp).all())


def _check_arbiter_properties(seed: int):
    """The two lock_arbiter properties: exactly one winner per active key
    per owner group, and padding-invariance (extra inactive tail entries
    never change the live prefix's winners)."""
    rng = np.random.default_rng(seed)
    G = int(rng.integers(1, 4))
    M = int(rng.integers(5, 200))
    nk = int(rng.integers(2, 30))
    keys, hi, lo, act = _arbiter_case(G, M, nk, seed)
    won = np.asarray(lock_arbiter(keys, hi, lo, act, interpret=True))
    # exactly one winner per distinct active key per group
    for g in range(G):
        for k in set(np.asarray(keys)[g][np.asarray(act)[g]].tolist()):
            contenders = (np.asarray(keys)[g] == k) & np.asarray(act)[g]
            assert won[g][contenders].sum() == 1, (g, k)
        assert not won[g][~np.asarray(act)[g]].any()
    # padding-invariance: a bigger tile (inactive tail) gives the same winners
    pad = int(rng.integers(1, 64))
    kp = jnp.pad(keys, ((0, 0), (0, pad)), constant_values=-1)
    hp = jnp.pad(hi, ((0, 0), (0, pad)))
    lp = jnp.pad(lo, ((0, 0), (0, pad)))
    ap = jnp.pad(act, ((0, 0), (0, pad)))
    won_p = np.asarray(lock_arbiter(kp, hp, lp, ap, interpret=True))
    assert (won_p[:, :M] == won).all() and not won_p[:, M:].any()


def _np_version_oracle(wh, wl, ch, cl, lh, ll):
    """Numpy Cond R1/R2 oracle, written independently of the jnp reference:
    per row, scan the slots for the lexicographically largest (wh, wl)
    strictly below (ch, cl), skipping empty (0, 0) slots; R2 = lock free or
    lock after ctts."""
    M, S = wh.shape
    found = np.zeros(M, bool)
    slot = np.zeros(M, np.int32)
    for i in range(M):
        best = None
        for s in range(S):
            v = (int(wh[i, s]), int(wl[i, s]))
            if v == (0, 0) or v >= (int(ch[i]), int(cl[i])):
                continue
            if best is None or v > best:
                best, found[i], slot[i] = v, True, s
    ok = ((lh == 0) & (ll == 0)) | (ch < lh) | ((ch == lh) & (cl < ll))
    return found, slot, ok


def _check_version_select(seed: int):
    rng = np.random.default_rng(seed)
    M = int(rng.integers(1, 400))
    S = int(rng.integers(2, 6))
    wh = rng.integers(0, 5, (M, S)).astype(np.int32)
    wl = rng.integers(0, 4, (M, S)).astype(np.int32)
    ch = rng.integers(0, 6, M).astype(np.int32)
    cl = rng.integers(0, 4, M).astype(np.int32)
    lh = rng.integers(0, 3, M).astype(np.int32)
    ll = rng.integers(0, 2, M).astype(np.int32)
    f, s, o = mvcc_version_select(*map(jnp.asarray, (wh, wl, ch, cl, lh, ll)), interpret=True)
    ef, es, eo = _np_version_oracle(wh, wl, ch, cl, lh, ll)
    assert (np.asarray(f) == ef).all() and (np.asarray(o) == eo).all()
    assert (np.asarray(s)[ef] == es[ef]).all()


if HAVE_HYPOTHESIS:

    @settings(max_examples=10, deadline=None, derandomize=True)
    @given(st.integers(0, 2**31 - 1))
    def test_lock_arbiter_properties(seed):
        _check_arbiter_properties(seed)

    @settings(max_examples=10, deadline=None, derandomize=True)
    @given(st.integers(0, 2**31 - 1))
    def test_version_select_vs_numpy_oracle(seed):
        _check_version_select(seed)

else:

    @pytest.mark.parametrize("seed", [0, 1, 2, 3, 4])
    def test_lock_arbiter_properties(seed):
        _check_arbiter_properties(seed)

    @pytest.mark.parametrize("seed", [0, 1, 2, 3, 4])
    def test_version_select_vs_numpy_oracle(seed):
        _check_version_select(seed)


@pytest.mark.parametrize("R,A,M", [(64, 3, 40), (500, 7, 129), (128, 1, 1000)])
def test_multi_read(R, A, M):
    ks = [jax.random.fold_in(KEY, R * A + M + i) for i in range(2)]
    table = jax.random.randint(ks[0], (R, A), -(2**28), 2**28, dtype=jnp.int32)
    keys = jax.random.randint(ks[1], (M,), 0, R, dtype=jnp.int32)
    (out,) = multi_read((table,), keys, interpret=True)
    assert bool((out == table[keys]).all())
    assert bool((out == ref.multi_read_ref(table, keys)).all())
    # large int32 values survive exactly (no f32 rounding above 2^24)
    big = jnp.full((R, A), 2**30 - 7, jnp.int32)
    (out,) = multi_read((big,), keys, interpret=True)
    assert bool((out == 2**30 - 7).all())


def test_multi_read_padding_keys_gather_zero():
    table = jnp.arange(12, dtype=jnp.int32).reshape(6, 2) + 1
    keys = jnp.asarray([0, -1, 5, -1], jnp.int32)
    (out,) = multi_read((table,), keys, interpret=True)
    exp = ref.multi_read_ref(table, keys)
    assert bool((out == exp).all())
    assert not np.asarray(out)[1].any() and not np.asarray(out)[3].any()


@pytest.mark.parametrize("R,M", [(384, 1000), (300, 517), (130, 8)], ids=["R_lanes", "R_odd", "few_keys"])
def test_multi_read_unpacked_widths(R, M):
    """One call over a 1-D array and arrays of 1..9 words per record, each
    returned in its own shape; R and M off every block size; the edge keys
    (0, R - 1, padding) and the int32 extremes come back exactly."""
    ks = jax.random.split(jax.random.fold_in(KEY, R + M), 12)
    arrs = [jax.random.randint(ks[0], (R,), -(2**31) + 1, 2**31 - 1, jnp.int32)]
    arrs += [
        jax.random.randint(ks[w], (R, w), -(2**31) + 1, 2**31 - 1, jnp.int32) for w in range(1, 10)
    ]
    arrs[1] = arrs[1].at[R - 1].set(2**31 - 1).at[0].set(-(2**31) + 1)
    arrs[0] = arrs[0].at[R - 1].set(-(2**31) + 1)
    keys = jax.random.randint(ks[10], (M,), -2, R, dtype=jnp.int32)
    keys = keys.at[0].set(R - 1).at[M - 1].set(0).at[M // 2].set(-1)
    outs = multi_read(arrs, keys, interpret=True)
    for a, o in zip(arrs, outs):
        assert o.shape == keys.shape + a.shape[1:] and o.dtype == jnp.int32
        assert bool((o == ref.multi_read_ref(a, keys)).all())
        assert bool((o[keys >= 0] == a[keys[keys >= 0]]).all())
    assert int(outs[0][0]) == -(2**31) + 1 and int(outs[1][0, 0]) == 2**31 - 1
    # keys of any shape: the engine's (slots, ops) batches
    k2 = keys[: (M // 4) * 4].reshape(-1, 4)
    (o2,) = multi_read(arrs[3:4], k2, interpret=True)
    assert bool((o2 == ref.multi_read_ref(arrs[3], k2)).all())


@pytest.mark.parametrize("B", [3, 8], ids=["configs_3", "configs_8"])
def test_multi_read_vmapped_over_configs(B):
    """The sweep's config axis: each config reads its own tables at its own
    keys, in ONE pallas_call (no loop of kernel calls around it)."""
    R, M = 640, 300
    ks = jax.random.split(jax.random.fold_in(KEY, B), 3)
    lock = jax.random.randint(ks[0], (B, R), -(2**31) + 1, 2**31 - 1, jnp.int32)
    data = jax.random.randint(ks[1], (B, R, 2), -(2**31) + 1, 2**31 - 1, jnp.int32)
    keys = jax.random.randint(ks[2], (B, M), -1, R, dtype=jnp.int32).at[:, 0].set(R - 1)
    read = jax.vmap(lambda l, d, k: multi_read((l, d), k, interpret=True))
    jaxpr = str(jax.make_jaxpr(read)(lock, data, keys))
    assert jaxpr.count("pallas_call[") == 1 and "while[" not in jaxpr
    out_l, out_d = read(lock, data, keys)
    assert bool((out_l == jax.vmap(ref.multi_read_ref)(lock, keys)).all())
    assert bool((out_d == jax.vmap(ref.multi_read_ref)(data, keys)).all())
