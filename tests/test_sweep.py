"""Batched sweep engine (repro.core.sweep): batched-vs-sequential
equivalence, single-compilation guarantee, knob plumbing, and the
stage-graph runtime's pinned-golden / doorbell-merging guarantees."""
import json
import os

import numpy as np
import pytest

from repro.core import sweep
from repro.core.costmodel import N_HYBRID_STAGES, ONE_SIDED, RPC
from repro.core.sweep import all_hybrid_codes, grid_product, make_knobs, normalize_hybrid, run_grid

# tiny but contended: enough commits/aborts for the counters to be
# meaningfully compared, small enough that a grid run takes seconds
KW = dict(n_nodes=2, coroutines=8, records_per_node=128, ticks=64, warmup=8)
CODES = [0, 63, 0b010101, 0b101010]


def _run_cell(protocol, workload, hybrid, **kw):
    # import lazily: benchmarks/ is not an installed package, only reachable
    # when the repo root is on sys.path (conftest guarantees src/, CI runs
    # from the repo root)
    import os
    import sys

    sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))
    from benchmarks.common import run_cell

    m, _, _ = run_cell(protocol, workload, hybrid, **kw)
    return m


@pytest.mark.parametrize(
    "proto",
    [
        "nowait",
        "occ",
        # sundial is the slowest to compile sequentially; the exhaustive test
        # below spot-checks it in fast CI, the full sweep runs nightly
        pytest.param("sundial", marks=pytest.mark.slow),
    ],
)
def test_batched_matches_sequential(proto):
    rows = run_grid(proto, "smallbank", [{"hybrid": c} for c in CODES], **KW)
    for c, r in zip(CODES, rows):
        m = _run_cell(proto, "smallbank", c, **KW)
        # control flow is integer/bool-driven: counters must match exactly
        assert r["commits"] == m["commits"], (proto, c)
        assert r["aborts"] == m["aborts"], (proto, c)
        # float metrics may differ by fusion order only
        np.testing.assert_allclose(r["avg_latency_us"], m["avg_latency_us"], rtol=1e-4)
        np.testing.assert_allclose(
            r["stage_us_per_commit"], m["stage_us_per_commit"], rtol=1e-4, atol=1e-4
        )


@pytest.mark.slow  # 8 sequential reference compiles (~4 min); nightly CI
def test_knob_grid_matches_sequential():
    cfgs = grid_product(hybrid=[0, 63], hot_prob=[0.0, 0.9], seed=[0, 1])
    rows = run_grid("occ", "ycsb", cfgs, **KW)
    for cfg, r in zip(cfgs, rows):
        m = _run_cell(
            "occ", "ycsb", cfg["hybrid"], hot_prob=cfg["hot_prob"], seed=cfg["seed"], **KW
        )
        assert r["commits"] == m["commits"], cfg
        assert r["aborts"] == m["aborts"], cfg


def test_exhaustive_hybrid_single_compile():
    """The paper's 2^6 exhaustive coding sweep is ONE vmapped program."""
    before = sweep.compile_cache_size()
    kw = dict(n_nodes=2, coroutines=8, records_per_node=128, ticks=48, warmup=8)
    rows = run_grid("sundial", "smallbank", [{"hybrid": c} for c in all_hybrid_codes()], **kw)
    assert len(rows) == 2**N_HYBRID_STAGES
    assert all(r["commits"] > 0 for r in rows)
    assert all(np.isfinite(r["throughput_mtps"]) for r in rows)
    # a second grid over the same spec reuses the compiled program
    run_grid("sundial", "smallbank", [{"hybrid": 0b110011, "seed": 7}], **kw)
    after = sweep.compile_cache_size()
    assert after - before <= 2, (before, after)
    # codings 000000 and 111111 must match their sequential runs exactly
    for c in (0, 63):
        m = _run_cell("sundial", "smallbank", c, **kw)
        assert rows[c]["commits"] == m["commits"], c
        assert rows[c]["aborts"] == m["aborts"], c


def test_calvin_grid():
    rows = run_grid("calvin", "smallbank", [{"hybrid": 0}, {"hybrid": 63}], **KW)
    assert all(r["abort_rate"] == 0.0 for r in rows)
    assert rows[0]["commits"] == rows[1]["commits"]  # deterministic batch size
    m = _run_cell("calvin", "smallbank", (RPC,) * 6, **KW)
    assert rows[0]["commits"] == m["commits"]
    np.testing.assert_allclose(rows[0]["throughput_mtps"], m["throughput_mtps"], rtol=1e-4)


def test_stage_graph_pinned_golden_counters():
    """The stage-graph runtime (repro.core.rounds) reproduces the
    pre-refactor hand-rolled stage machines BITWISE: commit/abort counters
    for a pinned config grid were captured before the refactor
    (tests/data/stage_graph_golden.json, which also holds the grid it pins;
    counters taken under JAX's default ``jax_threefry_partitionable=True``)
    and must never drift."""
    path = os.path.join(os.path.dirname(__file__), "data", "stage_graph_golden.json")
    with open(path) as f:
        golden = json.load(f)
    assert golden["kw"] == KW and golden["cells"]["smallbank"]["codes"] == CODES
    for workload, cell in golden["cells"].items():
        for proto in cell["protocols"]:
            rows = run_grid(proto, workload, [{"hybrid": c} for c in cell["codes"]], **KW)
            for r in rows:
                g = golden["counters"][f"{proto}/{workload}/{r['hybrid']}"]
                assert int(r["commits"]) == g["commits"], (proto, workload, r["hybrid"])
                assert int(r["aborts"]) == g["aborts"], (proto, workload, r["hybrid"])


def test_doorbell_merging_fuses_log_commit():
    """Cross-stage doorbell merging (§4.2): with LOG+COMMIT both one-sided,
    merging collapses them into one posted round — write txns finish in
    fewer ticks (more commits) with fewer round trips; RPC codings are
    untouched; and a fused mixed coding beats both pure codings."""
    kw = dict(n_nodes=2, coroutines=12, records_per_node=4096, ticks=96, warmup=8)
    fused_code = (1 << 3) | (1 << 4)  # LOG + COMMIT one-sided, rest RPC
    codes = [0, 63, fused_code]
    plain = run_grid("sundial", "smallbank", [{"hybrid": c} for c in codes], **kw)
    merged = run_grid(
        "sundial", "smallbank", [{"hybrid": c} for c in codes], merge_stages=True, **kw
    )
    # pure RPC has no one-sided LOG/COMMIT: merging must be a no-op
    assert merged[0]["commits"] == plain[0]["commits"]
    assert merged[0]["aborts"] == plain[0]["aborts"]
    # fusable codings commit more and round-trip less
    for i in (1, 2):
        assert merged[i]["commits"] > plain[i]["commits"], codes[i]
        assert merged[i]["avg_round_trips"] < plain[i]["avg_round_trips"], codes[i]
    # a fused mixed coding beats BOTH pure codings (the §5 hybrid claim)
    pure_best = max(merged[0]["throughput_mtps"], merged[1]["throughput_mtps"])
    assert merged[2]["throughput_mtps"] > pure_best


def test_normalize_hybrid():
    assert normalize_hybrid(0) == (RPC,) * 6
    assert normalize_hybrid(63) == (ONE_SIDED,) * 6
    assert normalize_hybrid(0b000101) == (1, 0, 1, 0, 0, 0)  # bit i = stage i
    assert normalize_hybrid((1, 0, 1, 0, 0, 0)) == (1, 0, 1, 0, 0, 0)
    with pytest.raises(ValueError):
        normalize_hybrid((1, 0))


def test_make_knobs_defaults_and_validation():
    kn = make_knobs("ycsb", [{}, {"hot_prob": 0.5, "exec_ticks": 7}])
    assert kn.hybrid.shape == (2, N_HYBRID_STAGES)
    assert kn.exec_ticks.tolist() == [3, 7]  # ycsb default exec_ticks = 3
    np.testing.assert_allclose(kn.hot_prob[0], 0.10)
    with pytest.raises(TypeError):
        make_knobs("ycsb", [{"bogus": 1}])
    with pytest.raises(TypeError):  # hot_prob is ycsb-only, not silently ignored
        make_knobs("smallbank", [{"hot_prob": 0.5}])
