"""The reduction of a trace to the program's own scopes and spans
(``bench/scopes.py``) and the per-layer metrics that read it: on hand-made
events, and on two small NOWAIT calls recorded on a TPU v5e
(``bench/record_trace.py``), one from before the program named its work
(``nowait.xplane.pb.gz``) and one after (``nowait_scoped.xplane.pb.gz``)."""
import os
from types import SimpleNamespace

import pytest

from bench import harness, scopes, trace
from bench.scopes import Op
from bench.trace import Event

DATA = os.path.join(os.path.dirname(__file__), "data")
UNSCOPED_TRACE = os.path.join(DATA, "nowait.xplane.pb.gz")
SCOPED_TRACE = os.path.join(DATA, "nowait_scoped.xplane.pb.gz")
METRICS = ["gather_share", "launch_gap_ms", "drain_gap_ms",
           "gather_share_short_calls", "launch_gap_ms_short_calls", "drain_gap_ms_short_calls"]
PALLAS = '%cc.1 = s32[4,64,2]{2,1,0} custom-call(s32[4,64,1]{2,1,0} %k), custom_call_target="tpu_custom_call"'
BODY = "jit(_run_grid_jit)/vmap()/while/body/closed_call"
OUTER = ["_run_grid_jit", "", "while", "body", "closed_call"]  # jit(...)/vmap()/while/body/...


@pytest.mark.parametrize("path, names, primitive, phase", [
    (f"{BODY}/stage_lock/gather/multi_read/pallas_call:", OUTER + ["stage_lock", "gather", "multi_read"],
     "gather", "stage_lock"),
    (f"{BODY}/stage_commit/gather:gather", OUTER + ["stage_commit"], scopes.UNSCOPED, "stage_commit"),
    ("jit(_run_grid_jit)/vmap(summarize)/reduce_sum:reduce_sum", ["_run_grid_jit", "summarize"],
     scopes.UNSCOPED, "summarize"),
    (f"{BODY}/stage_lock/gather/exchange/psum:psum", OUTER + ["stage_lock", "gather", "exchange"],
     "exchange", "stage_lock"),
    ("", [], scopes.UNSCOPED, scopes.UNSCOPED),
], ids=["kernel", "primitive-is-no-scope", "wrapped", "innermost", "empty"])
def test_scope_names(path, names, primitive, phase):
    """Whole components, transform wrappers peeled, the op's own primitive
    left out; the innermost primitive scope and the outermost phase."""
    got = scopes.scope_names(path)
    assert got == names
    assert scopes.primitive_of(got) == primitive
    assert scopes.phase_of(got) == phase


def test_reduce_by_scope_and_span():
    host = [Event("bench.call", 0, 1000), Event("repro.plan", 10, 40), Event("repro.execute", 50, 950),
            Event("repro.execute.knobs", 55, 100), Event("repro.execute.dispatch", 100, 300),
            Event("repro.execute.fetch", 300, 910), Event("repro.execute.rows", 920, 940)]
    ops = [
        Op("while.0", 200, 800, "jit(f)/vmap()/while:while"),  # encloses the rest
        Op(PALLAS, 250, 400, f"{BODY}/stage_lock/gather/multi_read/pallas_call:"),
        Op("fusion.1", 400, 500, f"{BODY}/stage_lock/arbitrate/lt:lt"),
        Op("fusion.2", 500, 600, f"{BODY}/begin_tick/add:add"),
        Op("gather.3", 600, 650, f"{BODY}/stage_commit/gather:gather"),
        Op("reduce.4", 700, 750, "jit(f)/vmap(summarize)/reduce_sum:reduce_sum"),
        Op(PALLAS, 760, 780, "jit(f)/pallas_call:"),  # a kernel under no kernel scope
        Op("fusion.5", 1200, 1300, f"{BODY}/begin_tick/add:add"),  # outside the window
    ]
    s = scopes.reduce(host, {"/device:TPU:0": ops})
    ns = pytest.approx
    assert s.busy_s == ns(600e-9)
    assert {k: round(v * 1e9) for k, v in s.by_primitive.items()} == {
        "gather": 150, "arbitrate": 100, scopes.UNSCOPED: 350}
    assert {k: round(v * 1e9) for k, v in s.by_phase.items()} == {
        "stage_lock": 250, "begin_tick": 100, "stage_commit": 50, "summarize": 50, scopes.UNSCOPED: 150}
    assert s.pallas_unscoped_s == ns(20e-9)
    # idle 0-200 (middle in dispatch) and 800-1000 (middle in fetch)
    assert [(g[0], round(g[1] * 1e9)) for g in s.idle_gaps] == [
        ("repro.execute.dispatch", 200), ("repro.execute.fetch", 200)]
    assert (s.launch_s, s.drain_s) == (ns(150e-9), ns(150e-9))
    # without the program's spans there is no launch or drain gap
    bare = scopes.reduce(host[:1], {"/device:TPU:0": ops})
    assert (bare.launch_s, bare.drain_s) == (None, None)
    assert [g[0] for g in bare.idle_gaps] == ["bench.call", "bench.call"]


@pytest.mark.parametrize("path", [UNSCOPED_TRACE, SCOPED_TRACE], ids=["unscoped", "scoped"])
def test_decode_agrees_with_profile_data(path):
    """The XSpace decode sees the device ops ``jax.profiler.ProfileData``
    sees, at the same times, and the same window."""
    host, devices = scopes.load(path)
    ref = trace.load(path)
    assert list(devices) == list(ref.devices)
    for plane, ops in devices.items():
        assert [(o.name, o.start_ns, o.end_ns) for o in ops] == [
            (e.name, e.start_ns, e.end_ns) for e in ref.devices[plane]]
    assert [e for e in host if e.name == trace.WINDOW_SPAN] == [
        e for e in ref.host if e.name == trace.WINDOW_SPAN]
    assert scopes.summarize(path).busy_s == pytest.approx(trace.reduce(ref).busy_s, rel=1e-12)


def _run(trace_summary):
    return SimpleNamespace(trace=trace_summary)


@pytest.mark.parametrize("name", METRICS)
def test_readers_none_when_untraced_or_unnamed(name, monkeypatch):
    read = harness.metric_reader(name)
    assert read(_run(None)) is None
    # a trace of a program that names nothing: the metric is left out
    monkeypatch.setattr(harness, "TRACE_DIR", UNSCOPED_TRACE)
    assert read(_run(object())) is None


def test_recorded_scoped_trace(monkeypatch):
    """Where the program's names land in a chip trace, and what the metrics read."""
    s = scopes.summarize(SCOPED_TRACE)
    assert s is scopes.summarize(SCOPED_TRACE)  # parsed once per process
    assert {"gather", "arbitrate", "service"} <= set(s.by_primitive)
    assert {"begin_tick", "stage_lock", "stage_commit", "stage_release", "summarize"} <= set(s.by_phase)
    assert sum(s.by_primitive.values()) == pytest.approx(s.busy_s, rel=1e-9)
    assert sum(s.by_phase.values()) == pytest.approx(s.busy_s, rel=1e-9)
    assert s.by_phase.get(scopes.UNSCOPED, 0.0) < 0.05 * s.busy_s
    assert s.pallas_unscoped_s == 0.0
    assert all(name.startswith("repro.") for name, sec in s.idle_gaps if sec >= scopes.GAP_FLOOR_S)
    under_execute = sum(sec for name, sec in trace.reduce(trace.load(SCOPED_TRACE)).idle_gaps
                        if name == "bench.execute")
    assert s.launch_s + s.drain_s >= 0.9 * under_execute

    monkeypatch.setattr(harness, "TRACE_DIR", SCOPED_TRACE)
    got = {m: harness.metric_reader(m)(_run(object())) for m in METRICS}
    assert got["gather_share"] == got["gather_share_short_calls"] == pytest.approx(
        100 * s.by_primitive["gather"] / s.busy_s)
    assert got["launch_gap_ms"] == got["launch_gap_ms_short_calls"] == pytest.approx(s.launch_s * 1e3)
    assert got["drain_gap_ms"] == got["drain_gap_ms_short_calls"] == pytest.approx(s.drain_s * 1e3)
    assert 0 < got["gather_share"] < 100 and got["launch_gap_ms"] > 0 and got["drain_gap_ms"] > 0
