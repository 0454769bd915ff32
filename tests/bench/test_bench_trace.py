"""The reduction from a profiler trace to busy time, op self time, Pallas
kernel time and idle gaps: on hand-made events, and on a trace of one
small NOWAIT call recorded on a TPU v5e (``bench/record_trace.py``)."""
import os

import pytest

from bench import trace
from bench.trace import Event, Trace

DATA = os.path.join(os.path.dirname(__file__), "data")


def ev(name, start, end):
    return Event(name, float(start), float(end))


def test_busy_self_time_and_gaps():
    host = [ev("bench.call", 0, 1000), ev("bench.plan", 0, 100), ev("bench.execute", 100, 900),
            ev("bench.rows", 900, 1000)]
    ops = [
        ev("while.1", 200, 600),  # encloses the two ops below
        ev("fusion.2", 250, 300),
        ev('%cc.3 = s32[4,64,2]{2,1,0} custom-call(s32[4,64,1]{2,1,0} %k), '
           'custom_call_target="tpu_custom_call"', 300, 400),
        ev("fusion.4", 700, 800),
        ev("fusion.5", 1500, 1600),  # outside the window
    ]
    s = trace.reduce(Trace(host=host, devices={"/device:TPU:0": ops}))
    assert s.window_s == pytest.approx(1e-6)
    assert s.busy_s == pytest.approx(500e-9)
    assert s.op_self_s["while.1"] == pytest.approx(250e-9)
    assert sum(s.op_self_s.values()) == pytest.approx(s.busy_s)
    assert s.op_self_s["custom-call s32[4,64,2] pallas"] == pytest.approx(100e-9)
    assert s.pallas_s == pytest.approx(100e-9)
    # gaps: 0-200 (plan, then execute at its middle 100), 600-700 (execute), 800-1000 (rows at 900)
    assert [g[0] for g in s.idle_gaps] == ["bench.execute", "bench.rows", "bench.execute"]
    assert [round(g[1] * 1e9) for g in s.idle_gaps] == [200, 200, 100]
    assert s.top_ops(2)[0][0] == "while.1"


def test_op_labels():
    assert trace.op_label(
        '%fusion.9 = (s32[64,2400]{1,0:T(8,128)}, pred[2]{0}) fusion(s32[2]{0} %a), kind=kLoop'
    ) == "fusion (s32[64,2400], pred[2])"
    assert trace.op_label("dot_general.1") == "dot_general.1"


def test_missing_window_is_an_error():
    with pytest.raises(ValueError):
        trace.reduce(Trace(host=[], devices={}))


def test_recorded_chip_trace():
    t = trace.load(os.path.join(DATA, "nowait.xplane.pb.gz"))
    assert list(t.devices) == ["/device:TPU:0"]
    assert {e.name for e in t.host} == {"bench.call", "bench.plan", "bench.execute", "bench.rows"}
    s = trace.reduce(t)
    assert s.n_devices == 1
    assert 0 < s.busy_s < s.window_s < 1.0
    assert sum(s.op_self_s.values()) == pytest.approx(s.busy_s, rel=1e-9)
    # the lock stage's packed gather (2 lock words x 64 keys, 4 configs) and the
    # lock arbiter run once per tick: 5 ticks plus the step before the scan
    pallas = {k: v for k, v in s.op_self_s.items() if k.endswith(" pallas")}
    assert set(pallas) == {"custom-call s32[4,64,2] pallas", "custom-call s32[4,1,1,128] pallas"}
    assert s.pallas_s == pytest.approx(sum(pallas.values()))
    assert 0 < s.pallas_s < s.busy_s
    assert {g[0] for g in s.idle_gaps} <= {"bench.call", "bench.plan", "bench.execute", "bench.rows"}
    assert s.idle_gaps[0][1] > 1e-3


def test_four_device_planes():
    """A four-chip trace: busy time and Pallas time are averaged over the
    planes, op and scope self times add up to the averaged busy time, so a
    share read from them means on four chips what it means on one; an idle
    gap is a stretch in which no plane ran anything, listed once."""
    from types import SimpleNamespace

    from bench import harness, scopes
    from bench.scopes import Op

    host = [ev("bench.call", 0, 1000), ev("repro.execute", 0, 1000)]
    body = "jit(_node_runner)/while/body/closed_call/stage_lock"
    pallas = ('%cc.1 = s32[1,64,2]{2,1,0} custom-call(s32[1,64,1]{2,1,0} %k), '
              'custom_call_target="tpu_custom_call"')
    planes = {}
    for d in range(4):  # plane d: a kernel of 100 + 20d ns under gather, an exchange of 50 ns
        k_end = 200 + 20 * d
        planes[f"/device:TPU:{d}"] = [
            Op(pallas, 100, k_end, f"{body}/gather/multi_read/pallas_call:"),
            Op("all-reduce.2", k_end, k_end + 50, f"{body}/gather/exchange/psum:psum"),
            Op("fusion.3", 600, 700, f"{body}/arbitrate/lt:lt"),
        ]
    busy = [100 + 20 * d + 50 + 100 for d in range(4)]  # 250, 270, 290, 310 ns

    t = trace.reduce(Trace(host=host, devices={k: [Event(o.name, o.start_ns, o.end_ns) for o in v]
                                               for k, v in planes.items()}))
    assert t.n_devices == 4
    assert t.busy_s == pytest.approx(sum(busy) / 4 * 1e-9)
    assert sum(t.op_self_s.values()) == pytest.approx(t.busy_s)
    assert t.pallas_s == pytest.approx(sum(100 + 20 * d for d in range(4)) / 4 * 1e-9)
    run = SimpleNamespace(trace=t, traced_call=SimpleNamespace(config_ticks=10))
    assert harness.metric_reader("pallas_share")(run) == pytest.approx(100 * t.pallas_s / t.busy_s)
    assert harness.metric_reader("device_us_per_config_tick")(run) == pytest.approx(t.busy_s * 1e5)
    assert 0 < harness.metric_reader("idle_share")(run) < 100
    # the planes ran over 100-310 (their longest) and 600-700 ns
    assert [(g[0], round(g[1] * 1e9)) for g in t.idle_gaps] == [
        ("repro.execute", 300), ("repro.execute", 290), ("repro.execute", 100)]

    s = scopes.reduce(host, planes)
    assert s.busy_s == pytest.approx(t.busy_s)
    assert sum(s.by_primitive.values()) == pytest.approx(s.busy_s)
    assert s.by_primitive["exchange"] == pytest.approx(50e-9)
    assert s.by_primitive["gather"] == pytest.approx(t.pallas_s)
    assert 100 * s.by_primitive["gather"] / s.busy_s < 100
    assert s.launch_s == pytest.approx(100e-9) and s.drain_s == pytest.approx(300e-9)
