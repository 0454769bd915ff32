"""The names the program writes for a profiler: device scopes in the
engine's lowered program and host spans around ``api.execute``.

The scopes are read back from a trace by the benchmark (``bench/scopes.py``)
as whole components of an op's scope path, so these tests check whole
components too.
"""
import glob
import os
import re

import jax
import pytest

from repro import api
from repro.core import sweep

TINY = dict(n_nodes=2, coroutines=4, records_per_node=64, ticks=4, warmup=1)
ALWAYS = {"init", "begin_tick", "stage_lock", "stage_commit", "summarize",
          "gather", "arbitrate", "service"}
_LOC = re.compile(r'loc\("([^"]*)"')
_WRAPPED = re.compile(r"^[\w.]+\((.*)\)$")


def _components(text: str) -> set:
    out = set()
    for path in _LOC.findall(text):
        for part in path.split("/"):
            while (m := _WRAPPED.match(part)) is not None:
                part = m.group(1)
            out.add(part)
    return out


@pytest.mark.parametrize("plane", ["jnp", "pallas_interpret"])
@pytest.mark.parametrize("protocol", ["nowait", "mvcc"])
def test_lowered_tick_carries_the_scopes(protocol, plane):
    spec = api.ExperimentSpec(protocol=protocol, workload="smallbank",
                              configs=[{"hybrid": 0}, {"hybrid": 63}], kernel_plane=plane, **TINY)
    pb = api.plan(spec).buckets[0]
    knobs = sweep.make_knobs(spec.workload, pb.bucket.knob_configs)
    text = sweep._run_grid_jit.lower(pb.grid_spec, knobs).as_text(debug_info=True)
    names = _components(text)
    want = set(ALWAYS)
    if protocol == "mvcc":
        want.add("version_select")
    if plane != "jnp":
        want |= {"multi_read", "lock_arbiter"}
        if protocol == "mvcc":
            want.add("mvcc_version_select")
    assert want <= names, sorted(want - names)


def _host_spans(trace_dir: str):
    from jax.profiler import ProfileData

    (path,) = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True)
    return [
        (e.name, e.start_ns, e.start_ns + e.duration_ns)
        for plane in ProfileData.from_file(path).planes if plane.name.startswith("/host:")
        for line in plane.lines for e in line.events if e.name.startswith("repro.")
    ]


@pytest.mark.parametrize("layout", ["dense", "node"])
def test_execute_spans_nest_and_time_the_call(layout, tmp_path):
    extra = dict(node_shards=1, layout="node") if layout == "node" else {}
    spec = api.ExperimentSpec(protocol="nowait", workload="smallbank", configs=[{"hybrid": 21}],
                              **TINY, **extra)
    api.execute(api.plan(spec))  # compile outside the trace
    with jax.profiler.trace(str(tmp_path)):
        res = api.execute(api.plan(spec))
    spans = sorted(_host_spans(str(tmp_path)), key=lambda s: s[1])
    names = [s[0] for s in spans]
    assert names == ["repro.plan", "repro.execute", "repro.execute.knobs", "repro.execute.dispatch",
                     "repro.execute.fetch", "repro.execute.rows"]
    _, lo, hi = spans[1]
    inner = spans[2:]
    assert all(lo <= s <= e <= hi for _, s, e in inner)
    assert all(a[2] <= b[1] for a, b in zip(inner, inner[1:]))  # one after another
    assert spans[0][2] <= lo
    # wall_s times the repro.execute interval, to 0.1 ms
    wall = res.rows[0]["wall_s"]
    assert wall == res.wall_s == round(wall, 4)
    assert abs(wall - (hi - lo) / 1e9) < 1e-3
