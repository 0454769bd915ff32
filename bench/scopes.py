"""The program's own names in one profiler trace: device time by scope,
idle gaps by host span.

    python3 bench/scopes.py [<trace dir or .xplane.pb[.gz]>]

prints three tables for the traced call (the host span ``bench.call``) of
the newest trace under ``bench/.trace`` (or the given path), and the
coverage checks below them.

The engine names its work with ``jax.named_scope``: the store reads
(``gather``), the CAS arbitration (``arbitrate``), the MVCC version pick
(``version_select``), the capacity ranking (``service``) and the node
layout's collectives (``exchange``) inside the tick's phases
(``begin_tick``, one ``stage_<name>`` per stage of the protocol's table)
and the run's ``init`` and ``summarize``.  ``repro.api`` writes host spans
on the profiler's clock: ``repro.plan``, and ``repro.execute`` holding
``repro.execute.knobs``, ``.dispatch``, ``.fetch`` and ``.rows``.

A device op's scope path is the ``tf_op`` stat of its event metadata in
the trace's XPlane, which ``jax.profiler.ProfileData`` does not expose, so
this module declares the few XSpace protobuf fields it reads and parses
the file with the installed ``google.protobuf``.  The path's components
are matched whole, after peeling a transform's wrapper (``vmap(init)`` is
``init``); the last component names the op's primitive and is no scope.  Device self times come from ``bench/trace.py``, as there.

A trace of a program without these names (an older commit) reduces to no
scoped time and no ``repro.*`` span: the readers below then return None.
"""
from __future__ import annotations

import functools
import glob
import gzip
import os
import re
import sys
from dataclasses import dataclass
from typing import Dict, List, Optional, Tuple

if __package__ in (None, ""):
    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from bench import harness, trace  # noqa: E402

PRIMITIVES = ("gather", "arbitrate", "version_select", "service", "exchange")
PHASES = ("begin_tick", "init", "summarize")  # and every stage_<name>
KERNEL_SCOPES = ("gather", "arbitrate", "version_select")
SPAN_PREFIX = "repro."
EXECUTE_SPAN = "repro.execute"
UNSCOPED = "(no scope)"
GAP_FLOOR_S = 1e-4  # idle gaps this long must lie under a repro.* span

_WRAPPED = re.compile(r"^[\w.]+\((.*)\)$")


@dataclass(frozen=True)
class Op(trace.Event):
    """A device op: ``name`` is its HLO text, ``path`` its scope path."""

    path: str = ""


@dataclass(frozen=True)
class Summary:
    window_s: float
    busy_s: float  # averaged over the devices that ran anything, as in trace.py
    by_primitive: Dict[str, float]  # innermost primitive scope -> self seconds
    by_phase: Dict[str, float]  # outermost phase scope -> self seconds
    pallas_unscoped_s: float  # Pallas kernels outside gather/arbitrate/version_select
    idle_gaps: List[Tuple[str, float]]  # (innermost repro.* span, seconds), longest first
    launch_s: Optional[float]  # repro.execute start -> first device op
    drain_s: Optional[float]  # last device op -> repro.execute end


# ---------------------------------------------------------------------------
# The XSpace fields read here (tsl/profiler/protobuf/xplane.proto); a map
# field is declared as its wire form, a repeated key/value entry.
# ---------------------------------------------------------------------------

_MESSAGES = {
    "XSpace": [("planes", 1, "repeated XPlane")],
    "XPlane": [("name", 2, "string"), ("lines", 3, "repeated XLine"),
               ("event_metadata", 4, "repeated EventMetadataEntry"),
               ("stat_metadata", 5, "repeated StatMetadataEntry")],
    "EventMetadataEntry": [("key", 1, "int64"), ("value", 2, "XEventMetadata")],
    "StatMetadataEntry": [("key", 1, "int64"), ("value", 2, "XStatMetadata")],
    "XLine": [("name", 2, "string"), ("timestamp_ns", 3, "int64"), ("events", 4, "repeated XEvent")],
    "XEvent": [("metadata_id", 1, "int64"), ("offset_ps", 2, "int64"), ("duration_ps", 3, "int64")],
    "XEventMetadata": [("id", 1, "int64"), ("name", 2, "string"), ("stats", 5, "repeated XStat")],
    "XStatMetadata": [("id", 1, "int64"), ("name", 2, "string")],
    "XStat": [("metadata_id", 1, "int64"), ("str_value", 5, "string"), ("ref_value", 7, "uint64")],
}
_SCALARS = {"int64": 3, "uint64": 4, "string": 9}  # FieldDescriptorProto.Type
_MESSAGE, _OPTIONAL, _REPEATED = 11, 1, 3


@functools.lru_cache(maxsize=None)
def _xspace_class():
    from google.protobuf import descriptor_pb2, descriptor_pool, message_factory

    f = descriptor_pb2.FileDescriptorProto(name="bench_xspace.proto", package="bench_xspace")
    for name, fields in _MESSAGES.items():
        m = f.message_type.add(name=name)
        for fname, number, kind in fields:
            repeated = kind.startswith("repeated ")
            kind = kind.removeprefix("repeated ")
            fd = m.field.add(name=fname, number=number, label=_REPEATED if repeated else _OPTIONAL)
            if kind in _SCALARS:
                fd.type = _SCALARS[kind]
            else:
                fd.type, fd.type_name = _MESSAGE, f".bench_xspace.{kind}"
    pool = descriptor_pool.DescriptorPool()
    pool.Add(f)
    return message_factory.GetMessageClass(pool.FindMessageTypeByName("bench_xspace.XSpace"))


def newest(path: str) -> Optional[str]:
    """``path`` itself, or the newest ``.xplane.pb`` under a directory (None if none)."""
    if not os.path.isdir(path):
        return path if os.path.isfile(path) else None
    found = sorted(glob.glob(os.path.join(path, "**", "*.xplane.pb"), recursive=True),
                   key=os.path.getmtime)
    return found[-1] if found else None


def load(path: str) -> Tuple[List[trace.Event], Dict[str, List[Op]]]:
    """(host spans, device plane -> its ops) of one ``.xplane.pb`` file,
    gzipped or not.  Host spans: ``bench.call`` and every ``repro.*`` span.
    Times in ns on the trace's clock, truncated as ``ProfileData`` does."""
    opener = gzip.open if path.endswith(".gz") else open
    with opener(path, "rb") as fh:
        space = _xspace_class().FromString(fh.read())
    host: List[trace.Event] = []
    devices: Dict[str, List[Op]] = {}
    for plane in space.planes:
        is_device = plane.name.startswith(trace.DEVICE_PREFIX)
        if not (is_device or plane.name.startswith("/host:")):
            continue
        meta = {e.key: e.value for e in plane.event_metadata}
        if is_device:
            stat_names = {e.key: e.value.name for e in plane.stat_metadata}
            paths = {k: _tf_op(m, stat_names) for k, m in meta.items()}
        for line in plane.lines:
            if is_device and line.name != trace.OPS_LINE:
                continue
            for ev in line.events:
                start = line.timestamp_ns + ev.offset_ps // 1000
                end = start + ev.duration_ps // 1000
                name = meta[ev.metadata_id].name if ev.metadata_id in meta else ""
                if is_device:
                    op = Op(name, start, end, paths.get(ev.metadata_id, ""))
                    devices.setdefault(plane.name, []).append(op)
                elif name == trace.WINDOW_SPAN or name.startswith(SPAN_PREFIX):
                    host.append(trace.Event(name, start, end))
    return host, devices


def _tf_op(meta, stat_names: Dict[int, str]) -> str:
    for st in meta.stats:
        if stat_names.get(st.metadata_id) == "tf_op":
            return st.str_value or stat_names.get(st.ref_value, "")
    return ""


def scope_names(path: str) -> List[str]:
    """The scopes of a ``tf_op`` path (``<scope>/.../<primitive>:<type>``),
    outermost first; the op's own primitive (``gather``, ``pallas_call``)
    is not one of them."""
    out = []
    for part in path.rsplit(":", 1)[0].split("/")[:-1]:
        while (m := _WRAPPED.match(part)) is not None:
            part = m.group(1)
        out.append(part)
    return out


def primitive_of(names: List[str]) -> str:
    return next((n for n in reversed(names) if n in PRIMITIVES), UNSCOPED)


def phase_of(names: List[str]) -> str:
    return next((n for n in names if n in PHASES or n.startswith("stage_")), UNSCOPED)


def reduce(host: List[trace.Event], devices: Dict[str, List[Op]]) -> Summary:
    """Reduce the traced window; raises if the window span is missing."""
    base = trace.reduce(trace.Trace(host=host, devices=devices), top_gaps=1 << 30)
    win = max((e for e in host if e.name == trace.WINDOW_SPAN), key=lambda e: e.end_ns - e.start_ns)
    lo, hi = win.start_ns, win.end_ns
    n = max(base.n_devices, 1)
    by_prim: Dict[str, float] = {}
    by_phase: Dict[str, float] = {}
    pallas_unscoped = 0.0
    for ops in devices.values():
        for op, ns in trace.self_times(ops, lo, hi):
            names = scope_names(op.path)
            prim, phase, s = primitive_of(names), phase_of(names), ns / 1e9 / n
            by_prim[prim] = by_prim.get(prim, 0.0) + s
            by_phase[phase] = by_phase.get(phase, 0.0) + s
            if trace.is_pallas(op) and prim not in KERNEL_SCOPES:
                pallas_unscoped += s
    launch, drain = _edges(host, devices, lo, hi)
    return Summary(
        window_s=base.window_s,
        busy_s=base.busy_s,
        by_primitive=by_prim,
        by_phase=by_phase,
        pallas_unscoped_s=pallas_unscoped,
        idle_gaps=base.idle_gaps,
        launch_s=launch,
        drain_s=drain,
    )


def _edges(host, devices, lo, hi) -> Tuple[Optional[float], Optional[float]]:
    """Device idle seconds at the start and at the end of each ``repro.execute``
    span in the window, summed over the spans and averaged over the devices
    that ran inside them; (None, None) without such a span."""
    spans = [e for e in host if e.name == EXECUTE_SPAN and lo <= e.start_ns and e.end_ns <= hi]
    launch, drain, seen = 0.0, 0.0, 0
    for ops in devices.values():
        ran = False
        for sp in spans:
            busy = trace.union([(max(op.start_ns, sp.start_ns), min(op.end_ns, sp.end_ns))
                                for op in ops if op.end_ns > sp.start_ns and op.start_ns < sp.end_ns])
            if busy:
                ran = True
                launch += busy[0][0] - sp.start_ns
                drain += sp.end_ns - busy[-1][1]
        seen += ran
    if not seen:
        return None, None
    return launch / seen / 1e9, drain / seen / 1e9


@functools.lru_cache(maxsize=None)
def summarize(path: str) -> Summary:
    """The reduction of one trace file, once per process."""
    return reduce(*load(path))


# ---------------------------------------------------------------------------
# Per-layer metrics (bench/metrics/<name>.py): None when the run was not
# traced or the trace holds nothing of the program's own names.
# ---------------------------------------------------------------------------


def for_run(run) -> Optional[Summary]:
    if run.trace is None:
        return None
    path = newest(harness.TRACE_DIR)
    return summarize(path) if path else None


def gather_share(run) -> Optional[float]:
    s = for_run(run)
    if s is None or s.busy_s <= 0 or not s.by_primitive.get("gather"):
        return None
    return 100.0 * s.by_primitive["gather"] / s.busy_s


def launch_gap_ms(run) -> Optional[float]:
    s = for_run(run)
    return None if s is None or s.launch_s is None else s.launch_s * 1e3


def drain_gap_ms(run) -> Optional[float]:
    s = for_run(run)
    return None if s is None or s.drain_s is None else s.drain_s * 1e3


# ---------------------------------------------------------------------------
# The operator's tables
# ---------------------------------------------------------------------------


def _table(title: str, rows: Dict[str, float], total: float) -> List[str]:
    out = [f"{title:<28} {'seconds':>14} {'share':>8}"]
    for name, s in sorted(rows.items(), key=lambda kv: -kv[1]):
        out.append(f"{name:<28} {s:>14.6f} {100 * s / total if total else 0:>7.2f}%")
    return out


def report(s: Summary, bench_execute_idle_s: Optional[float] = None) -> str:
    lines = [f"window {s.window_s:.6f} s, device busy {s.busy_s:.6f} s", ""]
    lines += _table("primitive scope", s.by_primitive, s.busy_s) + [""]
    lines += _table("phase scope", s.by_phase, s.busy_s) + [""]
    lines.append(f"{'idle gap under':<28} {'seconds':>14}")
    lines += [f"{name:<28} {sec:>14.6f}" for name, sec in s.idle_gaps if sec >= 1e-6]
    named = max(s.busy_s - s.by_phase.get(UNSCOPED, 0.0), 0.0)
    loose = [g for g in s.idle_gaps if g[1] >= GAP_FLOOR_S and not g[0].startswith(SPAN_PREFIX)]
    lines += [
        "",
        f"busy time under a phase scope: {100 * named / s.busy_s if s.busy_s else 0:.2f}%",
        f"Pallas kernels outside {'/'.join(KERNEL_SCOPES)}: {s.pallas_unscoped_s:.6f} s",
        f"idle gaps of {GAP_FLOOR_S * 1e3:g} ms or more outside a {SPAN_PREFIX}* span: {len(loose)}",
        f"launch gap {_ms(s.launch_s)} ms, drain gap {_ms(s.drain_s)} ms",
    ]
    if bench_execute_idle_s and s.launch_s is not None:
        edge = s.launch_s + s.drain_s
        lines.append(f"launch + drain against the idle time under bench.execute: "
                     f"{100 * edge / bench_execute_idle_s:.1f}%")
    return "\n".join(lines)


def _ms(x: Optional[float]) -> str:
    return "-" if x is None else f"{x * 1e3:.4f}"


def main(argv: List[str]) -> int:
    where = argv[0] if argv else harness.TRACE_DIR
    path = newest(where)
    if path is None:
        print(f"scopes: no .xplane.pb at {where}", file=sys.stderr)
        return 1
    base = trace.reduce(trace.load(path), top_gaps=1 << 30)
    under_execute = sum(sec for name, sec in base.idle_gaps if name == "bench.execute")
    print(path)
    print(report(summarize(path), under_execute))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
