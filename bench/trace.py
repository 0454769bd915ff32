"""Reduction of one profiler trace (``.xplane.pb``) to the per-layer numbers.

The traced window is the host span ``bench.call`` that the harness opens
around one whole call (plan, execute, row handling).  Inside it:

* device busy time: the union of the intervals in which an XLA op ran on a
  TPU core (``/device:TPU:<n>`` planes, ``XLA Ops`` line), averaged over
  the cores that ran anything;
* each op's self time: its duration minus the ops nested inside it on the
  same line, averaged over the cores like busy time, so self times add up
  to busy time on four chips as on one;
* Pallas kernels: the ops the compiler lowered from a ``pallas_call``,
  which the TPU trace names by their HLO text, a ``custom-call`` with
  ``custom_call_target="tpu_custom_call"`` (the kernels carry no name of
  their own, so one kernel is not told from another);
* idle gaps: the stretches of the window in which no device ran anything,
  each put down to the innermost ``bench.*`` host span that covers its
  middle.
"""
from __future__ import annotations

import glob
import os
import re
from dataclasses import dataclass
from typing import Dict, List, Optional, Sequence, Tuple

WINDOW_SPAN = "bench.call"
HOST_PREFIX = "bench."
DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
PALLAS_TARGET = 'custom_call_target="tpu_custom_call"'

Interval = Tuple[float, float]  # (start_ns, end_ns)


@dataclass(frozen=True)
class Event:
    name: str
    start_ns: float
    end_ns: float


@dataclass(frozen=True)
class Trace:
    """What the reduction needs from a trace, in plain lists."""

    host: List[Event]  # bench.* host spans
    devices: Dict[str, List[Event]]  # device plane -> its XLA op events


@dataclass(frozen=True)
class Summary:
    window_s: float
    busy_s: float
    op_self_s: Dict[str, float]  # op label -> self seconds, averaged over devices
    pallas_s: float  # self seconds of Pallas kernels, averaged over devices
    n_devices: int
    idle_gaps: List[Tuple[str, float]]  # (host span, seconds), longest first

    def top_ops(self, n: int = 10) -> List[Tuple[str, float]]:
        return sorted(self.op_self_s.items(), key=lambda kv: -kv[1])[:n]


def load(path: str) -> Trace:
    """Read a ``.xplane.pb`` file, gzipped or not, or the newest one under a
    directory."""
    import gzip

    from jax.profiler import ProfileData

    if os.path.isdir(path):
        found = sorted(glob.glob(os.path.join(path, "**", "*.xplane.pb"), recursive=True),
                       key=os.path.getmtime)
        if not found:
            raise FileNotFoundError(f"no .xplane.pb under {path}")
        path = found[-1]
    if path.endswith(".gz"):
        with gzip.open(path) as f:
            data = ProfileData.from_serialized_xspace(f.read())
    else:
        data = ProfileData.from_file(path)
    host: List[Event] = []
    devices: Dict[str, List[Event]] = {}
    for plane in data.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            for line in plane.lines:
                if line.name == OPS_LINE:
                    devices.setdefault(plane.name, []).extend(
                        Event(e.name, e.start_ns, e.end_ns) for e in line.events
                    )
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host.extend(
                    Event(e.name, e.start_ns, e.end_ns)
                    for e in line.events if e.name.startswith(HOST_PREFIX)
                )
    return Trace(host=host, devices=devices)


def is_pallas(event: Event) -> bool:
    return PALLAS_TARGET in event.name


LABEL_CHARS = 120
_HLO = re.compile(r"%[\w.\-]+ = (.+?) ([a-z][\w\-]*)\(")


def op_label(name: str) -> str:
    """A short label for an op named by its HLO text: the instruction kind
    and the result shape without layouts, e.g. ``custom-call s32[64,2560,8]``
    (``pallas`` added for a Pallas kernel).  Other names pass unchanged."""
    m = _HLO.match(name)
    if not m:
        return name
    label = f"{m.group(2)} {re.sub(r'{[^}]*}', '', m.group(1))}"[:LABEL_CHARS]
    return label + " pallas" if PALLAS_TARGET in name else label


def _clip(ev: Event, lo: float, hi: float) -> Optional[Interval]:
    s, e = max(ev.start_ns, lo), min(ev.end_ns, hi)
    return (s, e) if e > s else None


def union(intervals: Sequence[Interval]) -> List[Interval]:
    out: List[Interval] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], e))
        else:
            out.append((s, e))
    return out


def self_times(events: Sequence[Event], lo: float, hi: float) -> List[Tuple[Event, float]]:
    """(event, self ns) for the events of one line, clipped to [lo, hi]:
    each event's duration less the events nested directly inside it."""
    clipped = [(ev, iv) for ev in events if (iv := _clip(ev, lo, hi))]
    clipped.sort(key=lambda p: (p[1][0], -p[1][1]))
    out: Dict[int, float] = {}
    stack: List[Tuple[int, Interval]] = []
    for i, (_, (s, e)) in enumerate(clipped):
        while stack and stack[-1][1][1] <= s:
            stack.pop()
        out[i] = e - s
        if stack and e <= stack[-1][1][1]:
            out[stack[-1][0]] -= e - s
        stack.append((i, (s, e)))
    return [(clipped[i][0], max(ns, 0.0)) for i, ns in out.items()]


def reduce(trace: Trace, top_gaps: int = 10) -> Summary:
    """Reduce the traced window; raises if the window span is missing."""
    windows = [e for e in trace.host if e.name == WINDOW_SPAN]
    if not windows:
        raise ValueError(f"trace holds no {WINDOW_SPAN!r} host span")
    win = max(windows, key=lambda e: e.end_ns - e.start_ns)
    lo, hi = win.start_ns, win.end_ns
    op_self: Dict[str, float] = {}
    pallas_ns = 0.0
    busy_ns: List[float] = []
    ran: List[Interval] = []
    for events in trace.devices.values():
        busy = union([iv for ev in events if (iv := _clip(ev, lo, hi))])
        if not busy:
            continue
        busy_ns.append(sum(e - s for s, e in busy))
        ran += busy
        for ev, ns in self_times(events, lo, hi):
            label = op_label(ev.name)
            op_self[label] = op_self.get(label, 0.0) + ns / 1e9
            if is_pallas(ev):
                pallas_ns += ns
    edges = [lo] + [x for iv in union(ran) for x in iv] + [hi]
    gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2) if edges[i + 1] > edges[i]]
    spans = [e for e in trace.host if e.name != WINDOW_SPAN]

    def doing(mid: float) -> str:
        inside = [e for e in spans if e.start_ns <= mid < e.end_ns]
        return min(inside, key=lambda e: e.end_ns - e.start_ns).name if inside else WINDOW_SPAN

    idle = sorted(((doing((s + e) / 2), (e - s) / 1e9) for s, e in gaps), key=lambda g: -g[1])
    n = len(busy_ns)
    return Summary(
        window_s=(hi - lo) / 1e9,
        busy_s=sum(busy_ns) / n / 1e9 if n else 0.0,
        op_self_s={k: v / n for k, v in op_self.items()},
        pallas_s=pallas_ns / n / 1e9 if n else 0.0,
        n_devices=n,
        idle_gaps=idle[:top_gaps],
    )
