"""How ``correct`` is decided: the window's rows against the plain reference.

After the window closes, a sample of the rows the front door returned
(drawn from the seed, spread evenly over the window's calls and over each
call's grid positions) is recomputed by :mod:`bench.reference` and
compared:

* ``int_mismatch``: sampled rows whose integer counters (commits, aborts)
  or hybrid coding differ from the reference's, or that lack a field the
  reference gives.  Exact: limit 0.
* ``float_gap``: the widest relative gap of a float output (simulated
  throughput, mean latency, abort rate, round trips, the per-stage latency
  breakdown), each measured against the reference value's magnitude (for
  the per-stage breakdown, the largest stage of that row).

The limits live in the cell's file, ``bench/cells/<cell>.json``.
"""
from __future__ import annotations

import math
from typing import Dict, List, Sequence, Tuple

import numpy as np

INT_FIELDS = ("commits", "aborts")
NUMBERS = ("int_mismatch", "float_gap")


def sample_positions(n_rows: int, n_sample: int, seed: int) -> List[int]:
    """``n_sample`` of ``n_rows`` positions, evenly strided from a seeded offset."""
    n = min(n_sample, n_rows)
    if n == 0:
        return []
    offset = int(np.random.default_rng([seed % 2**63, 2]).integers(n_rows))
    return sorted({(offset + (k * n_rows) // n) % n_rows for k in range(n)})


def _values(v) -> List[float]:
    return [float(x) for x in (v if isinstance(v, (list, tuple)) else [v])]


def compare(rows: Sequence[Dict], refs: Sequence[Dict], codes: Sequence[int]) -> Dict[str, float]:
    """The compared numbers for program rows against reference rows."""
    mismatch = 0
    gap = 0.0
    for row, ref, code in zip(rows, refs, codes):
        want_code = "".join(str((code >> i) & 1) for i in range(6))
        if (any(k not in row for k in ref) or row.get("hybrid") != want_code
                or any(int(row[k]) != int(ref[k]) for k in INT_FIELDS)):
            mismatch += 1
            continue
        for k, rv in ref.items():
            if k in INT_FIELDS:
                continue
            want, got = _values(rv), _values(row[k])
            if len(want) != len(got) or not all(map(math.isfinite, got)):
                mismatch += 1
                break
            scale = max(abs(x) for x in want)
            for w, g in zip(want, got):
                if w != g:
                    gap = max(gap, abs(g - w) / (scale or 1.0))
    return {"int_mismatch": float(mismatch), "float_gap": gap}


def judge(numbers: Dict[str, float], limits: Dict[str, float]) -> bool:
    return all(numbers[k] <= limits[k] for k in NUMBERS)


def check(deployment: dict, traffic: dict, calls: Sequence[Tuple[object, List[Dict]]],
          n_sample: int, seed: int) -> Dict[str, float]:
    """Sample the window's (call, rows) pairs and compare them with the reference."""
    from bench import reference

    flat = [(call, i, rows) for call, rows in calls for i in range(len(call.knobs))]
    picks = [flat[p] for p in sample_positions(len(flat), n_sample, seed)]
    if not picks:  # no call of the window returned: every sampled row is missing
        return {"int_mismatch": float(n_sample), "float_gap": 0.0}
    knobs = [call.knobs[i] for call, i, _ in picks]
    first = picks[0][0]
    refs = reference.rows(deployment, {"protocol": first.protocol, "ticks": first.ticks,
                                       "warmup": first.warmup}, knobs)
    got = [rows[i] if rows is not None and i < len(rows) else {} for _, i, rows in picks]
    return compare(got, refs, [k["hybrid"] for k in knobs])
