"""The one traffic generator: turns a traffic file and a seed into calls.

A traffic file (``bench/traffic/<name>.json``) describes the stream of
front-door calls a user sends:

* ``protocol``: the concurrency-control protocol every call names;
* ``codes``: ``"all"`` puts every hybrid coding (0..63) in each call, in
  order; ``"cycle"`` walks a seeded permutation of the codings,
  ``configs_per_call`` at a time;
* ``configs_per_call``: configurations in one call;
* ``ticks`` / ``warmup``: measured and warm-up ticks of every call.

Every call gets one fresh engine seed drawn from (``--seed``, call index),
so no call repeats another, and every call has the same shapes, so one
compiled program serves the whole stream.  Call 0 is the set-up's warm
call; the window starts at call 1.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Iterator, List

import numpy as np

N_CODES = 64  # 2^6 one-sided/two-sided codings of the six network stages
CODE_ORDERS = ("all", "cycle")


@dataclass(frozen=True)
class Call:
    index: int
    protocol: str
    ticks: int
    warmup: int
    knobs: List[dict]  # one {"hybrid": code, "seed": engine seed} per configuration

    @property
    def config_ticks(self) -> int:
        """Configurations x (warm-up + measured) ticks: the work of the call."""
        return len(self.knobs) * (self.ticks + self.warmup)


def validate(traffic: dict) -> None:
    if traffic.get("codes") not in CODE_ORDERS:
        raise ValueError(f"traffic codes={traffic.get('codes')!r}: use one of {CODE_ORDERS}")
    n = traffic["configs_per_call"]
    if not 1 <= n <= N_CODES or (traffic["codes"] == "all" and n != N_CODES):
        raise ValueError(f"configs_per_call={n} does not fit codes={traffic['codes']!r}")
    if traffic["ticks"] < 1 or traffic["warmup"] < 0:
        raise ValueError("ticks must be >= 1 and warmup >= 0")


def calls(traffic: dict, seed: int) -> Iterator[Call]:
    """The endless call stream of one run; the same seed gives the same calls."""
    validate(traffic)
    seed %= 2**63  # any whole number; the generator takes non-negative entropy
    n = traffic["configs_per_call"]
    order = np.random.default_rng([seed, 0]).permutation(N_CODES)
    index = 0
    while True:
        engine_seed = int(np.random.default_rng([seed, 1, index]).integers(0, 2**31 - 1))
        if traffic["codes"] == "all":
            codes = range(N_CODES)
        else:
            codes = [int(order[(index * n + j) % N_CODES]) for j in range(n)]
        yield Call(
            index=index,
            protocol=traffic["protocol"],
            ticks=traffic["ticks"],
            warmup=traffic["warmup"],
            knobs=[{"hybrid": int(c), "seed": engine_seed} for c in codes],
        )
        index += 1
