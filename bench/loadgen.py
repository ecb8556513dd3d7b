"""The one request generator: arrival times and request rows from a
traffic mix's parameters and the seed.

A mix (``traffic/<mix>.json``) gives ``rate_rps``, the rate of steady
Poisson arrivals. The number of arrivals is fixed, ``round(rate *
seconds)``, and their times are uniform order statistics: a Poisson
process conditioned on its count. So every seed sends the same amount of
work, in another order and at other times.
Each request is one raw row drawn uniformly from the configuration's pool.
"""

from __future__ import annotations

import dataclasses

import numpy as np


@dataclasses.dataclass(frozen=True)
class Plan:
    t: np.ndarray      # (N,) float64 scheduled arrival, seconds from start
    row: np.ndarray    # (N,) int64 pool row of each request

    def __len__(self) -> int:
        return int(self.t.size)


def schedule(traffic: dict, seconds: float, pool_size: int,
             seed: int) -> Plan:
    rng = np.random.default_rng([seed, 0x10AD])
    n = int(round(float(traffic["rate_rps"]) * seconds))
    t = np.sort(rng.uniform(0.0, seconds, n))
    return Plan(t=t, row=rng.integers(0, pool_size, n))

