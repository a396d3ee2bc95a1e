"""Zipf(s) popularity over ranks 0..k-1: P(rank r) proportional to 1/(r+1)^s.

Copied from `dds_tpu/clt/distribution.ZipfKeys` (the original is listed in
PERF.md's open questions) so that no later PR can change the traffic by
editing the program. s = 0 is uniform.
"""

from __future__ import annotations

import bisect
import random


class Zipf:
    def __init__(self, k: int, s: float):
        if k < 1:
            raise ValueError("Zipf needs at least one rank")
        acc, cdf = 0.0, []
        for r in range(1, k + 1):
            acc += 1.0 / (r ** s)
            cdf.append(acc)
        self._cdf = [c / acc for c in cdf]

    def pick(self, rng: random.Random) -> int:
        return min(bisect.bisect_left(self._cdf, rng.random()),
                   len(self._cdf) - 1)
