"""A nearest-rank percentile, in ms, of the client-side latency of the
window's completed operations of the kinds listed: a tail that has too few
samples to be bounded end to end can still be watched here."""

import math


def reduce(w, kinds: list, q: float):
    ms = sorted(op.ms for op in w.ops
                if op.kind in kinds and op.status == 200)
    return ms[max(0, math.ceil(q * len(ms)) - 1)] if ms else None
