"""The share, in %, of the traced stretch in which no operation ran on
the device."""

from yardstick import trace_reduce as tr


def reduce(w):
    if w.trace is None:
        return None
    t = w.trace
    window = (t["hi_ns"] - t["lo_ns"]) / 1e9
    busy = tr.busy_seconds(t["planes"], t["lo_ns"], t["hi_ns"])
    return 100.0 * (1.0 - busy / window) if window > 0 and busy > 0 else None
