"""The share, in %, of the traced stretch that the spans named `span`
cover: their union, clipped to the stretch (`w.trace`'s `lo_pc`, `hi_pc`
on the host's clock), over the stretch's length. Nothing when no trace was
taken, or when the program records no such span. A `Window` does not know
the whole window's length, so the traced stretch is the denominator that
every run has."""

from yardstick.trace_reduce import union


def reduce(w, span: str):
    if w.trace is None:
        return None
    lo, hi = w.trace["lo_pc"], w.trace["hi_pc"]
    mine = [s for s in w.spans if s.name == span]
    if not mine or hi <= lo:
        return None
    covered = union([(max(lo, s.t_start), min(hi, s.t_end)) for s in mine
                     if s.t_end > lo and s.t_start < hi])
    return 100.0 * sum(b - a for a, b in covered) / (hi - lo)
