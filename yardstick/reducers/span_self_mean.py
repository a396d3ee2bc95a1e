"""Mean self time, in ms, of the spans whose name starts with `prefix`:
a span's duration less the part of it that its child spans cover."""

from yardstick.trace_reduce import union


def reduce(w, prefix: str):
    children: dict[str, list] = {}
    for s in w.spans:
        if s.parent_id is not None:
            children.setdefault(s.parent_id, []).append(s)
    selfs = []
    for s in w.spans:
        if not s.name.startswith(prefix):
            continue
        lo, hi = s.t_start, s.t_end
        covered = union([(max(lo, c.t_start), min(hi, c.t_end))
                         for c in children.get(s.span_id, [])
                         if c.t_end > lo and c.t_start < hi])
        selfs.append(s.dur_ms - sum(b - a for a, b in covered) * 1e3)
    return sum(selfs) / len(selfs) if selfs else None
