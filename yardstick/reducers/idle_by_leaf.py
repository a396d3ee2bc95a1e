"""What the program was doing while the device idled: the share, in %, of
the traced stretch's idle time that lies under a span which says so.

The idle gaps of the stretch (`trace_reduce.idle_gaps`, those longer than
`min_gap_us`) and the program's spans are put on one clock through
`w.trace`'s two marks. For every instant of every gap, the spans that
cover it and have no child covering it are its leaves: several when
threads or requests overlap. The instant is attributed if one of its
leaves is not a container (`exclude_prefixes`: spans that only hold other
spans, so that time under them alone is time nothing has named), and it
is split evenly among its attributing leaves: a gap goes to the leaves in
proportion to the time each lies under it, not whole to one.

Returns attributed idle seconds over idle seconds, in %, and prints one
line

    [idle_by_leaf] {"idle_s": ..., "by_leaf": {name: seconds, ...},
                    "unattributed_under": {container or "nothing": seconds}}

with `unattributed` among `by_leaf`, so that a traced run's log carries
the table. Nothing without a device trace, or when the program recorded
no span in the stretch.
"""

import json

from yardstick import trace_reduce as tr

NOTHING = "nothing"


def leaf_stretches(spans, lo: float, hi: float):
    """(start, end, name) of each span's stretches inside [lo, hi] that
    none of its children covers."""
    children: dict[str, list] = {}
    for s in spans:
        if s.parent_id is not None:
            children.setdefault(s.parent_id, []).append(s)
    out = []
    for s in spans:
        a, b = max(lo, s.t_start), min(hi, s.t_end)
        if b <= a:
            continue
        kids = children.get(s.span_id, []) if s.span_id is not None else []
        at = a
        for ka, kb in tr.union([(max(a, c.t_start), min(b, c.t_end))
                                for c in kids
                                if c.t_end > a and c.t_start < b]):
            if ka > at:
                out.append((at, ka, s.name))
            at = max(at, kb)
        if b > at:
            out.append((at, b, s.name))
    return out


def split(gaps, stretches, exclude_prefixes):
    """(seconds by leaf name, seconds unattributed by what lay over them)
    of `gaps`, a sorted list of disjoint (start, end)."""
    events = []   # (time, order, name): leaves close before they open
    for a, b, name in stretches:
        events.append((a, 1, name))
        events.append((b, 0, name))
    for a, b in gaps:
        events.append((a, 3, None))
        events.append((b, 2, None))
    events.sort(key=lambda e: (e[0], e[1]))
    tup = tuple(exclude_prefixes)
    by_leaf: dict[str, float] = {}
    under: dict[str, float] = {}
    active: dict[str, int] = {}
    in_gap, at = False, 0.0
    for t, order, name in events:
        if in_gap and t > at:
            dt = t - at
            named = {n: c for n, c in active.items() if not n.startswith(tup)}
            if named:
                total = sum(named.values())
                for n, c in named.items():
                    by_leaf[n] = by_leaf.get(n, 0.0) + dt * c / total
            else:
                total = sum(active.values())
                for n, c in (active or {NOTHING: 1}).items():
                    under[n] = under.get(n, 0.0) + dt * c / (total or 1)
        at = t
        if order == 1:
            active[name] = active.get(name, 0) + 1
        elif order == 0:
            if active[name] == 1:
                del active[name]
            else:
                active[name] -= 1
        else:
            in_gap = order == 3
    return by_leaf, under


def reduce(w, min_gap_us: float, exclude_prefixes: list):
    if w.trace is None:
        return None
    t = w.trace
    lo, hi = t["lo_pc"], t["hi_pc"]
    gaps = [(lo + (a - t["lo_ns"]) / 1e9, lo + (b - t["lo_ns"]) / 1e9)
            for a, b in tr.idle_gaps(t["planes"], t["lo_ns"], t["hi_ns"],
                                     min_gap_us * 1e3)]
    idle = sum(b - a for a, b in gaps)
    stretches = leaf_stretches(w.spans, lo, hi)
    if idle <= 0 or not stretches or not tr.device_planes(t["planes"]):
        return None
    by_leaf, under = split(gaps, stretches, exclude_prefixes)
    attributed = sum(by_leaf.values())
    by_leaf["unattributed"] = idle - attributed
    top = dict(sorted(by_leaf.items(), key=lambda kv: -kv[1])[:16])
    print("[idle_by_leaf] " + json.dumps(
        {"idle_s": idle, "by_leaf": top,
         "unattributed_under": dict(sorted(under.items(),
                                           key=lambda kv: -kv[1])[:8])}),
        flush=True)
    return 100.0 * attributed / idle
