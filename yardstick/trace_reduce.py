"""From a profiler trace to numbers: busy intervals, time per operation,
idle gaps. Checked against the small recorded trace in `tests/`.

`read_xplane` is the only part that needs jax (`ProfileData`); everything
else works on its plain output, a list of planes

    {"name": ..., "lines": [{"name": ..., "events": [[name, start_ns,
     duration_ns, stats], ...]}]}

so the reduction can be tested on a trace written down as JSON.

A device plane is one whose name starts with `/device:`. On it the line
`XLA Ops` holds every operation that ran (nested where a loop holds its
body), and `XLA Modules` one event per executed program. Busy time is the
union of the `XLA Ops` intervals; a program's device time is the sum of
its `XLA Modules` events.
"""

from __future__ import annotations

import re

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
SYNC_MARK = "yardstick.sync"


def read_xplane(path: str) -> list[dict]:
    import jax

    planes = []
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        device = plane.name.startswith("/device:")
        lines = []
        for line in plane.lines:
            events = []
            for e in line.events:
                # of a host plane only the harness's own marks are kept:
                # its other events are many and nothing here reads them
                if e.name == SYNC_MARK:
                    events.append([e.name, float(e.start_ns),
                                   float(e.duration_ns),
                                   {k: v for k, v in e.stats}])
                elif device:
                    # an operation's event is named by its whole HLO line;
                    # what stands before " = " is its name
                    events.append([e.name.split(" = ", 1)[0],
                                   float(e.start_ns), float(e.duration_ns),
                                   {}])
            if events:
                lines.append({"name": line.name, "events": events})
        if lines:
            planes.append({"name": plane.name, "lines": lines})
    return planes


def device_planes(planes: list[dict]) -> list[dict]:
    return [p for p in planes if p["name"].startswith("/device:")
            and any(ln["events"] for ln in p["lines"])]


def line_events(plane: dict, line_name: str) -> list[list]:
    return [e for ln in plane["lines"] if ln["name"] == line_name
            for e in ln["events"]]


def union(intervals: list[tuple[float, float]]) -> list[tuple[float, float]]:
    """Merged, sorted, non-overlapping intervals."""
    out: list[list[float]] = []
    for lo, hi in sorted(intervals):
        if out and lo <= out[-1][1]:
            out[-1][1] = max(out[-1][1], hi)
        else:
            out.append([lo, hi])
    return [(lo, hi) for lo, hi in out]


def clip(events: list[list], lo_ns: float, hi_ns: float) -> list[tuple]:
    """(name, start, end) of the parts of `events` inside [lo, hi]."""
    out = []
    for name, start, dur, *_ in events:
        a, b = max(start, lo_ns), min(start + dur, hi_ns)
        if b > a:
            out.append((name, a, b))
    return out


def busy_seconds(planes: list[dict], lo_ns: float, hi_ns: float) -> float:
    """Seconds in which an operation ran, averaged over the device planes
    that ran any."""
    per_plane = []
    for p in device_planes(planes):
        ops = clip(line_events(p, OPS_LINE), lo_ns, hi_ns)
        per_plane.append(
            sum(b - a for a, b in union([(a, b) for _, a, b in ops])) / 1e9)
    return sum(per_plane) / len(per_plane) if per_plane else 0.0


def op_seconds(planes: list[dict], line_name: str, lo_ns: float,
               hi_ns: float, pattern: str | None = None) -> dict[str, float]:
    """Seconds by event name on `line_name` of every device plane. Names
    lose a trailing `(...)` fingerprint so that runs agree on them."""
    rx = re.compile(pattern) if pattern else None
    totals: dict[str, float] = {}
    for p in device_planes(planes):
        for name, a, b in clip(line_events(p, line_name), lo_ns, hi_ns):
            name = re.sub(r"\(\d+\)$", "", name)
            if rx is None or rx.search(name):
                totals[name] = totals.get(name, 0.0) + (b - a) / 1e9
    return totals


def op_count(planes: list[dict], line_name: str, lo_ns: float, hi_ns: float,
             pattern: str) -> int:
    """Events on `line_name` that match `pattern` and start in [lo, hi]."""
    rx = re.compile(pattern)
    return sum(1 for p in device_planes(planes)
               for name, start, *_ in line_events(p, line_name)
               if lo_ns <= start <= hi_ns and rx.search(name))


def idle_gaps(planes: list[dict], lo_ns: float, hi_ns: float,
              min_ns: float = 0.0) -> list[tuple[float, float]]:
    """The stretches of [lo, hi], each longer than `min_ns`, in which no
    device ran anything."""
    spans = []
    for p in device_planes(planes):
        spans += [(a, b) for _, a, b in
                  clip(line_events(p, OPS_LINE), lo_ns, hi_ns)]
    gaps, at = [], lo_ns
    for a, b in union(spans):
        if a > at:
            gaps.append((at, a))
        at = max(at, b)
    if hi_ns > at:
        gaps.append((at, hi_ns))
    return [(a, b) for a, b in gaps if b - a > min_ns]


def sync_marks(planes: list[dict]) -> list[tuple[float, float]]:
    """(trace ns, host perf_counter ns) of every sync mark the harness
    wrote, so that the program's spans can be put on the trace's clock."""
    out = []
    for p in planes:
        for ln in p["lines"]:
            for name, start, _dur, stats in ln["events"]:
                if name == SYNC_MARK and "t_ns" in stats:
                    out.append((start, float(stats["t_ns"])))
    return sorted(out)
