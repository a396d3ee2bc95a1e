"""Structured tracing: causally-linked spans + point events.

The reference's only observability is three debug flags gating `println`s
and a client ops/s printout (SURVEY.md §5.1, `dds-system.conf:61-62`,
`clt/DDSHttpClient.scala:410-415`). This module is the structured upgrade
called for there, extended by Telescope (dds_tpu/obs) into DISTRIBUTED
tracing: every recorded span carries `(trace_id, span_id, parent_id)` from
the contextvar-propagated `obs.context`, so one REST request yields a span
tree — HTTP route -> quorum round -> per-replica handler -> crypto kernel —
instead of an anonymous flat ring. Point `event`s (chaos injections, retry
attempts, breaker transitions, attacks) annotate the same tree with zero
duration. Overhead is one perf_counter pair and a deque append per span.
Counts of occurrences live in `obs.metrics`, not here.

One clock, true placement. A record carries two ends: `ts` on the wall
clock (for people and for cross-host stitching) and `t_end` on
`time.perf_counter()`, the clock that can be mapped onto a device trace;
`tid` is the thread that recorded it. The rule every caller keeps: a span
is recorded WHEN IT ENDS, or passes its true end — a span measured after
the fact (`record(name, dur_ms, _t_end=...)`) hands over the
`perf_counter` instant at which it ended, and both ends are set back by
the same amount. Subscribers can therefore place a span at
`[t_end - dur_ms, t_end]` without knowing who recorded it.

Usage:

    from dds_tpu.utils.trace import tracer
    with tracer.span("abd.fetch", key=key) as meta:
        meta["coordinator"] = coord      # annotate mid-span
    tracer.event("breaker.open", target=coord)
    tracer.record("proxy.admission", ms, _t_end=t_decided)   # backdated
    print(tracer.summary())              # span stats
"""

from __future__ import annotations

import collections
import contextlib
import logging
import math
import os
import threading
import time
from dataclasses import dataclass, field
from typing import Optional

from dds_tpu.obs import context as obs_context


@dataclass
class SpanRecord:
    ts: float
    name: str
    dur_ms: float
    meta: dict
    trace_id: Optional[str] = None
    span_id: Optional[str] = None
    parent_id: Optional[str] = None
    kind: str = "span"  # "span" (timed) | "event" (zero-duration annotation)
    # the span's end on time.perf_counter() and the recording thread; None
    # on records rebuilt from another process's wire dicts (their clock is
    # not ours), which are placed by `ts`
    t_end: Optional[float] = None
    tid: Optional[int] = None

    @property
    def end(self) -> float:
        """Where the span ends, on the best clock the record has."""
        return self.t_end if self.t_end is not None else self.ts


class _Span:
    """`Tracer.span`'s context manager: a class, not a generator, because
    a served request opens a few dozen of them."""

    __slots__ = ("_tracer", "_name", "_ctx", "_meta", "_token", "_t0")

    def __init__(self, tracer, name, ctx, meta):
        self._tracer, self._name, self._ctx, self._meta = (
            tracer, name, ctx, meta)

    def __enter__(self) -> dict:
        self._token = obs_context.attach(self._ctx)
        self._t0 = time.perf_counter()
        return self._meta

    def __exit__(self, *_exc) -> bool:
        t1 = time.perf_counter()
        obs_context.detach(self._token)
        self._tracer.record(self._name, (t1 - self._t0) * 1e3,
                            _ctx=self._ctx, _t_end=t1, **self._meta)
        return False


def _percentile(sorted_durs: list[float], q: float) -> float:
    """Nearest-rank percentile over an ascending list (exact for small k:
    p95 of 20 samples is the 19th value, not the max)."""
    k = len(sorted_durs)
    return sorted_durs[max(0, min(k - 1, math.ceil(q * k) - 1))]


@dataclass
class Tracer:
    """Thread-safe bounded event recorder."""

    max_events: int = 65536
    enabled: bool = True
    _events: collections.deque = field(init=False, repr=False)
    _lock: threading.Lock = field(init=False, repr=False)
    _subscribers: list = field(init=False, repr=False)
    _notifying: threading.local = field(init=False, repr=False)

    def __post_init__(self):
        self._events = collections.deque(maxlen=self.max_events)
        self._lock = threading.Lock()
        self._subscribers = []
        self._notifying = threading.local()

    # ---------------------------------------------------------- subscribers

    def subscribe(self, fn) -> None:
        """Register `fn(SpanRecord)` to be called (outside the ring lock)
        for every record. The consumer side of Watchtower: an online
        auditor sees each span/event as it lands instead of polling the
        ring. Subscribers must be cheap and must not raise — exceptions
        are swallowed so telemetry consumers can never break the paths
        being observed."""
        if fn not in self._subscribers:
            self._subscribers.append(fn)

    def unsubscribe(self, fn) -> None:
        if fn in self._subscribers:
            self._subscribers.remove(fn)

    def _notify(self, rec: "SpanRecord") -> None:
        # re-entrancy guard: a subscriber that records a span of its own
        # must not recurse into the subscriber chain again on this thread
        if getattr(self._notifying, "active", False):
            return
        self._notifying.active = True
        try:
            for fn in list(self._subscribers):
                try:
                    fn(rec)
                except Exception:  # noqa: BLE001 — observers never break observed paths
                    logging.getLogger("dds.trace").exception(
                        "trace subscriber failed"
                    )
        finally:
            self._notifying.active = False

    def span(self, name: str, /, _ctx: Optional[obs_context.SpanContext] = None,
             **meta):
        """Timed span, as a context manager. Yields the (mutable) meta
        dict so callers can annotate facts learned mid-span (the chosen
        coordinator, a batch size). Installs a child trace context for the
        duration, so spans recorded inside — including ones in tasks
        spawned inside (asyncio copies contextvars at task creation) —
        become children."""
        if not self.enabled:
            return contextlib.nullcontext(meta)
        return _Span(self, name,
                     _ctx if _ctx is not None else obs_context.child(), meta)

    def record(self, name: str, dur_ms: float, /,
               _ctx: Optional[obs_context.SpanContext] = None,
               _kind: str = "span", _t_end: Optional[float] = None,
               **meta) -> None:
        """Record a span that ends now, or, with `_t_end` (an instant of
        `time.perf_counter()`), one that ended then."""
        if not self.enabled:
            return
        ctx = _ctx if _ctx is not None else obs_context.current()
        tid, sid, pid = (
            (ctx.trace_id, ctx.span_id, ctx.parent_id) if ctx is not None
            else (None, None, None)
        )
        ts = time.time()
        if _t_end is None:
            _t_end = time.perf_counter()
        else:
            ts -= max(0.0, time.perf_counter() - _t_end)
        rec = SpanRecord(ts, name, dur_ms, meta, tid, sid, pid, _kind,
                         _t_end, threading.get_ident())
        with self._lock:
            self._events.append(rec)
        if self._subscribers:
            self._notify(rec)

    def event(self, name: str, /, **meta) -> None:
        """Zero-duration annotation attached to the ACTIVE trace (chaos
        injections, retry attempts, breaker transitions, attacks). Outside
        any trace the event is recorded unlinked rather than minting a
        one-event orphan trace."""
        if not self.enabled:
            return
        cur = obs_context.current()
        ctx = obs_context.child(cur) if cur is not None else None
        self.record(name, 0.0, _ctx=ctx, _kind="event", **meta)

    # ------------------------------------------------------------- reporting

    def events(self, name: str | None = None) -> list[SpanRecord]:
        with self._lock:
            evs = list(self._events)
        return [e for e in evs if name is None or e.name == name]

    def trace_events(self, trace_id: str) -> list[SpanRecord]:
        """All recorded spans/events of one trace, in record order."""
        with self._lock:
            evs = list(self._events)
        return [e for e in evs if e.trace_id == trace_id]

    def summary(self) -> dict[str, dict]:
        """Per-span-name {count, total_ms, mean_ms, p50_ms, p95_ms} over
        TIMED spans only: zero-duration events would deflate the means."""
        groups: dict[str, list[float]] = collections.defaultdict(list)
        for e in self.events():
            if e.kind == "span":
                groups[e.name].append(e.dur_ms)
        out = {}
        for name, durs in sorted(groups.items()):
            durs.sort()
            k = len(durs)
            out[name] = {
                "count": k,
                "total_ms": round(sum(durs), 3),
                "mean_ms": round(sum(durs) / k, 3),
                "p50_ms": round(_percentile(durs, 0.50), 3),
                "p95_ms": round(_percentile(durs, 0.95), 3),
            }
        return out

    @staticmethod
    def event_dict(e: SpanRecord) -> dict:
        """One JSON-safe record. Meta lives under its own "meta" key so a
        span recorded with meta named `name`/`ts`/`dur_ms` can never
        shadow the record fields."""
        rec = {"ts": e.ts, "name": e.name, "dur_ms": e.dur_ms, "kind": e.kind}
        if e.t_end is not None:
            # this process's perf_counter: places the span among its
            # neighbours of one incident file; never read across hosts
            rec["t_end"] = e.t_end
            rec["tid"] = e.tid
        if e.trace_id is not None:
            rec["trace_id"] = e.trace_id
            rec["span_id"] = e.span_id
            rec["parent_id"] = e.parent_id
        if e.meta:
            rec["meta"] = e.meta
        return rec

    def reset(self) -> None:
        with self._lock:
            self._events.clear()


def _default_tracer() -> Tracer:
    """Process-wide tracer, env-tunable: DDS_OBS_RING sizes the span ring
    (default 65536), DDS_OBS_TRACE=0 disables recording entirely."""
    try:
        ring = int(os.environ.get("DDS_OBS_RING", "65536"))
    except ValueError:
        ring = 65536
    enabled = os.environ.get("DDS_OBS_TRACE", "").strip().lower() not in (
        "0", "false", "off", "no",
    )
    return Tracer(max_events=max(16, ring), enabled=enabled)


# process-wide default tracer (subsystems import this)
tracer = _default_tracer()
