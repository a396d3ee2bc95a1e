"""Trace-context propagation: the causal spine of Telescope.

A `SpanContext` names one node of a distributed trace: `(trace_id,
span_id, parent_id)`. The REST edge mints a root context per request
(`http/server.py handle`); every `tracer.span(...)` below it derives a
child and installs it in a `contextvars.ContextVar`, so nested spans link
parent->child without threading a parameter through 23 routes, the quorum
client, and the replica protocol handlers.

Cross-task propagation is free in-process: `asyncio.ensure_future` copies
the caller's contextvars at task-creation time, so a replica handler
scheduled by `InMemoryNet.send` (or a ChaosNet-deferred delivery) runs
under the quorum round's span context and its spans slot into the same
tree. Across a `TcpNet` hop the context travels as a tiny `tc` frame
field (`to_wire`/`from_wire`) — observability metadata only, deliberately
OUTSIDE the frame MAC/signature: a forged trace id can mislabel telemetry,
never affect protocol decisions.

Ids are 64-bit random hex (8 bytes), the W3C traceparent sizing halved —
collision-safe for a per-process ring of 64k spans.
"""

from __future__ import annotations

import contextvars
import random
from dataclasses import dataclass
from typing import Optional

__all__ = [
    "SpanContext", "current", "root", "child", "attach", "detach",
    "new_id", "to_wire", "from_wire", "from_header", "to_header",
]


@dataclass(frozen=True)
class SpanContext:
    trace_id: str
    span_id: str
    parent_id: Optional[str] = None


_current: contextvars.ContextVar[Optional[SpanContext]] = contextvars.ContextVar(
    "dds_span_context", default=None
)


def new_id() -> str:
    # telemetry labels, not secrets: the module's generator (seeded from
    # the OS, reseeded in a forked child) costs a third of an OS read
    return f"{random.getrandbits(64):016x}"


def current() -> Optional[SpanContext]:
    """The active span context of this task, or None outside any trace."""
    return _current.get()


def root() -> SpanContext:
    """Mint a fresh trace root (the REST edge, or a background job)."""
    return SpanContext(new_id(), new_id(), None)


def child(parent: Optional[SpanContext] = None) -> SpanContext:
    """A child of `parent` (default: the current context). With no parent
    anywhere, starts a fresh root — spans recorded outside a request still
    get ids, they just form single-span traces."""
    p = parent if parent is not None else _current.get()
    if p is None:
        return root()
    return SpanContext(p.trace_id, new_id(), p.span_id)


def attach(ctx: Optional[SpanContext]) -> contextvars.Token:
    return _current.set(ctx)


def detach(token: contextvars.Token) -> None:
    _current.reset(token)


# ------------------------------------------------------------------- wire

def to_wire(ctx: Optional[SpanContext] = None) -> Optional[dict]:
    """Compact dict for a transport frame (None = nothing to propagate).
    Carries (trace, span) of the SENDER's active span; the receiver's
    spans become its children."""
    ctx = ctx if ctx is not None else _current.get()
    if ctx is None:
        return None
    return {"t": ctx.trace_id, "s": ctx.span_id}


def _valid_id(v) -> bool:
    """Ids we mint are 16 lowercase-hex chars; accept up to 32 (the W3C
    traceparent width) so foreign tracers can interop, but ONLY hex — the
    `tc` field is unauthenticated, and these strings end up as collector
    dict keys, metric labels, and flight-incident headers."""
    return (isinstance(v, str) and 0 < len(v) <= 32
            and all(c in "0123456789abcdef" for c in v))


def from_wire(d) -> Optional[SpanContext]:
    """Parse a frame's `tc` field; garbage (or absence) degrades to None —
    a malformed trace context must never drop the message it rode on.
    Strict length/charset clamp: a hostile peer's oversized or non-hex
    ids are refused wholesale (the span orphans into a fresh local root)
    instead of truncated into a colliding-but-plausible id that would
    poison cross-host stitching."""
    if d is None:
        return None
    if not isinstance(d, dict):
        return _malformed()
    t, s = d.get("t"), d.get("s")
    if not _valid_id(t) or not _valid_id(s):
        return _malformed()
    return SpanContext(t, s)


def _malformed() -> None:
    """Present garbage (vs. absent context): count it so a peer spraying
    hostile `tc` fields is visible on /metrics."""
    from dds_tpu.obs.metrics import metrics  # lazy: avoid import cycle

    metrics.inc("dds_trace_context_malformed_total",
                help="hostile/garbled tc frame fields dropped at ingest")
    return None


# ----------------------------------------------------------------- header

def to_header(ctx: Optional[SpanContext] = None) -> str:
    """`x-dds-trace` header value ("trace_id-span_id"), "" when none."""
    ctx = ctx if ctx is not None else _current.get()
    return f"{ctx.trace_id}-{ctx.span_id}" if ctx is not None else ""


def from_header(value: str) -> Optional[SpanContext]:
    """Parse an inbound `x-dds-trace` header so an upstream caller (a
    gossiping peer proxy, a load-test harness) can stitch its trace onto
    this process's spans. Malformed values degrade to None (fresh root)."""
    if not value or "-" not in value:
        return None
    t, _, s = value.partition("-")
    t, s = t.strip(), s.strip()
    if not t or not s or len(t) > 32 or len(s) > 32:
        return None
    return SpanContext(t, s)
