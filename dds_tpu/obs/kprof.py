"""Kernel profiling hooks: dispatch(trace/compile) vs execute, cache hits.

"HEAAN Demystified" (arxiv 2003.04510) argues HE acceleration must start
from per-phase bottleneck accounting, and GPU HE accelerators (GME, arxiv
2309.11001) report compile-vs-execute splits per kernel. JAX hides the
boundary: calling a jitted fn returns as soon as the work is ENQUEUED
(having traced+compiled first on a cache miss), and only
`block_until_ready` waits for the device (the host's wait, which holds
the device's time plus queueing and the wake-up; device time proper
comes from a profiler trace). `profiled()` separates the two
into distinct tracer spans and metrics histograms; `cache_event`/`counted`
account compile-cache hits vs misses for the manual dict caches
(ops/foldmany) and `functools.lru_cache`d builders (ops/mont_mxu).

Cold calls split further: a compile-cache MISS (correlated by cache name,
or any miss landing during the dispatch window) marks the next
`profiled()` call for that kernel as a compile, and its host-side phase
records as `kernel.<name>.compile` INSTEAD of `.dispatch` — so dispatch
stats stay warm-only and Chronoscope's dispatch stage is never polluted
by one-time trace+compile time (which gets its own trace-compile stage).

Each phase is recorded at its own end (the tracer's rule): the host phase
at the instant the call returned, before `block_until_ready`, the execute
phase after it. A subscriber therefore never sees the dispatch phase
drawn over the execute phase.

`kernel.*.compile` counts the kernels' own builder caches and cannot see
XLA compilations made elsewhere (the pool's small gather and placement
programs). The first `profiled()` call therefore registers ONE
`jax.monitoring` listener for `backend_compile_duration`: every XLA
compilation of the process becomes an `xla.compile` span under the
request that was compiling, and counts in `dds_xla_compile_total`.

`kernel_summary()` condenses both for benchmark records
(benchmarks/common.emit attaches it to every row in results.json).
"""

from __future__ import annotations

import threading
import time

from dds_tpu.obs import context as obs_context
from dds_tpu.obs.metrics import metrics
from dds_tpu.utils.trace import tracer

__all__ = ["cache_event", "counted", "profiled", "kernel_summary", "reset",
           "watch_xla_compiles"]

_lock = threading.Lock()
_cache_stats: dict[str, list[int]] = {}  # cache name -> [hits, misses]
# cache names that missed since their last profiled() call: builder
# caches fire BEFORE the dispatch (the builder returns the jitted fn),
# so the miss is remembered until the matching kernel dispatches
_pending_compile: set[str] = set()
_xla_listener = False   # the jax.monitoring listener is registered once


def _on_jax_duration(name: str, seconds: float, **_kw) -> None:
    if not name.endswith("backend_compile_duration"):
        return
    cur = obs_context.current()
    tracer.record(
        "xla.compile", seconds * 1e3,
        _ctx=obs_context.child(cur) if cur is not None else None,
    )
    metrics.inc("dds_xla_compile_total",
                help="XLA compilations of this process, as jax reports them")


def watch_xla_compiles() -> None:
    """Register the listener, once. Lazily, by whoever is about to use
    jax (`profiled()`, a resident pool): replicas never import it, and
    jax keeps a listener for the life of the process."""
    global _xla_listener
    with _lock:
        if _xla_listener:
            return
        _xla_listener = True
    from jax import monitoring

    monitoring.register_event_duration_secs_listener(_on_jax_duration)


def cache_event(cache: str, hit: bool) -> None:
    """Record one compile-cache lookup (per kernel-builder cache)."""
    with _lock:
        s = _cache_stats.setdefault(cache, [0, 0])
        s[0 if hit else 1] += 1
        if not hit:
            _pending_compile.add(cache)
    metrics.inc(
        "dds_compile_cache_total", cache=cache,
        outcome="hit" if hit else "miss",
        help="kernel compile-cache lookups by outcome",
    )


def counted(cache: str, lru_fn, *args):
    """Call a `functools.lru_cache`d kernel builder, accounting the lookup
    as a compile-cache hit/miss via its cache_info miss delta."""
    before = lru_fn.cache_info().misses
    out = lru_fn(*args)
    cache_event(cache, hit=lru_fn.cache_info().misses == before)
    return out


def profiled(kernel: str, dispatch, **meta):
    """Run `dispatch()` (enqueue device work, return jax arrays) and time
    its two phases separately: the host-side call and the host's wait
    in `block_until_ready`. A cold call — its builder cache
    missed (by name) since the last dispatch, or any cache miss landed
    DURING the dispatch window — records its host phase as
    `kernel.<name>.compile`; warm calls record `.dispatch`. Both pair
    with `kernel.<name>.execute` spans plus metrics histograms; returns
    the (ready) dispatch result."""
    import jax

    if not _xla_listener:
        watch_xla_compiles()
    with _lock:
        compiled = kernel in _pending_compile
        _pending_compile.discard(kernel)
        misses0 = sum(m for _, m in _cache_stats.values())
    t0 = time.perf_counter()
    out = dispatch()
    t1 = time.perf_counter()
    with _lock:
        compiled = compiled or (
            sum(m for _, m in _cache_stats.values()) > misses0
        )
    # fresh child contexts: each phase record is its own span in the
    # trace tree, not a clone of the enclosing span's identity
    cur = obs_context.current()
    phase = "compile" if compiled else "dispatch"
    tracer.record(
        f"kernel.{kernel}.{phase}", (t1 - t0) * 1e3, _t_end=t1,
        _ctx=obs_context.child(cur) if cur is not None else None, **meta,
    )
    jax.block_until_ready(out)
    t2 = time.perf_counter()
    tracer.record(
        f"kernel.{kernel}.execute", (t2 - t1) * 1e3, _t_end=t2,
        _ctx=obs_context.child(cur) if cur is not None else None, **meta,
    )
    if compiled:
        metrics.observe(
            "dds_kernel_compile_seconds", t1 - t0, kernel=kernel,
            help="host-side trace+compile time on compile-cache misses",
        )
    else:
        metrics.observe(
            "dds_kernel_dispatch_seconds", t1 - t0, kernel=kernel,
            help="host-side dispatch time (warm calls only; cold calls "
                 "record dds_kernel_compile_seconds)",
        )
    metrics.observe(
        "dds_kernel_execute_seconds", t2 - t1, kernel=kernel,
        help="host wait in block_until_ready after the dispatch returned "
             "(queueing, execution and the wake-up; not device time)",
    )
    return out


def kernel_summary() -> dict:
    """{spans, compile_cache, dispatch_ms, execute_ms, compile_ms} over
    kernel.* spans recorded so far — the per-record accounting
    benchmarks attach."""
    spans = {
        name: stats
        for name, stats in tracer.summary().items()
        if name.startswith("kernel.")
    }
    with _lock:
        caches = {
            name: {
                "hits": h,
                "misses": m,
                "hit_rate": round(h / (h + m), 4) if h + m else None,
            }
            for name, (h, m) in sorted(_cache_stats.items())
        }
    dispatch_ms = sum(
        s["total_ms"] for n, s in spans.items() if n.endswith(".dispatch")
    )
    execute_ms = sum(
        s["total_ms"] for n, s in spans.items() if n.endswith(".execute")
    )
    compile_ms = sum(
        s["total_ms"] for n, s in spans.items() if n.endswith(".compile")
    )
    return {
        "spans": spans,
        "compile_cache": caches,
        "dispatch_ms": round(dispatch_ms, 3),
        "execute_ms": round(execute_ms, 3),
        "compile_ms": round(compile_ms, 3),
    }


def reset() -> None:
    with _lock:
        _cache_stats.clear()
        _pending_compile.clear()
