"""Shared timing helpers for the benchmark suite."""

from __future__ import annotations

import json
import time

# previous kprof snapshot, so each emitted record carries only ITS OWN
# kernel work (delta), not the whole run's cumulative totals
_kprof_prev: dict | None = None


def _kernel_delta() -> dict | None:
    """Kernel accounting since the last emit(): dispatch (trace+compile)
    vs execute ms and compile-cache hit rates from obs.kprof. None when no
    kernel ran in the window — pure-protocol benchmarks stay clean."""
    global _kprof_prev
    from dds_tpu.obs import kprof

    cur = kprof.kernel_summary()
    prev, _kprof_prev = _kprof_prev, cur
    # clamp at 0: span-ring eviction can shrink the cumulative totals the
    # summary is computed from on very long runs
    d = {
        "dispatch_ms": round(
            max(0.0, cur["dispatch_ms"] - (prev["dispatch_ms"] if prev else 0.0)), 3
        ),
        "execute_ms": round(
            max(0.0, cur["execute_ms"] - (prev["execute_ms"] if prev else 0.0)), 3
        ),
    }
    caches = {}
    for name, c in cur["compile_cache"].items():
        p = (prev or {}).get("compile_cache", {}).get(name, {})
        hits = max(0, c["hits"] - p.get("hits", 0))
        misses = max(0, c["misses"] - p.get("misses", 0))
        if hits or misses:
            caches[name] = {
                "hits": hits,
                "misses": misses,
                "hit_rate": round(hits / (hits + misses), 4),
            }
    if caches:
        d["compile_cache"] = caches
    if d["dispatch_ms"] or d["execute_ms"] or caches:
        return d
    return None


def best_of(fn, repeats: int = 3) -> float:
    """Min wall-clock seconds over `repeats` timed calls. All calls are
    timed — callers must warm/compile with an explicit untimed call first."""
    times = []
    for _ in range(repeats):
        t0 = time.perf_counter()
        fn()
        times.append(time.perf_counter() - t0)
    return min(times)


def sustained_device(dispatch, R: int = 16, repeats: int = 3) -> float:
    """Sustained per-dispatch seconds for a device computation.

    `dispatch()` must enqueue work and return a jax array WITHOUT fetching.
    Pipelines R dispatches on the device stream and fetches ONE device-side
    scalar combine, so the host<->device round trip is paid once per R
    dispatches — matching how a serving proxy overlaps aggregate
    dispatches. A blocking fetch per dispatch would add that round trip
    to every kernel.
    """
    import jax
    import numpy as np

    combine = jax.jit(lambda xs: sum(x.sum() for x in xs))

    def run():
        return np.asarray(combine([dispatch() for _ in range(R)]))

    run()  # warm/compile
    ts = []
    for _ in range(repeats):
        t0 = time.perf_counter()
        run()
        ts.append(time.perf_counter() - t0)
    return min(ts) / R


def emit(metric: str, value: float, unit: str, vs_baseline: float, **detail) -> dict:
    row = {
        "metric": metric,
        "value": round(value, 1),
        "unit": unit,
        "vs_baseline": round(vs_baseline, 3),
    }
    if detail:
        row["detail"] = detail
    try:
        kernel = _kernel_delta()
    except Exception:
        kernel = None  # telemetry must never fail a benchmark
    if kernel is not None:
        row["kernel"] = kernel
    try:
        # perf-regression sentry feed: persist per-kernel p50/p95
        # dispatch/execute stats into the baseline file (new kernels only
        # unless DDS_KERNEL_BASELINE_UPDATE; DDS_KERNEL_BASELINE="" turns
        # it off). benchmarks/sentry.py compares later runs against it.
        from dds_tpu.obs import sentry as _sentry

        _sentry.persist_from_tracer()
    except Exception:
        pass  # the baseline is telemetry too — never fail a benchmark
    print(json.dumps(row))
    return row
