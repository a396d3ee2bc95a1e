"""Host runtime probes: the event loop's lag, the collector's pauses, a stall.

Replicas, proxy and (in a benchmark) clients share one event loop and one
heap, so a request's latency is often not its own work: a coroutine that is
ready waits while another callback holds the loop, and any allocation can
start a collection that walks every container alive. Neither shows in a
request's span tree. Three probes make them visible, as spans placed at
their true ends (`utils/trace`'s rule), as metrics, and as an incident:

- `LoopSampler` sleeps `TICK` seconds on the loop, over and over. What the
  sleep overshoots is time in which the loop ran one callback and could
  run nothing else: every overshoot lands in histogram
  `dds_event_loop_lag_seconds`, and one of `BLOCKED_S` or more also as a
  `runtime.loop_blocked` span from the instant the timer was due to the
  instant it ran.
- `install_gc` hooks `gc.callbacks`. A collection can start inside any
  allocation, also while this thread holds the tracer's or the registry's
  lock, so the callback takes no lock and calls neither: it appends
  `(t_end, seconds, generation, collected)` to a deque, and the sampler
  drains that into counter `dds_gc_pause_seconds_total{generation}` (every
  pause) and into `runtime.gc` spans (generation 2, and any pause of
  `GC_SPAN_S` or more).
- a daemon thread beside the sampler watches the heartbeat the sampler
  stamps. A loop silent for `STALL_S` gets one warning with the loop
  thread's stack and the innermost frames of every other thread, at most
  once in `STALL_REPORT_EVERY` seconds, and a `loop_stall` flight
  incident when the recorder is configured.

Runtime spans belong to no request: they are recorded unlinked (no trace
id), like an event outside any trace.
"""

from __future__ import annotations

import asyncio
import collections
import gc
import logging
import sys
import threading
import time
import traceback

from dds_tpu.obs.metrics import metrics
from dds_tpu.utils.trace import tracer

__all__ = ["LoopSampler", "install_gc", "remove_gc", "drain_gc"]

log = logging.getLogger("dds.runtime")

TICK = 0.02                 # the sampler's sleep
BLOCKED_S = 0.010           # an overshoot this long becomes a span
GC_SPAN_S = 0.001           # so does a collector pause this long
STALL_S = 1.0               # a loop silent this long is a stall
STALL_REPORT_EVERY = 60.0   # seconds between two stall reports

# ------------------------------------------------------------- the collector

_gc_pauses: collections.deque = collections.deque(maxlen=4096)
_gc_started = 0.0


def _on_gc(phase: str, info: dict) -> None:
    # no lock, no tracer, no registry: see the module docstring. One
    # collection runs at a time in a process, so one start time is enough.
    global _gc_started
    if phase == "start":
        _gc_started = time.perf_counter()
    else:
        t = time.perf_counter()
        _gc_pauses.append((t, t - _gc_started, info.get("generation", -1),
                           info.get("collected", 0)))


def install_gc() -> None:
    if _on_gc not in gc.callbacks:
        gc.callbacks.append(_on_gc)


def remove_gc() -> None:
    if _on_gc in gc.callbacks:
        gc.callbacks.remove(_on_gc)
    drain_gc()


def drain_gc() -> None:
    """Turn the pauses noted since the last call into metrics and spans."""
    while True:
        try:
            t_end, seconds, generation, collected = _gc_pauses.popleft()
        except IndexError:
            return
        metrics.inc(
            "dds_gc_pause_seconds_total", seconds,
            generation=str(generation),
            help="seconds the collector held the process, by generation",
        )
        if generation == 2 or seconds >= GC_SPAN_S:
            tracer.record("runtime.gc", seconds * 1e3, _t_end=t_end,
                          generation=generation, collected=collected)


# ------------------------------------------------------------ the event loop


class LoopSampler:
    """The loop's lag, the drain of the collector's pauses, and the stall
    watchdog, for the loop `start()` is called on."""

    def __init__(self, tick: float = TICK, stall_s: float = STALL_S,
                 report_every: float = STALL_REPORT_EVERY):
        self.tick, self.stall_s, self.report_every = tick, stall_s, report_every
        self.stalls_reported = 0
        self._task: asyncio.Task | None = None
        self._watchdog: threading.Thread | None = None
        self._halt = threading.Event()
        self._beat = 0.0
        self._loop_tid = 0

    def start(self) -> None:
        from dds_tpu.utils.tasks import supervised_task

        self._beat = time.perf_counter()
        self._loop_tid = threading.get_ident()
        self._halt.clear()
        self._task = supervised_task(self._sample(),
                                     name="runtime.loop_sampler")
        self._watchdog = threading.Thread(
            target=self._watch, name="dds-loop-watchdog", daemon=True)
        self._watchdog.start()

    async def stop(self) -> None:
        self._halt.set()
        if self._task is not None:
            self._task.cancel()
            try:
                await self._task
            except asyncio.CancelledError:
                pass
            self._task = None
        if self._watchdog is not None:
            self._watchdog.join(timeout=2.0)
            self._watchdog = None
        drain_gc()

    async def _sample(self) -> None:
        tick = self.tick
        while True:
            self._beat = time.perf_counter()
            due = self._beat + tick
            await asyncio.sleep(tick)
            now = time.perf_counter()
            late = max(0.0, now - due)
            metrics.observe(
                "dds_event_loop_lag_seconds", late,
                help="how late a 20 ms timer ran: the loop was held by "
                     "one callback for that long",
            )
            if late >= BLOCKED_S:
                tracer.record("runtime.loop_blocked", late * 1e3, _t_end=now)
            drain_gc()

    # ------------------------------------------------------------- a stall

    def _watch(self) -> None:
        last_report = -self.report_every
        poll = min(0.1, self.stall_s / 10)
        while not self._halt.wait(poll):
            now = time.perf_counter()
            silent = now - self._beat - self.tick
            if silent < self.stall_s or now - last_report < self.report_every:
                continue
            last_report = now
            self._report_stall(silent)

    def _report_stall(self, silent: float) -> None:
        # the loop's thread first; the others after it, because a loop
        # found waiting in `select` is waiting for the interpreter lock,
        # and one of them holds it
        frames = sys._current_frames()
        frames.pop(threading.get_ident(), None)
        names = {t.ident: t.name for t in threading.enumerate()}
        frame = frames.pop(self._loop_tid, None)
        stack = ("".join(traceback.format_stack(frame)) if frame is not None
                 else "(the loop's thread is gone)\n")
        for tid, other in frames.items():
            stack += (f"thread {names.get(tid, tid)}:\n"
                      + "".join(traceback.format_stack(other)[-6:]))
        self.stalls_reported += 1
        metrics.inc("dds_event_loop_stalls_total",
                    help="stalls of the event loop reported by the watchdog")
        log.warning("event loop silent for %.2f s; its thread is at:\n%s",
                    silent, stack)
        from dds_tpu.obs.flight import flight

        if flight.enabled:
            try:
                flight.record("loop_stall", silent_s=round(silent, 3),
                              stack=stack)
            except Exception:  # noqa: BLE001 — telemetry never breaks serving
                log.exception("loop_stall incident write failed")
