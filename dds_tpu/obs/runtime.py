"""Host runtime probes: the event loop's lag, the collector's pauses, a stall.

Replicas, proxy and (in a benchmark) clients share one event loop and one
heap, so a request's latency is often not its own work: a coroutine that is
ready waits while another callback holds the loop, and any allocation can
start a collection that walks every container alive. Neither shows in a
request's span tree. Four probes make them visible, as spans placed at
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
- the loop's ledger (`_Ledger`, installed by the sampler while
  `tracer.enabled`): every instant of the loop's thread between `start()`
  and `stop()` goes to exactly one tenant of `TENANTS`, counted where the
  loop runs its callbacks. It leans on two private names of asyncio,
  `asyncio.events.Handle._run` (wrapped for the process, acting only on
  handles of the sampler's loop) and `BaseEventLoop._selector` (its
  `select` wrapped on the instance); where either is absent (another
  loop implementation, another Python) the ledger is not installed and
  everything else here works as before. This module is the only place
  that touches the loop's internals.

Runtime spans belong to no request: they are recorded unlinked (no trace
id), like an event outside any trace.
"""

from __future__ import annotations

import asyncio
import collections
import gc
import logging
import os
import random
import sys
import threading
import time
import traceback

from dds_tpu.obs.metrics import metrics
from dds_tpu.utils.trace import tracer

__all__ = ["LoopSampler", "TENANTS", "LEDGER_SERIES", "install_gc",
           "remove_gc", "drain_gc"]

log = logging.getLogger("dds.runtime")

TICK = 0.02                 # the sampler's sleep
BLOCKED_S = 0.010           # an overshoot this long becomes a span
GC_SPAN_S = 0.001           # so does a collector pause this long
STALL_S = 1.0               # a loop silent this long is a stall
STALL_REPORT_EVERY = 60.0   # seconds between two stall reports
LEDGER_BUSY = 8             # polls in a row after which the loop is under load:
LEDGER_EVERY = 8            # the ledger then books the callbacks of one pass of
                            # the loop in this many, and scales (see _Ledger)
LEDGER_SPLIT = 8            # such passes it gathers before it does

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


# --------------------------------------------------------- the loop's ledger

# The tenants, and how a task's name says which one it is. Closed: a task
# this table does not know is `background` or `foreign` by where its
# coroutine's code lives, never a new label value.
TENANTS = ("idle", "loop", "socket", "request", "replica", "proxy_inbox",
           "supervisor", "transport", "background", "foreign")
(_IDLE, _LOOP, _SOCKET, _REQUEST, _REPLICA, _PROXY_INBOX, _SUPERVISOR,
 _TRANSPORT, _BACKGROUND, _FOREIGN) = range(len(TENANTS))
# a named task, by its name up to the first ":"
_TASK_TENANT = {
    "http.conn": _REQUEST,       # http/miniserver: one REST connection
    "tcp.send": _TRANSPORT,      # core/transport.TcpNet: one frame out
    "tcp.serve": _TRANSPORT,     # ... and one inbound connection's frames
    "tcp.watch": _TRANSPORT,     # ... and the wait for an outbound one's end
}
# a delivery task runs an endpoint's handler: the rest of its name is the
# endpoint, whose role is the first of these words its name holds
_DELIVERY = ("inmem.deliver", "tcp.handle")
_ENDPOINT_TENANT = (("replica", _REPLICA), ("supervisor", _SUPERVISOR),
                    ("proxy", _PROXY_INBOX))
# any other task, by its coroutine's file: an unnamed one of `http/` is a
# request's own child (`asyncio.gather` inside a route), the rest of the
# package is `background`, everything else (a load generator) `foreign`
_PKG = os.path.dirname(os.path.dirname(os.path.abspath(__file__))) + os.sep
_HTTP = os.path.join(_PKG, "http") + os.sep

# the three counters, each by `tenant`
_SERIES = (
    ("dds_event_loop_seconds_total",
     "seconds of the event loop's thread by tenant, idle included: over "
     "any stretch they sum to its length"),
    ("dds_event_loop_callbacks_total",
     "handles the event loop ran, by tenant"),
    ("dds_event_loop_ready_wait_seconds_total",
     "seconds callbacks waited behind those run before them in the same "
     "pass of the loop, by tenant (a lower bound of their time in the "
     "ready queue)"),
)
LEDGER_SERIES = tuple(name for name, _help in _SERIES)

_ledger: "_Ledger | None" = None   # one a process: the first sampler's


class _Ledger:
    """Who holds the event loop: seconds, callbacks run and ready-wait by
    tenant, for the loop it is installed on.

    The selector's `select` is wrapped with two clock reads a pass of the
    loop, so `idle`, the polls, every pass's busy time and the count of
    passes are exact. `Handle._run` is wrapped too, and in a booked pass
    it takes one read a callback: a callback's time runs from the previous
    read to its own (the pass's first callback takes one more, which keeps
    `_run_once`'s bookkeeping out of it), so a booked pass's instants add
    up to its length. Booking every callback cost 7 % of an aggregate on a
    loop that is never idle (PERF.md, PR 39), and nothing that matters on
    one that waits. So every pass is booked while the loop has headroom
    (fewer than `LEDGER_BUSY` polls, `select(0)`, in a row since it last
    waited), and what it books then is exact. Under load one pass in
    `LEDGER_EVERY` is booked (1.5 to 2 % of an aggregate); in the others
    the wrapper only passes the call on. `flush` splits what those used
    among the tenants as the passes booked under load since the last split
    shared theirs, and scales their callbacks and ready-wait by passes over
    booked passes: under load a tenant's figures are an estimate from one
    pass in `LEDGER_EVERY`, and the tenants still sum to the wall time. The
    gaps between booked passes are drawn (1 to 2 x `LEDGER_EVERY` - 1): a
    fixed stride books a loop whose passes repeat at one phase of its
    period for ever. The tenants:

    - `idle`: inside `select` called with a timeout that is not zero.
      It includes a wake-up that waited for the interpreter lock held by
      a worker thread (`asyncio.to_thread` in the proxy's `_fold`);
    - `loop`: `select(0)` polls, `_run_once`'s own bookkeeping, and
      callbacks that belong to no task and no transport (timers, the
      wake-up of a future nobody's task waits for, the self-pipe);
    - `socket`: callbacks of transports, protocols and the loop's own
      accept / `sock_*` handlers;
    - a task's steps, its wake-ups and its done-callbacks: the tenant
      its name gives (`_TASK_TENANT`, `_ENDPOINT_TENANT`), else
      `background` or `foreign` by its coroutine's file.

    A collector pause falls to the tenant whose allocation started it;
    so does the time a callback waited to get the interpreter lock back.
    Ready-wait is, for each callback, from its pass's `select` return to
    its own start: what it waited behind the callbacks run before it in
    the same pass, a lower bound of its time in the ready queue (a
    callback made ready during a pass waits for the next one unseen).
    The per-callback path takes no lock and touches no registry: sums
    live in plain lists, and `flush` (the sampler's tick) moves them to
    `obs/metrics`.
    """

    def __init__(self, loop):
        self.loop = loop
        n = len(TENANTS)
        # seconds, callbacks and ready-wait by tenant: of `idle`, the polls
        # and the passes booked while the loop had headroom, which are
        # exact; and of the passes booked under load, which are a sample
        self.exact = ([0.0] * n, [0] * n, [0.0] * n)
        self.sample = ([0.0] * n, [0] * n, [0.0] * n)
        self._names: dict[str, int] = {}    # a task's name -> its tenant
        # set by `install`: closures over the wrappers' own state
        self.take_longest = self._take_rest = self._restore = None

    # ------------------------------------------------------ whose it is

    def _task_tenant(self, task) -> int:
        name = task.get_name()
        tenant = self._names.get(name)
        if tenant is not None:
            return tenant
        head, _, rest = name.partition(":")
        tenant = _TASK_TENANT.get(head)
        if tenant is None and head in _DELIVERY:
            endpoint = rest.rsplit("/", 1)[-1]
            tenant = next((t for word, t in _ENDPOINT_TENANT
                           if word in endpoint), _BACKGROUND)
        if tenant is None:
            coro = task.get_coro()
            code = (getattr(coro, "cr_code", None)
                    or getattr(coro, "gi_code", None)
                    or getattr(coro, "ag_code", None))
            file = getattr(code, "co_filename", "")
            tenant = (_FOREIGN if not file.startswith(_PKG)
                      else _REQUEST if (file.startswith(_HTTP)
                                        and name.startswith("Task-"))
                      else _BACKGROUND)
        # kept by name, default names too: a long-lived unnamed task (a
        # load generator's client) is then one dict probe a step
        if len(self._names) >= 4096:
            self._names.clear()
        self._names[name] = tenant
        return tenant

    def tenant(self, handle) -> int:
        owner = getattr(handle._callback, "__self__", None)
        if hasattr(owner, "get_coro"):   # a task's step or wake-up
            return self._task_tenant(owner)
        if isinstance(owner, (asyncio.BaseTransport, asyncio.BaseProtocol)):
            return _SOCKET
        if owner is self.loop and handle._callback.__name__.startswith(
                ("_accept", "_sock")):
            return _SOCKET
        args = handle._args    # a task's done-callback is the task's
        if args and hasattr(args[0], "get_coro"):
            return self._task_tenant(args[0])
        return _LOOP

    @staticmethod
    def label(handle) -> str:
        """The task's name, or the callback's qualified name."""
        cb = handle._callback
        owner = getattr(cb, "__self__", None)
        if hasattr(owner, "get_coro"):
            return owner.get_name()[:64]
        return (getattr(cb, "__qualname__", None) or type(cb).__name__)[:64]

    # ------------------------------------------------- on and off the loop

    def install(self) -> bool:
        """Wrap `Handle._run` and the selector's `select`; False, and
        nothing touched, where either private name is absent."""
        loop = self.loop
        selector = getattr(loop, "_selector", None)
        run = getattr(asyncio.events.Handle, "_run", None)
        if run is None or not callable(getattr(selector, "select", None)) \
                or not hasattr(selector, "__dict__"):
            return False
        select = selector.select
        exact, sample = self.exact, self.sample
        seconds, callbacks, waits = exact    # where a booked pass books
        tenant, clock = self.tenant, time.perf_counter
        last = woke = clock()   # the previous read; the last select's return
        booked = first = False  # this pass is booked; no callback of it ran yet
        polls = 0               # polls in a row since the loop last waited
        # passes under load until the next booked one: 1 to 2 * LEDGER_EVERY
        # - 1, drawn, because a fixed stride books a loop whose passes repeat
        # (one client's requests, a timer's tick) at one phase of it for ever
        until, draw = 1, random.Random(0).random
        rest = 0.0              # what the passes not booked used
        passes = booked_passes = 0   # under load
        longest, holder = 0.0, None

        def _run(handle):
            nonlocal last, first, longest, holder
            if not booked or handle._loop is not loop:
                return run(handle)
            if first:
                first = False
                t = clock()
                seconds[_LOOP] += t - last
                last = t
            run(handle)
            t = clock()
            dt = t - last
            if dt > longest:
                longest, holder = dt, handle
            i = tenant(handle)
            seconds[i] += dt
            callbacks[i] += 1
            waits[i] += last - woke
            last = t

        def _select(timeout=None):
            nonlocal last, woke, booked, first, polls, until, rest
            nonlocal passes, booked_passes, seconds, callbacks, waits
            t0 = clock()
            if booked:
                seconds[_LOOP] += t0 - last
            else:
                rest += t0 - woke
            events = select(timeout)
            last = woke = clock()
            if timeout is not None and timeout <= 0:
                exact[0][_LOOP] += woke - t0
                polls += 1
            else:
                exact[0][_IDLE] += woke - t0
                polls = 0
            if polls < LEDGER_BUSY:
                booked = first = True
                seconds, callbacks, waits = exact
            else:
                passes += 1
                until -= 1
                booked = first = until == 0
                if booked:
                    until = 1 + int(draw() * (2 * LEDGER_EVERY - 1))
                    booked_passes += 1
                    seconds, callbacks, waits = sample
            return events

        def take_longest():
            """The longest booked callback since the last call, and its
            handle."""
            nonlocal longest, holder
            got = longest, holder
            longest, holder = 0.0, None
            return got

        def take_rest(at_least):
            """Under load: seconds of the passes not booked, passes and
            booked passes since the last time it gave them; None, and
            nothing taken, under `at_least` booked passes."""
            nonlocal rest, passes, booked_passes
            if booked_passes < at_least:
                return None
            got = rest, passes, booked_passes
            rest, passes, booked_passes = 0.0, 0, 0
            return got

        def restore():
            nonlocal rest
            # the callback that stops the ledger is still running: what
            # it has used so far is its task's
            if booked:
                task = asyncio.current_task(loop)
                seconds[self._task_tenant(task) if task is not None
                        else _LOOP] += clock() - last
            else:
                rest += clock() - woke
            if asyncio.events.Handle._run is _run:
                asyncio.events.Handle._run = run
            if selector.__dict__.get("select") is _select:
                del selector.select

        asyncio.events.Handle._run = _run
        selector.select = _select
        self.take_longest, self._take_rest, self._restore = (
            take_longest, take_rest, restore)
        return True

    def remove(self) -> None:
        if self._restore is not None:
            self._restore()
            self._restore = None
            self.flush(last=True)

    def flush(self, last: bool = False) -> None:
        """Move what the lists hold to the three counters. What is exact
        goes at every call (the sampler's tick). The sums of the passes
        booked under load wait until `LEDGER_SPLIT` of them have gathered
        (0.04 to 0.1 s): what the passes not booked used since is then
        split as those split theirs, and their callbacks and ready-wait
        are scaled by passes over booked passes. The last call splits
        whatever there is, and with no booked pass gives it to `loop`."""
        for series, values in enumerate(self.exact):
            self._emit(series, values)
        got = self._take_rest(0 if last else LEDGER_SPLIT)
        if got is None:
            return
        rest, passes, booked_passes = got
        seconds, callbacks, waits = self.sample
        booked_s = sum(seconds)
        if booked_s > 0:
            by_n = passes / booked_passes if booked_passes else 1.0
            self._emit(0, seconds, 1 + rest / booked_s)
            self._emit(1, callbacks, by_n)
            self._emit(2, waits, by_n)
        elif rest:
            seconds[_LOOP] = rest
            self._emit(0, seconds)

    @staticmethod
    def _emit(series: int, values: list, by: float = 1.0) -> None:
        name, help = _SERIES[series]
        for i, value in enumerate(values):
            if value:
                metrics.inc(name, value * by, help=help, tenant=TENANTS[i])
                values[i] = 0


# ------------------------------------------------------------ the event loop


class LoopSampler:
    """The loop's lag, its ledger, the drain of the collector's pauses, and
    the stall watchdog, for the loop `start()` is called on."""

    def __init__(self, tick: float = TICK, stall_s: float = STALL_S,
                 report_every: float = STALL_REPORT_EVERY):
        self.tick, self.stall_s, self.report_every = tick, stall_s, report_every
        self.stalls_reported = 0
        self._task: asyncio.Task | None = None
        self._watchdog: threading.Thread | None = None
        self._halt = threading.Event()
        self._beat = 0.0
        self._loop_tid = 0
        self._ledger: _Ledger | None = None

    def start(self) -> None:
        global _ledger
        from dds_tpu.utils.tasks import supervised_task

        self._beat = time.perf_counter()
        self._loop_tid = threading.get_ident()
        self._halt.clear()
        if tracer.enabled and _ledger is None:
            ledger = _Ledger(asyncio.get_running_loop())
            if ledger.install():
                self._ledger = _ledger = ledger
        self._task = supervised_task(self._sample(),
                                     name="runtime.loop_sampler")
        self._watchdog = threading.Thread(
            target=self._watch, name="dds-loop-watchdog", daemon=True)
        self._watchdog.start()

    async def stop(self) -> None:
        global _ledger
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
        if self._ledger is not None:
            self._ledger.remove()
            self._ledger = _ledger = None
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
            held = {}
            if self._ledger is not None:
                # the longest booked callback since the last tick is the
                # holder if it ran most of the late stretch; a stretch of
                # a pass that was not booked names nobody
                seconds, handle = self._ledger.take_longest()
                if late >= BLOCKED_S and seconds >= late / 2:
                    held = self._held(self._ledger.tenant(handle),
                                      self._ledger.label(handle), seconds)
                self._ledger.flush()
            if late >= BLOCKED_S:
                tracer.record("runtime.loop_blocked", late * 1e3, _t_end=now,
                              **held)
            drain_gc()

    @staticmethod
    def _held(tenant: int, task: str, seconds: float) -> dict:
        return {"held_by": TENANTS[tenant], "task": task,
                "held_ms": round(seconds * 1e3, 3)}

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
        held = "no ledger"
        if self._ledger is not None:
            # the task whose step holds the loop, asked from this thread
            task = asyncio.current_task(self._ledger.loop)
            held = ("no task's step: in select (idle, or waiting for the "
                    "interpreter lock) or in a plain callback"
                    if task is None else
                    "held_by=%(held_by)s task=%(task)s held_ms=%(held_ms)s"
                    % self._held(self._ledger._task_tenant(task),
                                 task.get_name()[:64], silent))
        log.warning("event loop silent for %.2f s (%s); its thread is at:\n%s",
                    silent, held, stack)
        from dds_tpu.obs.flight import flight

        if flight.enabled:
            try:
                flight.record("loop_stall", silent_s=round(silent, 3),
                              stack=stack)
            except Exception:  # noqa: BLE001 — telemetry never breaks serving
                log.exception("loop_stall incident write failed")
