"""Spans placed at their true ends, inside the aggregate, round the thread
hop and on the event loop (utils/trace, obs/kprof, obs/chronoscope,
obs/runtime, http/server, resident/pool)."""

import asyncio
import gc
import json
import logging
import threading
import time

import pytest

from dds_tpu.obs import kprof, runtime
from dds_tpu.obs.chronoscope import STAGES, classify, critical_path
from dds_tpu.obs.metrics import metrics
from dds_tpu.utils.trace import Tracer, tracer

pytestmark = pytest.mark.obs


# ------------------------------------------------------------- one clock


def test_a_record_carries_its_end_on_perf_counter_and_its_thread():
    t = Tracer()
    before = time.perf_counter()
    with t.span("a"):
        time.sleep(0.002)
    after = time.perf_counter()
    (rec,) = t.events()
    assert before <= rec.t_end - rec.dur_ms / 1e3 <= rec.t_end <= after
    assert rec.tid == threading.get_ident()
    assert rec.end == rec.t_end
    d = json.loads(json.dumps(Tracer.event_dict(rec)))
    assert d["t_end"] == rec.t_end and d["tid"] == rec.tid


def test_a_span_measured_after_the_fact_is_set_back_on_both_clocks():
    t = Tracer()
    seen = []
    t.subscribe(seen.append)
    t_done = time.perf_counter()
    wall_done = time.time()
    time.sleep(0.03)
    t.record("late", 5.0, _t_end=t_done)
    t.record("now", 5.0)
    late, now = t.events()
    assert late.t_end == t_done
    assert abs(late.ts - wall_done) < 0.01      # not 30 ms after the fact
    assert now.t_end >= t_done + 0.03 and now.ts >= wall_done + 0.029
    assert [r.name for r in seen] == ["late", "now"]


def test_a_record_rebuilt_from_the_wire_is_placed_by_its_wall_clock():
    from dds_tpu.obs.panopticon import record_from_dict

    t = Tracer()
    t.record("shipped", 4.0)
    (rec,) = t.events()
    far = record_from_dict(Tracer.event_dict(rec))
    # another process's perf_counter means nothing here
    assert far.t_end is None and far.end == rec.ts


# ------------------------------------------------- kprof: each phase's end


def test_profiled_records_the_dispatch_phase_before_the_execute_phase():
    import jax.numpy as jnp

    seen = []
    tracer.subscribe(seen.append)
    try:
        with tracer.span("kernel.fold") as _:
            kprof.profiled("tracing.test", lambda: jnp.arange(8) * 2)
    finally:
        tracer.unsubscribe(seen.append)
    names = [r.name for r in seen]
    i = names.index("kernel.tracing.test.execute")
    host = seen[i - 1]
    assert host.name in ("kernel.tracing.test.dispatch",
                         "kernel.tracing.test.compile")
    execute = seen[i]
    # each was notified at its own end: the host phase ends where the
    # execute phase starts, and neither is drawn over the other
    assert host.t_end <= execute.t_end
    assert abs((execute.t_end - execute.dur_ms / 1e3) - host.t_end) < 1e-4
    res = critical_path([r for r in seen
                         if r.trace_id == execute.trace_id])
    by = {p["name"]: p for p in res["path"]}
    h, e = by[host.name], by[execute.name]
    assert h["start_ms"] + h["dur_ms"] <= e["start_ms"] + 1e-3
    assert h["dur_ms"] == pytest.approx(host.dur_ms, abs=0.01)
    assert e["dur_ms"] == pytest.approx(execute.dur_ms, abs=0.01)


def test_every_xla_compilation_becomes_a_span_and_a_count():
    import jax
    import jax.numpy as jnp

    kprof.watch_xla_compiles()
    kprof.watch_xla_compiles()   # registered once
    before = metrics.value("dds_xla_compile_total") or 0.0
    n_spans = len(tracer.events("xla.compile"))
    with tracer.span("http.GET.Compiling") as _:
        # a shape nothing else in the suite compiles
        jax.jit(lambda x: (x * 3 + 1).sum())(jnp.ones((3, 17, 5)))
        inside = tracer.events("xla.compile")[n_spans:]
    assert len(inside) >= 1
    assert (metrics.value("dds_xla_compile_total") or 0.0) \
        == before + len(inside)
    root = tracer.events("http.GET.Compiling")[-1]
    assert all(r.parent_id == root.span_id for r in inside)


# ------------------------------------------------------- the closed taxonomy

NEW_NAMES = {
    "assembly.state": "assemble", "assembly.validate_tags": "assemble",
    "assembly.pick_stale": "assemble", "assembly.reread": "assemble",
    "assembly.pairs": "assemble", "assembly.operands": "assemble",
    "residency.lookup": "host-to-device-transfer",
    "residency.convert": "host-to-device-transfer",
    "ingest.h2d": "host-to-device-transfer",
    "dispatch.thread_wait": "queue-wait",
    "dispatch.resume_wait": "queue-wait",
    "dispatch.gather": "dispatch", "dispatch.d2h": "device-to-host",
    "xla.compile": "trace-compile",
    "runtime.loop_blocked": "runtime", "runtime.gc": "runtime",
}


@pytest.mark.parametrize("name,stage", sorted(NEW_NAMES.items()))
def test_classify_places_every_new_span(name, stage):
    assert classify(name) == stage and stage in STAGES and stage != "other"


# --------------------------------------------- one aggregate's span tree


def _small_cfg():
    from dds_tpu.utils.config import DDSConfig

    cfg = DDSConfig()
    cfg.replicas.endpoints = [f"replica-{i}" for i in range(4)]
    cfg.replicas.sentinent = []
    cfg.replicas.byz_quorum_size = 3
    cfg.replicas.byz_max_faults = 1
    cfg.recovery.enabled = False
    cfg.proxy.port = 0
    cfg.proxy.crypto_backend = "tpu"
    return cfg


def _uncovered_ms(parent, kids) -> float:
    lo, hi = parent.t_end - parent.dur_ms / 1e3, parent.t_end
    at, bare = lo, 0.0
    for a, b in sorted((max(lo, k.t_end - k.dur_ms / 1e3), min(hi, k.t_end))
                       for k in kids):
        if a > at:
            bare += a - at
        at = max(at, b)
    return (bare + max(0.0, hi - at)) * 1e3


def test_one_sumall_after_a_write_yields_one_span_per_step(monkeypatch):
    from dds_tpu.http.miniserver import http_request
    from dds_tpu.run import launch

    monkeypatch.setenv("DDS_TPU_MIN_BATCH", "0")   # tiny folds reach the pool
    rows = 24
    nsqr = ((1 << 61) - 1) ** 2

    async def go():
        cfg = _small_cfg()
        dep = await launch(cfg)
        host, port = cfg.proxy.host, dep.server.cfg.port
        try:
            keys = []
            for i in range(rows):
                st, body = await http_request(
                    host, port, "POST", "/PutSet",
                    json.dumps({"contents": [str(i), "x", str(1000 + i)]}
                               ).encode())
                assert st == 200
                keys.append(body.decode())
            target = f"/SumAll?position=2&nsqr={nsqr}"
            for _ in range(2):   # ingest the rows, compile every shape
                st, first = await http_request(host, port, "GET", target)
                assert st == 200
            st, _b = await http_request(
                host, port, "PUT", f"/WriteElement/{keys[3]}?position=2",
                json.dumps({"value": "777"}).encode())
            assert st == 200
            st, _b = await http_request(host, port, "GET", target)
            st, _b = await http_request(
                host, port, "PUT", f"/WriteElement/{keys[5]}?position=2",
                json.dumps({"value": "778"}).encode())
            seen = []
            tracer.subscribe(seen.append)
            try:
                st, body = await http_request(host, port, "GET", target)
            finally:
                tracer.unsubscribe(seen.append)
            assert st == 200 and body != first
            st, prof = await http_request(host, port, "GET", "/profile")
            return seen, json.loads(prof)
        finally:
            await dep.stop()

    seen, prof = asyncio.run(go())
    root = next(r for r in seen if r.name == "http.GET.SumAll")
    tree = [r for r in seen if r.trace_id == root.trace_id
            and r.kind == "span"]
    named: dict[str, list] = {}
    for r in tree:
        named.setdefault(r.name, []).append(r)
    once = ["assembly.state", "assembly.validate_tags", "assembly.pick_stale",
            "assembly.reread", "assembly.pairs", "assembly.operands",
            "residency.convert", "ingest.h2d", "dispatch.thread_wait",
            "dispatch.gather", "dispatch.d2h", "dispatch.resume_wait"]
    assert {n: len(named.get(n, [])) for n in once} == dict.fromkeys(once, 1)
    # one per locked stretch of the pool
    assert [r.meta["stretch"] for r in named["residency.lookup"]] == [1, 2]
    # counts, never a span per row
    assert named["assembly.state"][0].meta["k"] == rows
    # the write went through this proxy: the quorum's max moved no tag
    assert named["assembly.validate_tags"][0].meta == {
        "k": rows, "stale": 0, "path": "unchanged"}
    assert named["assembly.pairs"][0].meta["k"] == rows
    assert named["assembly.operands"][0].meta == {"k": rows, "memo": False}
    assert named["assembly.reread"][0].meta == {"stale": 0, "audit": 2}
    assert all(r.meta["k"] == rows for r in named["residency.lookup"])
    assert named["residency.lookup"][0].meta["missing"] == 1
    assert named["residency.convert"][0].meta["rows"] == 1
    h2d = named["ingest.h2d"][0].meta
    assert (h2d["path"], h2d["rows"]) == ("fold", 1) and h2d["bytes"] > 0
    # the hop's two waits are recorded on either side of it
    assert named["dispatch.thread_wait"][0].tid != root.tid
    assert named["dispatch.resume_wait"][0].tid == root.tid
    # what no child covers is under a tenth of either container (or under
    # a millisecond: at this size the spans' own cost is what is left)
    for name in ("proxy.fetch_stored", "proxy.fold"):
        (parent,) = named[name]
        kids = [r for r in tree if r.parent_id == parent.span_id]
        left = _uncovered_ms(parent, kids)
        assert left <= max(0.10 * parent.dur_ms, 1.0), (name, left, parent)
    stages = prof["routes"]["http.GET.SumAll"]["stages"]
    assert "other" not in stages
    assert {"assemble", "queue-wait", "host-to-device-transfer"} <= set(stages)


# ------------------------------------------------------- the host runtime


async def _with_sampler(body, **kw):
    sampler = runtime.LoopSampler(**kw)
    sampler.start()
    try:
        await asyncio.sleep(0.05)   # let it tick
        return await body(sampler)
    finally:
        await sampler.stop()


def test_a_sleep_on_the_loop_is_one_loop_blocked_span():
    async def body(_sampler):
        n = len(tracer.events("runtime.loop_blocked"))
        lag = metrics.histogram_stats("dds_event_loop_lag_seconds")["count"]
        t0 = time.perf_counter()
        time.sleep(0.06)
        t1 = time.perf_counter()
        await asyncio.sleep(0.05)
        spans = tracer.events("runtime.loop_blocked")[n:]
        assert metrics.histogram_stats(
            "dds_event_loop_lag_seconds")["count"] > lag
        return spans, t0, t1

    spans, t0, t1 = asyncio.run(_with_sampler(body))
    # one span covers the sleep; a loaded host (six test workers) may run a
    # later tick late too, which is a span of its own, after the sleep
    (s,) = [x for x in spans if x.t_end - x.dur_ms / 1e3 < t1]
    assert all(x.t_end - x.dur_ms / 1e3 >= s.t_end for x in spans if x is not s)
    # the timer was due up to one tick into the sleep, and ran when the
    # loop came back: at once on an idle host, a while later on a loaded one
    assert 40.0 - 1.0 <= s.dur_ms <= (s.t_end - t0) * 1e3
    # placed truly: it starts inside the sleep and ends after it
    assert t0 - 0.005 <= s.t_end - s.dur_ms / 1e3 <= t0 + runtime.TICK + 0.005
    assert t1 <= s.t_end <= t1 + 1.0
    assert s.trace_id is None   # belongs to no request


def test_a_collection_is_one_gc_span_and_a_count_and_stop_removes_the_hook():
    from dds_tpu.run import launch

    async def go():
        dep = await launch(_small_cfg())
        try:
            assert runtime._on_gc in gc.callbacks
            await asyncio.sleep(0.05)
            n = len(tracer.events("runtime.gc"))
            before = metrics.value("dds_gc_pause_seconds_total",
                                   generation="2") or 0.0
            gc.collect()
            await asyncio.sleep(0.08)   # the sampler drains the note
            spans = tracer.events("runtime.gc")[n:]
            after = metrics.value("dds_gc_pause_seconds_total",
                                  generation="2")
            return spans, before, after
        finally:
            await dep.stop()

    spans, before, after = asyncio.run(go())
    full = [s for s in spans if s.meta["generation"] == 2]
    assert len(full) == 1
    assert after == pytest.approx(before + full[0].dur_ms / 1e3)
    assert full[0].dur_ms > 0 and "collected" in full[0].meta
    assert runtime._on_gc not in gc.callbacks


def test_a_stalled_loop_is_reported_once_with_its_stack(tmp_path, caplog):
    from dds_tpu.obs.flight import flight

    def the_culprit():
        time.sleep(1.2)

    async def body(sampler):
        with caplog.at_level(logging.WARNING, logger="dds.runtime"):
            the_culprit()
            await asyncio.sleep(0.05)
        return sampler.stalls_reported

    was = flight.min_interval
    flight.configure(dir=str(tmp_path), min_interval=0.0)
    try:
        reported = asyncio.run(_with_sampler(body))
    finally:
        flight.configure(dir="", min_interval=was)
    assert reported == 1    # at most one a minute
    warned = [r.getMessage() for r in caplog.records
              if r.name == "dds.runtime"]
    assert len(warned) == 1 and "the_culprit" in warned[0]
    assert "held_by=foreign task=Task-" in warned[0]    # the ledger's word
    (incident,) = tmp_path.glob("incident-*-loop_stall.jsonl")
    head = json.loads(incident.read_text().splitlines()[0])
    assert head["incident"] == "loop_stall"
    assert "the_culprit" in head["info"]["stack"]
    assert head["info"]["silent_s"] >= 1.0
    assert "counters" not in head


# ------------------------------------------------------ the loop's ledger

def _ledger_now() -> dict:
    return {t: [metrics.value(n, tenant=t) or 0.0
                for n in runtime.LEDGER_SERIES]
            for t in runtime.TENANTS}


def _ledgered(body):
    """Run `body` under a sampler of its own: what it returned, what each
    tenant gained (seconds, callbacks, ready-wait) from `start()` to the
    end of `stop()`, and how long that was."""
    async def go():
        before = _ledger_now()
        sampler = runtime.LoopSampler()
        t0 = time.perf_counter()
        sampler.start()
        try:
            got = await body()
        finally:
            await sampler.stop()
        wall = time.perf_counter() - t0
        after = _ledger_now()
        return got, {t: [b - a for a, b in zip(before[t], after[t])]
                     for t in runtime.TENANTS}, wall

    return asyncio.run(go())


def _spin(seconds: float) -> None:
    end = time.perf_counter() + seconds
    while time.perf_counter() < end:
        pass


@pytest.fixture
def every_pass(monkeypatch):
    """Book every pass of the loop: whose a callback is, told apart from
    how many of them the ledger looks at."""
    monkeypatch.setattr(runtime, "LEDGER_EVERY", 1)


def test_the_tenants_seconds_sum_to_the_wall_time():
    async def body():
        async def worker():
            for _ in range(25):
                _spin(0.004)
                await asyncio.sleep(0.006)

        await asyncio.gather(worker(), worker(), asyncio.sleep(0.5))

    _got, gained, wall = _ledgered(body)
    total = sum(v[0] for v in gained.values())
    assert wall >= 0.5 and total == pytest.approx(wall, rel=0.02)
    assert gained["idle"][1] == 0              # idle runs no handle
    assert gained["foreign"][0] >= 0.19        # 2 x 25 x 4 ms of spinning
    assert all(v[2] >= 0 for v in gained.values())


def test_a_loop_that_only_sleeps_is_idle():
    async def body():
        await asyncio.sleep(0.5)

    _got, gained, wall = _ledgered(body)
    assert gained["idle"][0] >= 0.90 * wall
    assert gained["background"][1] > 0         # the sampler's own ticks


@pytest.mark.parametrize("name, tenant", [
    ("http.conn", "request"),
    ("tcp.send:127.0.0.1:4000/replica-1", "transport"),
    ("tcp.serve", "transport"),
    ("inmem.deliver:replica-2", "replica"),
    ("tcp.handle:s0-replica-3", "replica"),
    ("inmem.deliver:proxy-0", "proxy_inbox"),
    ("tcp.handle:supervisor", "supervisor"),
    ("inmem.deliver:nodehost", "background"),
    ("antientropy.loop", "foreign"),           # by its code: the test's
    (None, "foreign"),
])
def test_a_task_that_spins_lands_under_its_tenant(every_pass, name, tenant):
    async def body():
        async def spinner():
            _spin(0.05)

        task = asyncio.ensure_future(spinner())
        if name:
            task.set_name(name)
        await task

    _got, gained, _wall = _ledgered(body)
    assert gained[tenant][0] >= 0.05 and gained[tenant][1] >= 1
    others = sum(v[0] for t, v in gained.items()
                 if t not in (tenant, "idle"))
    assert others < gained[tenant][0]


def test_while_the_loop_has_headroom_every_pass_is_booked_and_exact():
    async def body():
        async def worker():
            for _ in range(20):
                _spin(0.003)
                await asyncio.sleep(0.005)

        task = asyncio.ensure_future(worker())
        task.set_name("inmem.deliver:replica-1")
        await task

    _got, gained, wall = _ledgered(body)
    assert runtime.LEDGER_EVERY > 1
    seconds, callbacks, _wait = gained["replica"]
    # 20 steps of 3 ms between waits: nothing is sampled, nothing scaled
    assert 0.060 <= seconds <= 0.075
    assert callbacks == 21           # the first step and twenty wake-ups
    assert sum(v[0] for v in gained.values()) == pytest.approx(wall, rel=0.02)


def test_the_passes_not_booked_are_split_as_the_booked_ones_and_scaled():
    async def body():
        async def hopper():
            for _ in range(1500):
                _spin(0.0002)
                await asyncio.sleep(0)

        task = asyncio.ensure_future(hopper())
        task.set_name("inmem.deliver:replica-0")
        await task

    _got, gained, wall = _ledgered(body)
    assert runtime.LEDGER_EVERY > 1
    # 0.3 s of spinning in 1,500 passes and never a wait, so the loop is
    # under load and one pass in `LEDGER_EVERY` is booked: the tenant gets (nearly) all of it, the sum stays exact, and
    # the callbacks and the ready-wait are scaled up to all the passes
    assert gained["replica"][0] >= 0.27
    assert sum(v[0] for v in gained.values()) == pytest.approx(wall, rel=0.02)
    assert 1200 <= gained["replica"][1] <= 1800
    assert gained["idle"][1] == 0


@pytest.mark.parametrize("period", [2, 4])
def test_a_loop_whose_passes_repeat_is_not_booked_at_one_phase(
        monkeypatch, period):
    """One step of 0.3 ms under `replica`, then `period` - 1 passes of a
    plain callback of 0.1 ms each, 1,500 times over: with a fixed stride of
    4 the booked passes fell on one phase and `replica` read 95 % where
    booking every pass reads 72 (period 2) and 47 (period 4)."""
    async def body():
        loop = asyncio.get_running_loop()

        async def chain():
            for _ in range(1500):
                _spin(0.0003)
                fut, left = loop.create_future(), [period - 1]

                def hop():
                    _spin(0.0001)
                    left[0] -= 1
                    if left[0] <= 0:
                        fut.set_result(None)
                    else:
                        loop.call_soon(hop)

                loop.call_soon(hop)
                await fut

        task = asyncio.ensure_future(chain())
        task.set_name("inmem.deliver:replica-0")
        await task

    def share():
        _got, gained, _wall = _ledgered(body)
        return gained["replica"][0] / sum(v[0] for v in gained.values())

    thinned = share()
    monkeypatch.setattr(runtime, "LEDGER_EVERY", 1)
    # an estimate from an eighth of the passes: a few points of noise,
    # where one phase of the period reads 23 to 48 points off
    assert thinned == pytest.approx(share(), abs=0.12)


def test_an_unnamed_task_of_the_package_is_background_and_of_http_a_request():
    from dds_tpu.http.miniserver import http_request
    from dds_tpu.utils.tasks import drain

    async def body():
        # both fail fast (nothing listens on port 1): what matters is
        # whose coroutine the unnamed task runs
        t1 = asyncio.ensure_future(http_request("127.0.0.1", 1, "GET", "/"))
        t2 = asyncio.ensure_future(drain(0.01))
        await asyncio.gather(t1, t2, return_exceptions=True)
        return runtime._ledger._task_tenant(t1), runtime._ledger._task_tenant(t2)

    (of_http, of_utils), _gained, _wall = _ledgered(body)
    assert runtime.TENANTS[of_http] == "request"
    assert runtime.TENANTS[of_utils] == "background"


@pytest.mark.parametrize("transport", ["memory", "tcp"])
def test_a_deployment_books_its_work_under_the_tables_tenants(
        monkeypatch, every_pass, transport):
    import re

    from dds_tpu.http.miniserver import http_request
    from dds_tpu.run import launch

    monkeypatch.setenv("DDS_TPU_MIN_BATCH", "0")
    nsqr = ((1 << 61) - 1) ** 2

    async def go():
        cfg = _small_cfg()
        if transport == "tcp":
            cfg.transport.kind, cfg.transport.port = "tcp", 0
            cfg.security.transport_frame_secret = "ledger-test"
        before = _ledger_now()
        frames = metrics.value("dds_net_frames_total", direction="received",
                               msg="Envelope") or 0
        dep = await launch(cfg)
        host, port = cfg.proxy.host, dep.server.cfg.port
        try:
            for i in range(6):
                st, _b = await http_request(
                    host, port, "POST", "/PutSet",
                    json.dumps({"contents": [str(i), "x", str(9 + i)]}
                               ).encode())
                assert st == 200
            st, _b = await http_request(
                host, port, "GET", f"/SumAll?position=2&nsqr={nsqr}")
            assert st == 200
        finally:
            await dep.stop()
        after = _ledger_now()
        got = (metrics.value("dds_net_frames_total", direction="received",
                             msg="Envelope") or 0) - frames
        return {t: [b - a for a, b in zip(before[t], after[t])]
                for t in runtime.TENANTS}, got

    gained, frames = asyncio.run(go())
    busy = {"request", "replica", "proxy_inbox", "socket", "foreign", "loop",
            "background"}
    if transport == "tcp":
        busy.add("transport")
        assert frames > 0
        # a frame is one `tcp.send` task and at least one step of
        # `tcp.serve`: the transport's callbacks outnumber the frames
        assert gained["transport"][1] > 2 * frames
    else:
        assert gained["transport"] == [0, 0, 0]
    for t in busy:
        assert gained[t][0] > 0 and gained[t][1] > 0, t
    # every label value ever emitted is one of the table's
    for series in runtime.LEDGER_SERIES:
        values = set(re.findall(
            series + r'\{tenant="([^"]*)"\}', metrics.render()))
        assert values and values <= set(runtime.TENANTS)
    # the per-frame drain histogram went with the ledger's coming
    assert "drain_seconds" not in metrics.render()


def test_stop_restores_the_loop_twice_over_and_another_threads_loop_is_not_counted():
    from asyncio import events

    run = events.Handle._run
    other = {}

    def elsewhere():
        async def hops():
            for _ in range(3000):
                await asyncio.sleep(0)
            _spin(0.03)

        other["done"] = asyncio.run(hops()) is None

    async def body():
        selector = asyncio.get_running_loop()._selector
        assert "select" in vars(selector) and events.Handle._run is not run
        thread = threading.Thread(target=elsewhere)
        thread.start()
        while thread.is_alive():
            await asyncio.sleep(0.02)
        thread.join(timeout=5)
        return selector

    for _ in range(2):
        selector, gained, wall = _ledgered(body)
        assert events.Handle._run is run
        assert "select" not in vars(selector)
        assert runtime._ledger is None
        assert other.pop("done") is True
        # the other loop's 3,000 hops and 30 ms are nobody's here: this
        # loop only polled the thread
        assert sum(v[1] for v in gained.values()) < 1500
        assert sum(v[0] for v in gained.values()) == pytest.approx(
            wall, rel=0.05, abs=0.02)


def test_a_second_sampler_on_the_loop_leaves_the_first_ones_ledger_alone(
        every_pass):
    from asyncio import events

    run = events.Handle._run

    async def body():
        first = runtime._ledger
        second = runtime.LoopSampler()
        second.start()
        try:
            assert runtime._ledger is first and second._ledger is None
        finally:
            await second.stop()
        assert runtime._ledger is first and events.Handle._run is not run
        _spin(0.02)

    _got, gained, _wall = _ledgered(body)
    assert events.Handle._run is run and gained["foreign"][0] >= 0.02


def _blocked_by_a_sleeper(times: int, under_load: bool):
    """`runtime.loop_blocked` spans of `times` tasks named
    `tcp.send:the-sleeper` that each hold the loop for 60 ms; `under_load`
    a hopper keeps the loop from ever waiting meanwhile."""
    async def body():
        async def sleeper():
            time.sleep(0.06)

        async def hopper():
            while under_load:
                await asyncio.sleep(0)

        load = asyncio.ensure_future(hopper())
        spans = []
        for _ in range(times):
            await asyncio.sleep(0.05)    # let the sampler tick
            n = len(tracer.events("runtime.loop_blocked"))
            t0 = time.perf_counter()
            task = asyncio.ensure_future(sleeper())
            task.set_name("tcp.send:the-sleeper")
            await task
            await asyncio.sleep(0.05)
            spans += [s for s in tracer.events("runtime.loop_blocked")[n:]
                      if s.t_end - s.dur_ms / 1e3 <= t0 + 0.06]
        load.cancel()
        return spans

    return _ledgered(body)[0]


def test_a_loop_blocked_span_names_its_holder():
    (s,) = _blocked_by_a_sleeper(1, under_load=False)
    assert s.meta["held_by"] == "transport"
    assert s.meta["task"] == "tcp.send:the-sleeper"
    assert 59.0 <= s.meta["held_ms"] <= s.dur_ms + runtime.TICK * 1e3 + 1.0


def test_under_load_a_late_pass_not_booked_names_nobody_never_another():
    spans = _blocked_by_a_sleeper(8, under_load=True)
    assert len(spans) == 8 and runtime.LEDGER_EVERY > 1
    named = [s for s in spans if "held_by" in s.meta]
    assert len(named) < 8    # the sleeper's pass was not always a booked one
    for s in named:
        assert s.meta["task"] == "tcp.send:the-sleeper"
        assert s.meta["held_ms"] >= 59.0


def test_with_the_tracer_off_no_ledger_is_installed(monkeypatch):
    from asyncio import events

    run = events.Handle._run
    monkeypatch.setattr(tracer, "enabled", False)

    async def body():
        assert events.Handle._run is run and runtime._ledger is None
        assert "select" not in vars(asyncio.get_running_loop()._selector)
        _spin(0.02)
        await asyncio.sleep(0.05)

    _got, gained, _wall = _ledgered(body)
    assert all(v == [0, 0, 0] for v in gained.values())


def test_a_loop_without_the_private_names_gets_no_ledger(monkeypatch):
    from asyncio import events

    run = events.Handle._run

    async def body():
        ledger = runtime._Ledger(asyncio.get_running_loop())
        monkeypatch.delattr(events.Handle, "_run")
        try:
            assert ledger.install() is False
        finally:
            monkeypatch.undo()

        class Selectorless:
            pass

        assert runtime._Ledger(Selectorless()).install() is False
        assert events.Handle._run is run

    asyncio.run(body())


# ------------------------------------------------ what a span costs a trace


def test_a_handler_is_analysed_over_its_own_subtree_and_only_exemplars_keep_a_waterfall():
    from dds_tpu.obs import chronoscope as cs
    from dds_tpu.obs.metrics import Registry
    from dds_tpu.utils.trace import SpanRecord

    def rec(name, start, end, sid, parent=None):
        return SpanRecord(ts=end, name=name, dur_ms=(end - start) * 1e3,
                          meta={}, trace_id="t", span_id=sid,
                          parent_id=parent)

    seen = []
    real = cs.critical_path

    def counting(records, **kw):
        records = list(records)
        seen.append((kw.get("root_span_id"), len(records),
                     kw.get("with_path", True)))
        return real(records, **kw)

    scope = cs.Chronoscope(Registry(), exemplars=1)
    cs.critical_path = counting
    try:
        for i in range(30):   # a long request before the handler reports
            scope.on_record(rec("assembly.state", 0.01, 0.02, f"a{i}", "r"))
        scope.on_record(rec("net.serialize", 0.031, 0.032, "s", "h"))
        scope.on_record(rec("replica.handle", 0.03, 0.04, "h", "q"))
        scope.on_record(rec("abd.fetch", 0.025, 0.05, "q", "r"))
        scope.on_record(rec("http.GET.SumAll", 0.0, 0.1, "r"))
    finally:
        cs.critical_path = real
    handler = [s for s in seen if s[0] == "h"]
    # the handler and its one child, whatever else the trace holds; the
    # stage sums need no waterfall, the kept exemplar gets one built
    assert handler[0][1:] == (2, False)
    assert [s for s in seen if s[0] == "r"][0][2] is False
    prof = scope.profile()["routes"]
    assert prof["replica.handle"]["stages"]["serialize"]["p50_ms"] == 1.0
    (ex,) = prof["http.GET.SumAll"]["exemplars"]
    assert [e["name"] for e in ex["path"]][:1] == ["http.GET.SumAll"]
    assert "other" not in prof["http.GET.SumAll"]["stages"]
