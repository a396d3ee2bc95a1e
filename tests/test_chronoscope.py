"""Chronoscope tests: critical-path extraction over span trees (linear,
parallel fan-out, orphaned/partial), the attribution-coverage property on
REAL traces from a seeded ChaosNet cluster, the per-route aggregate +
gauge surface, the TimedQueue telemetry shared by the ingest queues, the
kprof compile/dispatch split, the Panopticon fleet-profile rollup, and
the sentry `pipe profile` record contract.
"""

import asyncio
import json
import time
from types import SimpleNamespace

import pytest

from dds_tpu.core.chaos import ChaosNet, LinkFaults
from dds_tpu.core.quorum_client import AbdClient, AbdClientConfig
from dds_tpu.core.replica import BFTABDNode, ReplicaConfig
from dds_tpu.core.transport import InMemoryNet
from dds_tpu.http.miniserver import http_request
from dds_tpu.http.server import DDSRestServer, ProxyConfig
from dds_tpu.obs.chronoscope import (
    STAGES, Chronoscope, classify, critical_path,
)
from dds_tpu.obs.metrics import Registry
from dds_tpu.utils.queues import TimedQueue
from dds_tpu.utils.trace import SpanRecord, tracer

pytestmark = pytest.mark.obs


def run(coro):
    return asyncio.run(coro)


def S(name, start, end, span_id, parent_id=None, tid="t1", kind="span",
      **meta):
    """A synthetic SpanRecord: ts is the END instant (spans record on
    exit), dur covers [start, end] in seconds."""
    return SpanRecord(ts=end, name=name, dur_ms=(end - start) * 1e3,
                      meta=meta, trace_id=tid, span_id=span_id,
                      parent_id=parent_id, kind=kind)


# ------------------------------------------------------------ taxonomy


def test_classify_is_closed_over_stages():
    for name in ("proxy.admission", "proxy.fold", "net.serialize",
                 "abd.verify", "abd.write", "abd.read_quorum",
                 "ingest.queue_wait", "ingest.h2d", "replica.handle",
                 "antientropy.sync", "kernel.fold_weighted.compile",
                 "kernel.fold_weighted.dispatch", "kernel.fold_weighted.execute",
                 "proxy.scatter_fold", "http.POST.PutSet",
                 "proxy.get_set", "totally.unknown"):
        assert classify(name) in STAGES
    assert classify("abd.verify") == "hmac-verify"
    assert classify("abd.write") == "quorum-rtt"
    assert classify("kernel.fold_weighted.compile") == "trace-compile"
    assert classify("kernel.fold_weighted.execute") == "device-execute"
    assert classify("ingest.h2d") == "host-to-device-transfer"
    assert classify("totally.unknown") == "other"


# ------------------------------------------------- critical-path extraction


def test_linear_chain_attributes_self_times():
    """root[0,100ms] -> abd.write[10,90] -> replica.handle[20,60]: each
    level's self-time is its window minus the claimed child window, and
    the stage sums reconstruct the root wall exactly."""
    recs = [
        S("replica.handle", 0.020, 0.060, "c2", "c1"),
        S("abd.write", 0.010, 0.090, "c1", "r"),
        S("http.POST.PutSet", 0.000, 0.100, "r"),
    ]
    res = critical_path(recs)
    assert res is not None and res["route"] == "http.POST.PutSet"
    assert res["wall_ms"] == pytest.approx(100.0, abs=0.01)
    assert res["stages"]["response"] == pytest.approx(20.0, abs=0.01)
    assert res["stages"]["quorum-rtt"] == pytest.approx(40.0, abs=0.01)
    assert res["stages"]["replica-apply"] == pytest.approx(40.0, abs=0.01)
    assert sum(res["stages"].values()) == pytest.approx(100.0, abs=0.05)
    assert res["coverage"] == pytest.approx(1.0, abs=0.001)
    # the waterfall is chronological parent-then-claimed-children
    assert [e["name"] for e in res["path"]] == [
        "http.POST.PutSet", "abd.write", "replica.handle"]


def test_parallel_fanout_claims_slowest_branch():
    """Two overlapping quorum legs: the slower branch claims the window,
    the faster sibling (fully covered) contributes nothing — critical
    path semantics, not sum-of-children (which would exceed the wall)."""
    recs = [
        S("abd.write", 0.010, 0.090, "slow", "r", coordinator="replica-1"),
        S("abd.write", 0.010, 0.050, "fast", "r", coordinator="replica-2"),
        S("http.POST.PutSet", 0.000, 0.100, "r"),
    ]
    res = critical_path(recs)
    assert res["stages"]["quorum-rtt"] == pytest.approx(80.0, abs=0.01)
    assert res["stages"]["response"] == pytest.approx(20.0, abs=0.01)
    assert sum(res["stages"].values()) <= res["wall_ms"] + 0.05
    legs = [e for e in res["path"] if e["name"] == "abd.write"]
    assert len(legs) == 1 and legs[0]["meta"]["coordinator"] == "replica-1"


def test_partially_overlapping_siblings_claim_disjoint_windows():
    """Staggered siblings: the later-ending child claims its window, the
    earlier one keeps only the uncovered head — total claimed never
    exceeds the parent window."""
    recs = [
        S("abd.read_quorum", 0.000, 0.060, "a", "r"),
        S("abd.write", 0.040, 0.100, "b", "r"),
        S("http.POST.PutSet", 0.000, 0.100, "r"),
    ]
    res = critical_path(recs)
    # b claims [40,100], a keeps [0,40]: root self-time is zero
    assert res["stages"]["quorum-rtt"] == pytest.approx(100.0, abs=0.05)
    assert res["stages"].get("response", 0.0) == pytest.approx(0.0, abs=0.05)


def test_orphaned_spans_attach_to_root_clamped():
    """A span whose parent never arrived (Panopticon straggler) hangs off
    the root, clamped to the root window — a partial tree still
    attributes instead of vanishing into 'other'."""
    recs = [
        # parent "ghost" never shipped; span also overhangs the root end
        S("replica.handle", 0.050, 0.150, "x", "ghost"),
        S("http.POST.PutSet", 0.000, 0.100, "r"),
    ]
    res = critical_path(recs)
    assert res["stages"]["replica-apply"] == pytest.approx(50.0, abs=0.01)
    assert res["stages"]["response"] == pytest.approx(50.0, abs=0.01)
    # without orphan adoption the same tree attributes everything to root
    res2 = critical_path(recs, orphans_to_root=False)
    assert res2["stages"]["response"] == pytest.approx(100.0, abs=0.01)
    assert "replica-apply" not in res2["stages"]


def test_no_usable_root_returns_none():
    assert critical_path([]) is None
    assert critical_path([S("abd.write", 0.0, 0.1, "c", "gone")],
                         root_span_id="nope") is None
    # zero-duration root cannot be attributed
    assert critical_path([S("http.GET.Health", 0.5, 0.5, "r")]) is None


def test_unknown_spans_count_against_coverage():
    recs = [
        S("totally.unknown", 0.000, 0.080, "u", "r"),
        S("http.POST.PutSet", 0.000, 0.100, "r"),
    ]
    res = critical_path(recs)
    assert res["stages"]["other"] == pytest.approx(80.0, abs=0.01)
    assert res["coverage"] == pytest.approx(0.2, abs=0.001)


# ----------------------------------------------------- aggregate + surface


def _feed_trace(cs, tid, wall_s, extra=()):
    cs.on_record(S("abd.write", 0.01, wall_s - 0.01, f"{tid}-c", f"{tid}-r",
                   tid=tid))
    for rec in extra:
        cs.on_record(rec)
    cs.on_record(S("http.POST.PutSet", 0.0, wall_s, f"{tid}-r", tid=tid))


def test_chronoscope_aggregates_routes_and_exports_gauges():
    reg = Registry()
    cs = Chronoscope(registry=reg, slow_ms=1e9)
    for i, wall in enumerate((0.100, 0.080, 0.120)):
        _feed_trace(cs, f"t{i}", wall)
    prof = cs.profile()
    rs = prof["routes"]["http.POST.PutSet"]
    assert rs["count"] == 3 and prof["traces_profiled"] >= 3
    assert rs["wall_p95_ms"] == pytest.approx(120.0, abs=0.5)
    assert rs["top_stage"] == "quorum-rtt"
    assert rs["coverage"] > 0.99
    assert rs["stages"]["quorum-rtt"]["p95_ms"] > 0
    cs.export_gauges(reg)
    text = reg.render()
    assert 'dds_pipe_wall_p95_ms{route="http.POST.PutSet"}' in text
    assert 'dds_pipe_stage_p95_ms{route="http.POST.PutSet"' in text
    assert 'stage="quorum-rtt"' in text
    # folded flamegraph text carries route;stage cumulative totals
    assert "http.POST.PutSet;quorum-rtt" in cs.folded()


def test_chronoscope_keeps_worst_k_exemplars():
    cs = Chronoscope(registry=Registry(), exemplars=2, slow_ms=1e9)
    for i, wall in enumerate((0.010, 0.200, 0.020, 0.150, 0.030)):
        _feed_trace(cs, f"t{i}", wall)
    ex = cs.profile()["routes"]["http.POST.PutSet"]["exemplars"]
    walls = [e["wall_ms"] for e in ex]
    assert walls == sorted(walls, reverse=True)[:2]
    assert walls[0] == pytest.approx(200.0, abs=0.5)
    assert ex[0]["path"], "exemplars retain the waterfall"


def test_chronoscope_replica_subtree_profiled_once():
    """replica.handle subtrees are profiled as their own route when they
    land, and NOT re-absorbed when the http root closes the trace."""
    cs = Chronoscope(registry=Registry(), slow_ms=1e9)
    cs.on_record(S("replica.handle", 0.02, 0.06, "h", "c", tid="t9"))
    assert cs.profile()["routes"]["replica.handle"]["count"] == 1
    cs.on_record(S("abd.write", 0.01, 0.09, "c", "r", tid="t9"))
    cs.on_record(S("http.POST.PutSet", 0.0, 0.1, "r", tid="t9"))
    prof = cs.profile()
    assert prof["routes"]["replica.handle"]["count"] == 1
    # ...but its time still attributes inside the http route's tree
    assert prof["routes"]["http.POST.PutSet"]["stages"]["replica-apply"]


def test_chronoscope_disabled_by_env(monkeypatch):
    monkeypatch.setenv("DDS_OBS_PIPE", "0")
    cs = Chronoscope(registry=Registry())
    assert cs.enabled is False
    _feed_trace(cs, "t0", 0.1)
    assert cs.profile()["routes"] == {}


def test_ingest_tree_profiles_stitched_trace():
    cs = Chronoscope(registry=Registry(), slow_ms=1e9)
    cs.ingest_tree([
        S("replica.handle", 0.02, 0.06, "h", "c"),
        S("abd.write", 0.01, 0.09, "c", "r"),
        S("http.POST.PutSet", 0.0, 0.1, "r"),
    ])
    prof = cs.profile()
    assert prof["routes"]["http.POST.PutSet"]["count"] == 1
    assert prof["routes"]["replica.handle"]["count"] == 1


# --------------------------------------- real traces under seeded ChaosNet


async def _chaos_stack(seed=21, delay=0.001, jitter=0.002):
    net = ChaosNet(InMemoryNet(), seed=seed)
    net.default_faults = LinkFaults(delay=delay, jitter=jitter)
    addrs = [f"replica-{i}" for i in range(7)]
    replicas = {
        a: BFTABDNode(a, addrs, "supervisor", net,
                      ReplicaConfig(quorum_size=5))
        for a in addrs
    }
    abd = AbdClient("proxy-0", net, addrs,
                    AbdClientConfig(request_timeout=2.0, quorum_size=5))
    server = DDSRestServer(
        abd, ProxyConfig(host="127.0.0.1", port=0, request_budget=10.0,
                         trace_route_enabled=True))
    await server.start()
    return net, server, replicas


async def _call(server, method, target, obj=None):
    body = json.dumps(obj).encode() if obj is not None else None
    return await http_request("127.0.0.1", server.cfg.port, method, target,
                              body, timeout=10.0)


def test_attribution_coverage_on_real_chaos_traces():
    """Acceptance property: on real PutSet/GetSet traces from a seeded
    ChaosNet cluster, the critical path attributes >=95% of every
    request's wall time to NAMED stages."""
    cs = Chronoscope(registry=Registry(), slow_ms=1e9)

    async def go():
        net, server, _ = await _chaos_stack()
        try:
            tracer.reset()
            cs.attach(tracer)
            status, body = await _call(server, "POST", "/PutSet",
                                       {"contents": ["a", "b"]})
            assert status == 200
            key = bytes(body).decode()
            status, _ = await _call(server, "GET", "/GetSet/" + key)
            assert status == 200
            await net.quiesce()
        finally:
            cs.detach()
            await server.stop()

    run(go())
    roots = [e for e in tracer.events()
             if e.kind == "span" and e.parent_id is None
             and e.name.startswith("http.")]
    assert len(roots) == 2
    for root in roots:
        res = critical_path(tracer.trace_events(root.trace_id),
                            root_span_id=root.span_id)
        assert res is not None
        assert res["coverage"] >= 0.95, (root.name, res["stages"])
        # the quorum round must be visible as a named stage
        assert res["stages"].get("quorum-rtt", 0.0) > 0
    # the live-attached Chronoscope absorbed the same routes
    routes = cs.profile()["routes"]
    assert "http.POST.PutSet" in routes and "http.GET.GetSet" in routes
    assert routes["http.POST.PutSet"]["coverage"] >= 0.95


def test_injected_quorum_delay_moves_top_stage_to_quorum_rtt():
    """Acceptance: a seeded ChaosNet delay on the quorum links makes
    quorum-rtt the top stage, and the worst exemplar's waterfall carries
    the injected chaos.delay annotations."""
    cs = Chronoscope(registry=Registry(), slow_ms=1e9)

    async def go():
        net, server, _ = await _chaos_stack(seed=5, delay=0.03, jitter=0.01)
        try:
            tracer.reset()
            cs.attach(tracer)
            status, _ = await _call(server, "POST", "/PutSet",
                                    {"contents": ["x"]})
            assert status == 200
            await net.quiesce()
        finally:
            cs.detach()
            await server.stop()

    run(go())
    rs = cs.profile()["routes"]["http.POST.PutSet"]
    assert rs["top_stage"] == "quorum-rtt"
    ex = rs["exemplars"][0]
    names = [ev["name"] for e in ex["path"] for ev in e.get("events", ())]
    assert any(n.startswith("chaos.") for n in names)


# ------------------------------------------------------------- TimedQueue


def test_timed_queue_bounds_and_drop_reasons():
    reg = Registry()
    clk = [0.0]
    q = TimedQueue("test-q", maxlen=2, clock=lambda: clk[0], registry=reg)
    assert q.offer("a") and q.offer("b")
    assert not q.offer("c")  # full
    assert q.dropped("full") == 1
    assert reg.value("dds_queue_dropped_total", queue="test-q",
                     reason="full") == 1
    q.drop(3, reason="no_pool")
    assert q.dropped("no_pool") == 3 and q.dropped() == 4
    assert q.offer_many(["d", "e"]) == 0  # still full, both rejected
    assert q.dropped("full") == 3
    clk[0] = 0.25
    entries = q.drain_entries()
    assert [i for _, i in entries] == ["a", "b"]
    assert all(w == pytest.approx(0.25) for w, _ in entries)
    assert q.depth() == 0 and q.drain() == []
    st = q.stats()
    assert st["offered"] == 2 and st["drained"] == 2
    assert st["dropped"] == {"full": 3, "no_pool": 3}


def test_timed_queue_age_clear_and_gauges():
    reg = Registry()
    clk = [10.0]
    q = TimedQueue("age-q", clock=lambda: clk[0], registry=reg)
    q.offer("x")
    clk[0] = 10.5
    assert q.oldest_age() == pytest.approx(0.5)
    q.export_gauges(reg)
    text = reg.render()
    assert 'dds_queue_depth{queue="age-q"} 1' in text
    assert 'dds_queue_oldest_age_seconds{queue="age-q"} 0.5' in text
    assert q.clear(reason="invalidated") == 1
    assert q.dropped("invalidated") == 1
    assert q.clear() == 0


def test_timed_queue_drain_records_queue_wait_span():
    tracer.reset()
    clk = [0.0]
    q = TimedQueue("span-q", clock=lambda: clk[0], registry=Registry())
    q.offer("x")
    clk[0] = 0.1
    q.drain()
    waits = tracer.events("ingest.queue_wait")
    assert len(waits) == 1
    assert waits[0].dur_ms == pytest.approx(100.0, abs=0.5)
    assert waits[0].meta["queue"] == "span-q"


# ------------------------------------------------- kprof compile split


def test_kprof_splits_cold_compile_from_warm_dispatch():
    from dds_tpu.obs import kprof

    kprof.reset()
    tracer.reset()
    kprof.cache_event("splitk", hit=False)   # builder cache miss -> cold
    kprof.profiled("splitk", lambda: 3)
    kprof.cache_event("splitk", hit=True)
    kprof.profiled("splitk", lambda: 3)      # warm
    names = [e.name for e in tracer.events() if e.name.startswith("kernel.")]
    assert names.count("kernel.splitk.compile") == 1
    assert names.count("kernel.splitk.dispatch") == 1
    assert names.count("kernel.splitk.execute") == 2
    summary = kprof.kernel_summary()
    assert summary["compile_ms"] >= 0 and "compile_ms" in summary


def test_sentry_collect_includes_compile_phase():
    from dds_tpu.obs import sentry
    from dds_tpu.utils.trace import Tracer

    t = Tracer()
    t.record("kernel.splitk.compile", 5.0, k=4)
    t.record("kernel.splitk.dispatch", 1.0, k=4)
    t.record("kernel.splitk.execute", 2.0, k=4)
    stats = sentry.collect(t)
    (key,) = [k for k in stats if "splitk" in k]
    assert set(stats[key]) == {"compile", "dispatch", "execute"}
    # round-trips through the baseline schema
    assert sentry.compare({key: stats[key]}, {key: stats[key]}) == []


# --------------------------------------------- Panopticon fleet rollup


class _StubNet:
    """The TcpNet sliver FleetCollector touches: addr composition,
    endpoint registry, fire-and-forget send."""

    def __init__(self, advertised="127.0.0.1:70"):
        self.advertised = advertised
        self.handlers = {}
        self.sent = []

    def local_addr(self, name):
        return f"{self.advertised}/{name}"

    def register(self, addr, handler):
        self.handlers[addr.rsplit("/", 1)[-1]] = handler

    def unregister(self, addr):
        self.handlers.pop(addr.rsplit("/", 1)[-1], None)

    def send(self, src, dest, msg):
        self.sent.append((src, dest, msg))


def _pipe_text(route, stage, p95, wall=50.0, cov=0.97):
    return "\n".join([
        f'dds_pipe_wall_p95_ms{{route="{route}"}} {wall}',
        f'dds_pipe_coverage{{route="{route}"}} {cov}',
        f'dds_pipe_stage_p95_ms{{route="{route}",stage="{stage}"}} {p95}',
        "",
    ])


def test_fleet_profile_rolls_up_max_across_hosts():
    from dds_tpu.obs.panopticon import FleetCollector

    reg = Registry()
    reg.set("dds_pipe_wall_p95_ms", 50.0, route="http.POST.PutSet")
    reg.set("dds_pipe_coverage", 0.99, route="http.POST.PutSet")
    reg.set("dds_pipe_stage_p95_ms", 12.0, route="http.POST.PutSet",
            stage="quorum-rtt")
    col = FleetCollector(
        _StubNet(), secret=b"s", host="proxy-0", registry=reg,
        watchtower=SimpleNamespace(on_record=lambda r: None))
    col._sources["group-1"] = {
        "role": "group", "shard": "s0", "ts": 0.0, "region": "",
        "mono": time.monotonic(), "seq": 1, "slo": {}, "dropped": 0,
        "metrics_text": _pipe_text("http.POST.PutSet", "replica-apply", 30.0),
    }
    fp = col.fleet_profile()
    route = fp["fleet"]["routes"]["http.POST.PutSet"]
    assert route["wall_p95_ms"] == 50.0
    assert route["coverage_min"] == 0.97
    assert route["stages"]["replica-apply"] == {
        "p95_ms": 30.0, "host": "group-1"}
    assert route["top_stage"]["stage"] == "replica-apply"
    assert fp["fleet"]["top"] == {
        "route": "http.POST.PutSet", "stage": "replica-apply",
        "p95_ms": 30.0, "host": "group-1"}
    assert "proxy-0" in fp["hosts"] and "group-1" in fp["hosts"]


def test_collector_replay_feeds_profiler_stitched_tree():
    from dds_tpu.obs.panopticon import FleetCollector

    col = FleetCollector(
        _StubNet(), secret=b"s", host="proxy-0", registry=Registry(),
        watchtower=SimpleNamespace(on_record=lambda r: None),
        stitch_window=0.0)
    cs = Chronoscope(registry=Registry(), slow_ms=1e9)
    col.profiler = cs
    col._buffer(S("abd.write", 0.01, 0.09, "c", "r", tid="tz"), local=True)
    col._buffer(S("replica.handle", 0.02, 0.06, "h", "c", tid="tz"),
                local=False)
    col._buffer(S("http.POST.PutSet", 0.0, 0.1, "r", tid="tz"), local=True)
    col._replay_due()
    prof = cs.profile()
    assert prof["routes"]["http.POST.PutSet"]["count"] == 1
    assert prof["routes"]["http.POST.PutSet"]["stages"]["replica-apply"]


# ------------------------------------------- sentry `pipe profile` contract


def test_sentry_validates_pipe_profile_records(tmp_path):
    from benchmarks.sentry import _check_pipe_records

    good = {
        "metric": "pipe profile", "value": 43.1, "unit": "ms",
        "vs_baseline": 0.97,
        "detail": {
            "rate": 60.0, "duration": 2.0, "processes": 3,
            "open_loop": True, "route": "http.POST.PutSet",
            "wall_p95_ms": 43.1, "coverage": 0.968,
            "top_stage": "quorum-rtt",
            "stages": {"quorum-rtt": 21.0, "response": 5.2},
            "fleet_top_stage": "quorum-rtt", "agree": True,
            "traces_profiled": 110, "on_good": 105, "off_good": 107,
            "overhead_pct": 1.87,
        },
    }
    bench = tmp_path / "benchmarks"
    bench.mkdir()
    (bench / "results_quick.json").write_text(json.dumps([good]))
    assert _check_pipe_records(str(tmp_path)) == {"rows": 1}
    for mutate in (
        {"value": 0},                                        # no wall time
        {"detail": dict(good["detail"], route="")},
        {"detail": dict(good["detail"], coverage=1.5)},      # not a fraction
        {"detail": dict(good["detail"], top_stage="warp")},  # off-taxonomy
        {"detail": dict(good["detail"], stages={})},         # nothing named
        {"detail": dict(good["detail"], stages={"quorum-rtt": -1})},
        {"detail": dict(good["detail"], agree="yes")},
        {"detail": dict(good["detail"], processes=1)},       # not a fleet
        {"detail": dict(good["detail"], open_loop=False)},
        {"detail": dict(good["detail"], overhead_pct="2%")},
    ):
        (bench / "results_quick.json").write_text(
            json.dumps([dict(good, **mutate)]))
        with pytest.raises(ValueError):
            _check_pipe_records(str(tmp_path))
    (bench / "results_quick.json").write_text(json.dumps([{"metric": "sweep"}]))
    assert _check_pipe_records(str(tmp_path)) == {"rows": 0}
