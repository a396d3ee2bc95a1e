"""Telescope telemetry tests: tracer core, trace-context propagation,
MetricsRegistry + Prometheus exposition, kernel profiling hooks, flight
recorder, and the end-to-end acceptance paths — a request through the REST
proxy under an active ChaosNet schedule yields ONE trace tree spanning
proxy -> quorum round -> >=2f+1 replica handlers, `GET /metrics` serves
parseable Prometheus text, and a Nemesis-triggered fault freezes the
faulting trace into a JSONL incident file.
"""

import asyncio
import json
import random
import re
import threading

import pytest

from dds_tpu.core.chaos import ChaosNet, LinkFaults
from dds_tpu.core.quorum_client import AbdClient, AbdClientConfig
from dds_tpu.core.replica import BFTABDNode, ReplicaConfig
from dds_tpu.core.transport import InMemoryNet
from dds_tpu.http.miniserver import http_request
from dds_tpu.http.server import DDSRestServer, ProxyConfig
from dds_tpu.malicious.trudy import Nemesis
from dds_tpu.obs import context as obs_context
from dds_tpu.obs import kprof
from dds_tpu.obs.flight import FlightRecorder, flight
from dds_tpu.obs.metrics import Registry, metrics
from dds_tpu.utils.trace import Tracer, tracer

pytestmark = pytest.mark.obs


def run(coro):
    return asyncio.run(coro)


# ------------------------------------------------------------- tracer core


def test_ring_buffer_bound_evicts_oldest():
    t = Tracer(max_events=32)
    for i in range(100):
        t.record(f"s{i}", 1.0)
    evs = t.events()
    assert len(evs) == 32
    assert evs[0].name == "s68" and evs[-1].name == "s99"


def test_summary_excludes_counters_and_zero_duration_events():
    t = Tracer()
    for d in (1.0, 2.0, 3.0):
        t.record("op", d)
    t.event("op")  # same NAME as the span family — must not inflate count
    t.event("annotation")
    s = t.summary()
    assert s["op"]["count"] == 3 and s["op"]["mean_ms"] == 2.0
    assert "annotation" not in s


def test_percentiles_nearest_rank_small_k():
    t = Tracer()
    for d in range(1, 21):  # 1..20 ms
        t.record("op", float(d))
    s = t.summary()["op"]
    # nearest-rank: p95 of 20 samples is the 19th value, NOT the max
    assert s["p95_ms"] == 19.0
    assert s["p50_ms"] == 10.0

    t2 = Tracer()
    t2.record("one", 7.0)
    assert t2.summary()["one"]["p95_ms"] == 7.0  # k=1 must not index [-1]


def test_thread_safety_under_concurrent_record_and_count():
    t = Tracer(max_events=100_000)
    n_threads, per = 8, 500

    idents = set()

    def work():
        idents.add(threading.get_ident())
        for i in range(per):
            t.record("op", float(i))
            t.event("hits")

    threads = [threading.Thread(target=work) for _ in range(n_threads)]
    for th in threads:
        th.start()
    for th in threads:
        th.join()
    assert len(t.events("hits")) == n_threads * per
    assert t.summary()["op"]["count"] == n_threads * per
    # every record names the thread that made it
    assert {e.tid for e in t.events("op")} == idents


def test_event_dict_namespaces_meta():
    t = Tracer()
    # hostile meta: keys that collide with the record's own fields
    t.record("real-name", 42.0, name="shadow", ts=-1, dur_ms=0.0)
    (e,) = t.events()
    rec = json.loads(json.dumps(Tracer.event_dict(e)))
    assert rec["name"] == "real-name" and rec["dur_ms"] == 42.0
    assert rec["meta"] == {"name": "shadow", "ts": -1, "dur_ms": 0.0}


def test_nested_spans_link_parent_child():
    t = Tracer()
    with t.span("outer"):
        with t.span("inner"):
            pass
    inner, outer = t.events("inner")[0], t.events("outer")[0]
    assert inner.trace_id == outer.trace_id
    assert inner.parent_id == outer.span_id
    assert t.trace_events(outer.trace_id) == [inner, outer]


# ---------------------------------------------------------- trace context


def test_context_wire_and_header_round_trip():
    ctx = obs_context.root()
    back = obs_context.from_wire(obs_context.to_wire(ctx))
    assert (back.trace_id, back.span_id) == (ctx.trace_id, ctx.span_id)
    h = obs_context.from_header(obs_context.to_header(ctx))
    assert (h.trace_id, h.span_id) == (ctx.trace_id, ctx.span_id)


def test_context_malformed_degrades_to_none():
    for garbage in (None, "x", 7, [], {"t": 3, "s": "ok"}, {"t": "", "s": "y"}):
        assert obs_context.from_wire(garbage) is None
    for header in ("", "noseparator", "-", "a" * 40 + "-b"):
        assert obs_context.from_header(header) is None


def test_child_derives_from_parent():
    root = obs_context.root()
    c = obs_context.child(root)
    assert c.trace_id == root.trace_id and c.parent_id == root.span_id
    assert c.span_id != root.span_id


# --------------------------------------------------------- MetricsRegistry


def test_registry_counters_gauges_and_kind_conflict():
    r = Registry()
    r.inc("reqs_total", route="a")
    r.inc("reqs_total", 2, route="a")
    r.set("depth", 7.5)
    assert r.value("reqs_total", route="a") == 3
    assert r.value("depth") == 7.5
    with pytest.raises(ValueError):
        r.set("reqs_total", 1)  # counter re-registered as gauge


_LINE = re.compile(
    r"^[a-zA-Z_:][a-zA-Z0-9_:]*"            # metric name
    r'(\{[a-zA-Z0-9_]+="(\\.|[^"\\])*"'      # first label
    r'(,[a-zA-Z0-9_]+="(\\.|[^"\\])*")*\})?' # more labels
    r" [0-9.eE+-]+$|^[a-zA-Z_:][a-zA-Z0-9_:]*(\{.*\})? \+Inf$"
)


def _parse_prom(text: str) -> dict[str, float]:
    """Tiny exposition parser: {name{labels}: value}; asserts line syntax."""
    out = {}
    for line in text.splitlines():
        if not line or line.startswith("#"):
            continue
        assert _LINE.match(line), f"unparseable exposition line: {line!r}"
        series, value = line.rsplit(" ", 1)
        out[series] = float(value)
    return out


def test_histogram_exposition_round_trip():
    r = Registry()
    for v in (0.0005, 0.003, 0.003, 0.04, 99.0):
        r.observe("lat_seconds", v, buckets=(0.001, 0.01, 0.1), op="w")
    parsed = _parse_prom(r.render())
    assert parsed['lat_seconds_bucket{op="w",le="0.001"}'] == 1
    assert parsed['lat_seconds_bucket{op="w",le="0.01"}'] == 3
    assert parsed['lat_seconds_bucket{op="w",le="0.1"}'] == 4
    assert parsed['lat_seconds_bucket{op="w",le="+Inf"}'] == 5  # overflow obs
    assert parsed['lat_seconds_count{op="w"}'] == 5
    assert abs(parsed['lat_seconds_sum{op="w"}'] - 99.0465) < 1e-9
    assert r.histogram_stats("lat_seconds", op="w") == {
        "count": 5, "sum": 0.0005 + 0.003 + 0.003 + 0.04 + 99.0,
    }


def test_label_values_escaped():
    r = Registry()
    r.inc("c_total", route='we"ird\nkey\\x')
    text = r.render()
    assert '\\"' in text and "\\n" in text and "\\\\" in text
    assert "\n\n" not in text  # the raw newline never splits the line


# ------------------------------------------------------------------- kprof


def test_cache_event_accounting_and_counted():
    import functools

    kprof.reset()
    calls = []

    @functools.lru_cache(maxsize=None)
    def build(n):
        calls.append(n)
        return n * 2

    assert kprof.counted("t.cache", build, 3) == 6  # miss
    assert kprof.counted("t.cache", build, 3) == 6  # hit
    kprof.cache_event("t.cache", hit=True)
    stats = kprof.kernel_summary()["compile_cache"]["t.cache"]
    assert stats == {"hits": 2, "misses": 1, "hit_rate": round(2 / 3, 4)}
    assert calls == [3]


def test_profiled_splits_dispatch_from_execute():
    import jax.numpy as jnp

    tracer.reset()
    out = kprof.profiled("testk", lambda: jnp.arange(8) * 2, k=8)
    assert list(out) == list(range(0, 16, 2))
    s = tracer.summary()
    assert s["kernel.testk.dispatch"]["count"] == 1
    assert s["kernel.testk.execute"]["count"] == 1
    ks = kprof.kernel_summary()
    assert ks["dispatch_ms"] >= 0 and ks["execute_ms"] >= 0


# --------------------------------------------------------- flight recorder


def test_flight_recorder_disabled_without_dir():
    fr = FlightRecorder(dir=None)
    assert not fr.enabled and fr.record("breaker_open") is None


def test_flight_recorder_writes_incident_with_faulting_trace(tmp_path):
    tracer.reset()
    fr = FlightRecorder(dir=str(tmp_path), min_interval=0.0)
    with tracer.span("http.GET.GetSet") as _:
        ctx = obs_context.current()
        with tracer.span("abd.fetch"):
            pass
        path = fr.record("deadline_exceeded", trace_id=ctx.trace_id,
                         route="GetSet")
    assert path is not None
    lines = [json.loads(l) for l in open(path)]
    header, rest = lines[0], lines[1:]
    assert header["incident"] == "deadline_exceeded"
    assert header["trace_id"] == ctx.trace_id
    assert header["info"] == {"route": "GetSet"}
    trace_lines = [l for l in rest if l.get("section") == "trace"]
    assert {l["trace_id"] for l in trace_lines} == {ctx.trace_id}
    assert "abd.fetch" in {l["name"] for l in trace_lines}
    assert not list(tmp_path.glob("*.tmp"))  # atomic: no leftover temp file


def test_flight_recorder_rate_limits_per_kind(tmp_path):
    fr = FlightRecorder(dir=str(tmp_path), min_interval=60.0)
    assert fr.record("breaker_open") is not None
    assert fr.record("breaker_open") is None          # suppressed
    assert fr.record("suspicion_quorum") is not None  # other kinds unaffected


def test_flight_recorder_prunes_old_incidents(tmp_path):
    fr = FlightRecorder(dir=str(tmp_path), max_incidents=2, min_interval=0.0)
    for i in range(5):
        fr.record(f"kind_{i}")
    left = sorted(tmp_path.glob("incident-*.jsonl"))
    assert len(left) == 2
    assert all("kind_3" in p.name or "kind_4" in p.name for p in left)


# --------------------------------------------- end-to-end REST acceptance


async def _obs_rest_stack(seed=21, budget=10.0, timeout=2.0, **proxy_kw):
    """7-replica / q=5 (f=2) cluster behind a mildly-delaying ChaosNet."""
    net = ChaosNet(InMemoryNet(), seed=seed)
    net.default_faults = LinkFaults(delay=0.001, jitter=0.002)
    addrs = [f"replica-{i}" for i in range(7)]
    replicas = {
        a: BFTABDNode(a, addrs, "supervisor", net, ReplicaConfig(quorum_size=5))
        for a in addrs
    }
    abd = AbdClient(
        "proxy-0", net, addrs,
        AbdClientConfig(request_timeout=timeout, quorum_size=5),
    )
    server = DDSRestServer(
        abd,
        ProxyConfig(host="127.0.0.1", port=0, request_budget=budget,
                    trace_route_enabled=True, **proxy_kw),
    )
    await server.start()
    return net, server, replicas


async def _call(server, method, target, obj=None):
    body = json.dumps(obj).encode() if obj is not None else None
    return await http_request(
        "127.0.0.1", server.cfg.port, method, target, body, timeout=10.0
    )


def test_request_under_chaos_yields_single_trace_tree():
    """Acceptance: one REST request under an active ChaosNet schedule
    produces ONE trace tree — proxy route span -> quorum round -> >=2f+1
    replica handler spans with per-replica attribution — plus chaos
    annotations on the same trace."""

    async def go():
        net, server, _ = await _obs_rest_stack()
        try:
            tracer.reset()
            status, _ = await _call(
                server, "POST", "/PutSet", {"contents": ["a", "b"]}
            )
            assert status == 200
            await net.quiesce()
        finally:
            await server.stop()

    run(go())
    roots = tracer.events("http.POST.PutSet")
    assert len(roots) == 1
    root = roots[0]
    assert root.trace_id and root.parent_id is None
    tree = tracer.trace_events(root.trace_id)

    # the quorum round is a direct child of the route span
    writes = [e for e in tree if e.name == "abd.write"]
    assert writes and all(e.parent_id == root.span_id for e in writes)
    assert writes[0].meta.get("coordinator", "").startswith("replica-")

    # >=2f+1 DISTINCT replicas served handler spans inside this one trace
    handlers = [e for e in tree if e.name == "replica.handle"]
    assert all(e.parent_id is not None for e in handlers)
    assert len({e.meta["replica"] for e in handlers}) >= 5

    # the fabric's injections annotate the same trace
    chaos_events = [e for e in tree if e.name.startswith("chaos.")]
    assert chaos_events and all(e.kind == "event" for e in chaos_events)


def test_metrics_route_serves_parseable_prometheus_text():
    """Acceptance: GET /metrics is Prometheus exposition text covering
    route latency histograms, quorum RTT, and compile-cache hit rate."""
    from dds_tpu.ops.foldmany import fold_weighted

    # drive the instrumented kernel path so compile-cache series exist
    n = 7 * 11
    assert fold_weighted([2, 3], [[1, 1], [2, 1]], n) == [6, 12]
    fold_weighted([2, 3], [[1, 1], [2, 1]], n)  # second call: cache hit

    async def go():
        net, server, _ = await _obs_rest_stack()
        try:
            status, _ = await _call(
                server, "POST", "/PutSet", {"contents": ["x"]}
            )
            assert status == 200
            status, body = await _call(server, "GET", "/metrics")
            assert status == 200
            await net.quiesce()
            return body.decode()
        finally:
            await server.stop()

    text = run(go())
    parsed = _parse_prom(text)

    def series(prefix):
        return {k: v for k, v in parsed.items() if k.startswith(prefix)}

    # route latency histogram, labelled by route
    buckets = series("dds_http_request_seconds_bucket")
    assert any('route="PutSet"' in k for k in buckets)
    assert any('le="+Inf"' in k for k in buckets)
    # quorum round-trips observed
    assert sum(series("dds_quorum_rtt_seconds_count").values()) >= 1
    # compile-cache accounting from the kernel path (1 miss, then hits)
    cache = series("dds_compile_cache_total")
    hits = sum(v for k, v in cache.items()
               if 'cache="fold_weighted"' in k and 'outcome="hit"' in k)
    misses = sum(v for k, v in cache.items()
                 if 'cache="fold_weighted"' in k and 'outcome="miss"' in k)
    assert misses >= 1 and hits >= 1
    # scrape-time state gauges
    assert series("dds_trusted_replicas")
    assert any(k.startswith("dds_breaker_state") for k in parsed)


def test_trace_route_reports_span_summary_only():
    async def go():
        net, server, _ = await _obs_rest_stack()
        try:
            tracer.reset()
            await _call(server, "POST", "/PutSet", {"contents": ["y"]})
            status, body = await _call(server, "GET", "/_trace")
            assert status == 200
            await net.quiesce()
            return json.loads(body)
        finally:
            await server.stop()

    out = run(go())
    # occurrences are counted in obs.metrics (GET /metrics), not here
    assert set(out) == {"spans", "stored_keys"}
    assert "http.POST.PutSet" in out["spans"]


def test_nemesis_fault_writes_incident_containing_faulting_trace(tmp_path):
    """Acceptance: a Nemesis partition makes a request degrade, and the
    flight recorder freezes that request's trace into a JSONL incident."""

    async def go():
        net, server, _ = await _obs_rest_stack(
            seed=5, budget=0.5, timeout=0.1,
            retry_backoff=0.02, retry_max_delay=0.05,
        )
        flight.configure(dir=str(tmp_path), min_interval=0.0)
        try:
            nem = Nemesis(net, [f"replica-{i}" for i in range(7)],
                          max_faults=7, rng=random.Random(3))
            assert len(nem.trigger("partition")) == 7  # total partition
            status, _ = await _call(server, "GET", "/GetSet/" + "ab" * 64)
            assert status == 503
            await net.quiesce()
        finally:
            flight.configure(dir="")  # back to disabled for other tests
            await server.stop()

    run(go())
    incidents = sorted(tmp_path.glob("incident-*.jsonl"))
    assert incidents
    kinds = {}
    for p in incidents:
        lines = [json.loads(l) for l in open(p)]
        kinds[lines[0]["incident"]] = lines
    # the attack itself recorded an incident...
    assert "attack_partition" in kinds
    # ...and the degraded request recorded one CONTAINING its trace
    fault = kinds.get("deadline_exceeded") or kinds.get("no_trusted_nodes")
    assert fault is not None
    header, rest = fault[0], fault[1:]
    assert header["trace_id"]
    trace_lines = [l for l in rest if l.get("section") == "trace"]
    assert trace_lines
    assert all(l["trace_id"] == header["trace_id"] for l in trace_lines)
    names = {l["name"] for l in trace_lines}
    assert any(n.startswith("http.GET") for n in names)  # the route span
