"""Bulwark overload-control tests (ISSUE 7).

The admission math — token-bucket refill/burst, priority ordering,
shed/unshed hysteresis — runs on FAKE clocks, so
every ratchet step is deterministic. The storage-layer fast-fail and the
REST surface (429/503 with derived Retry-After, exempt observability
routes) run on small real stacks. The flagship drives a seeded ChaosNet
flood twice — admission off, then on — and asserts the acceptance claim:
Bulwark-enabled interactive goodput beats the no-admission baseline,
shed requests complete in a fraction of the Deadline budget, transitions
are flight-recorded with dds_admission_* metrics, and /health + /slo
stay reachable throughout.
"""

import asyncio
import contextlib
import json
import random
import time

import pytest

from dds_tpu.core.admission import (
    CLASSES,
    AdmissionController,
    TokenBucket,
    route_class,
)
from dds_tpu.core.errors import AllBreakersOpenError
from dds_tpu.core.quorum_client import AbdClient, AbdClientConfig
from dds_tpu.http.miniserver import http_request, http_request_full
from dds_tpu.http.server import DDSRestServer, ProxyConfig
from dds_tpu.core.transport import InMemoryNet
from dds_tpu.obs.metrics import metrics
from dds_tpu.utils.config import AdmissionConfig, DDSConfig
from dds_tpu.utils.retry import Deadline

pytestmark = pytest.mark.overload


class FakeClock:
    def __init__(self, t: float = 1000.0):
        self.t = t

    def __call__(self) -> float:
        return self.t

    def advance(self, dt: float) -> None:
        self.t += dt


# ------------------------------------------------------ token-bucket math


def test_token_bucket_burst_refill_and_eta():
    clk = FakeClock()
    b = TokenBucket(rate=2.0, burst=4.0, clock=clk)
    # the full burst is available up front, then the bucket is dry
    assert all(b.try_acquire() for _ in range(4))
    assert not b.try_acquire()
    # refill is linear in elapsed time: 0.5 s -> 1 token
    assert b.refill_eta() == pytest.approx(0.5)
    clk.advance(0.5)
    assert b.try_acquire()
    assert not b.try_acquire()
    # capacity clamps: a long idle period never exceeds the burst
    clk.advance(3600.0)
    assert b.tokens == pytest.approx(4.0)
    for _ in range(4):
        b.try_acquire()
    # eta for a multi-token ask scales with the deficit
    assert b.refill_eta(3.0) == pytest.approx(1.5)


def test_token_bucket_zero_rate_never_refills():
    clk = FakeClock()
    b = TokenBucket(rate=0.0, burst=1.0, clock=clk)
    assert b.try_acquire()
    clk.advance(1e6)
    assert not b.try_acquire()
    assert b.refill_eta() == float("inf")


def test_route_priority_classes_and_overrides():
    assert CLASSES[route_class("GetSet")] == "interactive"
    assert CLASSES[route_class("PutSet")] == "interactive"
    assert CLASSES[route_class("SumAll")] == "aggregate"
    assert CLASSES[route_class("MatVec")] == "aggregate"
    assert CLASSES[route_class("_sync")] == "background"
    assert CLASSES[route_class("NoSuchRoute")] == "background"
    # operator overrides win; junk override values are ignored
    assert CLASSES[route_class("SearchEq", {"SearchEq": "background"})] \
        == "background"
    assert CLASSES[route_class("SumAll", {"SumAll": "bogus"})] == "aggregate"


# ------------------------------------------------- shed ratchet/hysteresis


def _controller(clk, alerts=None, breakers=None, **kw):
    state = {"alerts": alerts or set(), "breakers": breakers or (0, [])}
    kw.setdefault("rates", {})  # unthrottled: these tests isolate shedding
    c = AdmissionController(
        eval_interval=1.0,
        shed_hold=3,
        max_shed_level=kw.pop("max_shed_level", 3),
        alerts=lambda: state["alerts"],
        breakers=lambda: state["breakers"],
        clock=clk,
        **kw,
    )
    return c, state


def test_shed_ratchet_sheds_lowest_class_first():
    clk = FakeClock()
    c, state = _controller(clk)
    assert c.decide("_sync").admitted  # healthy: everything flows
    state["alerts"] = {"GetSet"}  # interactive burning budget = distress
    for expected in (1, 2, 3):
        clk.advance(1.0)
        assert c.evaluate() == expected
    clk.advance(1.0)
    assert c.evaluate() == 3  # clamped at max_shed_level

    # priority ordering at each level, checked via fresh controllers
    for level, admitted in ((1, {"GetSet": True, "SumAll": True, "_sync": False}),
                            (2, {"GetSet": True, "SumAll": False, "_sync": False}),
                            (3, {"GetSet": False, "SumAll": False, "_sync": False})):
        c2, s2 = _controller(FakeClock())
        c2.shed_level = level
        for route, want in admitted.items():
            d = c2.decide(route)
            assert d.admitted == want, (level, route)
            if not want:
                assert d.status == 503


def test_unshed_hysteresis_steps_down_one_level_per_hold():
    clk = FakeClock()
    c, state = _controller(clk)
    state["alerts"] = {"SumAll"}
    clk.advance(1.0)
    assert c.evaluate() == 1
    clk.advance(1.0)
    assert c.evaluate() == 2
    # recovery: alert clears, but un-shedding needs shed_hold=3 clean
    # evaluations per level — and any distress resets the streak
    state["alerts"] = set()
    clk.advance(1.0)
    assert c.evaluate() == 2
    clk.advance(1.0)
    assert c.evaluate() == 2
    state["alerts"] = {"GetSet"}  # relapse mid-recovery
    clk.advance(1.0)
    assert c.evaluate() == 3  # distress ratchets straight back up
    state["alerts"] = set()
    for _ in range(2):
        clk.advance(1.0)
        assert c.evaluate() == 3
    clk.advance(1.0)
    assert c.evaluate() == 2  # third clean eval: one level down
    for _ in range(6):  # two more holds of 3 walk 2 -> 1 -> 0
        clk.advance(1.0)
        c.evaluate()
    assert c.shed_level == 0  # and eventually all the way down


def test_shed_class_burn_does_not_latch_the_ratchet():
    """A shed class 503s by construction; its own burn alert must not
    count as distress or the ratchet could never recover."""
    clk = FakeClock()
    c, state = _controller(clk)
    state["alerts"] = {"_sync"}  # background burning
    clk.advance(1.0)
    assert c.evaluate() == 1  # background now shed
    # the background alert keeps firing (shed 503s burn its budget), but
    # it is no longer a SERVED class: clean evals walk the level back down
    for _ in range(3):
        clk.advance(1.0)
        c.evaluate()
    assert c.shed_level == 0


def test_breaker_census_triggers_shed_and_retry_after():
    clk = FakeClock()
    c, state = _controller(clk)
    state["breakers"] = (4, [3.2, 5.0])  # 2 of 4 refusing = fraction 0.5
    clk.advance(1.0)
    assert c.evaluate() == 1
    d = c.decide("_sync")
    assert not d.admitted and d.status == 503
    # shed Retry-After prefers the nearest breaker half-open probe
    assert d.retry_after == pytest.approx(3.2)
    # without breaker ETAs it falls back to the ratchet cadence
    state["breakers"] = (4, [])
    state["alerts"] = {"GetSet"}
    d = c.decide("_sync")
    assert d.retry_after == pytest.approx(c.eval_interval * c.shed_hold)


def test_tenant_token_buckets_isolate_the_hot_tenant():
    clk = FakeClock()
    c = AdmissionController(
        rates={"interactive": (1.0, 2.0)}, clock=clk,
        eval_interval=1e9,  # no ratchet in this test
    )
    assert c.decide("GetSet", tenant="hot").admitted
    assert c.decide("GetSet", tenant="hot").admitted
    d = c.decide("GetSet", tenant="hot")
    assert not d.admitted and d.status == 429
    assert d.retry_after == pytest.approx(1.0)  # 1 token at 1/s
    # a different tenant has its own bucket: unaffected
    assert c.decide("GetSet", tenant="cold").admitted
    # ...and the hot tenant recovers by waiting out the eta
    clk.advance(1.0)
    assert c.decide("GetSet", tenant="hot").admitted


def test_transitions_are_metered_and_flight_recorded(tmp_path):
    from dds_tpu.obs.flight import flight

    clk = FakeClock()
    flight.configure(dir=str(tmp_path), min_interval=0.0)
    try:
        c, state = _controller(clk)
        state["alerts"] = {"GetSet"}
        clk.advance(1.0)
        c.evaluate()
        state["alerts"] = set()
        for _ in range(3):
            clk.advance(1.0)
            c.evaluate()
        assert c.shed_level == 0
        assert [t["direction"] for t in c.transitions] == ["shed", "unshed"]
        assert (metrics.value("dds_admission_transitions_total",
                              direction="shed", reason="slo_burn") or 0) >= 1
        assert (metrics.value("dds_admission_transitions_total",
                              direction="unshed", reason="recovered") or 0) >= 1
        index = (tmp_path / "index.jsonl").read_text().splitlines()
        kinds = [json.loads(line)["kind"] for line in index]
        assert "admission_shed" in kinds and "admission_unshed" in kinds
    finally:
        flight.configure(dir="")


# ------------------------------------------------- storage-layer fast-fail


def _open_all_breakers(abd: AbdClient, reset: float):
    from dds_tpu.utils.retry import CircuitBreaker

    for n in abd.replicas.get_trusted():
        b = abd.breakers[n] = CircuitBreaker(3, reset, name=n)
        for _ in range(3):
            b.record_failure()
        assert not b.allow()


def test_fast_fail_when_no_probe_fits_the_budget():
    """All trusted coordinators' breakers open, nearest half-open probe
    beyond the remaining budget: the op must degrade in microseconds with
    the typed error instead of burning the Deadline on futile attempts."""

    async def go():
        net = InMemoryNet()
        abd = AbdClient("proxy-ff", net, ["r0", "r1"],
                        AbdClientConfig(request_timeout=5.0, quorum_size=2))
        _open_all_breakers(abd, reset=60.0)
        dl = Deadline(0.5)
        t0 = time.perf_counter()
        with pytest.raises(AllBreakersOpenError) as ei:
            await abd.fetch_set("k", deadline=dl)
        assert time.perf_counter() - t0 < 0.1  # no timeout was burned
        assert ei.value.eta > dl.remaining()
        assert ei.value.targets == 2
        # the batched tag round fast-fails identically
        with pytest.raises(AllBreakersOpenError):
            await abd.read_tags(["k"], deadline=dl)
        assert (metrics.value("dds_fast_fail_total", op="fetch") or 0) >= 1

    asyncio.run(go())


def test_no_fast_fail_while_a_probe_still_fits():
    """With the half-open probe inside the budget, the degraded try must
    proceed (it is what heals the breaker) — here it times out against
    unregistered endpoints instead of failing instantly."""

    async def go():
        net = InMemoryNet()
        abd = AbdClient("proxy-ff2", net, ["r0", "r1"],
                        AbdClientConfig(request_timeout=0.05))
        _open_all_breakers(abd, reset=0.2)
        with pytest.raises(asyncio.TimeoutError):
            await abd.fetch_set("k", deadline=Deadline(1.0))

    asyncio.run(go())


def test_fast_fail_disabled_by_config_flag():
    async def go():
        net = InMemoryNet()
        abd = AbdClient(
            "proxy-ff3", net, ["r0"],
            AbdClientConfig(request_timeout=0.05, fast_fail_all_open=False),
        )
        _open_all_breakers(abd, reset=60.0)
        with pytest.raises(asyncio.TimeoutError):
            await abd.fetch_set("k", deadline=Deadline(0.5))

    asyncio.run(go())


# ------------------------------------------------------------ REST surface


@contextlib.asynccontextmanager
async def admission_stack(acfg: AdmissionConfig | None = None, n=4, quorum=3):
    from dds_tpu.core.replica import BFTABDNode, ReplicaConfig

    net = InMemoryNet()
    rcfg = ReplicaConfig(quorum_size=quorum)
    addrs = [f"replica-{i}" for i in range(n)]
    replicas = {a: BFTABDNode(a, addrs, "supervisor", net, rcfg) for a in addrs}
    abd = AbdClient("proxy-0", net, addrs,
                    AbdClientConfig(request_timeout=2.0, quorum_size=quorum))
    server = DDSRestServer(
        abd, ProxyConfig(host="127.0.0.1", port=0, admission=acfg)
    )
    await server.start()
    try:
        yield server, replicas
    finally:
        await server.stop()


def test_throttle_answers_429_with_refill_retry_after():
    acfg = AdmissionConfig(enabled=True, aggregate_rate=0.5,
                           aggregate_burst=1.0, eval_interval=1e9)

    async def go():
        async with admission_stack(acfg) as (server, _):
            status, _ = await http_request(
                "127.0.0.1", server.cfg.port, "POST", "/PutSet",
                json.dumps({"contents": ["12345"]}).encode(),
            )
            assert status == 200
            status, _ = await http_request(
                "127.0.0.1", server.cfg.port, "GET",
                "/SumAll?position=0&nsqr=77",
            )
            assert status == 200  # burst of 1
            t0 = time.perf_counter()
            status, headers, _ = await http_request_full(
                "127.0.0.1", server.cfg.port, "GET",
                "/SumAll?position=0&nsqr=77",
            )
            assert status == 429
            assert time.perf_counter() - t0 < 0.2  # microseconds, not budget
            # Retry-After = ceil(refill eta) at 0.5 tokens/s = 2 s
            assert headers["retry-after"] == "2"
            assert (metrics.value("dds_admission_requests_total",
                                  outcome="throttled",
                                  **{"class": "aggregate"}) or 0) >= 1

    asyncio.run(go())


def test_tenant_header_separates_budgets_at_the_edge():
    acfg = AdmissionConfig(enabled=True, interactive_rate=0.1,
                           interactive_burst=1.0, eval_interval=1e9)

    async def go():
        async with admission_stack(acfg) as (server, _):
            async def get(tenant):
                r, w = await asyncio.open_connection("127.0.0.1",
                                                     server.cfg.port)
                w.write(
                    b"GET /GetSet/deadbeef HTTP/1.1\r\nHost: x\r\n"
                    b"x-dds-tenant: " + tenant.encode() + b"\r\n"
                    b"Content-Length: 0\r\nConnection: close\r\n\r\n"
                )
                await w.drain()
                status = int((await r.readline()).split()[1])
                w.close()
                return status

            assert await get("alice") == 404  # admitted (missing key)
            assert await get("alice") == 429  # alice's bucket is dry
            assert await get("bob") == 404    # bob's is not

    asyncio.run(go())


def test_observability_routes_answer_during_a_full_shed():
    """ISSUE 7 satellite: /health, /metrics, /slo (and /shards where
    sharded) are admission-exempt so the system stays debuggable while
    overloaded — a full shed must not silence them."""
    acfg = AdmissionConfig(enabled=True, max_shed_level=3, eval_interval=1e9)

    async def go():
        async with admission_stack(acfg) as (server, _):
            server.admission.shed_level = 3  # force a full shed
            status, headers, _ = await http_request_full(
                "127.0.0.1", server.cfg.port, "GET", "/GetSet/abc"
            )
            assert status == 503 and "retry-after" in headers
            status, body = await http_request(
                "127.0.0.1", server.cfg.port, "GET", "/health"
            )
            assert status in (200, 503) and json.loads(body)["status"]
            status, body = await http_request(
                "127.0.0.1", server.cfg.port, "GET", "/metrics"
            )
            assert status == 200
            assert "dds_admission_shed_level 3" in body.decode()
            status, body = await http_request(
                "127.0.0.1", server.cfg.port, "GET", "/slo"
            )
            assert status == 200
            report = json.loads(body)["admission"]
            assert report["shed_level"] == 3
            assert report["shedding"] == list(CLASSES)

    asyncio.run(go())


def test_degraded_retry_after_derived_from_breaker_eta():
    """ISSUE 7 satellite: the 503 paths derive Retry-After from the
    nearest breaker half-open ETA instead of the config constant, which
    remains only as the fallback."""

    async def go():
        async with admission_stack(None) as (server, _):
            assert server.admission is None  # admission off: still derived
            server.abd.breaker_census = lambda: (4, [3.2, 9.0])
            resp = server._unavailable("quorum down")
            assert resp.headers["Retry-After"] == "4"
            # an explicit candidate (fast-fail ETA) can be nearer still
            resp = server._unavailable("quorum down", eta=1.4)
            assert resp.headers["Retry-After"] == "2"
            # no measurable recovery pending -> the config hint
            server.abd.breaker_census = lambda: (4, [])
            resp = server._unavailable("quorum down")
            assert resp.headers["Retry-After"] == str(
                max(1, round(server.cfg.retry_after_hint))
            )

    asyncio.run(go())


# --------------------------------------------------- flagship: the cliff


def _overload_cfg(admission: bool, seed: int, budget: float,
                  flight_dir: str = "") -> DDSConfig:
    cfg = DDSConfig()
    cfg.replicas.endpoints = [f"replica-{i}" for i in range(4)]
    cfg.replicas.sentinent = []
    cfg.replicas.byz_quorum_size = 3
    cfg.replicas.byz_max_faults = 1
    cfg.proxy.port = 0
    cfg.proxy.request_budget = budget
    cfg.proxy.intranet_request_timeout = budget / 2
    cfg.recovery.enabled = False
    cfg.recovery.anti_entropy_enabled = False
    cfg.obs.audit_enabled = False
    cfg.obs.flight_dir = flight_dir
    cfg.obs.slo_fast_window = 1.0
    cfg.obs.slo_slow_window = 2.0
    cfg.attacks.enabled = True
    cfg.attacks.chaos_enabled = True
    cfg.attacks.chaos_seed = seed
    cfg.admission.enabled = admission
    cfg.admission.eval_interval = 0.1
    cfg.admission.shed_hold = 8
    # admit enough aggregates that the SLO engine SEES the overload (they
    # exhaust their budgets and burn), so the shed ratchet fires mid-run
    cfg.admission.aggregate_rate = 30.0
    cfg.admission.aggregate_burst = 30.0
    # an aggressive aggregate objective: admitted folds running past 20 ms
    # under overload burn the SumAll budget, so the multiwindow alert (and
    # with it the shed ratchet) fires organically mid-run
    cfg.obs.slo_routes = {"SumAll": {"objective": 0.99, "latency-ms": 20.0}}
    return cfg


async def _drive_overload(admission: bool, tmp_path) -> dict:
    """One seeded ChaosNet flood run; returns goodput + shed stats."""
    from dds_tpu.run import launch

    seed, budget, duration, bits, n_keys = 7, 1.0, 1.6, 4096, 160
    flight_dir = str(tmp_path / ("bulwark" if admission else "baseline"))
    dep = await launch(_overload_cfg(admission, seed, budget, flight_dir))
    host, port = "127.0.0.1", dep.server.cfg.port
    rng = random.Random(seed)
    modulus = (1 << bits) - 159
    keys = []
    for _ in range(n_keys):
        status, body = await http_request(
            host, port, "POST", "/PutSet",
            json.dumps(
                {"contents": [str(rng.getrandbits(bits) % modulus)]}
            ).encode(), timeout=10.0,
        )
        assert status == 200
        keys.append(body.decode())

    results: list[tuple[str, int, float, bool]] = []
    probes: list[tuple[str, int]] = []

    async def call(klass, method, target):
        t0 = time.perf_counter()
        try:
            status, data = await http_request(host, port, method, target,
                                              timeout=budget + 2.0)
        except (OSError, asyncio.TimeoutError, EOFError, ConnectionError):
            status, data = -1, b""
        # admission rejections (429 throttle / 503 shed) vs degraded 503s
        # that burned their budget first: the rejection body is explicit,
        # so the "fail in microseconds" claim is measured on exactly the
        # requests Bulwark rejected at the edge
        rejected = status == 429 or (
            status == 503 and data.startswith(b"admission rejected")
        )
        results.append((klass, status, time.perf_counter() - t0, rejected))

    async def probe():
        # the acceptance claim: observability stays reachable THROUGHOUT
        for route in ("/health", "/slo"):
            try:
                status, _ = await http_request(host, port, "GET", route,
                                               timeout=2.0)
            except (OSError, asyncio.TimeoutError, EOFError, ConnectionError):
                status = -1
            probes.append((route, status))

    # Event-driven run length (the PR 5 delay-storm treatment): the old
    # fixed 1.6 s duration raced the shed ratchet against CI load — on a
    # slow machine the SLO burn windows could still be filling when the
    # drive stopped, and the "ratchet actually fired" assertion flaked.
    # Subscribing to the controller's transition hook makes the signal
    # explicit: the Bulwark run keeps driving (same open-loop schedule)
    # until the shed transition has BEEN OBSERVED, up to a hard cap, then
    # finishes the measurement window. The baseline run has no ratchet
    # and keeps the original duration.
    shed_seen = asyncio.Event()
    if admission:
        dep.server.admission.subscribe(
            lambda rec: shed_seen.set() if rec["direction"] == "shed" else None
        )
    max_duration = duration * 4

    dep.trudy.trigger("delay")
    sched = random.Random(seed + 1)
    tasks, t0, t = [], time.perf_counter(), 0.0
    flood_at, probe_at = 0.0, 0.0
    while t < duration or (
        admission and not shed_seen.is_set() and t < max_duration
    ):
        now = time.perf_counter() - t0
        if now < t:
            await asyncio.sleep(t - now)
        if t >= flood_at:
            dep.trudy.trigger("flood")
            flood_at += 0.3
        if t >= probe_at:
            tasks.append(asyncio.ensure_future(probe()))
            probe_at += 0.4
        # ~12 interactive + ~220 aggregate arrivals per second (open loop)
        key = keys[sched.randrange(len(keys))]
        tasks.append(asyncio.ensure_future(
            call("interactive", "GET", f"/GetSet/{key}")))
        for _ in range(18):
            tasks.append(asyncio.ensure_future(
                call("aggregate", "GET", f"/SumAll?position=0&nsqr={modulus}")))
        t += 0.08
    await asyncio.wait_for(asyncio.gather(*tasks), budget + 30.0)
    wall = time.perf_counter() - t0
    transitions = list(dep.server.admission.transitions) if admission else []
    await dep.stop()

    good = sum(1 for k, s, lat, _ in results
               if k == "interactive" and s == 200 and lat <= 0.3)
    shed_lat = sorted(lat for _, _, lat, rejected in results if rejected)
    return {
        "goodput": good / wall,
        "interactive": sum(1 for k, *_ in results if k == "interactive"),
        "shed": len(shed_lat),
        "shed_p50": shed_lat[len(shed_lat) // 2] if shed_lat else 0.0,
        "shed_p95": shed_lat[int(0.95 * len(shed_lat))] if shed_lat else 0.0,
        "probes": probes,
        "transitions": transitions,
        "flight_dir": flight_dir,
        "budget": budget,
    }


def test_overload_goodput_bulwark_beats_the_503_cliff(tmp_path):
    """Acceptance (ISSUE 7): under a seeded ChaosNet flood/overload
    schedule, Bulwark-enabled interactive goodput beats the no-admission
    baseline; shed requests complete in a small fraction of the Deadline
    budget; shed transitions are flight-recorded with dds_admission_*
    metrics; /health and /slo answer throughout."""
    import pathlib

    from dds_tpu.obs.flight import flight

    try:
        baseline = asyncio.run(_drive_overload(False, tmp_path))
        bulwark = asyncio.run(_drive_overload(True, tmp_path))
    finally:
        flight.configure(dir="")  # launch() armed the global recorder

    # the cliff: the same schedule that starves baseline interactive
    # traffic leaves Bulwark's interactive class serving
    assert bulwark["goodput"] > baseline["goodput"] * 1.5, (baseline, bulwark)
    assert bulwark["goodput"] > 3.0, bulwark

    # shed requests fail fast instead of burning the Deadline like the
    # baseline's 503s do: typically ~1 ms server-side — the p50 bound is
    # an order of magnitude under the budget, and even the client-observed
    # tail (which rides the congested pre-shed event loop) stays under
    # half of it
    assert bulwark["shed"] > 50
    assert bulwark["shed_p50"] < bulwark["budget"] / 10, bulwark["shed_p50"]
    assert bulwark["shed_p95"] < bulwark["budget"] / 2, bulwark["shed_p95"]

    # the ratchet actually fired (admitted aggregates burned the SumAll
    # budget -> multiwindow alert -> shed), was metered and flight-recorded
    assert any(t["direction"] == "shed" for t in bulwark["transitions"])
    assert (metrics.value("dds_admission_transitions_total",
                          direction="shed", reason="slo_burn") or 0) >= 1
    index = pathlib.Path(bulwark["flight_dir"]) / "index.jsonl"
    kinds = [json.loads(line)["kind"]
             for line in index.read_text().splitlines()]
    assert "admission_shed" in kinds

    # observability stayed reachable through the whole flood (the claim
    # is about the Bulwark run — the baseline's jammed loop answering its
    # exempt probes slowly is exactly the cliff being demonstrated)
    assert bulwark["probes"], "no probes recorded"
    assert all(s in (200, 503) for _, s in bulwark["probes"]), bulwark["probes"]
    assert all(s == 200 for r, s in bulwark["probes"] if r == "/slo")


# ------------------------------------------------------------------ sentry


def test_sentry_check_parses_overload_records(tmp_path):
    from benchmarks.sentry import _check_overload_records

    bench = tmp_path / "benchmarks"
    bench.mkdir()
    good = {
        "metric": "overload goodput interactive",
        "value": 31.1, "unit": "req/s", "vs_baseline": 233.9,
        "detail": {
            "baseline_goodput": 0.133, "shed_requests": 1157,
            "shed_p95_ms": 8.7, "aggregate_rate": 400.0,
        },
    }
    (bench / "results.json").write_text(json.dumps([good]))
    assert _check_overload_records(str(tmp_path)) == {"rows": 1}
    bad = dict(good, detail={"baseline_goodput": 0.1})  # missing shed census
    (bench / "results.json").write_text(json.dumps([good, bad]))
    with pytest.raises(ValueError):
        _check_overload_records(str(tmp_path))
    # other record families are ignored by this checker
    (bench / "results.json").write_text(
        json.dumps([{"metric": "analytics matvec: x", "value": -1}])
    )
    assert _check_overload_records(str(tmp_path)) == {"rows": 0}
