"""Meridian multi-host fabric tests.

Covers the acceptance surface of the multi-host plane on REAL loopback
sockets: the role-driven TCP constellation (`[fabric]` role = all /
group:N / proxy), conditional `GET /shards` (ETag + 304 + long-poll
gossip push), a remote proxy bootstrapping the signed map and surviving
its own restart with zero operator input, cross-host live resharding
under a seeded ChaosNet schedule with a writer hammering a moving key,
trace-context propagation across TcpNet frames (one request = one span
tree), the node-key minting helper, the open-loop load generator's
coordinated-omission safety, and the sentry record contract for
`multihost load` rows.

Everything here runs over real TCP sockets. The in-tier-1 tests keep the
whole fleet inside ONE pytest process (multiple TcpNet instances on one
event loop — real frames, deterministic scheduling); the flagship
multi-OS-process test spawns actual `python -m dds_tpu.run` processes
and is additionally marked `slow` (sockets + interpreter startup make it
flaky-prone under CI load — the loopback smokes keep tier-1 coverage).
"""

import asyncio
import json
import random
import socket
import time

import pytest

from dds_tpu.core import messages as M
from dds_tpu.core.errors import WrongShardError
from dds_tpu.fabric.deploy import initial_map, parse_role
from dds_tpu.fabric.gossip import RemoteShardManager
from dds_tpu.http.miniserver import (
    HttpServer,
    Response,
    http_request,
    http_request_full,
)
from dds_tpu.shard.shardmap import ShardMap
from dds_tpu.utils import sigs
from dds_tpu.utils.config import DDSConfig
from tests.test_core import run

pytestmark = pytest.mark.multihost

SECRET = b"intranet-abd-secret"


def free_port() -> int:
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def fabric_cfg(role, t_port, groups, bootstrap=(), status_port=0, *,
               count=2, audit=False):
    cfg = DDSConfig()
    cfg.shard.enabled = True
    cfg.shard.count = count
    cfg.transport.kind = "tcp"
    cfg.transport.port = t_port
    cfg.proxy.port = 0
    cfg.recovery.enabled = False
    cfg.obs.audit_enabled = audit
    cfg.fabric.role = role
    cfg.fabric.groups = dict(groups)
    cfg.fabric.bootstrap = list(bootstrap)
    cfg.fabric.status_port = status_port
    cfg.fabric.gossip_wait = 2.0
    cfg.fabric.admin_routes = True
    return cfg


async def _put(port, contents, timeout=10.0):
    status, body = await http_request(
        "127.0.0.1", port, "POST", "/PutSet",
        json.dumps({"contents": contents}).encode(), timeout=timeout,
    )
    assert status == 200, (status, body)
    return body.decode()


# ------------------------------------------------------------------- units


def test_parse_role_and_initial_map_determinism():
    assert parse_role("all") == ("all", None)
    assert parse_role("proxy") == ("proxy", None)
    assert parse_role("group:2") == ("group", "s2")
    assert parse_role("group:s7") == ("group", "s7")
    for bad in ("bogus", "group:", "groups:1", ""):
        if bad == "":
            assert parse_role(bad) == ("all", None)  # empty = default
            continue
        with pytest.raises(ValueError):
            parse_role(bad)
    cfg = DDSConfig()
    cfg.shard.count = 3
    m1, m2 = initial_map(cfg), initial_map(cfg)
    assert m1.vnodes == m2.vnodes and m1.epoch == m2.epoch == 1
    assert m1.verify(cfg.security.abd_mac_secret.encode())


def test_remote_shard_manager_verified_and_forward_only():
    m1 = ShardMap.build(["s0", "s1"], 8).sign(SECRET)
    mgr = RemoteShardManager(m1, SECRET)
    assert mgr.epoch == 1 and mgr.state == "stable"
    m2 = m1.split("s1", "s2").sign(SECRET)
    assert mgr.install(m2, state="resharding")
    assert mgr.epoch == 2 and mgr.state == "resharding"
    # redelivery and backwards epochs are ignored, forgeries raise
    assert not mgr.install(m2)
    assert not mgr.install(m1, state="stable")
    assert mgr.epoch == 2 and mgr.state == "stable"
    forged = ShardMap(m2.epoch + 1, m2.vnodes, m2.groups, b"nope")
    with pytest.raises(ValueError):
        mgr.install(forged)


# ------------------------------------------- role "all" over real sockets


def test_tcp_all_role_smoke_and_shards_conditional_get():
    """The tier-1 loopback smoke: a whole S=2 constellation over real
    TCP sockets in one process — point ops, /shards with ETag, and a
    near-free 304 freshness probe."""

    async def go():
        from dds_tpu.run import launch

        cfg = fabric_cfg("all", 0, {})
        dep = await launch(cfg)
        try:
            port = dep.server.cfg.port
            key = await _put(port, ["11", "22"])
            status, body = await http_request(
                "127.0.0.1", port, "GET", f"/GetSet/{key}", timeout=10.0)
            assert status == 200
            assert json.loads(body)["contents"] == ["11", "22"]
            status, headers, body = await http_request_full(
                "127.0.0.1", port, "GET", "/shards", timeout=5.0)
            assert status == 200 and headers.get("etag") == '"1"'
            served = ShardMap.from_wire(json.loads(body)["map"])
            assert served.verify(SECRET)
            # freshness probe: same epoch = 304, no body re-serialization
            status, headers, body = await http_request_full(
                "127.0.0.1", port, "GET", "/shards",
                headers={"If-None-Match": '"1"'}, timeout=5.0)
            assert status == 304 and body == b"" \
                and headers.get("etag") == '"1"'
            # a stale etag gets the full signed map immediately
            status, _, body = await http_request_full(
                "127.0.0.1", port, "GET", "/shards",
                headers={"If-None-Match": '"0"'}, timeout=5.0)
            assert status == 200 and json.loads(body)["map"]["epoch"] == 1
        finally:
            await dep.stop()

    run(go())


def test_shards_longpoll_returns_push_on_epoch_bump():
    """Epoch gossip is change notification, not polling: a parked
    long-poll (If-None-Match + wait) returns the NEW signed map the
    moment a live split activates, well before its wait expires."""

    async def go():
        from dds_tpu.run import launch

        cfg = fabric_cfg("all", 0, {})
        dep = await launch(cfg)
        try:
            port = dep.server.cfg.port
            await _put(port, ["1"])

            async def longpoll():
                t0 = time.monotonic()
                status, _, body = await http_request_full(
                    "127.0.0.1", port, "GET", "/shards?wait=30",
                    headers={"If-None-Match": '"1"'}, timeout=40.0)
                return status, json.loads(body), time.monotonic() - t0

            poll = asyncio.ensure_future(longpoll())
            await asyncio.sleep(0.1)
            assert not poll.done()  # parked, not busy-polling
            status, body = await http_request(
                "127.0.0.1", port, "POST", "/_reshard",
                json.dumps({"source": "s1"}).encode(), timeout=30.0)
            assert status == 200, body
            st, d, held = await asyncio.wait_for(poll, 10.0)
            assert st == 200 and d["map"]["epoch"] == 2
            assert held < 8.0  # pushed on the bump, not held to the cap
            assert ShardMap.from_wire(d["map"]).verify(SECRET)
        finally:
            await dep.stop()

    run(go())


# ----------------------------- multi-process-shaped fleet, one event loop


class _MiniFleet:
    """S=2 (+ optional standby) groups and a separate proxy, each on its
    OWN TcpNet — real loopback frames between 'processes' that happen to
    share one event loop, so tests stay deterministic and fast."""

    def __init__(self, standby=0, audit=False):
        self.t_ports = {f"s{i}": free_port() for i in range(2 + standby)}
        self.s_ports = {gid: free_port() for gid in self.t_ports}
        self.groups = {
            gid: f"127.0.0.1:{p}" for gid, p in self.t_ports.items()
        }
        self.bootstrap = [f"127.0.0.1:{p}" for p in self.s_ports.values()]
        self.audit = audit
        self.deps = {}

    async def start(self):
        from dds_tpu.run import launch

        for gid, t_port in self.t_ports.items():
            cfg = fabric_cfg(f"group:{gid[1:]}", t_port, self.groups,
                             self.bootstrap, self.s_ports[gid],
                             audit=self.audit)
            self.deps[gid] = await launch(cfg)
        await self.start_proxy("proxy")
        return self

    async def start_proxy(self, name):
        from dds_tpu.run import launch

        cfg = fabric_cfg("proxy", free_port(), self.groups, self.bootstrap,
                         audit=False)
        self.deps[name] = await launch(cfg)
        return self.deps[name]

    def proxy_port(self, name="proxy"):
        return self.deps[name].server.cfg.port

    async def stop(self):
        for dep in reversed(list(self.deps.values())):
            await dep.stop()
        self.deps.clear()


def test_remote_proxy_bootstrap_sumall_bitforbit_and_restart():
    """A separate proxy 'process' bootstraps the signed map from a group
    status listener, serves point ops and a scatter-gather SumAll
    bit-for-bit equal to the single-process result over IDENTICAL
    ciphertexts, and — killed and restarted — re-bootstraps from
    GET /shards with zero operator input."""
    from dds_tpu.http.server import DDSRestServer, ProxyConfig
    from dds_tpu.models import HEKeys

    from dds_tpu.utils import sigs

    he = HEKeys.generate(paillier_bits=512, rsa_bits=512)
    pk = he.psse.public
    vals = [7, 21, 301, 44, 5, 600]
    # ONE encryption feeds both runs (bit-for-bit comparison); blinding
    # randomizes the content-hash keys, so re-encrypt until the sample
    # provably spans both groups of the deterministic epoch-1 map
    smap = ShardMap.build(["s0", "s1"], 16)
    while True:
        rows = [[str(pk.encrypt(v))] for v in vals]
        owners = {smap.owner(sigs.key_from_set(r)) for r in rows}
        if owners == {"s0", "s1"}:
            break

    async def single_process_result():
        from dds_tpu.core.transport import InMemoryNet
        from dds_tpu.shard import build_constellation

        const = build_constellation(InMemoryNet(), shard_count=1,
                                    n_sentinent=0)
        server = DDSRestServer(const.router, ProxyConfig(port=0))
        await server.start()
        for row in rows:
            await _put(server.cfg.port, row)
        status, body = await http_request(
            "127.0.0.1", server.cfg.port, "GET",
            f"/SumAll?position=0&nsqr={pk.nsquare}", timeout=30.0)
        assert status == 200
        await server.stop()
        await const.stop()
        return json.loads(body)["result"]

    async def go():
        single = await single_process_result()
        fleet = await _MiniFleet().start()
        try:
            port = fleet.proxy_port()
            keys = [await _put(port, row) for row in rows]
            # the sample genuinely spans both groups
            owners = {
                fleet.deps["proxy"].server.abd.owner(k) for k in keys
            }
            assert owners == {"s0", "s1"}
            status, body = await http_request(
                "127.0.0.1", port, "GET",
                f"/SumAll?position=0&nsqr={pk.nsquare}", timeout=30.0)
            assert status == 200
            sharded = json.loads(body)["result"]
            assert sharded == single  # bit-for-bit across process shapes
            assert he.psse.decrypt(int(sharded)) == sum(vals)

            # kill the proxy process outright; a FRESH proxy bootstraps
            # the map from the groups' /shards and serves immediately
            await fleet.deps.pop("proxy").stop()
            await fleet.start_proxy("proxy2")
            port2 = fleet.proxy_port("proxy2")
            assert port2 != port
            for k, row in zip(keys, rows):
                status, body = await http_request(
                    "127.0.0.1", port2, "GET", f"/GetSet/{k}", timeout=10.0)
                assert status == 200
                assert json.loads(body)["contents"] == row
            status, _, body = await http_request_full(
                "127.0.0.1", port2, "GET", "/shards", timeout=5.0)
            assert status == 200
            assert ShardMap.from_wire(json.loads(body)["map"]).verify(SECRET)
        finally:
            await fleet.stop()

    run(go())


@pytest.mark.chaos
def test_cross_host_reshard_over_sockets_under_chaos():
    """Flagship loopback schedule: an S=2 fleet plus a standby group and
    a separate proxy, every hop on real TCP sockets, the proxy's and
    target group's fabrics wrapped in seeded ChaosNet schedules
    (delay + duplicate on the migration stream). A writer hammers a
    MOVING key over HTTP while POST /_reshard drives a live cross-host
    split. Asserts: the split activates epoch 2 everywhere, every acked
    write stays readable (the last one wins), the fence actually engaged
    (wrong-shard retries observed), and a Watchtower with per-group
    geometry reports zero quorum-intersection violations."""
    from dds_tpu.core.chaos import LinkFaults
    from dds_tpu.obs.metrics import metrics
    from dds_tpu.obs.watchtower import Watchtower
    from dds_tpu.utils.trace import tracer

    async def go():
        fleet = await _MiniFleet(standby=1).start()
        wt = Watchtower(quorum_size=3, n_replicas=4)
        wt.configure(group_geometry={"s0": (3, 4), "s1": (3, 4),
                                     "s2": (3, 4)})
        wt.attach(tracer)
        try:
            port = fleet.proxy_port()
            smap = initial_map(fleet.deps["proxy"].cfg)
            m2 = smap.split("s1", "s2").sign(SECRET)
            # seed rows until one key moves s1 -> s2 under the split
            rng = random.Random(5)
            moving = None
            while moving is None:
                row = [str(rng.randrange(1 << 16))]
                k = await _put(port, row)
                if smap.owner(k) == "s1" and m2.owner(k) == "s2":
                    moving = k
            def fence_count():
                total = 0
                for s in ("s0", "s1", "s2"):
                    total += (metrics.value(
                        "dds_wrong_shard_retries_total", shard=s) or 0)
                    for msg in ("Envelope", "Write", "ReadTagBatch"):
                        total += (metrics.value(
                            "dds_shard_fenced_total", shard=s, msg=msg)
                            or 0)
                return total

            fences_before = fence_count()
            # seeded chaos on the fabrics that carry the migration
            # stream: the proxy's sends (writes, manifests, chunks) and
            # the target group's internal traffic. The delays also
            # stretch the freeze->activate window so the hammering
            # writers demonstrably cross it.
            for name in ("proxy", "s2"):
                fleet.deps[name].net.default_faults = LinkFaults(
                    delay=0.005, jitter=0.02, duplicate=0.15
                )
            done = asyncio.Event()
            wrote = []

            async def writer(wid):
                i = 0
                while not (done.is_set() and i >= 3):
                    value = f"w{wid}-{i}"
                    status, _ = await http_request(
                        "127.0.0.1", port, "PUT",
                        f"/WriteElement/{moving}?position=0",
                        json.dumps({"value": value}).encode(),
                        timeout=20.0,
                    )
                    if status == 200:
                        wrote.append(value)
                    i += 1

            async def split():
                await asyncio.sleep(0.05)
                try:
                    status, body = await http_request(
                        "127.0.0.1", port, "POST", "/_reshard",
                        json.dumps(
                            {"source": "s1", "target": "s2"}
                        ).encode(),
                        timeout=45.0,
                    )
                    assert status == 200, body
                    return json.loads(body)
                finally:
                    done.set()

            _, _, split_result = await asyncio.gather(
                writer(0), writer(1), split()
            )
            assert split_result["epoch"] == 2
            assert wrote, "no write ever succeeded"
            # writes kept landing THROUGH the split, and the value served
            # afterwards is one of the final acked writes (two concurrent
            # writers: either one's last commit may hold the max tag —
            # but never a lost, misrouted, or phantom value)
            status, body = await http_request(
                "127.0.0.1", port, "GET", f"/GetSet/{moving}", timeout=10.0)
            assert status == 200
            final = json.loads(body)["contents"][0]
            last_idx = {
                wid: max(int(v.split("-")[1]) for v in wrote
                         if v.startswith(f"w{wid}-"))
                for wid in (0, 1)
                if any(v.startswith(f"w{wid}-") for v in wrote)
            }
            assert final in {
                f"w{wid}-{i}" for wid, i in last_idx.items()
            }, (final, last_idx)
            # the new owner serves it; the fleet agrees on epoch 2
            assert fleet.deps["proxy"].server.abd.owner(moving) == "s2"
            for gid, sp in fleet.s_ports.items():
                status, _, body = await http_request_full(
                    "127.0.0.1", sp, "GET", "/shards", timeout=5.0)
                assert status == 200
                assert json.loads(body)["map"]["epoch"] == 2, gid
            # the epoch fence engaged during the split (no silent
            # misroutes — stale routes were rejected and re-routed)
            assert fence_count() > fences_before
            bad = [v for v in wt.verdicts()
                   if v.invariant == "quorum_intersection"]
            assert not bad, bad
        finally:
            wt.detach()
            await fleet.stop()

    run(go())


# --------------------------------------------- trace context across TcpNet


def test_trace_context_propagates_across_tcp_sockets():
    """Satellite: one request through a loopback TCP proxy -> quorum hop
    still yields a SINGLE span tree — the `tc` frame field survives real
    socket serialization, not just the in-memory fabric."""
    from dds_tpu.run import launch
    from dds_tpu.utils.trace import tracer

    async def go():
        cfg = DDSConfig()
        cfg.transport.kind = "tcp"
        cfg.transport.port = 0
        cfg.proxy.port = 0
        cfg.recovery.enabled = False
        cfg.obs.audit_enabled = False
        dep = await launch(cfg)
        try:
            tracer.reset()
            status, _ = await http_request(
                "127.0.0.1", dep.server.cfg.port, "POST", "/PutSet",
                json.dumps({"contents": ["a", "b"]}).encode(), timeout=15.0)
            assert status == 200
            await asyncio.sleep(0.2)  # let straggler acks cross the sockets
        finally:
            await dep.stop()

        roots = tracer.events("http.POST.PutSet")
        assert len(roots) == 1
        root = roots[0]
        assert root.trace_id and root.parent_id is None
        tree = tracer.trace_events(root.trace_id)
        writes = [e for e in tree if e.name == "abd.write"]
        assert writes and all(e.parent_id == root.span_id for e in writes)
        # >=2f+1 DISTINCT replicas' handler spans joined THIS trace even
        # though every hop crossed a real TCP frame
        handlers = [e for e in tree if e.name == "replica.handle"]
        assert len({e.meta["replica"] for e in handlers}) >= 5
        assert all(e.trace_id == root.trace_id for e in handlers)

    run(go())


# ------------------------------------------------------- mint-node-keys


def test_mint_node_keys_provisions_files_and_stanza(tmp_path):
    pytest.importorskip(
        "cryptography", reason="nodeauth needs the cryptography package"
    )
    from dds_tpu.run import mint_node_keys
    from dds_tpu.utils import nodeauth

    hosts = ["10.0.0.1:2552", "10.0.0.2:2552", "10.0.0.3:2552"]
    stanza = mint_node_keys(3, str(tmp_path), hosts)
    # re-running reuses the SAME keys (never rotates under a live fleet)
    assert mint_node_keys(3, str(tmp_path), hosts) == stanza
    try:
        import tomllib
    except ModuleNotFoundError:
        import tomli as tomllib

    parsed = tomllib.loads(stanza)
    registry = parsed["security"]["node-public-keys"]
    assert sorted(registry) == sorted(hosts)
    for i, hp in enumerate(hosts):
        key = nodeauth.load_private((tmp_path / f"node_{i}.key").read_text())
        assert nodeauth.public_hex(key) == registry[hp]
        mode = (tmp_path / f"node_{i}.key").stat().st_mode & 0o777
        assert mode == 0o600


# ------------------------------------------------------------- load plane


def test_zipf_distribution_skew_and_percentile_math():
    from dds_tpu.clt.distribution import ZipfKeys
    from dds_tpu.fabric.loadgen import percentile

    keys = [f"K{i}" for i in range(50)]
    z = ZipfKeys(keys, s=1.2, rng=random.Random(1))
    counts = {}
    for _ in range(4000):
        k = z.pick()
        counts[k] = counts.get(k, 0) + 1
    # rank-1 dominates; the tail still gets traffic
    assert counts["K0"] == max(counts.values())
    assert counts["K0"] > 4000 / 50 * 4
    assert len(counts) > 25
    # weights sum to ~1 and are monotonically non-increasing
    w = [z.weight(r) for r in range(1, 51)]
    assert abs(sum(w) - 1.0) < 1e-9
    assert all(a >= b - 1e-12 for a, b in zip(w, w[1:]))
    with pytest.raises(ValueError):
        ZipfKeys([], 1.0)
    vals = sorted([0.01 * i for i in range(1, 101)])
    assert percentile(vals, 50) == pytest.approx(0.50)
    assert percentile(vals, 99) == pytest.approx(0.99)
    assert percentile([], 99) == 0.0


def test_open_loop_is_coordinated_omission_safe():
    """The property that separates this generator from the closed-loop
    client: a STALLED server does not slow the offered load, and the
    stall shows up in the percentiles because latency is measured from
    each request's scheduled arrival."""
    from dds_tpu.clt.distribution import ZipfKeys
    from dds_tpu.fabric.loadgen import OpenLoopLoad

    stall = 0.25

    async def handler(req):
        await asyncio.sleep(stall)
        return Response.json({"contents": ["1"]})

    async def go():
        server = HttpServer("127.0.0.1", 0, handler)
        await server.start()
        try:
            load = OpenLoopLoad(
                [f"127.0.0.1:{server.port}"], mix={"GetSet": 1.0},
                timeout=2.0, seed=4, max_outstanding=512,
            )
            # bypass seeding: the stub serves any key
            load.keys = ["K"]
            load._zipf = ZipfKeys(load.keys, 1.0, random.Random(0))
            rate, duration = 80.0, 1.0
            report = await load.run(rate, duration)
            # open loop: arrivals kept coming while every request sat in
            # the 250 ms stall (a closed loop would have collapsed to
            # ~4 requests per connection)
            assert report.scheduled > rate * duration * 0.6
            assert report.good > 20
            # CO-safety: no latency can undercut the server stall, and
            # the percentile floor proves scheduled-time measurement
            assert report.p50_ms >= stall * 1e3 * 0.95
            assert report.p99_ms >= report.p95_ms >= report.p50_ms
            # the SLO engine saw every sample (default 250ms objective:
            # the stall makes them all bad-latency)
            slo_routes = load.slo.report()["routes"]
            assert slo_routes["GetSet"]["windows"]["300s"]["total"] \
                >= report.completed
        finally:
            await server.stop()

    run(go())


def test_open_loop_against_constellation_reports_slo():
    """End-to-end smoke: the load plane drives a real (in-memory)
    constellation proxy and reports ordered percentiles, a per-class
    split, and the SLO engine's burn view."""
    from dds_tpu.core.transport import InMemoryNet
    from dds_tpu.fabric.loadgen import OpenLoopLoad
    from dds_tpu.http.server import DDSRestServer, ProxyConfig
    from dds_tpu.shard import build_constellation

    async def go():
        const = build_constellation(InMemoryNet(), shard_count=2,
                                    n_sentinent=0)
        server = DDSRestServer(const.router, ProxyConfig(port=0))
        await server.start()
        try:
            load = OpenLoopLoad([f"127.0.0.1:{server.cfg.port}"], keys=10,
                                seed=9, timeout=3.0)
            keys = await load.seed()
            assert len(keys) == 10 and len(set(keys)) == 10
            reports = await load.sweep([60.0], 1.0)
            r = reports[0]
            assert r.scheduled > 30 and r.good > 30
            assert r.errors == 0 and r.failures == 0
            assert r.p50_ms <= r.p95_ms <= r.p99_ms
            assert set(r.per_class) <= {"interactive", "aggregate"}
            assert "interactive" in r.per_class
            assert "GetSet" in r.slo["routes"]
            d = r.to_dict()
            assert json.loads(json.dumps(d)) == d  # JSON-safe record
        finally:
            await server.stop()
            await const.stop()

    run(go())


def test_sentry_validates_multihost_load_records(tmp_path):
    from benchmarks.sentry import _check_multihost_records

    good = {
        "metric": "multihost load", "value": 98.0, "unit": "req/s",
        "vs_baseline": 1.0,
        "detail": {
            "rates": [40.0, 100.0], "processes": 3, "open_loop": True,
            "p50_ms": 8.0, "p95_ms": 20.0, "p99_ms": 70.0,
        },
    }
    bench = tmp_path / "benchmarks"
    bench.mkdir()
    (bench / "results.json").write_text(json.dumps([good]))
    assert _check_multihost_records(str(tmp_path)) == {"rows": 1}
    for mutate in (
        {"value": 0},                                   # no goodput
        {"detail": dict(good["detail"], processes=1)},  # not multi-process
        {"detail": dict(good["detail"], open_loop=False)},
        {"detail": dict(good["detail"], p50_ms=99.0)},  # p50 > p95
        {"detail": dict(good["detail"], rates=[])},
    ):
        (bench / "results.json").write_text(
            json.dumps([dict(good, **mutate)])
        )
        with pytest.raises(ValueError):
            _check_multihost_records(str(tmp_path))


# ------------------------------------------- flagship: real OS processes


@pytest.mark.slow
def test_flagship_multi_os_process_fleet(tmp_path):
    """The acceptance flagship on REAL OS processes: an S=2 constellation
    spread across 4 processes (two groups + a standby group + a separate
    proxy) on loopback TCP. Point ops and SumAll serve through the
    remote proxy; a live cross-host split (POST /_reshard) completes
    mid-load; killing and restarting the proxy process re-bootstraps the
    shard map from GET /shards without operator input."""
    from benchmarks.multihost_load import Fleet

    async def go():
        fleet = Fleet(str(tmp_path), standby=1)
        try:
            fleet.start()
            await fleet.wait_healthy(timeout=120.0)
            port = int(fleet.proxy_targets[0].rsplit(":", 1)[1])
            vals = [3, 141, 59, 26, 535, 8979]
            keys = [await _put(port, [str(v)], timeout=20.0) for v in vals]
            status, body = await http_request(
                "127.0.0.1", port, "GET", "/SumAll?position=0",
                timeout=30.0)
            assert status == 200
            assert json.loads(body)["result"] == str(sum(vals))

            async def writer():
                ok = 0
                for i in range(30):
                    status, _ = await http_request(
                        "127.0.0.1", port, "PUT",
                        f"/WriteElement/{keys[0]}?position=1",
                        json.dumps({"value": f"mid-{i}"}).encode(),
                        timeout=20.0,
                    )
                    ok += status == 200
                    await asyncio.sleep(0.02)
                return ok

            async def split():
                await asyncio.sleep(0.1)
                status, body = await http_request(
                    "127.0.0.1", port, "POST", "/_reshard",
                    json.dumps({"source": "s1"}).encode(), timeout=60.0)
                assert status == 200, body
                return json.loads(body)

            ok_writes, split_result = await asyncio.gather(writer(), split())
            assert split_result["epoch"] == 2
            assert "s2" in split_result["groups"]
            assert ok_writes > 0
            # the fleet still serves every key and the SAME aggregate
            status, body = await http_request(
                "127.0.0.1", port, "GET", "/SumAll?position=0",
                timeout=30.0)
            assert status == 200
            assert json.loads(body)["result"] == str(sum(vals))

            # kill the proxy PROCESS; a restarted one re-bootstraps the
            # epoch-2 map from the group processes' GET /shards
            proxy = fleet.procs.pop("proxy0")
            proxy.terminate()
            proxy.wait(timeout=15)
            fleet.spawn("proxy0")
            await fleet.wait_healthy(timeout=120.0)
            status, _, body = await http_request_full(
                "127.0.0.1", port, "GET", "/shards", timeout=10.0)
            assert status == 200
            d = json.loads(body)
            assert d["map"]["epoch"] == 2 and "s2" in d["map"]["groups"]
            for k, v in zip(keys, vals):
                status, body = await http_request(
                    "127.0.0.1", port, "GET", f"/GetSet/{k}", timeout=20.0)
                assert status == 200
                assert json.loads(body)["contents"][0] == str(v)
        finally:
            fleet.stop()

    asyncio.run(go())


# ------------------------------------- the tag round's delta reply on the wire


def _votes_by_kind() -> dict:
    from dds_tpu.obs.metrics import metrics

    return {k: metrics.value("dds_tag_round_votes_total", kind=k) or 0.0
            for k in ("unchanged", "delta", "full")}


def test_tag_round_delta_fields_round_trip_and_older_frames_still_decode():
    """(d) `ReadTagBatch.base` and `TagBatchReply.base` / `.positions` go
    through `messages.dumps` / `loads` (positions element-wise, as ints),
    and a frame written before the fields existed decodes to their
    defaults."""
    fp, base = bytes(range(32)), bytes(range(32, 64))
    req = M.ReadTagBatch(("a", "b"), 7, b"sig", fp, epoch=3, base=base)
    delta = M.TagBatchReply(
        (M.ABDTag(9, "replica-1"), M.ABDTag(4, "r;2|x")), "DIGEST", b"mac",
        7, fingerprint=fp, base=base, positions=(0, 5))
    for m in (req, delta, M.ReadTagBatch(("a",), 1),
              M.TagBatchReply((), "D", b"", 1, unchanged=True,
                              fingerprint=fp)):
        back = M.loads(M.dumps(m))
        assert back == m
        assert type(back) is type(m)
    back = M.loads(M.dumps(delta))
    assert isinstance(back.positions, tuple) and back.positions == (0, 5)
    assert back.base == base and isinstance(back.tags[1], M.ABDTag)
    for m, new in ((req, ("base",)), (delta, ("base", "positions"))):
        old = json.loads(M.dumps(m))
        for name in new:
            del old[name]
        was = M.from_dict(old)
        assert was.base is None and getattr(was, "positions", ()) == ()
        assert was.nonce == m.nonce and was.fingerprint == m.fingerprint
    with pytest.raises(KeyError):   # a field without a default is still owed
        M.from_dict({"__msg__": "TagBatchReply", "tags": []})


def test_over_tcp_every_tag_is_a_fresh_object_and_a_delta_round_still_holds():
    """(d) Over `TcpNet` no tag of a reply is the caller's object: the
    kept vectors compare by value, a delta round crosses the wire, and the
    caller still gets its own list back when nothing differs."""

    async def go():
        from dds_tpu.core.quorum_client import AbdClient, AbdClientConfig
        from dds_tpu.core.replica import BFTABDNode, ReplicaConfig
        from dds_tpu.core.transport import TcpNet

        net = TcpNet("127.0.0.1", 0)
        await net.start()
        host = net.advertised
        try:
            addrs = [f"{host}/replica-{i}" for i in range(4)]
            nodes = [BFTABDNode(a, addrs, f"{host}/supervisor", net,
                                ReplicaConfig(quorum_size=3)) for a in addrs]
            client = AbdClient(
                f"{host}/proxy", net, addrs,
                AbdClientConfig(request_timeout=3.0, quorum_size=3))
            keys = [f"k{i}" for i in range(12)]
            for i, k in enumerate(keys):
                for n in nodes:
                    n._store(k, M.ABDTag(i + 1, "replica-0"), [i])
            cached = await client.read_tags(keys)
            fp = sigs.tags_fingerprint(cached)
            assert (await client.read_tags(
                keys, fingerprint=fp, cached_tags=cached)) is cached
            before = _votes_by_kind()
            newer = M.ABDTag(100, "replica-1")
            for n in nodes:
                n._store(keys[5], newer, [100])
            got = await client.read_tags(keys, fingerprint=fp,
                                         cached_tags=cached)
            assert got == cached[:5] + [newer] + cached[6:]
            assert got[5] is not newer          # it crossed the wire
            assert _votes_by_kind() == {**before,
                                        "delta": before["delta"] + 3}
            # the caller takes it in: the next round is all `unchanged`
            fp2 = sigs.tags_fingerprint(got)
            assert (await client.read_tags(
                keys, fingerprint=fp2, cached_tags=got)) is got
        finally:
            await net.stop()

    run(go())
