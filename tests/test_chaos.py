"""ChaosNet fabric tests: determinism, each fault type, partitions,
Nemesis attacks, breaker-driven recovery, and REST graceful degradation
(503 + Retry-After under a full partition, service resumed after heal
without a restart).

Every schedule is seeded and short-interval — wall-clock sleeps stay in
the tens of milliseconds so the suite fits the tier-1 budget."""

import asyncio
import json
import time

import pytest

from dds_tpu.core import messages as M
from dds_tpu.core.chaos import ChaosNet, LinkFaults
from dds_tpu.core.quorum_client import AbdClient, AbdClientConfig
from dds_tpu.core.replica import BFTABDNode, ReplicaConfig
from dds_tpu.core.transport import InMemoryNet
from dds_tpu.http.miniserver import http_request, http_request_full
from dds_tpu.http.server import DDSRestServer, ProxyConfig
from dds_tpu.malicious.trudy import Nemesis, parse_attack
from dds_tpu.utils.retry import CircuitBreaker

pytestmark = pytest.mark.chaos


def run(coro):
    return asyncio.run(coro)


async def _scripted_sends(seed):
    """A fixed send sequence through a faulty fabric; returns the trace."""
    net = ChaosNet(InMemoryNet(), seed=seed)
    net.default_faults = LinkFaults(
        drop=0.2, delay=0.001, jitter=0.002, duplicate=0.2, reorder=0.2,
        corrupt=0.2,
    )
    got = []

    async def handler(sender, msg):
        got.append((sender, msg))

    net.register("sink", handler)
    for i in range(40):
        net.send(f"src-{i % 3}", "sink", M.ReadTag(f"k{i}", i))
    await net.quiesce()
    return list(net.trace), got


# ------------------------------------------------------------- determinism


def test_same_seed_reproduces_identical_fault_trace():
    t1, _ = run(_scripted_sends(1234))
    t2, _ = run(_scripted_sends(1234))
    assert t1 == t2
    assert len(t1) > 0  # the schedule actually injected faults


def test_different_seed_changes_the_fault_trace():
    t1, _ = run(_scripted_sends(1234))
    t3, _ = run(_scripted_sends(4321))
    assert t1 != t3


# --------------------------------------------------------- individual faults


def _sink_net(seed=0):
    net = ChaosNet(InMemoryNet(), seed=seed)
    got = []

    async def handler(sender, msg):
        got.append(msg)

    net.register("sink", handler)
    return net, got


def test_drop_fault_loses_the_message():
    async def go():
        net, got = _sink_net()
        net.set_link("a", "sink", LinkFaults(drop=1.0))
        net.send("a", "sink", M.ReadTag("k", 1))
        net.send("b", "sink", M.ReadTag("k", 2))  # unfaulted link flows
        await net.quiesce()
        assert [m.nonce for m in got] == [2]
        assert any(e[4] == "drop" for e in net.trace)

    run(go())


def test_delay_fault_defers_but_delivers():
    async def go():
        net, got = _sink_net()
        net.set_dest("sink", LinkFaults(delay=0.03))
        t0 = time.monotonic()
        net.send("a", "sink", M.ReadTag("k", 1))
        assert got == []  # not yet
        await net.quiesce()
        assert [m.nonce for m in got] == [1]
        assert time.monotonic() - t0 >= 0.025

    run(go())


def test_duplicate_fault_delivers_twice():
    async def go():
        net, got = _sink_net()
        net.set_link("a", "sink", LinkFaults(duplicate=1.0))
        net.send("a", "sink", M.ReadTag("k", 7))
        await net.quiesce()
        assert [m.nonce for m in got] == [7, 7]

    run(go())


def test_reorder_fault_swaps_consecutive_messages():
    async def go():
        net, got = _sink_net()
        net.set_link("a", "sink", LinkFaults(reorder=1.0))
        net.send("a", "sink", M.ReadTag("k", 1))  # parked
        net.send("a", "sink", M.ReadTag("k", 2))  # overtakes
        await net.quiesce()
        assert [m.nonce for m in got] == [2, 1]

    run(go())


def test_parked_message_flushes_on_a_quiet_link():
    async def go():
        net, got = _sink_net()
        net.set_link("a", "sink", LinkFaults(reorder=1.0))
        net.send("a", "sink", M.ReadTag("k", 1))  # parked, nothing follows
        await net.quiesce()  # quiesce releases it rather than stranding it
        assert [m.nonce for m in got] == [1]

    run(go())


def test_corrupt_fault_mutates_or_drops_never_passes_verbatim():
    async def go():
        net, got = _sink_net(seed=3)
        net.set_link("a", "sink", LinkFaults(corrupt=1.0))
        sent = [M.ReadTag(f"key-{i}", i) for i in range(20)]
        for m in sent:
            net.send("a", "sink", m)
        await net.quiesce()
        assert len(got) < len(sent)  # some corruptions were undecodable
        for m in got:
            assert m not in sent  # every survivor is a mutated payload

    run(go())


# ---------------------------------------------------------------- partitions


def test_symmetric_partition_blocks_both_directions_and_heals():
    async def go():
        net = ChaosNet(InMemoryNet(), seed=0)
        boxes = {"a": [], "b": []}

        async def make(name):
            async def h(sender, msg):
                boxes[name].append(msg.nonce)
            net.register(name, h)

        await make("a")
        await make("b")
        p = net.partition(["a"])
        net.send("a", "b", M.ReadTag("k", 1))
        net.send("b", "a", M.ReadTag("k", 2))
        await net.quiesce()
        assert boxes == {"a": [], "b": []}
        p.heal()
        net.send("a", "b", M.ReadTag("k", 3))
        net.send("b", "a", M.ReadTag("k", 4))
        await net.quiesce()
        assert boxes == {"a": [4], "b": [3]}

    run(go())


def test_asymmetric_partition_blocks_one_direction_only():
    async def go():
        net = ChaosNet(InMemoryNet(), seed=0)
        boxes = {"a": [], "b": []}
        for name in ("a", "b"):
            async def h(sender, msg, _name=name):
                boxes[_name].append(msg.nonce)
            net.register(name, h)
        net.partition(["a"], ["b"], symmetric=False)
        net.send("a", "b", M.ReadTag("k", 1))  # a -> b cut
        net.send("b", "a", M.ReadTag("k", 2))  # b -> a flows
        await net.quiesce()
        assert boxes == {"a": [2], "b": []}

    run(go())


def test_timed_partition_heals_itself():
    async def go():
        net = ChaosNet(InMemoryNet(), seed=0)
        got = []

        async def h(sender, msg):
            got.append(msg.nonce)

        net.register("b", h)
        net.partition(["a"], duration=0.05)
        net.send("a", "b", M.ReadTag("k", 1))
        await asyncio.sleep(0.08)
        net.send("a", "b", M.ReadTag("k", 2))
        await net.quiesce()
        assert got == [2]

    run(go())


def test_partition_matches_bare_names_on_hostport_addresses():
    p = ChaosNet(InMemoryNet()).partition(["replica-1"])
    assert p.blocks("10.0.0.1:2552/replica-1", "10.0.0.2:2552/replica-2")
    assert p.blocks("10.0.0.2:2552/replica-2", "10.0.0.1:2552/replica-1")
    assert not p.blocks("10.0.0.2:2552/replica-2", "10.0.0.2:2552/replica-3")


# ------------------------------------------------------------------- Nemesis


def test_parse_attack_knows_the_nemesis_attacks():
    for name in ("partition", "delay", "flood", "heal"):
        assert parse_attack(name).value == name
    with pytest.raises(ValueError):
        parse_attack("emp")


def test_nemesis_partition_delay_flood_heal():
    async def go():
        import random

        net = ChaosNet(InMemoryNet(), seed=0)
        flood_seen = []

        async def h(sender, msg):
            flood_seen.append(msg)

        net.register("replica-0", h)
        nem = Nemesis(net, ["replica-0"], max_faults=1,
                      rng=random.Random(1), delay=0.01, flood_messages=5)

        assert nem.trigger("partition") == ["replica-0"]
        assert net.partitions and net.partitions[0].blocks("replica-0", "x")

        nem.trigger("delay")
        assert net.links["replica-0"].delay == 0.01

        nem.trigger("flood")
        await net.quiesce()
        # flood arrives (the partition blocks replica-0's traffic, but
        # trudy is outside the partitioned group on the trudy->replica link?
        # no: replica-0 is isolated, so the junk is CUT — heal first)
        nem.trigger("heal")
        assert not net.partitions and not net.links
        nem.trigger("flood")
        await net.quiesce()
        assert len(flood_seen) == 5
        assert all(isinstance(m, M.Envelope) for m in flood_seen)

    run(go())


def test_nemesis_refuses_network_attacks_on_plain_transport():
    import random

    nem = Nemesis(InMemoryNet(), ["r0"], rng=random.Random(0))
    with pytest.raises(TypeError):
        nem.trigger("partition")


# --------------------------------------- breaker integration (quorum client)


def test_timeouts_trip_breaker_not_permanent_suspicion():
    """A partitioned coordinator opens its circuit breaker (self-healing)
    but earns NO permanent suspicion strikes — after heal + reset the same
    replica coordinates again without any membership reset."""

    async def go():
        from tests.test_core import Cluster

        net = ChaosNet(InMemoryNet(), seed=9)
        c = Cluster(net=net)
        c.client.cfg.request_timeout = 0.1
        c.client.cfg.breaker_reset = 0.15
        c.client.replicas.reset(["replica-0"])  # force the coordinator pick
        p = net.partition(["proxy-0"])
        for _ in range(3):
            with pytest.raises(asyncio.TimeoutError):
                await c.client.fetch_set("K")
        assert c.client.breakers["replica-0"].state == CircuitBreaker.OPEN
        assert c.client.replicas._strikes["replica-0"] == 0  # no strikes
        assert c.client.replicas.get_trusted() == ["replica-0"]  # still member
        p.heal()
        await asyncio.sleep(0.2)  # past breaker_reset -> half-open probe
        assert await c.client.fetch_set("K") is None  # quorum works again
        assert c.client.breakers["replica-0"].state == CircuitBreaker.CLOSED

    run(go())


@pytest.mark.parametrize("when", ["open", "half_open"])
def test_a_cut_off_coordinator_is_no_users_to_try_while_another_stands(when):
    """replica-0 is cut off from the proxy and its breaker opens on three
    timeouts. From then on, open or half-open, every request goes through
    replica-1; what the proxy sends replica-0 is its own probe (the tags of
    no keys), which closes the breaker after the heal with no user's
    request spent on finding out.

    The breaker's 0.15 s of `breaker_reset` run on a clock the test
    moves: a loaded machine cannot outrun the stretch in which the breaker
    has to stay open (PR 48's run of six workers did), and after the heal
    the clock goes with the wall clock the loop waits on."""

    async def go():
        from tests.test_core import Cluster
        from tests.test_retry import FakeClock

        net = ChaosNet(InMemoryNet(), seed=9)
        c = Cluster(net=net)
        cfg = c.client.cfg
        cfg.request_timeout, cfg.breaker_reset = 0.1, 0.15
        cfg.breaker_probe_timeout = 0.05
        clock = FakeClock()
        c.client.breakers["replica-0"] = CircuitBreaker(
            cfg.breaker_threshold, cfg.breaker_reset, clock=clock,
            name="replica-0")
        c.client._preferred = ["replica-0", "replica-1"]
        sent = []

        async def note(msg):
            sent.append(msg)
            return msg

        net.inner.link_filters[("proxy-0", "replica-0")] = note
        p = net.partition(["proxy-0"], ["replica-0"])
        await c.client.write_set("K", ["row"])
        b = c.client._breaker("replica-0")
        for _ in range(40):
            if not b.settled:
                break
            try:
                await c.client.fetch_set("K")
            except asyncio.TimeoutError:
                pass
        assert b.state == CircuitBreaker.OPEN
        assert c.client.replicas._strikes["replica-0"] == 0
        if when == "half_open":
            clock.advance(0.16)
            assert b.state == CircuitBreaker.HALF_OPEN
        for _ in range(20):
            assert await c.client.fetch_set("K") == ["row"]
            assert (await c.client.fetch_sets_attributed(["K"]))[0][2] == (
                "replica-1")
        assert not b.settled
        p.heal()
        del sent[:]
        for _ in range(40):
            if b.settled:
                break
            clock.advance(0.02)
            await asyncio.sleep(0.02)
        assert b.state == CircuitBreaker.CLOSED
        # found by the probe: nothing of a user's went there meanwhile
        assert sent and all(isinstance(m, M.ReadTagBatch) and m.count == 0
                            for m in sent)
        assert c.client._probe_tasks == {}
        picked = {(await c.client.fetch_set_attributed("K"))[2]
                  for _ in range(40)}
        assert picked == {"replica-0", "replica-1"}      # coordinates again
        await c.client.stop()

    run(go())


def test_an_audit_that_excludes_the_rest_still_avoids_the_open_breaker():
    """`exclude` (the audit's "another coordinator, please") and the open
    breakers can together name everyone: a blocked coordinator is then the
    last choice, after the one the caller wanted to avoid."""

    async def go():
        from tests.test_core import Cluster

        c = Cluster(n_active=4, n_sentinent=0, quorum=3)
        c.client.cfg.breaker_reset = 30.0
        await c.client.write_set("K", ["row"])
        for _ in range(3):
            c.client._breaker_failed("replica-3")
        assert not c.client._breaker("replica-3").settled
        others = ("replica-0", "replica-1", "replica-2")
        for _ in range(12):
            _, _, coord = await c.client.fetch_set_attributed(
                "K", exclude=others)
            assert coord in others
        await c.client.stop()

    run(go())


# ------------------------------------- REST graceful degradation end-to-end


async def _chaos_rest_stack():
    net = ChaosNet(InMemoryNet(), seed=77)
    rcfg = ReplicaConfig(quorum_size=3)
    addrs = [f"replica-{i}" for i in range(4)]
    replicas = {a: BFTABDNode(a, addrs, "supervisor", net, rcfg) for a in addrs}
    abd = AbdClient(
        "proxy-0", net, addrs,
        AbdClientConfig(request_timeout=0.12, quorum_size=3,
                        breaker_reset=0.15),
    )
    server = DDSRestServer(
        abd,
        ProxyConfig(
            host="127.0.0.1", port=0, request_budget=0.8,
            retry_backoff=0.02, retry_max_delay=0.1, retry_after_hint=1.0,
        ),
    )
    await server.start()
    return net, server, replicas


def test_rest_returns_503_with_retry_after_under_full_partition_then_heals():
    """Acceptance: a GET/PUT issued while every replica is unreachable
    returns 503 + Retry-After within the configured budget (no unbounded
    hang), and the SAME server serves again after heal — no restart."""

    async def go():
        net, server, _ = await _chaos_rest_stack()
        try:
            # healthy baseline: store a row
            status, _, body = await http_request_full(
                "127.0.0.1", server.cfg.port, "POST", "/PutSet",
                json.dumps({"contents": ["a", "b"]}).encode(),
            )
            assert status == 200
            key = body.decode()

            # cut the proxy off from EVERY replica
            p = net.partition(["proxy-0"])

            for method, target, payload in (
                ("GET", f"/GetSet/{key}", None),
                ("POST", "/PutSet", json.dumps({"contents": ["x"]}).encode()),
            ):
                t0 = time.monotonic()
                status, headers, _ = await http_request_full(
                    "127.0.0.1", server.cfg.port, method, target, payload,
                )
                elapsed = time.monotonic() - t0
                assert status == 503, (method, status)
                assert int(headers["retry-after"]) >= 1
                # bounded by the budget (plus scheduling slack), not hanging
                assert elapsed < 3 * server.cfg.request_budget, elapsed

            # degraded /health while partitioned. Organic traffic spreads
            # failures over random coordinators, so drive every breaker to
            # its threshold deterministically before probing the route.
            for r in server.abd.replicas.get_all():
                for _ in range(server.abd.cfg.breaker_threshold):
                    server.abd._breaker(r).record_failure()
            status, headers, body = await http_request_full(
                "127.0.0.1", server.cfg.port, "GET", "/health",
            )
            health = json.loads(body)
            assert status == 503 and health["status"] == "degraded"
            assert health["reachable_replicas"] < health["quorum_size"]
            assert "retry-after" in headers

            # heal; after the breaker reset the SAME server serves again
            p.heal()
            await asyncio.sleep(0.2)
            status, _, body = await http_request_full(
                "127.0.0.1", server.cfg.port, "GET", f"/GetSet/{key}",
            )
            assert status == 200
            assert json.loads(body)["contents"] == ["a", "b"]

            status, _, body = await http_request_full(
                "127.0.0.1", server.cfg.port, "GET", "/health",
            )
            health = json.loads(body)
            assert status == 200 and health["status"] == "ok"
            assert health["active_replicas"] == 4
            assert all(s == "closed" for s in health["breakers"].values()) or \
                health["reachable_replicas"] >= health["quorum_size"]
        finally:
            await server.stop()

    run(go())


def test_health_route_reports_ok_on_a_healthy_stack():
    async def go():
        net, server, _ = await _chaos_rest_stack()
        try:
            status, body = await http_request(
                "127.0.0.1", server.cfg.port, "GET", "/health"
            )
            health = json.loads(body)
            assert status == 200
            assert health["status"] == "ok"
            assert health["active_replicas"] == 4
            assert health["quorum_size"] == 3
            assert health["breakers"] == {}  # no failures yet
        finally:
            await server.stop()

    run(go())
