"""End-to-end REST tests: every route, real HTTP, real quorum, real crypto.

Mirrors the reference's only verification mode — a client driving the full
proxy/ABD stack over HTTP (SURVEY.md §4) — but as a deterministic pytest
suite. Clients encrypt with tier-1 schemes; the proxy computes over
ciphertexts through a CryptoBackend; results decrypt to the expected
plaintext values.
"""

import asyncio
import contextlib
import json
import random

import pytest

from dds_tpu.core.quorum_client import AbdClient, AbdClientConfig
from dds_tpu.core.replica import BFTABDNode, ReplicaConfig
from dds_tpu.core.supervisor import BFTSupervisor, SupervisorConfig
from dds_tpu.core.transport import InMemoryNet
from dds_tpu.http.miniserver import http_request
from dds_tpu.http.server import DDSRestServer, ProxyConfig
from dds_tpu.models import HEKeys, HomoProvider

rng = random.Random(5)
KEYS = HEKeys.generate(paillier_bits=512, rsa_bits=512)
PROVIDER = HomoProvider(KEYS)


@contextlib.asynccontextmanager
async def rest_stack(crypto_backend="cpu", n=7, quorum=5):
    net = InMemoryNet()
    rcfg = ReplicaConfig(quorum_size=quorum)
    addrs = [f"replica-{i}" for i in range(n)]
    replicas = {a: BFTABDNode(a, addrs, "supervisor", net, rcfg) for a in addrs}
    supervisor = BFTSupervisor(
        "supervisor", addrs, [], net,
        SupervisorConfig(quorum_size=quorum, proactive_recovery_enabled=False),
    )
    abd = AbdClient("proxy-0", net, addrs, AbdClientConfig(request_timeout=2.0))
    server = DDSRestServer(
        abd, ProxyConfig(host="127.0.0.1", port=0, crypto_backend=crypto_backend)
    )
    await server.start()
    try:
        yield server, replicas, supervisor
    finally:
        await server.stop()


async def call(server, method, target, obj=None):
    body = json.dumps(obj).encode() if obj is not None else None
    status, data = await http_request(
        "127.0.0.1", server.cfg.port, method, target, body, timeout=10.0
    )
    return status, data


def test_putset_getset_removeset():
    async def go():
        async with rest_stack() as (server, _, _):
            row = PROVIDER.encrypt_row([5, "alice", 100], 3, ["OPE", "CHE", "PSSE"])
            status, key = await call(server, "POST", "/PutSet", {"contents": row})
            assert status == 200
            key = key.decode()
            assert len(key) == 128  # sha-512 hex

            status, data = await call(server, "GET", f"/GetSet/{key}")
            assert status == 200
            assert json.loads(data)["contents"] == row

            status, _ = await call(server, "DELETE", f"/RemoveSet/{key}")
            assert status == 200
            status, _ = await call(server, "GET", f"/GetSet/{key}")
            assert status == 404

    asyncio.run(go())


def test_putset_empty_body_random_key():
    async def go():
        async with rest_stack() as (server, _, _):
            status, key = await call(server, "POST", "/PutSet")
            assert status == 200 and len(key.decode()) == 128
            # empty set stored as None -> GetSet gives 404 (same as reference)
            status, _ = await call(server, "GET", f"/GetSet/{key.decode()}")
            assert status == 404

    asyncio.run(go())


def test_element_routes():
    async def go():
        async with rest_stack() as (server, _, _):
            row = ["a", "b"]
            _, key = await call(server, "POST", "/PutSet", {"contents": row})
            key = key.decode()

            status, _ = await call(server, "PUT", f"/AddElement/{key}", {"value": "c"})
            assert status == 200
            status, data = await call(server, "GET", f"/ReadElement/{key}?position=2")
            assert status == 200 and json.loads(data)["value"] == "c"

            status, _ = await call(
                server, "PUT", f"/WriteElement/{key}?position=0", {"value": "z"}
            )
            assert status == 200
            _, data = await call(server, "GET", f"/GetSet/{key}")
            assert json.loads(data)["contents"] == ["z", "b", "c"]

            # position past end appends
            status, _ = await call(
                server, "PUT", f"/WriteElement/{key}?position=9", {"value": "w"}
            )
            assert status == 200
            _, data = await call(server, "GET", f"/GetSet/{key}")
            assert json.loads(data)["contents"] == ["z", "b", "c", "w"]

            status, data = await call(server, "POST", f"/IsElement/{key}", {"value": "b"})
            assert status == 200 and json.loads(data)["result"] is True
            status, data = await call(server, "POST", f"/IsElement/{key}", {"value": "q"})
            assert json.loads(data)["result"] is False

            status, _ = await call(server, "GET", f"/ReadElement/{key}?position=99")
            assert status == 404
            status, _ = await call(server, "GET", "/ReadElement/NOKEY?position=0")
            assert status == 404

    asyncio.run(go())


@pytest.mark.parametrize("backend", ["cpu", "tpu", "native"])
def test_sum_and_sumall_paillier(backend):
    async def go():
        async with rest_stack(crypto_backend=backend) as (server, _, _):
            pk = KEYS.psse.public
            vals = [rng.randrange(1 << 24) for _ in range(5)]
            keys = []
            for v in vals:
                row = [str(pk.encrypt(v))]
                _, key = await call(server, "POST", "/PutSet", {"contents": row})
                keys.append(key.decode())

            nsqr = pk.nsquare
            status, data = await call(
                server,
                "GET",
                f"/Sum?key1={keys[0]}&key2={keys[1]}&position=0&nsqr={nsqr}",
            )
            assert status == 200
            c = int(json.loads(data)["result"])
            assert KEYS.psse.decrypt(c) == vals[0] + vals[1]

            status, data = await call(server, "GET", f"/SumAll?position=0&nsqr={nsqr}")
            assert status == 200
            c = int(json.loads(data)["result"])
            assert KEYS.psse.decrypt(c) == sum(vals)

            # plain (no nsqr) falls back to integer addition of ciphertexts
            status, data = await call(
                server, "GET", f"/Sum?key1={keys[0]}&key2={keys[1]}&position=0"
            )
            assert status == 200

            # bad position -> 404
            status, _ = await call(
                server, "GET", f"/Sum?key1={keys[0]}&key2={keys[1]}&position=5&nsqr={nsqr}"
            )
            assert status == 404

    asyncio.run(go())


@pytest.mark.parametrize("backend", ["cpu", "tpu", "native"])
def test_mult_and_multall_rsa(backend):
    async def go():
        async with rest_stack(crypto_backend=backend) as (server, _, _):
            k = KEYS.mse
            vals = [rng.randrange(1 << 8) for _ in range(4)]
            for v in vals:
                row = [str(k.public.encrypt(v))]
                await call(server, "POST", "/PutSet", {"contents": row})

            status, data = await call(
                server, "GET", f"/MultAll?position=0&pubkey={k.n}"
            )
            assert status == 200
            c = int(json.loads(data)["result"])
            want = 1
            for v in vals:
                want *= v
            assert k.decrypt(c) == want

    asyncio.run(go())


def test_order_and_range_search_ope():
    async def go():
        async with rest_stack() as (server, _, _):
            vals = [50, -3, 1000, 7]
            key_by_val = {}
            for v in vals:
                row = [KEYS.ope.encrypt(v), "pad"]
                _, key = await call(server, "POST", "/PutSet", {"contents": row})
                key_by_val[v] = key.decode()

            _, data = await call(server, "GET", "/OrderLS?position=0")
            ordered = json.loads(data)["keyset"]
            assert ordered == [key_by_val[v] for v in sorted(vals, reverse=True)]

            _, data = await call(server, "GET", "/OrderSL?position=0")
            assert json.loads(data)["keyset"] == [key_by_val[v] for v in sorted(vals)]

            # range search: stored > 7  (ciphertext comparison)
            q = KEYS.ope.encrypt(7)
            _, data = await call(server, "POST", "/SearchGt?position=0", {"value": q})
            got = set(json.loads(data)["keyset"])
            assert got == {key_by_val[50], key_by_val[1000]}

            _, data = await call(server, "POST", "/SearchGtEq?position=0", {"value": q})
            assert set(json.loads(data)["keyset"]) == {
                key_by_val[7], key_by_val[50], key_by_val[1000]
            }
            _, data = await call(server, "POST", "/SearchLt?position=0", {"value": q})
            assert set(json.loads(data)["keyset"]) == {key_by_val[-3]}
            _, data = await call(server, "POST", "/SearchLtEq?position=0", {"value": q})
            assert set(json.loads(data)["keyset"]) == {key_by_val[-3], key_by_val[7]}

    asyncio.run(go())


def test_eq_search_det():
    async def go():
        async with rest_stack() as (server, _, _):
            c_bob = KEYS.che.encrypt("bob")
            c_eve = KEYS.che.encrypt("eve")
            _, k1 = await call(server, "POST", "/PutSet", {"contents": ["x", c_bob]})
            _, k2 = await call(server, "POST", "/PutSet", {"contents": ["y", c_eve]})
            k1, k2 = k1.decode(), k2.decode()

            _, data = await call(server, "POST", "/SearchEq?position=1", {"value": c_bob})
            assert json.loads(data)["keyset"] == [k1]
            _, data = await call(server, "POST", "/SearchNEq?position=1", {"value": c_bob})
            assert json.loads(data)["keyset"] == [k2]

    asyncio.run(go())


def test_entry_search_routes():
    async def go():
        async with rest_stack() as (server, _, _):
            ca, cb, cc = (KEYS.che.encrypt(s) for s in ("aa", "bb", "cc"))
            _, k1 = await call(server, "POST", "/PutSet", {"contents": [ca, cb, cc]})
            _, k2 = await call(server, "POST", "/PutSet", {"contents": [ca, "zz", "ww"]})
            k1, k2 = k1.decode(), k2.decode()

            _, data = await call(server, "POST", "/SearchEntry", {"value": ca})
            assert set(json.loads(data)["keyset"]) == {k1, k2}

            trip = {"value1": ca, "value2": cb, "value3": cc}
            _, data = await call(server, "POST", "/SearchEntryOR", trip)
            assert set(json.loads(data)["keyset"]) == {k1, k2}
            _, data = await call(server, "POST", "/SearchEntryAND", trip)
            assert json.loads(data)["keyset"] == [k1]

    asyncio.run(go())


def test_sync_gossip_ingest():
    async def go():
        async with rest_stack() as (server, _, _):
            status, _ = await call(
                server, "POST", "/_sync", {"keyset": ["AAA", "BBB"]}
            )
            assert status == 204
            assert {"AAA", "BBB"} <= server.stored_keys

    asyncio.run(go())


def test_unknown_route_and_bad_body():
    async def go():
        async with rest_stack() as (server, _, _):
            status, _ = await call(server, "GET", "/Nope")
            assert status == 404
            status, _ = await call(server, "POST", "/PutSet", {"wrong": 1})
            assert status == 400
            status, _ = await call(server, "POST", "/SearchEq?position=0", {"v": 1})
            assert status == 400

    asyncio.run(go())


def test_proxy_gossip_between_two_proxies():
    async def go():
        async with rest_stack() as (s1, replicas, _):
            net = s1.abd.net
            abd2 = AbdClient("proxy-1", net, list(replicas), AbdClientConfig(request_timeout=2.0))
            s2 = DDSRestServer(
                abd2,
                ProxyConfig(
                    host="127.0.0.1",
                    port=0,
                    key_sync_enabled=True,
                    key_sync_warmup=0.05,
                    key_sync_interval=0.2,
                    peers=[f"127.0.0.1:{s1.cfg.port}"],
                ),
            )
            await s2.start()
            try:
                _, key = await call(s2, "POST", "/PutSet", {"contents": [1, 2]})
                await asyncio.sleep(0.4)  # let gossip fire
                assert key.decode() in s1.stored_keys
                # proxy-1's record is aggregatable via proxy-0 now
                _, data = await call(s1, "GET", "/SumAll?position=0")
                assert json.loads(data)["result"] == "1"
            finally:
                await s2.stop()

    asyncio.run(go())


def test_negative_position_rejected():
    async def go():
        async with rest_stack() as (server, _, _):
            _, key = await call(server, "POST", "/PutSet", {"contents": ["a", "b"]})
            key = key.decode()
            status, _ = await call(server, "GET", f"/ReadElement/{key}?position=-1")
            assert status == 400
            status, _ = await call(server, "GET", "/SumAll?position=-1&nsqr=9")
            assert status == 400


    asyncio.run(go())


def test_removeset_stops_aggregation():
    async def go():
        async with rest_stack() as (server, _, _):
            _, k1 = await call(server, "POST", "/PutSet", {"contents": [5]})
            _, k2 = await call(server, "POST", "/PutSet", {"contents": [7]})
            await call(server, "DELETE", f"/RemoveSet/{k1.decode()}")
            assert k1.decode() not in server.stored_keys
            _, data = await call(server, "GET", "/SumAll?position=0")
            assert json.loads(data)["result"] == "7"

    asyncio.run(go())


def test_sumall_executes_sharded_on_mesh(monkeypatch):
    """End-to-end §5.7: a proxy `SumAll` on a 4-device mesh runs the fold
    through the sharded kernel and still decrypts correctly."""
    from dds_tpu.models.backend import TpuBackend
    from dds_tpu.parallel import mesh as pm
    from dds_tpu.parallel.mesh import make_mesh

    calls = {"n": 0}
    orig = pm.sharded_reduce_mul_fixed

    def spy(*a, **k):
        calls["n"] += 1
        return orig(*a, **k)

    monkeypatch.setattr(pm, "sharded_reduce_mul_fixed", spy)

    async def go():
        async with rest_stack() as (server, _, _):
            server.backend = TpuBackend(
                pallas=False, min_device_batch=0, mesh=make_mesh(4)
            )
            pk = PROVIDER.keys.psse.public
            vals = [7, 8, 9, 10, 11]
            for v in vals:
                row = PROVIDER.encrypt_row([v], 1, ["PSSE"])
                await call(server, "POST", "/PutSet", {"contents": row})
            _, data = await call(
                server, "GET", f"/SumAll?position=0&nsqr={pk.nsquare}"
            )
            got = PROVIDER.keys.psse.decrypt(int(json.loads(data)["result"]))
            assert got == sum(vals)
            assert calls["n"] >= 1  # the fold actually went through the mesh

    asyncio.run(go())


def test_trace_route_reports_span_summary():
    """GET /_trace exposes the live tracer summary: after a PutSet and a
    GetSet, the quorum spans appear with counts and millisecond stats."""

    async def go():
        async with rest_stack() as (server, _, _):
            from dds_tpu.utils.trace import tracer

            tracer.reset()
            row = PROVIDER.encrypt_row([5], 1, ["PSSE"])
            _, key = await call(server, "POST", "/PutSet", {"contents": row})
            await call(server, "GET", f"/GetSet/{key.decode()}")
            status, _ = await call(server, "GET", "/_trace")
            assert status == 404  # gated off by default (workload shape)
            server.cfg.trace_route_enabled = True
            status, data = await call(server, "GET", "/_trace")
            assert status == 200
            body = json.loads(data)
            assert body["stored_keys"] == 1
            spans = body["spans"]
            assert spans["abd.write"]["count"] >= 1
            assert spans["abd.fetch"]["count"] >= 1
            assert spans["http.POST.PutSet"]["mean_ms"] > 0

    asyncio.run(go())


def test_stored_keys_survive_proxy_restart_via_snapshot(tmp_path):
    """SURVEY.md §7 do-not-copy quirk: the reference loses the proxy's
    aggregate key set on restart, silently shrinking every SumAll. With
    keys_path set, a fresh server object (modeling the restarted process)
    recovers the keys from the snapshot and folds ALL K sets."""

    async def go():
        snap = str(tmp_path / "proxy_keys.json")
        net = InMemoryNet()
        addrs = [f"replica-{i}" for i in range(7)]
        replicas = {
            a: BFTABDNode(a, addrs, "supervisor", net, ReplicaConfig(quorum_size=5))
            for a in addrs
        }
        del replicas  # replicas only need to exist on the net
        abd = AbdClient("proxy-0", net, addrs, AbdClientConfig(request_timeout=2.0))
        pk = KEYS.psse.public
        vals = [rng.randrange(1 << 24) for _ in range(6)]

        s1 = DDSRestServer(abd, ProxyConfig(host="127.0.0.1", port=0, keys_path=snap))
        await s1.start()
        try:
            for v in vals:
                row = [str(pk.encrypt(v))]
                status, _ = await call(s1, "POST", "/PutSet", {"contents": row})
                assert status == 200
            _, data = await call(s1, "GET", f"/SumAll?position=0&nsqr={pk.nsquare}")
            assert KEYS.psse.decrypt(int(json.loads(data)["result"])) == sum(vals)
        finally:
            await s1.stop()  # flushes the debounced snapshot

        # "restart": brand-new server object, same snapshot path
        s2 = DDSRestServer(abd, ProxyConfig(host="127.0.0.1", port=0, keys_path=snap))
        await s2.start()
        try:
            assert len(s2.stored_keys) == len(vals)  # recovered, not empty
            _, data = await call(s2, "GET", f"/SumAll?position=0&nsqr={pk.nsquare}")
            got = KEYS.psse.decrypt(int(json.loads(data)["result"]))
            assert got == sum(vals)  # did NOT silently shrink
        finally:
            await s2.stop()

    asyncio.run(go())


def test_stored_keys_bootstrap_pull_from_peer_on_start():
    """A proxy restarted WITHOUT a snapshot recovers stored_keys by pulling
    GET /_sync from its gossip peers at start, instead of waiting for the
    next periodic push."""

    async def go():
        net = InMemoryNet()
        addrs = [f"replica-{i}" for i in range(7)]
        replicas = {
            a: BFTABDNode(a, addrs, "supervisor", net, ReplicaConfig(quorum_size=5))
            for a in addrs
        }
        del replicas
        abd1 = AbdClient("proxy-0", net, addrs, AbdClientConfig(request_timeout=2.0))
        abd2 = AbdClient("proxy-1", net, addrs, AbdClientConfig(request_timeout=2.0))
        pk = KEYS.psse.public
        vals = [3, 5, 11]

        # serving side of the pull is gated on key_sync_enabled too (with
        # gossip off, GET /_sync would leak the record-key set to clients)
        s1 = DDSRestServer(
            abd1,
            ProxyConfig(host="127.0.0.1", port=0, key_sync_enabled=True,
                        key_sync_warmup=60.0, key_sync_interval=60.0),
        )
        await s1.start()
        try:
            for v in vals:
                await call(s1, "POST", "/PutSet", {"contents": [str(pk.encrypt(v))]})
            # gossip-off proxies refuse the pull (info leak gate)
            st, _ = await call(s1, "GET", "/_sync")
            assert st == 200
            s_off = DDSRestServer(abd2, ProxyConfig(host="127.0.0.1", port=0))
            await s_off.start()
            st, _ = await call(s_off, "GET", "/_sync")
            assert st == 404
            await s_off.stop()
            # restarted peer: no snapshot, pulls from s1 at start (long
            # gossip interval proves it's the pull, not a push, that fills it)
            s2 = DDSRestServer(
                abd2,
                ProxyConfig(
                    host="127.0.0.1", port=0, key_sync_enabled=True,
                    key_sync_warmup=60.0, key_sync_interval=60.0,
                    peers=[f"127.0.0.1:{s1.cfg.port}"],
                ),
            )
            await s2.start()
            try:
                assert len(s2.stored_keys) == len(vals)
                _, data = await call(
                    s2, "GET", f"/SumAll?position=0&nsqr={pk.nsquare}"
                )
                got = KEYS.psse.decrypt(int(json.loads(data)["result"]))
                assert got == sum(vals)
            finally:
                await s2.stop()
        finally:
            await s1.stop()

    asyncio.run(go())


def test_concurrent_narrow_sumalls_fold_apart_and_wait_for_no_wide_one():
    """R concurrent SumAlls below the device crossover each take their own
    fold and decrypt to the right total, and a narrow fold is answered
    while a wide one is still in flight: nothing gathers folds."""
    from dds_tpu.models.backend import TpuBackend

    import threading

    async def go():
        async with rest_stack() as (server, _, _):
            # position 0 is in all 18 rows (wide: at or above the
            # crossover of 10), position 1 in 6 of them (narrow)
            be = TpuBackend(pallas=False, min_device_batch=10)
            orig_res = be.modmul_fold_resident
            widths: list[int] = []
            wide_started, release = threading.Event(), threading.Event()

            def gated(cs, mod):
                widths.append(len(cs))
                if len(cs) >= be.min_device_batch:
                    wide_started.set()
                    assert release.wait(30), "the wide fold was never let go"
                return orig_res(cs, mod)

            be.modmul_fold_resident = gated
            server.backend = be
            pk = KEYS.psse.public
            ones = [rng.randrange(1 << 24) for _ in range(12)]
            pairs = [(rng.randrange(1 << 24), rng.randrange(1 << 24))
                     for _ in range(6)]
            for v in ones:
                await call(server, "POST", "/PutSet",
                           {"contents": [str(pk.encrypt(v))]})
            for v, w in pairs:
                await call(server, "POST", "/PutSet", {"contents": [
                    str(pk.encrypt(v)), str(pk.encrypt(w))]})
            narrow = f"/SumAll?position=1&nsqr={pk.nsquare}"
            narrow_sum = sum(w for _, w in pairs)

            def total(answer):
                status, data = answer
                assert status == 200
                return KEYS.psse.decrypt(int(json.loads(data)["result"]))

            results = await asyncio.gather(
                *(call(server, "GET", narrow) for _ in range(5)))
            assert [total(r) for r in results] == [narrow_sum] * 5
            assert widths == [6] * 5  # one fold a request, none merged

            wide = asyncio.ensure_future(call(
                server, "GET", f"/SumAll?position=0&nsqr={pk.nsquare}"))
            assert await asyncio.to_thread(wide_started.wait, 30)
            try:
                answer = await asyncio.wait_for(
                    call(server, "GET", narrow), timeout=15)
                assert not wide.done()
            finally:
                release.set()
            assert total(answer) == narrow_sum
            assert total(await wide) == sum(ones) + sum(v for v, _ in pairs)

    asyncio.run(go())


def test_a_fold_that_raises_fails_its_own_request_and_no_other():
    """A fold that raises answers 500 to the request it belongs to (never
    a hang); the folds beside it and the next burst succeed."""
    from dds_tpu.models.backend import TpuBackend

    async def go():
        async with rest_stack() as (server, _, _):
            be = TpuBackend(pallas=False, min_device_batch=10)
            orig_resident = be.modmul_fold_resident
            calls = {"n": 0}

            def second_one_booms(cs, mod):
                calls["n"] += 1
                if calls["n"] == 2:
                    raise RuntimeError("device fell off")
                return orig_resident(cs, mod)

            be.modmul_fold_resident = second_one_booms
            server.backend = be
            pk = KEYS.psse.public
            vals = [2, 3, 5, 7, 11, 13]
            for v in vals:
                await call(server, "POST", "/PutSet", {"contents": [str(pk.encrypt(v))]})

            target = f"/SumAll?position=0&nsqr={pk.nsquare}"
            for burst in range(2):
                results = await asyncio.wait_for(
                    asyncio.gather(*(call(server, "GET", target) for _ in range(5))),
                    timeout=15,
                )
                failed = 1 if burst == 0 else 0
                assert sorted(st for st, _ in results) == (
                    [200] * (5 - failed) + [500] * failed)
                for st, data in results:
                    if st == 200:
                        assert KEYS.psse.decrypt(
                            int(json.loads(data)["result"])) == sum(vals)

    asyncio.run(go())
