"""Tests for auxiliary subsystems: tracing, snapshots, TLS (SURVEY §5)."""

import asyncio
import json
import ssl

import pytest

from dds_tpu.utils.trace import Tracer


# ------------------------------------------------------------------- tracing


def test_tracer_spans_and_summary():
    t = Tracer()
    for _ in range(3):
        with t.span("abd.fetch", key="k"):
            pass
    s = t.summary()
    assert s["abd.fetch"]["count"] == 3
    assert s["abd.fetch"]["p95_ms"] >= 0
    assert len(t.events("abd.fetch")) == 3


def test_tracer_disabled_records_nothing():
    t = Tracer(enabled=False)
    with t.span("x"):
        pass
    t.record("y", 1.0)
    t.event("z")
    assert t.summary() == {} and t.events() == []


def test_tracer_event_dict_is_json_safe():
    t = Tracer()
    with t.span("a", foo=1):
        pass
    (e,) = t.events()
    rec = json.loads(json.dumps(Tracer.event_dict(e)))
    # meta lives under its own key so span meta can never shadow the
    # record's fields (PR 2 namespaced it)
    assert rec["name"] == "a" and rec["meta"]["foo"] == 1


def test_tracer_bounded():
    t = Tracer(max_events=10)
    for i in range(25):
        t.record("e", 1.0)
    assert len(t.events()) == 10


# ----------------------------------------------------------------- snapshots


def test_snapshot_roundtrip(tmp_path):
    from dds_tpu.core import snapshot as snap
    from dds_tpu.core.messages import ABDTag
    from dds_tpu.core.replica import BFTABDNode, ReplicaConfig
    from dds_tpu.core.transport import InMemoryNet

    net = InMemoryNet()
    addrs = ["r0", "r1"]
    node = BFTABDNode("r0", addrs, "sup", net, ReplicaConfig(quorum_size=1))
    node.repository["k1"] = (ABDTag(3, "r0"), [1, "a", 2])
    node.repository["k2"] = (ABDTag(1, "r1"), None)
    node.incoming[12345] = True
    node.incoming[99] = False

    snap.save_replica(node, tmp_path)

    fresh = BFTABDNode("r0", addrs, "sup", InMemoryNet(), ReplicaConfig(quorum_size=1))
    assert snap.load_replica(fresh, tmp_path)
    assert fresh.repository["k1"] == (ABDTag(3, "r0"), [1, "a", 2])
    assert fresh.repository["k2"] == (ABDTag(1, "r1"), None)
    assert fresh.incoming[12345] is True
    # v2 persists the FULL anti-replay map: an in-flight (unexpired) nonce
    # must survive the round trip or it becomes replayable after restore
    assert fresh.incoming[99] is False


def test_snapshot_load_missing(tmp_path):
    from dds_tpu.core import snapshot as snap
    from dds_tpu.core.replica import BFTABDNode, ReplicaConfig
    from dds_tpu.core.transport import InMemoryNet

    node = BFTABDNode("rX", ["rX"], "sup", InMemoryNet(), ReplicaConfig(quorum_size=1))
    assert not snap.load_replica(node, tmp_path)


def test_snapshot_save_all_load_all(tmp_path):
    from dds_tpu.core import snapshot as snap
    from dds_tpu.core.messages import ABDTag
    from dds_tpu.core.replica import BFTABDNode, ReplicaConfig
    from dds_tpu.core.transport import InMemoryNet

    net = InMemoryNet()
    addrs = ["r0", "r1", "r2"]
    replicas = {
        a: BFTABDNode(a, addrs, "sup", net, ReplicaConfig(quorum_size=2))
        for a in addrs
    }
    replicas["r1"].repository["k"] = (ABDTag(7, "r1"), ["x"])
    assert snap.save_all(replicas, tmp_path) == 3
    fresh = {
        a: BFTABDNode(a, addrs, "sup", InMemoryNet(), ReplicaConfig(quorum_size=2))
        for a in addrs
    }
    assert snap.load_all(fresh, tmp_path) == 3
    assert fresh["r1"].repository["k"] == (ABDTag(7, "r1"), ["x"])


# ----------------------------------------------------------------------- TLS


def test_tls_cert_generation_and_contexts(tmp_path):
    from dds_tpu.utils import tlsutil

    paths = tlsutil.generate_ca_and_cert(tmp_path, hosts=("127.0.0.1", "localhost"))
    for p in paths.values():
        assert p.exists()
    # idempotent
    again = tlsutil.generate_ca_and_cert(tmp_path)
    assert again == paths

    srv = tlsutil.server_context(paths["cert"], paths["key"], paths["ca"])
    assert srv.verify_mode == ssl.CERT_REQUIRED
    cli = tlsutil.client_context(paths["ca"], paths["cert"], paths["key"])
    assert cli.check_hostname is False


def test_mutual_tls_http_roundtrip(tmp_path):
    """Full mutual-TLS HTTP round trip through the miniserver."""
    from dds_tpu.http.miniserver import HttpServer, Response, http_request
    from dds_tpu.utils import tlsutil

    paths = tlsutil.generate_ca_and_cert(tmp_path)
    srv_ctx = tlsutil.server_context(paths["cert"], paths["key"], paths["ca"])
    cli_ctx = tlsutil.client_context(paths["ca"], paths["cert"], paths["key"])

    async def go():
        async def handler(req):
            return Response.text("secure-ok")

        server = HttpServer("127.0.0.1", 0, handler, srv_ctx)
        await server.start()
        try:
            status, body = await http_request(
                "127.0.0.1", server.port, "GET", "/", ssl_context=cli_ctx, timeout=5.0
            )
            assert status == 200 and body == b"secure-ok"
            # a client WITHOUT a cert is rejected by mutual auth
            anon = tlsutil.client_context(paths["ca"])
            with pytest.raises((ssl.SSLError, OSError, asyncio.TimeoutError)):
                await http_request(
                    "127.0.0.1", server.port, "GET", "/", ssl_context=anon, timeout=5.0
                )
        finally:
            await server.stop()

    asyncio.run(go())


def test_launch_with_tls_and_snapshots(tmp_path):
    """Boot the full deployment with TLS + snapshots enabled, run a client
    op over HTTPS, snapshot, and restore into a fresh boot."""
    import secrets

    from dds_tpu.core import snapshot as snap
    from dds_tpu.http.miniserver import http_request
    from dds_tpu.run import launch
    from dds_tpu.utils.config import DDSConfig

    async def go():
        cfg = DDSConfig()
        cfg.security.tls_enabled = True
        cfg.security.tls_dir = str(tmp_path / "certs")
        cfg.recovery.snapshot_dir = str(tmp_path / "snaps")
        cfg.recovery.enabled = False
        cfg.proxy.port = 0
        dep = await launch(cfg)
        try:
            body = json.dumps({"contents": [1, 2, 3]}).encode()
            status, key = await http_request(
                "127.0.0.1", dep.server.cfg.port, "POST", "/PutSet", body,
                ssl_context=dep.ssl_client, timeout=10.0,
            )
            assert status == 200
            snap.save_all(dep.replicas, cfg.recovery.snapshot_dir)
        finally:
            await dep.stop()

        # fresh boot restores the snapshots
        dep2 = await launch(cfg)
        try:
            stored = [
                r for r in dep2.replicas.values() if r.repository
            ]
            assert stored, "no replica restored its snapshot"
        finally:
            await dep2.stop()

    asyncio.run(go())


# ------------------------------------------- config + transport hardening


def test_default_toml_parses_and_is_production_safe():
    """The shipped catalog config must be deployment-safe: fault injection
    OFF by default (replicas then ignore Trudy's Crash/Compromise control
    messages — the dataclass default, which the catalog previously
    overrode to True)."""
    import pathlib

    from dds_tpu.utils.config import DDSConfig

    cfg = DDSConfig.load(
        pathlib.Path(__file__).resolve().parent.parent / "configs/default.toml"
    )
    assert cfg.attacks.enabled is False
    assert cfg.client.fast_blinding is True
    assert cfg.transport.advertise == ""


def test_tcpnet_advertised_address():
    from dds_tpu.core.transport import TcpNet

    net = TcpNet("0.0.0.0", 2552)
    assert net.advertised == "0.0.0.0:2552"
    assert TcpNet("0.0.0.0", 2552, advertise="10.0.0.9").advertised == "10.0.0.9:2552"
    assert (
        TcpNet("0.0.0.0", 2552, advertise="10.0.0.9:9999").advertised
        == "10.0.0.9:9999"
    )
    assert (
        TcpNet("0.0.0.0", 2552, advertise="edge.example:2552").local_addr("r-0")
        == "edge.example:2552/r-0"
    )


def test_launch_rejects_unregistered_advertised_address(tmp_path):
    """With per-node identity on, a process whose advertised address is not
    in node_public_keys would emit frames no peer can verify (and, bound to
    0.0.0.0, would itself reject every signed inbound frame) — launch()
    must fail fast instead of deploying a silently deaf fabric."""
    from dds_tpu.run import launch
    from dds_tpu.utils import nodeauth
    from dds_tpu.utils.config import DDSConfig

    async def go():
        key = nodeauth.generate()
        cfg = DDSConfig()
        cfg.transport.kind = "tcp"
        cfg.transport.port = 0
        cfg.transport.host = "127.0.0.1"
        cfg.recovery.enabled = False
        cfg.proxy.port = 0
        cfg.security.node_key_path = str(tmp_path / "node.key")
        # registry names an address this process does NOT advertise
        cfg.security.node_public_keys = {
            "10.9.9.9:2552": nodeauth.public_hex(key)
        }
        with pytest.raises(ValueError, match="advertised"):
            await launch(cfg)

    asyncio.run(go())


def test_undecodable_frame_does_not_kill_connection():
    """A malformed frame (bad JSON, unknown message type) must be dropped
    per-frame — not tear down the shared cached connection and lose every
    queued frame behind it (rolling-upgrade safety)."""
    from dds_tpu.core import messages as M
    from dds_tpu.core.transport import TcpNet

    async def go():
        net = TcpNet("127.0.0.1", 0)
        await net.start()
        got = []

        async def handler(src, msg):
            got.append(msg)

        net.register("sink", handler)
        try:
            reader, writer = await asyncio.open_connection("127.0.0.1", net.port)

            def frame(raw: bytes) -> bytes:
                return len(raw).to_bytes(4, "big") + raw

            good = json.dumps(
                {
                    "src": "peer",
                    "dest": "sink",
                    "msg": M.to_dict(M.Redeploy("replica-0")),
                }
            ).encode()
            writer.write(frame(b"this is not json"))
            writer.write(frame(json.dumps({"src": "p"}).encode()))  # missing keys
            writer.write(  # type-confused fields must not escape the guard
                frame(json.dumps({"src": "p", "dest": 123, "msg": {}}).encode())
            )
            writer.write(frame(json.dumps(["a", "list"]).encode()))
            writer.write(
                frame(
                    json.dumps(
                        {"src": "p", "dest": "sink", "msg": {"__msg__": "Nope"}}
                    ).encode()
                )
            )
            writer.write(frame(good))  # must still arrive on the SAME conn
            await writer.drain()
            for _ in range(100):
                if got:
                    break
                await asyncio.sleep(0.02)
            assert got and isinstance(got[0], M.Redeploy)
            writer.close()
        finally:
            await net.stop()

    asyncio.run(go())


def test_fast_blinding_knob_and_scaled_s_bits():
    from dds_tpu.models.paillier import PaillierPublicKey
    from dds_tpu.run import load_provider
    from dds_tpu.utils.config import DDSConfig

    cfg = DDSConfig()
    cfg.client.paillier_bits = 1024
    cfg.client.rsa_bits = 1024
    cfg.client.fast_blinding = False
    assert load_provider(cfg).fast_blinding is False
    cfg.client.fast_blinding = True
    assert load_provider(cfg).fast_blinding is True

    # s_bits scales with the modulus strength instead of a fixed 448
    assert PaillierPublicKey(1 << 2047)._djn_s_bits() == 448
    assert PaillierPublicKey(1 << 3071)._djn_s_bits() == 512
    assert PaillierPublicKey(1 << 4095)._djn_s_bits() == 608
    assert PaillierPublicKey(1 << 1023)._djn_s_bits() == 320


def test_workload_bulk_encrypt_backend_batches_obfuscators():
    """client.bulk-encrypt-backend routes a digest's PSSE obfuscator
    modexps through ONE batched backend dispatch (full-width exponent),
    and the workload still completes — the encrypt-grade modexp wiring of
    r4 verdict #3, driven through launch() + run_workload()."""
    import asyncio as _asyncio

    from dds_tpu.run import launch, load_provider, run_workload
    from dds_tpu.utils.config import DDSConfig

    async def go():
        cfg = DDSConfig()
        cfg.recovery.enabled = False
        cfg.proxy.port = 0
        cfg.client.nr_of_operations = 100
        cfg.client.paillier_bits = 512
        cfg.client.rsa_bits = 512
        cfg.client.bulk_encrypt_backend = "tpu"
        cfg.client.proportions = {"put-set": 0.9, "sum-all": 0.1}
        provider = load_provider(cfg)
        be = provider.bulk_backend
        assert be is not None and be.name == "tpu"
        be.min_device_batch = 0
        calls = []
        orig = be.powmod_batch
        be.powmod_batch = lambda bases, exp, mod: calls.append(
            (len(bases), exp.bit_length())
        ) or orig(bases, exp, mod)

        dep = await launch(cfg)
        try:
            reports = await run_workload(dep, provider=provider, seed=3)
        finally:
            await dep.stop()
        assert all(r.failed == 0 for r in reports)
        # one batched dispatch, full-width (n-bit) exponent, >= min_batch rows
        assert calls and calls[0][0] >= 60 and calls[0][1] >= 511
        assert len(provider._blind_pool) == 0  # drained by the PutSets

    _asyncio.run(go())
