"""BFT-ABD protocol tests over the in-memory transport.

The property layer the reference never had (SURVEY.md §4): quorum
read/write semantics, replay/signature rejection, Byzantine tolerance up to
f=2 with n=7/q=5, and the supervisor's swap/recovery choreography.
"""

import asyncio
import random

import pytest

from dds_tpu.core import messages as M
from dds_tpu.core.errors import ByzantineError
from dds_tpu.core.quorum_client import AbdClient, AbdClientConfig
from dds_tpu.core.replica import BFTABDNode, ReplicaConfig
from dds_tpu.core.supervisor import BFTSupervisor, SupervisorConfig
from dds_tpu.core.transport import InMemoryNet
from dds_tpu.obs.metrics import metrics
from dds_tpu.utils import sigs


class Cluster:
    """In-process cluster: n replicas (+spares), a supervisor, one client.

    `net` lets chaos suites inject a fault fabric (e.g. a ChaosNet over
    the default InMemoryNet) without re-plumbing the topology."""

    def __init__(self, n_active=7, n_sentinent=2, quorum=5, proactive=False,
                 net=None):
        self.net = net or InMemoryNet()
        self.rcfg = ReplicaConfig(quorum_size=quorum)
        all_addrs = [f"replica-{i}" for i in range(n_active + n_sentinent)]
        self.active = all_addrs[:n_active]
        self.sentinent = all_addrs[n_active:]
        self.replicas = {
            a: BFTABDNode(a, all_addrs, "supervisor", self.net, self.rcfg)
            for a in all_addrs
        }
        for a in self.sentinent:
            self.replicas[a].behavior = "sentinent"
        self.supervisor = BFTSupervisor(
            "supervisor",
            self.active,
            self.sentinent,
            self.net,
            SupervisorConfig(
                quorum_size=quorum,
                proactive_recovery_enabled=proactive,
                proactive_recovery_warmup=0.05,
                proactive_recovery_interval=0.1,
                sentinent_awake_timeout=0.5,
                # bounded so a dead-host seed path (and the graceful
                # stop() that now awaits it) cannot pin a test for the
                # 12 s production default
                crashed_recovery_timeout=2.0,
            ),
            redeploy=self._redeploy,
            rng=random.Random(3),
        )
        self.client = AbdClient(
            "proxy-0",
            self.net,
            self.active,
            AbdClientConfig(request_timeout=1.0),
        )
        self.client.replicas._rng = random.Random(7)

    async def _redeploy(self, endpoint):
        self.replicas[endpoint] = BFTABDNode(
            endpoint, list(self.replicas), "supervisor", self.net, self.rcfg
        )


def run(coro):
    return asyncio.run(coro)


def test_write_then_read_roundtrip():
    async def go():
        c = Cluster()
        value = [41, "enc-blob", "123456789", None]
        key = sigs.key_from_set(value)
        assert await c.client.write_set(key, value) == key
        assert await c.client.fetch_set(key) == value
        await c.net.quiesce()
        # at least a quorum of replicas hold the value
        holders = [
            r for r in c.replicas.values()
            if r.repository.get(key, (None, None))[1] == value
        ]
        assert len(holders) >= 5

    run(go())


def test_read_missing_key_returns_none():
    async def go():
        c = Cluster()
        assert await c.client.fetch_set("DEADBEEF") is None

    run(go())


def test_remove_via_write_none():
    async def go():
        c = Cluster()
        key = "K1"
        await c.client.write_set(key, [1, 2, 3])
        await c.client.write_set(key, None)
        assert await c.client.fetch_set(key) is None

    run(go())


def test_sequential_writes_last_wins():
    async def go():
        c = Cluster()
        key = "K2"
        for i in range(5):
            await c.client.write_set(key, [i])
        assert await c.client.fetch_set(key) == [4]

    run(go())


def test_byzantine_minority_tolerated():
    async def go():
        c = Cluster()
        # compromise f=2 replicas (not the ones the seeded client rng picks)
        victims = ["replica-5", "replica-6"]
        for v in victims:
            c.net.send("trudy", v, M.Compromise())
        await c.net.quiesce()
        c.client.replicas.reset([a for a in c.active if a not in victims])
        value = [7, "x"]
        key = sigs.key_from_set(value)
        await c.client.write_set(key, value)
        assert await c.client.fetch_set(key) == value

    run(go())


def test_byzantine_coordinator_detected():
    async def go():
        c = Cluster()
        c.client.replicas.reset(["replica-0"])  # force coordinator choice
        c.net.send("trudy", "replica-0", M.Compromise())
        await c.net.quiesce()
        with pytest.raises((ByzantineError, asyncio.TimeoutError)):
            await c.client.fetch_set("ANYKEY")
        assert c.client.replicas._strikes["replica-0"] >= 1

    run(go())


def test_replayed_proxy_nonce_ignored():
    async def go():
        c = Cluster()
        key = "K3"
        nonce = sigs.generate_nonce()
        sig = sigs.proxy_signature(c.rcfg.proxy_mac_secret, key, nonce, [1])
        env = M.Envelope(M.IWrite(key, [1]), nonce, sig)
        c.net.send("proxy-0", "replica-0", env)
        await c.net.quiesce()
        before = c.replicas["replica-1"].repository.get(key)
        # replay the same nonce with different contents
        sig2 = sigs.proxy_signature(c.rcfg.proxy_mac_secret, key, nonce, [2])
        c.net.send("proxy-0", "replica-0", M.Envelope(M.IWrite(key, [2]), nonce, sig2))
        await c.net.quiesce()
        after = c.replicas["replica-1"].repository.get(key)
        assert before == after  # second write never executed

    run(go())


def test_bad_proxy_signature_rejected():
    async def go():
        c = Cluster()
        nonce = sigs.generate_nonce()
        env = M.Envelope(M.IWrite("K4", [1]), nonce, b"forged")
        c.net.send("proxy-0", "replica-0", env)
        await c.net.quiesce()
        assert all("K4" not in r.repository for r in c.replicas.values())

    run(go())


def test_suspicion_quorum_triggers_recovery():
    async def go():
        c = Cluster()
        # 5 distinct replicas vote against replica-6
        for i in range(5):
            c.net.send(
                f"replica-{i}", "supervisor", M.Suspect("replica-6", sigs.generate_nonce())
            )
        await c.net.quiesce()
        await asyncio.sleep(0.1)
        await c.net.quiesce()
        # replica-6 was demoted to sentinent; one spare was promoted
        assert "replica-6" in c.supervisor.sentinent
        active_names = [a for a, _ in c.supervisor.active]
        assert "replica-6" not in active_names
        assert len(active_names) == 7
        assert c.replicas["replica-6"].behavior == "sentinent"

    run(go())


def test_recovery_preserves_data():
    async def go():
        c = Cluster()
        value = [9, "persist"]
        key = sigs.key_from_set(value)
        await c.client.write_set(key, value)
        await c.net.quiesce()
        # recover replica-0 explicitly (as the proactive timer would)
        await c.supervisor.recover("replica-0")
        await c.net.quiesce()
        # the promoted spare holds the data (it observed quorum writes while
        # sentinent) and the demoted node was reseeded with it
        assert c.replicas["replica-0"].repository.get(key, (None, None))[1] == value
        assert await c.client.fetch_set(key) == value

    run(go())


def test_proactive_recovery_loop():
    async def go():
        c = Cluster(proactive=True)
        c.supervisor.start()
        await asyncio.sleep(0.4)
        await c.supervisor.stop()
        await c.net.quiesce()
        # at least one swap happened; membership sizes preserved
        assert len(c.supervisor.active) == 7
        assert len(c.supervisor.sentinent) == 2

    run(go())


def test_request_replicas_returns_freshest_half():
    async def go():
        c = Cluster()
        got = []

        async def catcher(sender, msg):
            got.append(msg)

        c.net.register("observer", catcher)
        c.net.send("observer", "supervisor", M.RequestReplicas())
        await c.net.quiesce()
        assert isinstance(got[0], M.ActiveReplicas)
        assert len(got[0].replicas) == 3  # newest half of 7

    run(go())


def test_message_serialization_roundtrip():
    msgs = [
        M.Envelope(M.IWrite("K", [1, "a", None]), 42, b"\x01\x02"),
        M.TagReply(M.ABDTag(3, "replica-1"), "K", None, b"sig", 9),
        M.Sleep({"K": {"tag": [1, "r"], "value": [1]}}, [4, 5]),
        M.ActiveReplicas(["a", "b"]),
        M.Compromise(),
    ]
    for m in msgs:
        assert M.loads(M.dumps(m)) == m


def test_tcp_transport_roundtrip():
    async def go():
        from dds_tpu.core.transport import TcpNet

        net = TcpNet("127.0.0.1", 39471)
        await net.start()
        got = asyncio.get_event_loop().create_future()

        async def handler(sender, msg):
            got.set_result((sender, msg))

        net.register("127.0.0.1:39471/alice", handler)
        net.send("bob", "127.0.0.1:39471/alice", M.ReadTag("K", 77))
        sender, msg = await asyncio.wait_for(got, 3)
        assert msg == M.ReadTag("K", 77)
        await net.stop()

    run(go())


def test_tcp_frame_mac_rejects_spoofed_frames():
    async def go():
        import json as _json

        from dds_tpu.core.transport import TcpNet

        net = TcpNet("127.0.0.1", 0 or 39474, frame_secret=b"cluster-secret")
        await net.start()
        got = []

        async def handler(sender, msg):
            got.append((sender, msg))

        net.register("127.0.0.1:39474/sup", handler)
        # legitimate frame (signed by the transport itself)
        net.send("replica-0", "127.0.0.1:39474/sup", M.ReadTag("K", 1))
        await asyncio.sleep(0.2)
        # forged frame: attacker with socket access but no frame secret
        r, w = await asyncio.open_connection("127.0.0.1", 39474)
        frame = _json.dumps(
            {"src": "replica-1", "dest": "127.0.0.1:39474/sup",
             "msg": M.to_dict(M.Suspect("replica-6", 99))}
        ).encode()
        w.write(len(frame).to_bytes(4, "big") + frame)
        await w.drain()
        await asyncio.sleep(0.2)
        w.close()
        await net.stop()
        assert [type(m).__name__ for _, m in got] == ["ReadTag"]  # spoof dropped

    run(go())


def test_tcp_intranet_mutual_tls_rejects_certless_peer(tmp_path):
    """The replica fabric under mutual TLS (`dds-system.conf:18-58`): a
    certified peer's frames arrive; a peer that completes TCP but presents
    no client certificate fails the handshake and delivers nothing."""

    async def go():
        import ssl as _ssl

        from dds_tpu.core.transport import TcpNet
        from dds_tpu.utils import tlsutil

        paths = tlsutil.generate_ca_and_cert(tmp_path, hosts=("127.0.0.1",))
        ca, cert, key = paths["ca"], paths["cert"], paths["key"]
        server_ctx = tlsutil.server_context(cert, key, ca)
        client_ctx = tlsutil.client_context(ca, cert, key)

        net = TcpNet("127.0.0.1", 39481, ssl_server=server_ctx, ssl_client=client_ctx)
        await net.start()
        got = []

        async def handler(sender, msg):
            got.append((sender, msg))

        net.register("127.0.0.1:39481/sup", handler)
        net.send("replica-0", "127.0.0.1:39481/sup", M.ReadTag("K", 1))
        await asyncio.sleep(0.3)
        assert [type(m).__name__ for _, m in got] == ["ReadTag"]

        # unauthenticated peer: trusts the CA but presents no client cert
        certless = tlsutil.client_context(ca)
        try:
            _, w = await asyncio.open_connection(
                "127.0.0.1", 39481, ssl=certless, server_hostname="localhost"
            )
            frame = b'{"src":"replica-1","dest":"127.0.0.1:39481/sup","msg":{}}'
            w.write(len(frame).to_bytes(4, "big") + frame)
            await w.drain()
            await asyncio.sleep(0.3)
            w.close()
        except (_ssl.SSLError, ConnectionResetError):
            pass  # handshake refusal is the expected outcome
        assert len(got) == 1  # nothing further was delivered
        await net.stop()

    run(go())


def test_oversized_tcp_frame_drops_connection():
    """A peer declaring a frame above MAX_FRAME (reference parity:
    maximum-frame-size, dds-system.conf:58) gets its connection dropped
    before the receiver buffers anything; normal traffic still flows."""

    async def go():
        from dds_tpu.core.transport import TcpNet

        net = TcpNet("127.0.0.1", 39551)
        await net.start()
        got = []

        async def handler(sender, msg):
            got.append(msg)

        net.register("127.0.0.1:39551/sup", handler)
        try:
            r, w = await asyncio.open_connection("127.0.0.1", 39551)
            w.write((TcpNet.MAX_FRAME + 1).to_bytes(4, "big") + b"x" * 64)
            await w.drain()
            # the server DROPS the connection (not just the frame): EOF
            assert await asyncio.wait_for(r.read(1), 2) == b""
            # a fresh, sane frame on a new connection still works
            net.send("a", "127.0.0.1:39551/sup", M.ReadTag("k", 1))
            await asyncio.sleep(0.2)
            w.close()
            assert [type(m).__name__ for m in got] == ["ReadTag"]
        finally:
            await net.stop()

    run(go())


def test_node_signed_frames_reject_credentialed_src_forgery():
    """Per-node frame signatures (utils/nodeauth): member B holds VALID
    cluster credentials (its own Ed25519 key, registered in the registry)
    but forges frames claiming member A's src addresses. The receiver
    verifies the signature against the claimed src's registered key, so
    B's forgeries are dropped while its honest frames flow — one
    compromised member cannot stuff sender-keyed quorums (WriteAck /
    Suspect / TagBatchReply) with spoofed votes."""

    async def go():
        import json as _json

        from dds_tpu.core.transport import TcpNet
        from dds_tpu.utils import nodeauth

        key_a, key_b = nodeauth.generate(), nodeauth.generate()
        reg = {
            "127.0.0.1:39511": nodeauth.load_public(nodeauth.public_hex(key_a)),
            "127.0.0.1:39512": nodeauth.load_public(nodeauth.public_hex(key_b)),
        }
        net_a = TcpNet("127.0.0.1", 39511, node_key=key_a, peer_keys=reg)
        net_b = TcpNet("127.0.0.1", 39512, node_key=key_b, peer_keys=reg)
        await net_a.start()
        await net_b.start()
        got = []

        async def handler(sender, msg):
            got.append((sender, type(msg).__name__))

        net_a.register("127.0.0.1:39511/sup", handler)
        try:
            # honest frame from B: accepted
            net_b.send("127.0.0.1:39512/replica-2", "127.0.0.1:39511/sup",
                       M.WriteAck("k", 1))
            # forgery: B signs with ITS key but claims A's own replica as src
            net_b.send("127.0.0.1:39511/replica-0", "127.0.0.1:39511/sup",
                       M.WriteAck("k", 2))
            # forgery: B claims an unregistered host
            net_b.send("10.0.0.9:999/replica-9", "127.0.0.1:39511/sup",
                       M.WriteAck("k", 3))
            await asyncio.sleep(0.3)
            assert got == [("127.0.0.1:39512/replica-2", "WriteAck")]

            # an unsigned frame (attacker without any node key) is dropped
            r, w = await asyncio.open_connection("127.0.0.1", 39511)
            frame = _json.dumps(
                {"src": "127.0.0.1:39512/replica-2",
                 "dest": "127.0.0.1:39511/sup",
                 "msg": M.to_dict(M.WriteAck("k", 4))}
            ).encode()
            w.write(len(frame).to_bytes(4, "big") + frame)
            await w.drain()
            await asyncio.sleep(0.2)
            w.close()
            assert len(got) == 1

            # a captured VALID signed frame replayed verbatim is dropped
            # (the signed counter must strictly increase per src host)
            src, dest = "127.0.0.1:39512/replica-2", "127.0.0.1:39511/sup"
            payload = M.to_dict(M.WriteAck("k", 5))
            ctr = 10**30  # far above anything sent so far
            body = TcpNet._frame_body(src, dest, payload, ctr)
            obj = {"src": src, "dest": dest, "msg": payload, "ctr": ctr,
                   "sig": key_b.sign(body).hex()}
            raw = _json.dumps(obj).encode()
            r, w = await asyncio.open_connection("127.0.0.1", 39511)
            for _ in range(2):  # original + replay
                w.write(len(raw).to_bytes(4, "big") + raw)
            await w.drain()
            await asyncio.sleep(0.3)
            w.close()
            assert len(got) == 2  # exactly one of the two was accepted
        finally:
            await net_a.stop()
            await net_b.stop()

    run(go())


def test_launch_tcp_with_intranet_tls_end_to_end(tmp_path):
    """launch() with transport=tcp + intranet mutual TLS: the full quorum
    path (PutSet-style write then read) works over the TLS replica fabric."""

    async def go():
        from dds_tpu.run import launch
        from dds_tpu.utils.config import DDSConfig

        cfg = DDSConfig()
        cfg.transport.kind = "tcp"
        cfg.transport.port = 39491
        cfg.security.intranet_tls_enabled = True
        cfg.security.tls_dir = str(tmp_path)
        cfg.proxy.port = 0
        dep = await launch(cfg)
        try:
            assert dep.net._ssl_server is not None  # contexts actually wired
            prefix = f"127.0.0.1:39491/"
            abd = dep.server.abd
            k, tag = await abd.write_set_tagged("tls-key", [41, 42])
            assert k == "tls-key" and tag is not None
            value, rtag = await abd.fetch_set_tagged("tls-key")
            assert value == [41, 42] and rtag == tag
            tags = await abd.read_tags(["tls-key"])
            assert tags == [rtag]
        finally:
            await dep.stop()

    run(go())


def test_two_process_deployment_quorum_across_tcp(tmp_path):
    """`Main.scala:90-99` + `dds-system.conf:113-128` parity: the same
    binary runs on multiple hosts, each spawning only ITS replicas, with
    the quorum spanning hosts over the intranet fabric. Two launch()es
    (two TcpNets = two processes in miniature) host disjoint halves of a
    4-replica f=1 quorum under mutual intranet TLS; writes and reads
    coordinate across both, and BOTH proxies see the data."""

    async def go():
        from dds_tpu.run import launch
        from dds_tpu.utils import tlsutil
        from dds_tpu.utils.config import DDSConfig

        from dds_tpu.utils import nodeauth

        port_a, port_b = 39501, 39502
        host_a, host_b = f"127.0.0.1:{port_a}", f"127.0.0.1:{port_b}"
        paths = tlsutil.generate_ca_and_cert(tmp_path, hosts=("127.0.0.1",))
        # per-process Ed25519 identities, provisioned like the certs
        key_a, key_b = nodeauth.generate(), nodeauth.generate()
        (tmp_path / "node_a.key").write_text(nodeauth.private_hex(key_a))
        (tmp_path / "node_b.key").write_text(nodeauth.private_hex(key_b))
        registry = {host_a: nodeauth.public_hex(key_a),
                    host_b: nodeauth.public_hex(key_b)}

        def make_cfg(port, remote_map, local):
            cfg = DDSConfig()
            cfg.transport.kind = "tcp"
            cfg.transport.port = port
            cfg.replicas.endpoints = [f"replica-{i}" for i in range(4)]
            cfg.replicas.sentinent = []
            cfg.replicas.byz_quorum_size = 3
            cfg.replicas.addresses = remote_map
            cfg.replicas.local = local
            cfg.replicas.supervisor_address = host_a  # supervisor on A
            cfg.recovery.enabled = False
            cfg.proxy.port = 0
            cfg.security.intranet_tls_enabled = True
            cfg.security.tls_ca = paths["ca"]
            cfg.security.tls_cert = paths["cert"]
            cfg.security.tls_key = paths["key"]
            cfg.security.node_key_path = str(
                tmp_path / ("node_a.key" if port == port_a else "node_b.key")
            )
            cfg.security.node_public_keys = dict(registry)
            return cfg

        cfg_a = make_cfg(
            port_a, {"replica-2": host_b, "replica-3": host_b},
            ["replica-0", "replica-1"],
        )
        cfg_b = make_cfg(
            port_b, {"replica-0": host_a, "replica-1": host_a},
            ["replica-2", "replica-3"],
        )

        dep_a = await launch(cfg_a)
        dep_b = await launch(cfg_b)
        try:
            assert set(dep_a.replicas) == {f"{host_a}/replica-0",
                                           f"{host_a}/replica-1"}
            assert set(dep_b.replicas) == {f"{host_b}/replica-2",
                                           f"{host_b}/replica-3"}
            assert dep_a.supervisor is not None
            assert dep_b.supervisor is None  # remote supervisor

            # write through A's proxy: quorum 3 of 4 must span both hosts
            k, tag = await dep_a.server.abd.write_set_tagged("xhost", [5, 6])
            assert k == "xhost" and tag is not None
            value, rtag = await dep_a.server.abd.fetch_set_tagged("xhost")
            assert value == [5, 6] and rtag == tag
            # B's proxy reads the same data through its own coordinators
            value_b, rtag_b = await dep_b.server.abd.fetch_set_tagged("xhost")
            assert value_b == [5, 6] and rtag_b == tag
            # the batched tag round also spans hosts
            tags = await dep_b.server.abd.read_tags(["xhost"])
            assert tags == [tag]
            # data actually lives on both hosts (quorum intersected)
            holders = [
                node for dep in (dep_a, dep_b)
                for node in dep.replicas.values()
                if node.repository.get("xhost", (None, None))[1] == [5, 6]
            ]
            assert len(holders) >= 3
        finally:
            await dep_b.stop()
            await dep_a.stop()

    run(go())


def test_trudy_crash_and_suspicion_recovery_over_tcp():
    """Fault injection + recovery on the REAL fabric (`Trudy.scala:14-32` +
    `BFTSupervisor.scala:97-153`): Trudy's crash rides the TCP transport as
    a Crash control message, the damaged quorum keeps serving, a suspicion
    quorum then recovers the dead replica over TCP — spare promoted via
    Awake/State, victim redeployed and reseeded via Sleep/Complying — and
    the recovered fabric still completes quorums."""

    async def go():
        import random as _random

        from dds_tpu.core.errors import ByzantineError
        from dds_tpu.run import launch
        from dds_tpu.utils.config import DDSConfig

        port = 39531
        prefix = f"127.0.0.1:{port}/"
        cfg = DDSConfig()
        cfg.transport.kind = "tcp"
        cfg.transport.port = port
        cfg.attacks.enabled = True    # deployment honors Trudy's injections
        cfg.recovery.enabled = False  # manual recovery only, timing-clean
        cfg.recovery.sentinent_awake_timeout = 1.0
        cfg.recovery.crashed_recovery_timeout = 3.0
        cfg.proxy.port = 0
        cfg.proxy.intranet_request_timeout = 1.0
        dep = await launch(cfg)
        try:
            abd = dep.server.abd
            k, tag = await abd.write_set_tagged("rkey", [1, 2])
            assert tag is not None

            dep.trudy._rng = _random.Random(5)
            victims = dep.trudy.trigger("crash")
            assert len(victims) == 2
            await asyncio.sleep(0.3)
            # crashed endpoints are actually off the transport
            for v in victims:
                assert v.rsplit("/", 1)[-1] not in dep.net._handlers

            # the damaged quorum (7-2=5 = q) still serves; a crashed
            # coordinator draw times out and gets struck, so retry
            for _ in range(8):
                try:
                    value, _ = await abd.fetch_set_tagged("rkey")
                    break
                except (ByzantineError, asyncio.TimeoutError):
                    continue
            else:
                raise AssertionError("quorum never completed after crash")
            assert value == [1, 2]

            # suspicion quorum against one victim, voted over the fabric
            victim = victims[0]
            healthy = [a for a, _ in dep.supervisor.active if a not in victims]
            for voter in healthy[:5]:
                dep.net.send(
                    voter, f"{prefix}supervisor",
                    M.Suspect(victim, sigs.generate_nonce()),
                )
            # recovery: Awake spare (fast), Kill+Sleep victim (1s timeout,
            # dead), redeploy, Sleep again -> Complying
            for _ in range(40):
                await asyncio.sleep(0.2)
                if victim in dep.supervisor.sentinent:
                    break
            assert victim in dep.supervisor.sentinent
            active_now = [a for a, _ in dep.supervisor.active]
            assert victim not in active_now
            assert len(active_now) == 7  # a spare was promoted
            # the redeployed victim is back on the transport, reseeded
            assert victim.rsplit("/", 1)[-1] in dep.net._handlers
            assert dep.replicas[victim].repository.get("rkey", (None, None))[1] \
                == [1, 2]

            # recovered fabric completes fresh quorums (incl. the spare)
            for _ in range(8):
                try:
                    k2, t2 = await abd.write_set_tagged("rkey2", [9])
                    break
                except (ByzantineError, asyncio.TimeoutError):
                    continue
            else:
                raise AssertionError("quorum never completed after recovery")
            assert t2 is not None
        finally:
            await dep.stop()

    run(go())


def test_cross_host_redeploy_recovers_dead_remote_replica():
    """The RemoteScope parity case (`BFTSupervisor.scala:130-149`): the
    supervisor on host A recovers a crashed replica living on host B — the
    spare wakes over TCP, the victim's rebuild goes through B's node-host
    agent, and the Sleep reseed lands on the fresh node."""

    async def go():
        from dds_tpu.core.errors import ByzantineError
        from dds_tpu.run import launch
        from dds_tpu.utils.config import DDSConfig

        port_a, port_b = 39541, 39542
        host_a, host_b = f"127.0.0.1:{port_a}", f"127.0.0.1:{port_b}"

        def make_cfg(port, remote_map, local):
            cfg = DDSConfig()
            cfg.transport.kind = "tcp"
            cfg.transport.port = port
            cfg.replicas.endpoints = [f"replica-{i}" for i in range(5)]
            cfg.replicas.sentinent = ["replica-4"]
            cfg.replicas.byz_quorum_size = 3   # n_active=4, f=1
            cfg.replicas.addresses = remote_map
            cfg.replicas.local = local
            cfg.replicas.supervisor_address = host_a
            cfg.attacks.enabled = True
            cfg.recovery.enabled = False
            cfg.recovery.sentinent_awake_timeout = 1.0
            cfg.recovery.crashed_recovery_timeout = 3.0
            cfg.proxy.port = 0
            cfg.proxy.intranet_request_timeout = 1.0
            return cfg

        b_names = ("replica-3", "replica-4")
        cfg_a = make_cfg(port_a, {n: host_b for n in b_names},
                         ["replica-0", "replica-1", "replica-2"])
        cfg_b = make_cfg(port_b,
                         {n: host_a for n in ("replica-0", "replica-1",
                                              "replica-2")},
                         list(b_names))
        dep_a = await launch(cfg_a)
        dep_b = await launch(cfg_b)
        try:
            abd = dep_a.server.abd
            await abd.write_set_tagged("xk", [3])

            victim = f"{host_b}/replica-3"  # lives on B; supervisor on A
            old_node = dep_b.replicas[victim]
            dep_a.net.send(f"{host_a}/trudy", victim, M.Crash())
            await asyncio.sleep(0.3)
            assert "replica-3" not in dep_b.net._handlers  # actually dead

            for voter in (f"{host_a}/replica-0", f"{host_a}/replica-1",
                          f"{host_a}/replica-2"):
                dep_a.net.send(voter, f"{host_a}/supervisor",
                               M.Suspect(victim, sigs.generate_nonce()))
            for _ in range(40):
                await asyncio.sleep(0.2)
                if victim in dep_a.supervisor.sentinent:
                    break
            assert victim in dep_a.supervisor.sentinent
            # B's node agent rebuilt it: new object, re-registered, reseeded
            new_node = dep_b.replicas[victim]
            assert new_node is not old_node
            assert "replica-3" in dep_b.net._handlers
            assert new_node.repository.get("xk", (None, None))[1] == [3]
            assert new_node.behavior == "sentinent"  # demoted after reseed

            # the promoted spare keeps the quorum serving
            for _ in range(8):
                try:
                    value, _ = await abd.fetch_set_tagged("xk")
                    break
                except (ByzantineError, asyncio.TimeoutError):
                    continue
            else:
                raise AssertionError("quorum never completed after recovery")
            assert value == [3]
        finally:
            await dep_b.stop()
            await dep_a.stop()

    run(go())


def test_he_key_persistence_roundtrip(tmp_path):
    """client.conf:81-88 contract: run 1 generates keys (persisted via
    client.he_keys_path) and uploads encrypted rows; run 2's freshly-loaded
    provider (a new process would do exactly this) decrypts SumAll against
    the existing store. A provider with independent keys cannot."""

    async def go():
        import json as _json

        from dds_tpu.http.miniserver import http_request
        from dds_tpu.models.facade import HomoProvider
        from dds_tpu.run import launch, load_provider
        from dds_tpu.utils.config import DDSConfig

        cfg = DDSConfig()
        cfg.proxy.port = 0
        cfg.recovery.enabled = False
        cfg.client.paillier_bits = 1024  # keep keygen fast in tests
        cfg.client.he_keys_path = str(tmp_path / "he_keys.json")

        dep = await launch(cfg)
        try:
            host, port = cfg.proxy.host, dep.server.cfg.port
            run1 = load_provider(cfg)  # generates + persists
            vals = [7, 11]
            for v in vals:
                row = run1.encrypt_row([v], 1, ["PSSE"])
                status, _ = await http_request(
                    host, port, "POST", "/PutSet",
                    _json.dumps({"contents": row}).encode(),
                )
                assert status == 200

            run2 = load_provider(cfg)  # fresh object, loaded from disk
            assert run2 is not run1
            nsqr = run2.keys.psse.public.nsquare
            status, body = await http_request(
                host, port, "GET", f"/SumAll?position=0&nsqr={nsqr}"
            )
            assert status == 200
            total = int(_json.loads(body)["result"])
            assert run2.keys.psse.decrypt_signed(total) == sum(vals)

            # and literally from a FRESH PROCESS: only the persisted key
            # file crosses the boundary
            import subprocess
            import sys

            out = subprocess.run(
                [sys.executable, "-c", (
                    "import sys\n"
                    "from dds_tpu.models.keys import HEKeys\n"
                    "k = HEKeys.from_json(open(sys.argv[1]).read())\n"
                    "print(k.psse.decrypt_signed(int(sys.argv[2])))\n"
                ), cfg.client.he_keys_path, str(total)],
                capture_output=True, text=True, timeout=120,
            )
            assert out.returncode == 0, out.stderr
            assert int(out.stdout.strip()) == sum(vals)

            stranger = HomoProvider.generate(1024, 1024)
            assert stranger.keys.psse.decrypt_signed(total) != sum(vals)
        finally:
            await dep.stop()

    run(go())


def test_he_keys_inline_config_wins_over_path(tmp_path):
    """An inline HEKeys blob in the config takes precedence over the keys
    file — the direct analogue of keys shipped inside client.conf."""
    from dds_tpu.models.keys import HEKeys
    from dds_tpu.run import load_provider
    from dds_tpu.utils.config import DDSConfig

    inline = HEKeys.generate(paillier_bits=1024, rsa_bits=1024)
    other = HEKeys.generate(paillier_bits=1024, rsa_bits=1024)
    path = tmp_path / "keys.json"
    path.write_text(other.to_json())

    cfg = DDSConfig()
    cfg.client.he_keys_inline = inline.to_json()
    cfg.client.he_keys_path = str(path)
    p = load_provider(cfg)
    assert p.keys.psse.n == inline.psse.n  # inline won
    cfg.client.he_keys_inline = ""
    p2 = load_provider(cfg)
    assert p2.keys.psse.n == other.psse.n  # falls back to the file


def test_unreachable_replica_struck_then_dropped():
    """A replica that never complies after redeploy stays a (struck) spare
    — one miss may be a slow restart — but DROP_STRIKES consecutive
    failures drop it from membership so a phantom cannot pin future
    recoveries. A transient single miss self-heals on the next contact."""

    async def go():
        c = Cluster()
        victim = "replica-0"
        c.supervisor.cfg.sentinent_awake_timeout = 0.2
        c.supervisor.cfg.crashed_recovery_timeout = 0.2

        async def broken_redeploy(endpoint):
            pass  # rebuild never happens: node stays gone

        c.supervisor.redeploy = broken_redeploy
        c.net.unregister(victim)  # hard-dead: Kill and Sleep go nowhere
        await c.supervisor.recover(victim)
        active_names = [a for a, _ in c.supervisor.active]
        assert victim not in active_names
        assert len(active_names) == 7           # a real spare was promoted
        # strike 1: kept as a spare (could be a slow restart)
        assert victim in c.supervisor.sentinent
        assert c.supervisor._strikes[victim] == 1
        # once it is the ONLY spare left, it gets retried and keeps
        # failing Awake: strikes 2, 3 -> dropped
        c.supervisor.sentinent = [victim]
        await c.supervisor.recover(active_names[0])
        assert c.supervisor._strikes[victim] == 2
        assert victim in c.supervisor.sentinent  # still quarantined-spare
        await c.supervisor.recover(active_names[0])
        assert victim not in c.supervisor.sentinent  # dropped, loudly
        assert victim not in [a for a, _ in c.supervisor.active]
        assert victim not in c.supervisor._strikes  # bookkeeping cleared

    run(go())


def test_dead_spare_deprioritized_and_next_spare_used():
    """A spare whose Awake times out earns a strike and recovery proceeds
    with the next spare in the SAME attempt, so the offender still gets
    swapped; the struck spare is deprioritized for later picks but NOT
    dropped on a single miss."""

    async def go():
        c = Cluster()
        c.supervisor.cfg.sentinent_awake_timeout = 0.2
        dead_spare = "replica-7"
        c.net.unregister(dead_spare)  # cannot Awake
        # deterministic pick order among equal-strike spares
        c.supervisor._rng.choice = lambda seq: sorted(seq)[0]
        victim = "replica-0"
        await c.supervisor.recover(victim)
        # single miss: still a spare, but struck
        assert dead_spare in c.supervisor.sentinent
        assert c.supervisor._strikes[dead_spare] == 1
        active_names = [a for a, _ in c.supervisor.active]
        assert victim not in active_names  # offender really was swapped
        assert "replica-8" in active_names  # the live spare got promoted
        assert victim in c.supervisor.sentinent
        # later recoveries prefer the unstruck spare over the struck one
        await c.supervisor.recover(active_names[0])
        assert dead_spare in c.supervisor.sentinent  # was not even tried
        assert c.supervisor._strikes[dead_spare] == 1

    run(go())


def test_concurrent_suspects_single_recovery():
    async def go():
        c = Cluster()
        # flood: every replica votes many times against replica-6
        for round_ in range(3):
            for i in range(7):
                c.net.send(
                    f"replica-{i}", "supervisor",
                    M.Suspect("replica-6", sigs.generate_nonce()),
                )
        await c.net.quiesce()
        await asyncio.sleep(0.2)
        await c.net.quiesce()
        # exactly one swap: sizes intact, no duplicate active entries
        names = [a for a, _ in c.supervisor.active]
        assert len(names) == len(set(names)) == 7
        assert len(c.supervisor.sentinent) == 2
        # non-active endpoints are not recoverable
        await c.supervisor.recover("proxy-0")
        assert len(c.supervisor.active) == 7

    run(go())


# ------------------------------------------- the batched read (IReadBatch)
#
# An aggregate's re-reads go to one coordinator as one ABD round over a key
# list. Per key it is the single read: same value, same tag, stored at a
# quorum before the answer.


def small(quorum=3, n=4):
    c = Cluster(n_active=n, n_sentinent=0, quorum=quorum)
    c.client.cfg.quorum_size = quorum
    return c


def watch(c, dest, seen):
    """Note every message delivered to `dest` (class name, message)."""

    async def f(msg):
        seen.append(msg)
        return msg

    c.net.link_filters[dest] = f


BATCH_CASES = {
    "stored": ["a", "b", "c"],
    "missing": ["nope-1", "nope-2"],
    "removed": ["gone", "a"],
    "mixed": ["a", "nope-1", "gone", "c", "b"],
    "one_key": ["b"],
    "repeated_key": ["a", "b", "a"],
}


@pytest.mark.parametrize("case", sorted(BATCH_CASES))
def test_a_batched_read_returns_what_single_reads_return(case):
    async def go():
        c = small()
        for k in ("a", "b", "c", "gone"):
            await c.client.write_set(k, [k, 1])
        await c.client.write_set("b", ["b", 2])
        await c.client.write_set("gone", None)
        keys = BATCH_CASES[case]
        singles = [await c.client.fetch_set_attributed(k) for k in keys]
        batch = await c.client.fetch_sets_attributed(keys)
        assert [r[:2] for r in batch] == [r[:2] for r in singles]
        # one coordinator served the whole batch
        assert len({r[2] for r in batch}) == 1
        assert await c.client.fetch_sets_attributed([]) == []

    run(go())


@pytest.mark.parametrize("lagging", ["replica-0", "replica-1", "replica-2"])
def test_a_batch_writes_back_only_the_keys_its_quorum_disagreed_on(lagging):
    """replica-3 is cut off, so the quorum is the other three and holds the
    laggard: the one key it trails on is written back, in one `WriteBatch`
    of one entry, and is stored at a quorum BEFORE the proxy is answered;
    the keys the quorum agreed on are answered without a write phase."""

    async def go():
        c = small()
        keys = ["a", "b", "c"]
        for k in keys:
            await c.client.write_set(k, [k, 1])
        await c.net.quiesce()
        c.client._preferred = ["replica-0"]
        node = c.replicas[lagging]
        newest = node.repository["b"]
        node.repository["b"] = (M.ABDTag(0, lagging), None)

        async def cut(msg):
            return None

        c.net.link_filters["replica-3"] = cut
        sent: dict[str, list] = {}
        for r in ("replica-0", "replica-1", "replica-2"):
            watch(c, r, sent.setdefault(r, []))
        holders_at_answer = []

        async def at_answer(msg):
            if isinstance(msg, M.Envelope):
                holders_at_answer.append(sum(
                    c.replicas[r].repository["b"] == newest
                    for r in ("replica-0", "replica-1", "replica-2")))
            return msg

        c.net.link_filters["proxy-0"] = at_answer
        back = metrics.value("dds_read_batch_keys_total",
                             outcome="written_back") or 0.0
        settled = metrics.value("dds_read_batch_keys_total",
                                outcome="settled") or 0.0
        out = await c.client.fetch_sets_attributed(keys)
        await c.net.quiesce()
        assert [r[0] for r in out] == [[k, 1] for k in keys]
        assert holders_at_answer == [3]
        writes = [m for m in sent[lagging] if isinstance(m, M.WriteBatch)]
        assert len(writes) == 1
        assert [e.key for e in writes[0].entries] == ["b"]
        assert node.repository["b"] == newest
        assert metrics.value("dds_read_batch_keys_total",
                             outcome="written_back") - back == 1
        assert metrics.value("dds_read_batch_keys_total",
                             outcome="settled") - settled == 2
        # no single-key protocol message was used for any of it
        assert not any(isinstance(m, (M.Read, M.Write, M.ReadReply))
                       for msgs in sent.values() for m in msgs)

    run(go())


def test_a_batch_whose_quorum_agrees_has_no_write_phase():
    async def go():
        c = small()
        for k in ("a", "b"):
            await c.client.write_set(k, [k])
        await c.net.quiesce()
        seen: list = []
        for r in c.active:
            watch(c, r, seen)
        await c.client.fetch_sets_attributed(["a", "b"])
        await c.net.quiesce()
        kinds = {type(m).__name__ for m in seen}
        assert kinds == {"Envelope", "ReadBatch", "ReadBatchReply"}
        # the tenth message is the proxy's: 1 + 4 + 4 at the replicas
        assert len(seen) == 9

    run(go())


def test_a_point_read_sends_what_it_sent_before_batches():
    """`GetSet` and `WriteElement` keep `IRead` / `IWrite`: no batch class
    on a point operation's path, 9 and 17 messages at the replicas."""

    async def go():
        c = small()
        seen: list = []
        for r in c.active:
            watch(c, r, seen)
        await c.client.write_set("k", [1])
        await c.net.quiesce()
        assert {type(m).__name__ for m in seen} == {
            "Envelope", "ReadTag", "TagReply", "Write", "WriteAck"}
        assert len(seen) == 17
        del seen[:]
        await c.client.fetch_set("k")
        await c.net.quiesce()
        assert {type(m).__name__ for m in seen} == {
            "Envelope", "Read", "ReadReply"}
        assert len(seen) == 9

    run(go())


@pytest.mark.parametrize("late", ["ReadBatchReply", "WriteBatchAck"])
def test_a_batch_reply_after_its_quorum_is_ignored(late):
    """The fourth replica's reply comes when the round is over (or its
    read phase is): nothing is refused, nobody is voted on, and the
    answer is the quorum's."""

    async def go():
        c = small()
        await c.client.write_set("a", [1])
        await c.net.quiesce()
        c.client._preferred = ["replica-0"]
        if late == "WriteBatchAck":
            c.replicas["replica-1"].repository["a"] = (
                M.ABDTag(0, "replica-1"), None)
        held: list = []
        gate = asyncio.Event()

        async def hold(msg):
            if type(msg).__name__ == late:
                held.append(msg)
                await gate.wait()
            return msg

        c.net.link_filters[("replica-3", "replica-0")] = hold
        before = {r: metrics.value("dds_replica_rejected_total", reason=r)
                  or 0.0 for r in ("bad_mac", "unknown_nonce",
                                   "repeated_nonce", "wrong_phase")}
        out = await c.client.fetch_sets_attributed(["a"])
        assert out[0][0] == [1] and len(held) == 1
        gate.set()
        await c.net.quiesce()
        after = {r: metrics.value("dds_replica_rejected_total", reason=r)
                 or 0.0 for r in before}
        assert after == before
        assert c.replicas["replica-1"].repository["a"][1] == [1]

    run(go())


_T = M.ABDTag(7, "replica-1;x|y")
_ENTRY = M.BatchEntry(_T, "kéy/1", [1, "a", None, {"__msg__": "Kill"}],
                      bytes(range(40)))
BATCH_MESSAGES = {
    "IReadBatch": M.Envelope(M.IReadBatch(("a", "b")), 2**63 + 5, b"\x01"),
    "IReadBatchReply": M.Envelope(M.IReadBatchReply((
        M.IReadReply("a", [1, None], tag=_T), M.IReadReply("b", None, tag=_T),
    )), 9, b"\x02"),
    "BatchEntry": _ENTRY,
    "ReadBatch": M.ReadBatch(("a", "b"), 11),
    "ReadBatchReply": M.ReadBatchReply((_ENTRY, _ENTRY), 11),
    "WriteBatch": M.WriteBatch((_ENTRY,), 11),
    "WriteBatchAck": M.WriteBatchAck(11),
}


@pytest.mark.parametrize("name", sorted(BATCH_MESSAGES))
def test_batch_messages_cross_the_codec(name):
    msg = BATCH_MESSAGES[name]
    back = M.loads(M.dumps(msg))
    assert back == msg and type(back) is type(msg)
    if name in ("ReadBatchReply", "WriteBatch"):
        assert type(back.entries) is tuple
        e = back.entries[0]
        assert type(e) is M.BatchEntry and type(e.tag) is M.ABDTag
        assert type(e.signature) is bytes
        # a stored row stays opaque: no column is decoded as a message
        assert e.value[3] == {"__msg__": "Kill"}
    if name == "IReadBatchReply":
        assert all(type(r) is M.IReadReply for r in back.call.replies)


@pytest.mark.parametrize("paillier_bits", [2048, 4096])
def test_a_capped_batch_fits_a_frame_many_times_over(paillier_bits):
    """`REREAD_BATCH` rows as the cells store them (one Paillier
    ciphertext mod n^2 and an RSA-1024 one as decimal strings, five short
    columns), in the widest message of the round, against `MAX_FRAME`."""
    from dds_tpu.core.transport import TcpNet
    from dds_tpu.http.server import REREAD_BATCH

    digits = len(str(2 ** (2 * paillier_bits)))
    row = [12345, "x" * 44, "9" * digits, "9" * 309, "y" * 44, "z" * 44,
           "w" * 44, None]
    key = sigs.key_from_set(row)
    entry = M.BatchEntry(M.ABDTag(3, "replica-2"), key, row, b"\x00" * 32)
    frame = M.dumps(M.ReadBatchReply((entry,) * REREAD_BATCH, 2**62))
    assert 100 <= REREAD_BATCH <= 1000
    assert len(frame) * 8 < TcpNet.MAX_FRAME
    answer = M.dumps(M.Envelope(M.IReadBatchReply(
        (M.IReadReply(key, row, tag=entry.tag),) * REREAD_BATCH), 1, b"s"))
    assert len(answer) * 8 < TcpNet.MAX_FRAME


class _Answering:
    """A coordinator under the test's control: answers `IReadBatch` with
    what `forge` makes of the honest answer."""

    def __init__(self, c, addr, forge):
        self.c, self.addr, self.forge = c, addr, forge
        c.net.register(addr, self.handle)

    async def handle(self, sender, msg):
        cfg = self.c.rcfg
        keys = msg.call.keys
        tag = M.ABDTag(4, "replica-1")
        replies = [M.IReadReply(k, [k], tag=tag) for k in keys]
        challenge = msg.nonce + cfg.nonce_increment
        digest = sigs.key_from_set(list(keys))

        def sign(replies, challenge=challenge, digest=digest):
            return sigs.proxy_signature(
                cfg.proxy_mac_secret, digest, challenge,
                [[r.set, sigs.tag_payload(r.tag)] for r in replies])

        self.c.net.send(self.addr, sender, self.forge(
            replies, challenge, sign))


FORGERIES = {
    "honest": (None, lambda rs, ch, sign: M.Envelope(
        M.IReadBatchReply(tuple(rs)), ch, sign(rs))),
    "wrong_challenge": ("ByzFailedNonceChallengeError",
                        lambda rs, ch, sign: M.Envelope(
        M.IReadBatchReply(tuple(rs)), ch + 1, sign(rs, challenge=ch + 1))),
    "bad_mac": ("ByzInvalidSignatureError", lambda rs, ch, sign: M.Envelope(
        M.IReadBatchReply(tuple(rs)), ch, b"forged")),
    "value_swapped_after_signing": (
        "ByzInvalidSignatureError", lambda rs, ch, sign: M.Envelope(
            M.IReadBatchReply((M.IReadReply(rs[0].key, ["planted"],
                                            tag=rs[0].tag), *rs[1:])),
            ch, sign(rs))),
    "tag_swapped_after_signing": (
        "ByzInvalidSignatureError", lambda rs, ch, sign: M.Envelope(
            M.IReadBatchReply((M.IReadReply(rs[0].key, rs[0].set,
                                            tag=M.ABDTag(99, "x")), *rs[1:])),
            ch, sign(rs))),
    "keys_in_another_order": (
        "ByzInvalidKeyError", lambda rs, ch, sign: M.Envelope(
            M.IReadBatchReply(tuple(rs[::-1])), ch, sign(rs[::-1]))),
    "a_key_left_out": (
        "ByzInvalidKeyError", lambda rs, ch, sign: M.Envelope(
            M.IReadBatchReply(tuple(rs[1:])), ch, sign(rs[1:]))),
    "signed_for_other_keys": (
        "ByzInvalidSignatureError", lambda rs, ch, sign: M.Envelope(
            M.IReadBatchReply(tuple(rs)), ch,
            sign(rs, digest=sigs.key_from_set(["other"])))),
    "a_single_reads_reply": (
        "ByzUnknownReplyError", lambda rs, ch, sign: M.Envelope(
            rs[0], ch, sigs.proxy_signature(
                b"rest2abd", rs[0].key, ch,
                [rs[0].set, sigs.tag_payload(rs[0].tag)]))),
    "bare_reply": ("ByzUnknownReplyError",
                   lambda rs, ch, sign: M.IReadReply("x", None)),
    "entries_of_no_shape": (
        "ByzInvalidSignatureError", lambda rs, ch, sign: M.Envelope(
            M.IReadBatchReply(("a", "b")), ch, b"sig")),
}


@pytest.mark.parametrize("kind", sorted(FORGERIES))
def test_the_proxy_verifies_a_batched_answer_as_it_verifies_a_single_one(kind):
    """Challenge nonce, proxy MAC over every value and tag, keys echoed in
    order: each failure is the typed Byzantine error a single read raises,
    and a strike on the coordinator."""
    from dds_tpu.core import errors

    async def go():
        c = small()
        error, forge = FORGERIES[kind]
        _Answering(c, "replica-0", forge)
        c.client._preferred = ["replica-0"]
        keys = ["a", "b", "c"]
        if error is None:
            out = await c.client.fetch_sets_attributed(keys)
            assert out == [([k], M.ABDTag(4, "replica-1"), "replica-0")
                           for k in keys]
            assert c.client.replicas._strikes.get("replica-0", 0) == 0
        else:
            with pytest.raises(getattr(errors, error)):
                await c.client.fetch_sets_attributed(keys)
            assert c.client.replicas._strikes["replica-0"] == 1

    run(go())


def test_a_batched_read_steers_its_coordinator_like_a_single_read():
    """`exclude`, the breakers and the deadline reach `_ask` unchanged."""
    from dds_tpu.utils.retry import Deadline, DeadlineExceededError

    async def go():
        c = small()
        await c.client.write_set("a", [1])
        for _ in range(8):
            out = await c.client.fetch_sets_attributed(
                ["a"], exclude=("replica-0", "replica-1", "replica-2"))
            assert out[0][2] == "replica-3"
        for _ in range(3):
            c.client._breaker("replica-3").record_failure()
        out = await c.client.fetch_sets_attributed(
            ["a"], exclude=("replica-0", "replica-1"))
        assert out[0][2] == "replica-2"
        rounds = metrics.value("dds_read_batch_rounds_total")
        with pytest.raises(DeadlineExceededError):
            await c.client.fetch_sets_attributed(["a"], deadline=Deadline(0.0))
        assert metrics.value("dds_read_batch_rounds_total") == rounds + 1
        stats = metrics.histogram_stats("dds_quorum_rtt_seconds",
                                        op="fetch_batch")
        assert stats["count"] >= 9

    run(go())
