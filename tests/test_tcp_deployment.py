"""The deployment with its replicas behind sockets (`transport.kind = "tcp"`).

A transport changes no answer: the same rows and operations through
`run.launch` over `TcpNet` and over `InMemoryNet` agree bit for bit with each
other and with a python-int fold; every message class survives the frame
codec; a tag round keeps its delta / unchanged economy whatever order the
replies arrive in; a frame that fails the channel MAC is dropped and counted
while the round completes on the others; and what the wire costs is recorded
per frame (`net.serialize`, `net.deserialize`, `dds_net_*`).
"""

import asyncio
import dataclasses
import functools
import json
import random

import pytest

from dds_tpu.core import messages as M
from dds_tpu.obs.metrics import metrics
from dds_tpu.utils import sigs
from dds_tpu.utils.trace import tracer

FRAME_SECRET = "a-fixed-frame-secret"


def run(coro):
    return asyncio.run(coro)


async def until(cond, timeout=5.0):
    """Wait for something another task does (a frame crossing loopback)."""
    end = asyncio.get_event_loop().time() + timeout
    while not cond() and asyncio.get_event_loop().time() < end:
        await asyncio.sleep(0.01)
    assert cond()


# ----------------------------------------- the same answers, either transport

ROWS = 20
SUM_MOD = ((1 << 61) - 1) ** 2          # "n^2" of the additive column
MULT_MOD = (1 << 89) - 1                # modulus of the multiplicative one


def _deployment_cfg(kind: str):
    from dds_tpu.utils.config import DDSConfig

    cfg = DDSConfig()
    cfg.replicas.endpoints = [f"replica-{i}" for i in range(4)]
    cfg.replicas.sentinent = []
    cfg.replicas.byz_quorum_size = 3
    cfg.replicas.byz_max_faults = 1
    cfg.recovery.enabled = False
    cfg.proxy.port = 0
    cfg.proxy.crypto_backend = "tpu"
    if kind == "tcp":
        cfg.transport.kind = "tcp"
        cfg.transport.port = 0
        cfg.security.transport_frame_secret = FRAME_SECRET
    return cfg


async def _script(kind: str, cfg=None) -> tuple[dict, dict]:
    """One seeded sequence of every REST operation kind through a
    deployment (`cfg`, or the plain one of that transport); returns (what
    each step answered, the model it leaves)."""
    from dds_tpu.core.transport import InMemoryNet, TcpNet
    from dds_tpu.http.miniserver import http_request
    from dds_tpu.run import launch

    rng = random.Random(34)
    rows = [[str(i), "x", str(rng.randrange(2, SUM_MOD)),
             str(rng.randrange(2, MULT_MOD)), "y", "z", "w", None]
            for i in range(ROWS)]
    cfg = cfg or _deployment_cfg(kind)
    dep = await launch(cfg)
    host, port = cfg.proxy.host, dep.server.cfg.port
    assert type(dep.net) is (TcpNet if kind == "tcp" else InMemoryNet)
    said: dict[str, list] = {k: [] for k in (
        "PutSet", "GetSet", "WriteElement", "SumAll", "MultAll")}

    async def call(op, method, target, body=None):
        st, raw = await http_request(
            host, port, method, target,
            None if body is None else json.dumps(body).encode())
        said[op].append((st, raw.decode()))
        return raw.decode()

    async def aggregates():
        await call("SumAll", "GET", f"/SumAll?position=2&nsqr={SUM_MOD}")
        await call("MultAll", "GET", f"/MultAll?position=3&pubkey={MULT_MOD}")

    try:
        keys = [await call("PutSet", "POST", "/PutSet", {"contents": r})
                for r in rows]
        await aggregates()
        for step in range(6):
            i, col = rng.randrange(ROWS), 2 + step % 2
            new = str(rng.randrange(2, (SUM_MOD, MULT_MOD)[col - 2]))
            await call("WriteElement", "PUT",
                       f"/WriteElement/{keys[i]}?position={col}",
                       {"value": new})
            rows[i][col] = new
            await call("GetSet", "GET", f"/GetSet/{keys[i]}")
            await call("GetSet", "GET",
                       f"/GetSet/{keys[rng.randrange(ROWS)]}")
            await aggregates()
        await aggregates()     # an unchanged store: the memoised path
    finally:
        await dep.stop()
    return said, {"rows": rows, "keys": keys}


@pytest.fixture(scope="module")
def both():
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("DDS_TPU_MIN_BATCH", "0")   # tiny folds reach the pool
        return {kind: run(_script(kind)) for kind in ("memory", "tcp")}


@pytest.mark.parametrize(
    "op", ["PutSet", "GetSet", "WriteElement", "SumAll", "MultAll"])
def test_a_transport_changes_no_answer(both, op):
    (mem, _), (tcp, _) = both["memory"], both["tcp"]
    assert mem[op] and all(st == 200 for st, _ in mem[op])
    assert tcp[op] == mem[op]                       # bit for bit


@pytest.mark.parametrize("kind", ["memory", "tcp"])
def test_both_equal_the_python_int_fold(both, kind):
    said, model = both[kind]
    rows = model["rows"]

    def fold(col, mod):
        return functools.reduce(lambda a, b: a * b % mod,
                                (int(r[col]) for r in rows), 1)

    assert int(json.loads(said["SumAll"][-1][1])["result"]) == fold(2, SUM_MOD)
    assert int(json.loads(said["MultAll"][-1][1])["result"]) == fold(
        3, MULT_MOD)
    # the aggregate moved with the writes, so the check above is of them
    assert len({body for _, body in said["SumAll"]}) >= 4
    last = json.loads(said["GetSet"][-1][1])["contents"]
    assert last in rows


# ------------------------------------------------- the codec, class by class

_TAG = M.ABDTag(7, "replica-1;x|y")
_BY_TYPE = {
    "str": "kéy/1", "int": 2**63 + 5, "float": 0.25, "bool": True,
    "bytes": bytes(range(256)), "Optional[bytes]": b"\x00\xff fp",
    "ABDTag": _TAG, "Optional[ABDTag]": _TAG,
    "Optional[DDSSet]": [1, "a", None, "9" * 700],
    "dict": {"K": {"tag": [1, "r"], "value": [1, None]}},
    "list": [1, "a"], "list[int]": [4, 5], "list[str]": ["a", "b"],
    "Any": M.IWrite("K", [1, "a", None]),
    "tuple": ("a", "b"),
}
_ENTRY = M.BatchEntry(_TAG, "kéy/1", [1, "a", None, "9" * 700],
                      bytes(range(256)))
_TUPLES = {("TagBatchReply", "tags"): (_TAG, M.ABDTag(1, "r")),
           ("TagBatchReply", "positions"): (0, 5),
           ("ReadBatchReply", "entries"): (_ENTRY, _ENTRY),
           ("WriteBatch", "entries"): (_ENTRY,),
           ("IReadBatchReply", "replies"): (
               M.IReadReply("K", [1, None], tag=_TAG),
               M.IReadReply("L", None, tag=_TAG))}


def _every_field_set(cls):
    return cls(**{
        f.name: _TUPLES.get((cls.__name__, f.name), _BY_TYPE[f.type])
        for f in dataclasses.fields(cls)})


@pytest.mark.parametrize("name", sorted(M._TYPES))
def test_every_message_class_survives_the_wire(name):
    msg = _every_field_set(M._TYPES[name])
    for f in dataclasses.fields(msg):      # nothing rode on a default
        assert getattr(msg, f.name) is not None, f.name
    back = M.from_dict(json.loads(json.dumps(M.to_dict(msg))))
    assert type(back) is type(msg) and back == msg
    for f in dataclasses.fields(msg):
        assert type(getattr(back, f.name)) is type(getattr(msg, f.name)), f.name
    if name == "TagBatchReply":
        assert all(type(t) is M.ABDTag for t in back.tags)
        assert all(type(p) is int for p in back.positions)
    if name in ("ReadBatchReply", "WriteBatch"):
        assert all(type(e) is M.BatchEntry and type(e.tag) is M.ABDTag
                   and type(e.signature) is bytes for e in back.entries)
    if name == "IReadBatchReply":
        assert all(type(r) is M.IReadReply for r in back.replies)


# ------------------------------- a tag round over sockets, in any reply order


def _votes(name="dds_tag_round_votes_total") -> dict:
    return {k: metrics.value(name, kind=k) or 0.0
            for k in ("unchanged", "delta", "full")}


def _late() -> dict:
    return _votes("dds_tag_round_late_votes_total")


class _Cluster:
    """Four replicas and one proxy-side client on one `TcpNet`, as
    `run.launch` lays them out: every endpoint `host:port/name` on the one
    listener. `order` permutes the delivery of each round's replies."""

    async def start(self):
        from dds_tpu.core.quorum_client import AbdClient, AbdClientConfig
        from dds_tpu.core.replica import BFTABDNode, ReplicaConfig
        from dds_tpu.core.transport import TcpNet

        self.net = TcpNet("127.0.0.1", 0,
                          frame_secret=FRAME_SECRET.encode())
        await self.net.start()
        host = self.net.advertised
        self.addrs = [f"{host}/replica-{i}" for i in range(4)]
        self.nodes = [BFTABDNode(a, self.addrs, f"{host}/supervisor",
                                 self.net, ReplicaConfig(quorum_size=3))
                      for a in self.addrs]
        self.client = AbdClient(
            f"{host}/proxy", self.net, self.addrs,
            AbdClientConfig(request_timeout=3.0, quorum_size=3))
        self.order = None
        self._held: dict[int, list] = {}
        self.net.register(f"{host}/proxy", self._deliver)
        return self

    async def _deliver(self, sender, msg):
        """Hold a round's replies until all four are in, then hand them to
        the client in `order` (positions in the trusted list)."""
        if self.order is None or not isinstance(msg, M.TagBatchReply):
            return await self.client.handle(sender, msg)
        held = self._held.setdefault(msg.nonce, [])
        held.append((sender, msg))
        if len(held) == len(self.addrs):
            by = {s: m for s, m in self._held.pop(msg.nonce)}
            for i in self.order:
                await self.client.handle(self.addrs[i], by[self.addrs[i]])


@pytest.mark.parametrize("first,second", [
    ((0, 1, 2, 3), (0, 1, 2, 3)),      # the order one process always gave
    ((3, 2, 1, 0), (3, 2, 1, 0)),      # the last replica's vote counts
    ((0, 1, 2, 3), (3, 0, 1, 2)),      # ... and counts where it came late before
    ((2, 0, 3, 1), (1, 3, 0, 2)),
])
def test_over_tcp_votes_cost_what_moved_in_any_reply_order(first, second):
    async def go():
        c = await _Cluster().start()
        try:
            keys = [f"k{i:03d}" for i in range(40)]
            for i, k in enumerate(keys):
                for n in c.nodes:
                    n._store(k, M.ABDTag(i + 1, "replica-0"), [i])
            cached = await c.client.read_tags(keys)
            fp = sigs.tags_fingerprint(cached)
            # an unchanged store: `unchanged` votes, the caller's own list
            c.order = first
            before, late = _votes(), _late()
            assert (await c.client.read_tags(
                keys, fingerprint=fp, cached_tags=cached)) is cached
            assert _votes() == {**before,
                                "unchanged": before["unchanged"] + 3}
            # the fourth vote moved no answer; it is verified and kept
            await until(lambda: _late() == {
                **late, "unchanged": late["unchanged"] + 1})
            # a write between two tag rounds: `delta` votes whichever three
            # come first, the one that came after the quorum last time among
            # them: the proxy holds a verified vector of every sender
            newer = M.ABDTag(100, "replica-1")
            for n in c.nodes:
                n._store(keys[7], newer, [100])
            c.order = second
            before, late = _votes(), _late()
            got = await c.client.read_tags(keys, fingerprint=fp,
                                           cached_tags=cached)
            assert got == cached[:7] + [newer] + cached[8:]
            assert _votes() == {**before, "delta": before["delta"] + 3}
            await until(lambda: _late() == {**late,
                                            "delta": late["delta"] + 1})
            # the caller takes it in: all `unchanged` again
            fp2 = sigs.tags_fingerprint(got)
            before = _votes()
            assert (await c.client.read_tags(
                keys, fingerprint=fp2, cached_tags=got)) is got
            assert _votes() == {**before,
                                "unchanged": before["unchanged"] + 3}
        finally:
            await c.net.stop()

    run(go())


def test_a_tag_reply_after_its_round_strikes_nobody_and_resolves_nothing():
    """Over sockets the fourth reply of a tag round is decoded when the
    loop gets to it, often after the round is over. It is late, not junk:
    an operation its sender happens to coordinate stays pending, ends
    well, and the sender keeps a clean record."""

    def violations():
        return metrics.value("dds_coordinator_violations_total",
                             node="replica-3") or 0.0

    async def go():
        c = await _Cluster().start()
        try:
            await c.client.write_set("K", [1, "a"])
            last = c.addrs[3]
            gate, round_over = asyncio.Event(), asyncio.Event()
            handle3 = c.nodes[3].handle

            async def slow_door(sender, msg):      # replica-3 coordinates
                if isinstance(msg, M.Envelope):    # ... when let in
                    await gate.wait()
                await handle3(sender, msg)

            landed = []

            async def last_comes_late(sender, msg):
                late = isinstance(msg, M.TagBatchReply) and sender == last
                if late:
                    await round_over.wait()
                await c.client.handle(sender, msg)
                if late:
                    landed.append(msg)

            c.net.register(last, slow_door)
            c.net.register(f"{c.net.advertised}/proxy", last_comes_late)
            before = violations()
            fetch = asyncio.ensure_future(
                c.client.fetch_set_attributed("K", exclude=c.addrs[:3]))
            await until(lambda: c.client._pending)  # in flight, through `last`
            tags = await c.client.read_tags(["K"])
            assert tags[0].seq == 1
            round_over.set()
            await until(lambda: landed)            # the late reply lands
            await asyncio.sleep(0.05)
            assert not fetch.done()
            gate.set()
            value, tag, coord = await asyncio.wait_for(fetch, 3)
            assert (value, tag.seq, coord) == ([1, "a"], 1, last)
            assert violations() == before
            assert c.client.replicas.suspicions()[last] == 0
        finally:
            await c.net.stop()

    run(go())


def test_a_vote_decoded_after_read_tags_returned_is_verified_and_kept():
    """The frame of a round's last reply is decoded whenever the loop gets
    to it, as a rule after `read_tags` has returned on the other three.
    The round stays open for it: its sender's vector is then one the
    proxy holds, and that replica's next reply is a delta like the rest,
    not all K tags for a vote nobody counts."""

    async def go():
        c = await _Cluster().start()
        try:
            keys = [f"k{i:03d}" for i in range(40)]
            for i, k in enumerate(keys):
                for n in c.nodes:
                    n._store(k, M.ABDTag(i + 1, "replica-0"), [i])
            cached = await c.client.read_tags(keys)
            fp = sigs.tags_fingerprint(cached)
            last, gate = c.addrs[3], asyncio.Event()

            async def last_comes_late(sender, msg):
                if isinstance(msg, M.TagBatchReply) and sender == last:
                    await gate.wait()
                await c.client.handle(sender, msg)

            c.net.register(f"{c.net.advertised}/proxy", last_comes_late)
            late = _late()
            assert (await c.client.read_tags(
                keys, fingerprint=fp, cached_tags=cached)) is cached
            (kept,) = c.client._kept_vectors.values()
            assert len(c.client._late_tags) == 1 and last not in kept.senders
            gate.set()
            await until(lambda: not c.client._late_tags)   # all four are in
            assert _late() == {**late, "unchanged": late["unchanged"] + 1}
            assert kept.senders[last][0] == fp
            # so after a write its vote, first in this time, is a delta
            newer = M.ABDTag(100, "replica-1")
            for n in c.nodes:
                n._store(keys[7], newer, [100])
            c.order, before = (3, 0, 1, 2), _votes()
            c.net.register(f"{c.net.advertised}/proxy", c._deliver)
            got = await c.client.read_tags(keys, fingerprint=fp,
                                           cached_tags=cached)
            assert got == cached[:7] + [newer] + cached[8:]
            assert _votes() == {**before, "delta": before["delta"] + 3}
            # rounds that met their quorum do not pile up
            for _ in range(12):
                gate.clear()
                c.net.register(f"{c.net.advertised}/proxy", last_comes_late)
                await c.client.read_tags(keys, fingerprint=fp,
                                         cached_tags=cached)
            assert len(c.client._late_tags) <= 8
            gate.set()
        finally:
            await c.net.stop()

    run(go())


def test_a_key_set_that_arrives_as_a_fresh_tuple_finds_its_kept_vector():
    """Over the wire a `ReadTagBatch`'s keys are a new tuple of new
    strings every round: the replica's kept vector is found by value, and
    patched, never rebuilt, once it exists."""

    def outcomes():
        return {o: metrics.value("dds_replica_tag_vector_total", outcome=o)
                or 0.0 for o in ("reused", "patched", "rebuilt")}

    async def go():
        c = await _Cluster().start()
        try:
            keys = [f"k{i:03d}" for i in range(40)]
            for i, k in enumerate(keys):
                for n in c.nodes:
                    n._store(k, M.ABDTag(i + 1, "replica-0"), [i])
            start = outcomes()
            cached = await c.client.read_tags(keys)
            fp = sigs.tags_fingerprint(cached)
            # every replica's turn comes, the fourth's after the quorum
            await until(lambda: outcomes()["rebuilt"] == start["rebuilt"] + 4)
            for n in c.nodes:
                n._store(keys[3], M.ABDTag(200, "replica-2"), [200])
            for done in (4, 8):
                await c.client.read_tags(list(keys), fingerprint=fp,
                                         cached_tags=cached)
                await until(lambda: sum(outcomes().values())
                            == sum(start.values()) + 4 + done)
            assert outcomes() == {"reused": start["reused"] + 4,
                                  "patched": start["patched"] + 4,
                                  "rebuilt": start["rebuilt"] + 4}
        finally:
            await c.net.stop()

    run(go())


# --------------------------------------- the channel MAC, and what is counted


# --------------------------- the batched read (IReadBatch) over sockets


def _frames(direction="sent") -> dict:
    names = ("Envelope", "Read", "ReadReply", "Write", "WriteAck",
             "ReadBatch", "ReadBatchReply", "WriteBatch", "WriteBatchAck")
    return {n: metrics.value("dds_net_frames_total", direction=direction,
                             msg=n) or 0.0 for n in names}


@pytest.mark.parametrize("lagging", [(), (1, 2)])
def test_a_batched_read_over_tcp_is_the_single_reads_in_fewer_frames(lagging):
    """Over `TcpNet` a batch of three keys answers what three single reads
    answer, in 10 frames where they take 30 (14 when a key is written
    back, where its single read takes 18); the point read's own frames
    are what they were."""

    async def go():
        c = await _Cluster().start()
        try:
            keys = ["K1", "K2", "K3"]
            for k in keys:
                await c.client.write_set(k, [k, "9" * 600])
            await until(lambda: all(
                k in n.repository and n.repository[k][1] for n in c.nodes
                for k in keys))
            def lag():   # two of four trail on K2: in every quorum of three
                for i in lagging:
                    c.nodes[i].repository["K2"] = (
                        M.ABDTag(0, f"replica-{i}"), None)

            lag()
            f0 = _frames()
            singles = [await c.client.fetch_set_attributed(k) for k in keys]
            await asyncio.sleep(0.05)
            f1 = _frames()
            lag()
            batch = await c.client.fetch_sets_attributed(keys)
            await asyncio.sleep(0.05)
            f2 = _frames()
            assert [r[:2] for r in batch] == [r[:2] for r in singles]
            single = {n: f1[n] - f0[n] for n in f0 if f1[n] != f0[n]}
            batched = {n: f2[n] - f1[n] for n in f0 if f2[n] != f1[n]}
            if not lagging:
                assert single == {"Envelope": 6, "Read": 12, "ReadReply": 12}
                assert batched == {"Envelope": 2, "ReadBatch": 4,
                                   "ReadBatchReply": 4}
            else:
                assert single == {"Envelope": 6, "Read": 12, "ReadReply": 12,
                                  "Write": 4, "WriteAck": 4}
                assert batched == {"Envelope": 2, "ReadBatch": 4,
                                   "ReadBatchReply": 4, "WriteBatch": 4,
                                   "WriteBatchAck": 4}
                await until(lambda: all(
                    c.nodes[i].repository["K2"][1] == ["K2", "9" * 600]
                    for i in lagging))
        finally:
            await c.net.stop()

    run(go())


def _dropped(reason: str) -> float:
    return metrics.value("dds_net_frames_dropped_total", reason=reason) or 0.0


def test_a_replica_whose_frames_fail_the_mac_is_outvoted_not_waited_for():
    """One replica sits behind a listener that holds another frame secret:
    the round's frame to it and its own frames to the proxy are dropped
    and counted, and the tag round completes on the other three."""

    async def go():
        from dds_tpu.core.replica import BFTABDNode, ReplicaConfig
        from dds_tpu.core.transport import TcpNet

        c = await _Cluster().start()
        odd = TcpNet("127.0.0.1", 0, frame_secret=b"another-secret")
        await odd.start()
        try:
            # replica-3 moves out: reached, and answering, through `odd`
            c.net.unregister(c.addrs[3])
            away = f"{odd.advertised}/replica-3"
            addrs = c.addrs[:3] + [away]
            c.nodes[3] = BFTABDNode(away, addrs, c.nodes[0].supervisor, odd,
                                    ReplicaConfig(quorum_size=3))
            c.client.replicas.reset(addrs)
            keys = [f"k{i}" for i in range(8)]
            for i, k in enumerate(keys):
                for n in c.nodes:
                    n._store(k, M.ABDTag(i + 1, "replica-0"), [i])
            before, votes = _dropped("bad_mac"), _votes()
            t0 = asyncio.get_event_loop().time()
            tags = await c.client.read_tags(keys)
            assert asyncio.get_event_loop().time() - t0 < 1.0   # not waited for
            assert [t.seq for t in tags] == list(range(1, 9))
            assert _votes() == {**votes, "full": votes["full"] + 3}
            # what it says itself fares no better on the proxy's listener
            odd.send(away, f"{c.net.advertised}/proxy",
                     M.TagBatchReply((), "D", b"", 1, unchanged=True))
            await until(lambda: _dropped("bad_mac") == before + 2)
            assert c.client.replicas.suspicions() == dict.fromkeys(addrs, 0)
        finally:
            await odd.stop()
            await c.net.stop()

    run(go())


@pytest.mark.parametrize("reason,frame", [
    ("undecodable", b"not json"),
    ("undecodable", json.dumps({"src": 1, "dest": "x", "msg": {}}).encode()),
    ("bad_mac", json.dumps({"src": "a", "dest": "alice", "mac": 5,
                            "msg": M.to_dict(M.ReadTag("K", 1))}).encode()),
    ("bad_mac", json.dumps({"src": "a", "dest": "alice", "mac": "é",
                            "msg": M.to_dict(M.ReadTag("K", 1))}).encode()),
    ("bad_payload", None),      # well MAC'd, names no message class
    ("oversize", None),
])
def test_a_refused_frame_is_counted_and_the_listener_lives(reason, frame):
    async def go():
        from dds_tpu.core.transport import TcpNet

        net = TcpNet("127.0.0.1", 0, frame_secret=b"s")
        await net.start()
        got = []

        async def handler(sender, msg):
            got.append(msg)

        here = net.local_addr("alice")
        net.register(here, handler)

        def sealed(payload) -> bytes:
            body = TcpNet._frame_body("a", here, payload)
            raw = json.dumps({"src": "a", "dest": here, "msg": payload,
                              "mac": net._frame_mac(body)}).encode()
            return len(raw).to_bytes(4, "big") + raw

        before = _dropped(reason)
        try:
            r, w = await asyncio.open_connection("127.0.0.1", net.port)
            if reason == "oversize":
                w.write((TcpNet.MAX_FRAME + 1).to_bytes(4, "big") + b"x" * 8)
            elif frame is None:
                w.write(sealed({"__msg__": "NoSuchMessage"}))
            else:
                w.write(len(frame).to_bytes(4, "big") + frame)
            await w.drain()
            await until(lambda: _dropped(reason) == before + 1)
            # a sound frame behind it on the very connection still arrives
            # (an oversize length takes its connection with it: the
            # listener serves the next one)
            if reason == "oversize":
                r, w = await asyncio.open_connection("127.0.0.1", net.port)
            w.write(sealed(M.to_dict(M.ReadTag("K", 77))))
            await w.drain()
            await until(lambda: got == [M.ReadTag("K", 77)])
            w.close()
        finally:
            await net.stop()

    run(go())


def test_every_frame_is_one_span_each_way_and_counted_by_class():
    """Outside any trace context too: `net.serialize` on the sending
    side, `net.deserialize` on the receiving side, both with the frame's
    bytes, message class and destination; bytes and frames counted by
    direction and class."""

    def counted(direction):
        return tuple(
            metrics.value(name, direction=direction, msg="ReadTagBatch")
            or 0.0
            for name in ("dds_net_frames_total", "dds_net_frame_bytes_total"))

    async def go():
        from dds_tpu.core.transport import TcpNet

        net = TcpNet("127.0.0.1", 0, frame_secret=b"s")
        await net.start()
        got = asyncio.get_event_loop().create_future()

        async def handler(sender, msg):
            got.set_result(msg)

        here = net.local_addr("alice")
        net.register(here, handler)
        seen = []
        tracer.subscribe(seen.append)
        sent0, recv0 = counted("sent"), counted("received")
        try:
            msg = M.ReadTagBatch(tuple(f"{i:0128x}" for i in range(50)), 9)
            net.send("bob", here, msg)
            assert await asyncio.wait_for(got, 3) == msg
        finally:
            tracer.unsubscribe(seen.append)
            await net.stop()
        spans = {r.name: r for r in seen if r.name.startswith("net.")}
        assert set(spans) == {"net.serialize", "net.deserialize"}
        size = spans["net.serialize"].meta["bytes"]
        assert size > 50 * 128
        for r in spans.values():
            assert r.meta == {"bytes": size, "msg": "ReadTagBatch",
                              "dest": "alice"}
            assert r.dur_ms > 0
        assert counted("sent") == (sent0[0] + 1, sent0[1] + size)
        assert counted("received") == (recv0[0] + 1, recv0[1] + size)
        # both ends of the codec are one stage of a request's waterfall
        from dds_tpu.obs.chronoscope import classify

        assert {classify(n) for n in spans} == {"serialize"}

    run(go())
