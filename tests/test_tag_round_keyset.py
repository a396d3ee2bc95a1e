"""The tag round names its key set by digest (`ReadTagBatch.digest`,
`count`): the keys travel once per replica, and again only to a replica
that says it holds none under that digest (`KeySetUnknown`).

What must hold: `read_tags` returns what a proxy that always carries the
keys returns, over either transport; a replica that dropped the set says
so once, is taught once and votes in that same round; nobody is struck for
having forgotten; a liar who always says "unknown" costs one carried
request a round and no vote of the others; an unauthenticated or replayed
named request gets what such a request always got; carried keys are
adopted only when they hash to the named digest; and the two yardstick
metrics that read the round's requests find their counters.
"""

import asyncio
import json
import os
import random
import types

import pytest

from dds_tpu.core import messages as M
from dds_tpu.core import quorum_client as qc
from dds_tpu.core import replica as replica_mod
from dds_tpu.core.errors import WrongShardError
from dds_tpu.core.quorum_client import AbdClient, AbdClientConfig
from dds_tpu.core.replica import BFTABDNode, ReplicaConfig
from dds_tpu.core.transport import InMemoryNet, TcpNet
from dds_tpu.obs.metrics import metrics
from dds_tpu.utils import sigs
from dds_tpu.utils.trace import tracer

from tests.test_core import run
from tests.test_replica_tag_vector import _Shard as Fence

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ABD_SECRET = AbdClientConfig().abd_mac_secret
PROXY_SECRET = AbdClientConfig().proxy_mac_secret


def requests_sent() -> dict:
    return {k: metrics.value("dds_tag_round_requests_total", keys=k) or 0.0
            for k in ("named", "carried")}


def keysets() -> dict:
    return {o: metrics.value("dds_replica_keyset_total", outcome=o) or 0.0
            for o in ("known", "learned", "unknown", "refused")}


def since(before: dict, now: dict) -> dict:
    return {k: now[k] - before[k] for k in now if now[k] != before[k]}


class Spans:
    """The spans of one name recorded while the block runs."""

    def __init__(self, name: str):
        self.name, self.got = name, []

    def _on(self, rec):
        if rec.kind == "span" and rec.name == self.name:
            self.got.append(rec)

    def __enter__(self):
        tracer.subscribe(self._on)
        return self.got

    def __exit__(self, *exc):
        tracer.unsubscribe(self._on)


class AlwaysCarries(AbdClient):
    """The proxy as it was: it remembers of no replica that it holds a key
    set, so every request of every round carries the keys."""

    def _holders_for(self, digest, trusted):
        return set()


class Rig:
    """n replicas and a proxy-side client on one transport of either kind.
    Every message bound for the proxy passes `inbox` first (a test may
    hold some back); every `ReadTagBatch` a replica is sent is noted in
    `asked` before the replica sees it."""

    def __init__(self, kind="memory", n=4, quorum=3, shard=None,
                 client_cls=AbdClient):
        self.kind, self.n, self.quorum = kind, n, quorum
        self.shard, self.client_cls = shard, client_cls
        self.asked: list[tuple[str, M.ReadTagBatch]] = []
        self.hold = None            # a predicate over (sender, msg), or None
        self.held: list = []
        self.suspects: list = []

    async def start(self):
        if self.kind == "tcp":
            self.net = TcpNet("127.0.0.1", 0, frame_secret=b"frame-secret")
            await self.net.start()
            at = self.net.advertised + "/"
        else:
            self.net = InMemoryNet()
            at = ""
        self.at = at
        self.addrs = [f"{at}replica-{i}" for i in range(self.n)]
        self.nodes = {}
        for a in self.addrs:
            node = BFTABDNode(a, self.addrs, f"{at}supervisor", self.net,
                              ReplicaConfig(quorum_size=self.quorum),
                              shard=self.shard)
            self.nodes[a] = node
            self.net.register(a, self._noting(a, node.handle))
        self.net.register(f"{at}supervisor", self._supervisor)
        self.client = self.proxy("proxy", self.client_cls)
        return self

    def proxy(self, name: str, cls=AbdClient) -> AbdClient:
        client = cls(f"{self.at}{name}", self.net, self.addrs,
                     AbdClientConfig(request_timeout=2.0,
                                     quorum_size=self.quorum))
        if name == "proxy":
            self.net.register(client.addr, self._inbox)
        return client

    def _noting(self, addr, handler):
        async def handle(sender, msg):
            if isinstance(msg, M.ReadTagBatch):
                self.asked.append((addr, msg))
            await handler(sender, msg)
        return handle

    async def _supervisor(self, sender, msg):
        if isinstance(msg, M.Suspect):
            self.suspects.append((sender, msg.replica))

    async def _inbox(self, sender, msg):
        if self.hold is not None and self.hold(sender, msg):
            self.held.append((sender, msg))
            return
        await self.client.handle(sender, msg)

    async def release(self):
        held, self.held, self.hold = self.held, [], None
        for sender, msg in held:
            await self.client.handle(sender, msg)

    async def settle(self):
        if self.kind == "tcp":
            await asyncio.sleep(0.15)
        else:
            await self.net.quiesce()

    async def stop(self):
        if self.kind == "tcp":
            await self.net.stop()

    def store(self, key, tag, at=None):
        for a in at or self.addrs:
            self.nodes[a]._store(key, tag, [tag.seq])

    def fresh_max(self, keys, voters) -> list:
        cols = []
        for a in voters:
            node = self.nodes[a]
            blank = (M.ABDTag(0, node.name), None)
            cols.append([node.repository.get(k, blank)[0] for k in keys])
        return [max(c) for c in zip(*cols)]

    def strikes(self) -> dict:
        return {a: n for a, n in self.client.replicas.suspicions().items()
                if n}


def rigged(body, **kw):
    """Run `body(rig)` on a started rig, and stop it."""
    async def go():
        rig = await Rig(**kw).start()
        try:
            await body(rig)
        finally:
            await rig.stop()
    run(go())


def seeded(keys, rig, n0=0):
    for i, k in enumerate(keys):
        rig.store(k, M.ABDTag(n0 + i + 1, "replica-0"))


# ----------------------------- the same answers, and a request of reply size


@pytest.mark.parametrize("kind", ["memory", "tcp"])
def test_named_rounds_answer_what_carried_rounds_answer(kind):
    """Over a real `TcpNet` (and in memory) at K = 4,096: from the second
    round on every request frame is under 2 KB, and under a seeded
    schedule of writes between rounds `read_tags` returns the list a proxy
    that always carries the keys returns."""
    K = 4096
    rng = random.Random(36)

    async def body(rig):
        old = rig.proxy("proxy-old", AlwaysCarries)
        keys = [f"{i:0128x}" for i in range(K)]
        seeded(keys, rig)
        digest = sigs.key_from_set(keys)
        # the first round, as the server makes it: with the fingerprint of
        # the tags its full reads left it, so the vote that comes after the
        # quorum is verified too and all four replicas are known holders
        cached = rig.fresh_max(keys, rig.addrs)
        sent = requests_sent()
        assert (await rig.client.read_tags(
            keys, digest=digest, fingerprint=sigs.tags_fingerprint(cached),
            cached_tags=cached)) is cached
        assert cached == await old.read_tags(keys, digest=digest)
        await rig.settle()
        assert since(sent, requests_sent()) == {"carried": 8.0}
        seq = K
        for round_ in range(5):
            # completed writes (every replica holds them), so whichever
            # three replicas vote, the per-key max is the same list
            for _ in range(rng.randrange(0, 6)):
                seq += 1
                rig.store(rng.choice(keys), M.ABDTag(seq, "replica-1"))
            fp = sigs.tags_fingerprint(cached)
            del rig.asked[:]
            sent = requests_sent()
            with Spans("net.serialize") as frames:
                got = await rig.client.read_tags(
                    keys, digest=digest, fingerprint=fp, cached_tags=cached)
                await rig.settle()
            mine = [m for _, m in rig.asked]
            assert len(mine) == 4
            # from the second round on: the digest, and no key
            assert since(sent, requests_sent()) == {"named": 4.0}
            assert all(m.keys == () and m.count == K
                       and m.digest == digest for m in mine)
            assert all(len(M.dumps(m)) < 2048 for m in mine)
            if kind == "tcp":
                sizes = [e.meta["bytes"] for e in frames
                         if e.meta.get("msg") == "ReadTagBatch"]
                assert len(sizes) == 4 and max(sizes) < 2048
            want = await old.read_tags(keys, digest=digest)
            await rig.settle()
            assert list(got) == want == rig.fresh_max(keys, rig.addrs)
            assert (got is cached) == (want == cached)
            cached = list(got)
        assert rig.strikes() == {} and old.replicas.suspicions() == \
            rig.client.replicas.suspicions()

    rigged(body, kind=kind)


@pytest.mark.parametrize("seed", range(4))
def test_the_same_votes_give_the_same_list_named_or_carried(seed):
    """In memory, where which three replicas vote is settled by who is
    reachable: under a seeded schedule of writes at some replicas, rounds
    over changing quorums and a replica that drops its vectors now and
    then, the naming proxy's list is element for element the carrying
    proxy's over the same voters."""
    rng = random.Random(seed)

    async def body(rig):
        old = rig.proxy("proxy-old", AlwaysCarries)
        keys = [f"key-{i:03d}" for i in range(48)]
        seeded(keys, rig)
        digest = sigs.key_from_set(keys)
        down: set = set()

        async def gate(addr, msg):
            return None if addr in down else msg

        for a in rig.addrs:
            rig.net.link_filters[a] = (lambda a: lambda m: gate(a, m))(a)
        cached = await rig.client.read_tags(keys, digest=digest)
        await rig.settle()
        seq = len(keys)
        for _ in range(40):
            for _ in range(rng.randrange(0, 4)):
                seq += 1
                rig.store(rng.choice(keys), M.ABDTag(seq, "replica-1"),
                          rng.sample(rig.addrs, rng.randrange(1, 5)))
            if rng.random() < 0.2:
                node = rig.nodes[rng.choice(rig.addrs)]
                node._install_repository(dict(node.repository))
            reach = rng.sample(rig.addrs, rng.choice([3, 3, 4]))
            down.clear()
            down.update(set(rig.addrs) - set(reach))
            fp = sigs.tags_fingerprint(cached)
            got = await rig.client.read_tags(
                keys, digest=digest, fingerprint=fp, cached_tags=cached)
            await rig.settle()
            if len(reach) == 3:
                assert list(got) == rig.fresh_max(keys, reach)
                assert list(got) == await old.read_tags(keys, digest=digest)
                await rig.settle()
            assert (got is cached) == (list(got) == cached)
            if rng.random() < 0.7:
                cached = list(got)
        assert rig.strikes() == {}

    rigged(body)


# ------------------------------------------------ a replica that forgot the set


@pytest.mark.parametrize("event", ["install", "wipe", "evict"])
def test_a_replica_that_dropped_the_set_is_taught_once_in_the_round(
        event, monkeypatch):
    """A reseed, a wipe or an eviction past `MAX_TAG_VECTORS` takes the
    digest's keys with the vector: the replica says "unknown" once, is
    carried the keys once, votes in that same round (the quorum needs it)
    and nobody is struck."""
    monkeypatch.setattr(replica_mod, "MAX_TAG_VECTORS", 2)

    async def body(rig):
        keys = [f"key-{i:03d}" for i in range(32)]
        seeded(keys, rig)
        digest = sigs.key_from_set(keys)
        cached = await rig.client.read_tags(keys, digest=digest)
        fp = sigs.tags_fingerprint(cached)
        assert (await rig.client.read_tags(
            keys, digest=digest, fingerprint=fp, cached_tags=cached)) is cached
        await rig.settle()
        victim = rig.nodes[rig.addrs[1]]
        if event == "install":
            victim._install_repository(dict(victim.repository))
        elif event == "wipe":
            victim._wipe()
        else:
            for j in range(2):      # two other key sets, asked of it alone
                ks = tuple(f"other-{j}-{i}" for i in range(3))
                d, nonce = sigs.key_from_set(list(ks)), sigs.generate_nonce()
                await victim.handle("proxy-other", M.ReadTagBatch(
                    ks, nonce, sigs.proxy_signature(PROXY_SECRET, d, nonce),
                    digest=d, count=3))
            await rig.settle()
        assert digest not in victim._tag_vectors
        del rig.asked[:]
        sent, sets = requests_sent(), keysets()
        with Spans("abd.read_tags") as rounds:
            got = await rig.client.read_tags(
                keys, digest=digest, fingerprint=fp, cached_tags=cached)
        # quorum 3 of 3: the round ended on the victim's vote
        assert list(got) == rig.fresh_max(keys, rig.addrs)
        await rig.settle()
        assert since(sent, requests_sent()) == {"named": 3.0, "carried": 1.0}
        moved = since(sets, keysets())
        assert moved.pop("unknown") == 1.0 and moved.pop("learned") == 1.0
        assert [(a, bool(m.keys)) for a, m in rig.asked
                if a == victim.addr] == [(victim.addr, False),
                                         (victim.addr, True)]
        first, taught = [m for a, m in rig.asked if a == victim.addr]
        assert taught.nonce != first.nonce and tuple(taught.keys) == tuple(keys)
        assert [r.meta["carried"] for r in rounds] == [1]
        assert digest in victim._tag_vectors
        assert rig.strikes() == {} and rig.suspects == []
        # taught: the next round names the set to everyone again
        sent = requests_sent()
        fp2 = sigs.tags_fingerprint(got)
        await rig.client.read_tags(keys, digest=digest, fingerprint=fp2,
                                   cached_tags=list(got))
        assert since(sent, requests_sent()) == {"named": 3.0}

    rigged(body, n=3, quorum=3)


def test_an_unknown_after_its_round_strikes_nobody_and_is_remembered():
    """The "unknown" of the replica nobody waited for is decoded after
    `read_tags` returned: it ends in `handle`, resolves nothing, strikes
    nobody, and the next request to that replica carries the keys."""

    async def body(rig):
        keys = [f"key-{i:03d}" for i in range(16)]
        seeded(keys, rig)
        digest = sigs.key_from_set(keys)
        cached = await rig.client.read_tags(keys, digest=digest)
        fp = sigs.tags_fingerprint(cached)
        await rig.client.read_tags(keys, digest=digest, fingerprint=fp,
                                   cached_tags=cached)
        await rig.settle()
        last = rig.addrs[3]
        assert rig.client._keyset_holders[digest] == set(rig.addrs)
        rig.nodes[last]._install_repository(dict(rig.nodes[last].repository))
        rig.hold = lambda s, m: isinstance(m, M.KeySetUnknown)
        # an operation that replica coordinates must not be resolved by it
        fut = asyncio.get_event_loop().create_future()
        rig.client._pending[12345] = (fut, last)
        sent = requests_sent()
        assert (await rig.client.read_tags(
            keys, digest=digest, fingerprint=fp, cached_tags=cached)) is cached
        await rig.settle()
        assert [type(m) for _, m in rig.held] == [M.KeySetUnknown]
        assert since(sent, requests_sent()) == {"named": 4.0}
        await rig.release()
        await rig.settle()
        assert not fut.done()
        del rig.client._pending[12345]
        assert rig.strikes() == {}
        assert rig.client._keyset_holders[digest] == set(rig.addrs[:3])
        # no request was sent for the late answer; the next round's is carried
        assert since(sent, requests_sent()) == {"named": 4.0}
        del rig.asked[:]
        assert (await rig.client.read_tags(
            keys, digest=digest, fingerprint=fp, cached_tags=cached)) is cached
        await rig.settle()
        assert sorted((a, bool(m.keys)) for a, m in rig.asked) == [
            (a, a == last) for a in rig.addrs]
        assert rig.client._keyset_holders[digest] == set(rig.addrs)
        # an "unknown" whose round is nowhere kept is dropped
        rig.client._late_tags.clear()
        await rig.client.handle(last, M.KeySetUnknown(
            digest, 777, sigs.abd_keyset_unknown_signature(
                ABD_SECRET, digest, 777)))
        assert rig.client._keyset_holders[digest] == set(rig.addrs)
        assert rig.strikes() == {}

    rigged(body)


def test_a_replica_that_always_says_unknown_costs_one_carried_request_a_round():
    """The one lever a Byzantine replica gains: "unknown" to everything,
    named or carried. Each round sends it the keys at most once, completes
    from the others, and strikes nobody."""

    async def body(rig):
        liar = rig.addrs[0]
        said = []

        async def always_unknown(sender, msg):
            if isinstance(msg, M.ReadTagBatch):
                said.append(bool(msg.keys))
                rig.net.send(liar, sender, M.KeySetUnknown(
                    msg.digest, msg.nonce, sigs.abd_keyset_unknown_signature(
                        ABD_SECRET, msg.digest, msg.nonce)))

        rig.net.register(liar, always_unknown)
        keys = [f"key-{i:03d}" for i in range(16)]
        seeded(keys, rig)
        digest = sigs.key_from_set(keys)
        cached = await rig.client.read_tags(keys, digest=digest)
        assert cached == rig.fresh_max(keys, rig.addrs[1:])
        fp = sigs.tags_fingerprint(cached)
        await rig.settle()
        for _ in range(2):       # whoever answered late is a holder by now
            await rig.client.read_tags(keys, digest=digest, fingerprint=fp,
                                       cached_tags=cached)
            await rig.settle()
        for round_ in range(4):
            del said[:]
            sent = requests_sent()
            got = await rig.client.read_tags(
                keys, digest=digest, fingerprint=fp, cached_tags=cached)
            await rig.settle()
            assert got is cached
            assert since(sent, requests_sent()) == {"named": 3.0,
                                                    "carried": 1.0}
            assert said == [True]        # and its second "unknown" got nothing
        # a liar the proxy believes to hold the set: named, "unknown", the
        # keys once, "unknown" again, and nothing more
        rig.client._keyset_holders[digest].add(liar)
        del said[:]
        sent = requests_sent()
        assert (await rig.client.read_tags(
            keys, digest=digest, fingerprint=fp, cached_tags=cached)) is cached
        await rig.settle()
        assert said == [False, True]
        assert since(sent, requests_sent()) == {"named": 4.0, "carried": 1.0}
        assert rig.strikes() == {}

    rigged(body)


@pytest.mark.parametrize("forgery", ["bad_mac", "other_digest", "not_asked",
                                     "junk_signature"])
def test_a_forged_unknown_moves_nothing(forgery):
    """An "unknown" that fails its MAC, names another digest, or comes
    from an address the round never asked: no request is sent for it, the
    proxy's memory of who holds the set stands, nobody is struck."""

    async def body(rig):
        keys = [f"key-{i:03d}" for i in range(16)]
        seeded(keys, rig)
        digest = sigs.key_from_set(keys)
        cached = await rig.client.read_tags(keys, digest=digest)
        fp = sigs.tags_fingerprint(cached)
        await rig.client.read_tags(keys, digest=digest, fingerprint=fp,
                                   cached_tags=cached)
        await rig.settle()
        holders = set(rig.client._keyset_holders[digest])
        assert holders == set(rig.addrs)
        # hold every reply, so the round is still waiting when the forgery
        # lands under its own nonce
        rig.hold = lambda s, m: isinstance(m, M.TagBatchReply)
        task = asyncio.ensure_future(rig.client.read_tags(
            keys, digest=digest, fingerprint=fp, cached_tags=cached))
        while not rig.client._pending_tags:
            await asyncio.sleep(0)
        await rig.settle()
        (nonce,) = rig.client._pending_tags
        sender, named = rig.addrs[2], digest
        sig = sigs.abd_keyset_unknown_signature(ABD_SECRET, digest, nonce)
        if forgery == "bad_mac":
            sig = sigs.abd_keyset_unknown_signature(b"guess", digest, nonce)
        elif forgery == "other_digest":
            named = sigs.key_from_set(keys[:3])
            sig = sigs.abd_keyset_unknown_signature(ABD_SECRET, named, nonce)
        elif forgery == "not_asked":
            sender = "mallory"
        else:
            sig = "not-bytes"
        sent = requests_sent()
        await rig.client.handle(sender, M.KeySetUnknown(named, nonce, sig))
        await rig.settle()
        assert since(sent, requests_sent()) == {}
        assert list(rig.client._pending_tags) == [nonce]
        assert rig.client._keyset_holders[digest] == holders
        await rig.release()
        assert (await task) is cached
        assert rig.strikes() == {}

    rigged(body)


# -------------------------------------------------- the replica's end, alone


class One:
    """One replica, asked by hand; `inbox` is what it answers."""

    def __init__(self, shard=None):
        self.net = InMemoryNet()
        self.inbox: list = []
        self.suspects: list = []
        self.net.register("proxy", self._collect)
        self.net.register("supervisor", self._supervisor)
        self.node = BFTABDNode("replica-0", ["replica-0"], "supervisor",
                               self.net, ReplicaConfig(quorum_size=1),
                               shard=shard)
        self.keys = tuple(f"key-{i:03d}" for i in range(12))
        for i, k in enumerate(self.keys):
            self.node._store(k, M.ABDTag(i + 1, "replica-0"), [i])
        self.digest = sigs.key_from_set(list(self.keys))

    async def _collect(self, sender, msg):
        self.inbox.append(msg)

    async def _supervisor(self, sender, msg):
        self.suspects.append(msg)

    async def ask(self, keys=(), digest=None, nonce=None, signature=None,
                  count=None):
        digest = self.digest if digest is None else digest
        nonce = sigs.generate_nonce() if nonce is None else nonce
        if signature is None:
            signature = sigs.proxy_signature(PROXY_SECRET, digest, nonce)
        del self.inbox[:]
        await self.node.handle("proxy", M.ReadTagBatch(
            tuple(keys), nonce, signature, None, digest=digest,
            count=len(self.keys) if count is None else count))
        await self.net.quiesce()
        return nonce, list(self.inbox)

    def state(self):
        node = self.node
        return (list(node._tag_vectors), dict(node.incoming),
                list(node._stored_since), node.repo_version)


def test_an_unauthenticated_named_request_learns_and_grows_nothing():
    """A named request under a bad proxy MAC, for a set the replica holds
    or for one it does not: no reply (not even "unknown"), no nonce burnt,
    nothing evicted, built or counted."""

    async def go():
        one = One()
        await one.ask(one.keys)                      # taught
        one.node._store(one.keys[0], M.ABDTag(99, "replica-1"), [99])
        state, counts = one.state(), keysets()
        vec = one.node._tag_vectors[one.digest]
        seen = (vec.seen, vec.fingerprint)
        for i in range(2 * replica_mod.MAX_TAG_VECTORS):
            _, got = await one.ask(signature=b"bogus")
            assert got == []
            _, got = await one.ask(digest=sigs.key_from_set([f"x{i}"]),
                                   signature=b"bogus", count=1)
            assert got == []
            _, got = await one.ask(keys=[f"x{i}"], signature=b"bogus",
                                   digest=sigs.key_from_set([f"x{i}"]),
                                   count=1)
            assert got == []
        assert one.state() == state and keysets() == counts
        assert (vec.seen, vec.fingerprint) == seen
        assert one.suspects == []
        # the authentic named request that follows is answered from the kept
        # keys, patched by the one store
        _, (reply,) = await one.ask()
        assert isinstance(reply, M.TagBatchReply)
        assert reply.tags[0] == M.ABDTag(99, "replica-1")
        assert since(counts, keysets()) == {"known": 1.0}

    run(go())


def test_an_authentic_named_request_for_an_unknown_digest_is_told_so():
    """`KeySetUnknown` under the replica's MAC over (digest, nonce); the
    nonce is spent; no vector is built."""

    async def go():
        one = One()
        counts = keysets()
        nonce, (reply,) = await one.ask()
        assert reply == M.KeySetUnknown(
            one.digest, nonce,
            sigs.abd_keyset_unknown_signature(ABD_SECRET, one.digest, nonce))
        assert sigs.validate_abd_keyset_unknown_signature(
            ABD_SECRET, one.digest, nonce, reply.signature)
        # not a vote under any of the reply MACs
        assert reply.signature != sigs.abd_batch_unchanged_signature(
            ABD_SECRET, b"", one.digest, nonce)
        assert one.node.incoming[nonce] is True
        assert one.node._tag_vectors == {}
        assert since(counts, keysets()) == {"unknown": 1.0}
        # carried under a fresh nonce: learned, and answered in full
        _, (full,) = await one.ask(one.keys)
        assert isinstance(full, M.TagBatchReply)
        assert len(full.tags) == len(one.keys)
        assert since(counts, keysets()) == {"unknown": 1.0, "learned": 1.0}
        assert one.node._tag_vectors[one.digest].keys == one.keys

    run(go())


def test_carried_keys_of_another_digest_are_refused_and_counted():
    """Keys that do not hash to the digest the request names (and its MAC
    covers) are not adopted under it: no reply, no nonce burnt, no vector,
    one `refused`. Where the digest is held already, what the request
    carries is not looked at: the kept keys answer."""

    async def go():
        one = One()
        counts, state = keysets(), one.state()
        wrong = one.keys[:-1] + ("key-zzz",)
        nonce, got = await one.ask(wrong)
        assert got == [] and nonce not in one.node.incoming
        assert one.state() == state
        assert since(counts, keysets()) == {"refused": 1.0}
        # the same nonce is still good for the honest request
        _, (reply,) = await one.ask(one.keys, nonce=nonce)
        assert len(reply.tags) == len(one.keys)
        _, (again,) = await one.ask(wrong)
        assert again.tags == reply.tags
        assert one.node._tag_vectors[one.digest].keys == one.keys
        assert since(counts, keysets()) == {
            "refused": 1.0, "learned": 1.0, "known": 1.0}

    run(go())


def test_a_replayed_named_request_is_suspected_like_any_replay():
    async def go():
        one = One()
        await one.ask(one.keys)
        nonce, (reply,) = await one.ask()
        assert isinstance(reply, M.TagBatchReply)
        counts = keysets()
        assert one.suspects == []
        _, got = await one.ask(nonce=nonce)
        assert got == []
        assert [type(m) for m in one.suspects] == [M.Suspect]
        assert one.suspects[0].replica == "proxy"
        assert since(counts, keysets()) == {}
        # and the replay of a request that was told "unknown"
        other = sigs.key_from_set(["elsewhere"])
        nonce, (reply,) = await one.ask(digest=other, count=1)
        assert isinstance(reply, M.KeySetUnknown)
        _, got = await one.ask(digest=other, count=1, nonce=nonce)
        assert got == [] and len(one.suspects) == 2

    run(go())


def test_a_named_request_for_a_set_with_a_fenced_key_is_fenced():
    """The fence checks every key of the set from the kept keys: a named
    request still gets `WrongShard` for the first key the group does not
    own, and through a quorum the round fails with `WrongShardError`."""

    async def go():
        fence = Fence()
        one = One(shard=fence)
        await one.ask(one.keys)
        _, (reply,) = await one.ask()
        assert isinstance(reply, M.TagBatchReply)
        fence.disowned.add(one.keys[4])
        nonce, (reply,) = await one.ask()
        assert isinstance(reply, M.WrongShard)
        assert (reply.key, reply.nonce) == (one.keys[4], nonce)
        assert one.node.incoming[nonce] is True

    run(go())

    async def body(rig):
        keys = [f"key-{i:03d}" for i in range(8)]
        seeded(keys, rig)
        cached = await rig.client.read_tags(keys)
        fp = sigs.tags_fingerprint(cached)
        for _ in range(2):       # whoever answers late is a holder by now
            await rig.settle()
            await rig.client.read_tags(keys, fingerprint=fp,
                                       cached_tags=cached)
        await rig.settle()
        rig.shard.disowned.add(keys[2])
        del rig.asked[:]
        with pytest.raises(WrongShardError):
            await rig.client.read_tags(keys)
        assert rig.asked and all(m.keys == () for _, m in rig.asked)
        assert rig.strikes() == {}

    rigged(body, shard=Fence())


def test_what_the_proxy_remembers_is_bounded_and_goes_oldest_first(
        monkeypatch):
    """Past `MAX_NAMED_SETS` digests the one used longest ago is forgotten
    and its next round carries the keys again; a replica no longer trusted
    is no holder."""
    monkeypatch.setattr(qc, "MAX_NAMED_SETS", 2)

    async def body(rig):
        sets = [[f"s{j}-{i}" for i in range(4)] for j in range(3)]
        for ks in sets:
            await rig.client.read_tags(ks)
            await rig.settle()
        assert len(rig.client._keyset_holders) == 2
        assert sigs.key_from_set(sets[0]) not in rig.client._keyset_holders
        sent = requests_sent()
        await rig.client.read_tags(sets[2])
        await rig.settle()
        assert since(sent, requests_sent()).get("named", 0) >= 3.0
        sent = requests_sent()
        await rig.client.read_tags(sets[0])
        await rig.settle()
        assert since(sent, requests_sent()) == {"carried": 4.0}
        for _ in range(3):
            rig.client.replicas.increment_suspicion(rig.addrs[0])
        await rig.client.read_tags(sets[0])
        assert rig.addrs[0] not in rig.client._keyset_holders[
            sigs.key_from_set(sets[0])]

    rigged(body)


def test_both_forms_and_the_unknown_survive_the_wire():
    digest = sigs.key_from_set(["a", "b"])
    for m in (M.ReadTagBatch((), 7, b"sig", b"\x01" * 32, 3, b"\x02" * 32,
                             digest, 2),
              M.ReadTagBatch(("a", "b"), 7, b"sig", digest=digest, count=2),
              M.KeySetUnknown(digest, 7, b"\x03" * 32)):
        assert M.loads(M.dumps(m)) == m
    # a frame of the schema before `digest`: the fields default
    old = M.to_dict(M.ReadTagBatch(("a",), 1, b"s"))
    del old["digest"], old["count"]
    assert M.from_dict(old) == M.ReadTagBatch(("a",), 1, b"s")


# ----------------------------------------------- the yardstick's two readers


def _layer(name: str) -> dict:
    with open(os.path.join(ROOT, "yardstick", "layers", f"{name}.json")) as f:
        return json.load(f)


def test_the_carried_share_reduces_recorded_counters_with_counter_share():
    """`yardstick/layers/quorum.tag_keys_carried_share.json` is data for
    the reducer the yardstick has: over a window in which 2 requests of
    400 carried the keys it reads 0.5 %, and nothing where no request was
    counted (the parent's program)."""
    from yardstick.reducers import counter_share
    from yardstick.run import Window

    spec = _layer("quorum.tag_keys_carried_share")
    assert spec["reducer"] == "counter_share"
    assert (spec["unit"], spec["better"], spec["moves"]) == (
        "%", "lower", "agg_p50_ms")
    assert spec["layer"] == _layer("quorum.tag_full_vote_share")["layer"]
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    entry = next(m for m in bench["per_layer"]
                 if m["name"] == "quorum.tag_keys_carried_share")
    assert entry["workloads"] == [w["name"] for w in bench["workloads"]]
    assert {k: entry[k] for k in ("unit", "better", "moves", "layer")} == {
        k: spec[k] for k in ("unit", "better", "moves", "layer")}

    w = Window({}, "test", {})
    w.open({"quorum.tag_keys_carried_share": spec})
    try:
        assert counter_share.reduce(w, **spec["args"]) is None
        metrics.inc("dds_tag_round_requests_total", 398, keys="named")
        metrics.inc("dds_tag_round_requests_total", 2, keys="carried")
        assert counter_share.reduce(w, **spec["args"]) == pytest.approx(0.5)
    finally:
        w.close()


def test_the_request_bytes_metric_finds_named_and_carried_frames_alike():
    """Both forms are one message class: `wire.tag_request_bytes_per_agg`
    reads `dds_net_frame_bytes_total{direction=sent,msg=ReadTagBatch}`
    after a carried and a named round over a real `TcpNet`, and what it
    reads is the bytes of both."""
    from yardstick.reducers import counter_per_op
    from yardstick.run import Window

    spec = _layer("wire.tag_request_bytes_per_agg")

    async def body(rig):
        keys = [f"{i:0128x}" for i in range(64)]
        seeded(keys, rig)
        w = Window({}, "test", {})
        w.open({"wire.tag_request_bytes_per_agg": spec})
        w.ops = [types.SimpleNamespace(kind="aggregate", status=200)] * 2
        try:
            sent = requests_sent()
            with Spans("net.serialize") as frames:
                cached = await rig.client.read_tags(keys)
                await rig.settle()
                fp = sigs.tags_fingerprint(cached)
                for _ in range(2):
                    await rig.client.read_tags(keys, fingerprint=fp,
                                               cached_tags=cached)
                    await rig.settle()
            moved = since(sent, requests_sent())
            assert moved["carried"] >= 4.0 and moved["named"] >= 4.0
            sizes = [e.meta["bytes"] for e in frames
                     if e.meta.get("msg") == "ReadTagBatch"]
            assert len(sizes) == 12
            assert min(sizes) < 2048 < max(sizes)
            got = counter_per_op.reduce(w, **spec["args"])
            assert got == pytest.approx(sum(sizes) / 2)
        finally:
            w.close()

    rigged(body, kind="tcp")


# ------------------- who is asked follows the breakers (ISSUE 42, points 2, 3)


def skipped() -> float:
    return metrics.value("dds_tag_round_skipped_total") or 0.0


async def _rounds(rig, keys, cached, n):
    """n rounds as the server makes them (digest and fingerprint), each
    settled; returns the requests each replica was sent, round by round."""
    digest, fp = sigs.key_from_set(keys), sigs.tags_fingerprint(cached)
    out = []
    for _ in range(n):
        del rig.asked[:]
        got = await rig.client.read_tags(keys, digest=digest, fingerprint=fp,
                                         cached_tags=cached)
        await rig.settle()
        assert got is cached
        out.append({a: m for a, m in rig.asked})
    return out


@pytest.mark.parametrize("silent", ["replica-0", "replica-3"])
def test_a_silent_replica_is_found_by_its_silence_then_skipped(silent):
    """A replica that answers no round: asked (and carried the K keys,
    being no holder) until `breaker_threshold` rounds have met their
    quorum, kept their late window open `MAX_LATE_ROUNDS` deep and closed
    it without a word; then its breaker is open and it is sent nothing.
    Three verified votes make every round, before and after; nobody is
    struck."""
    async def body(rig):
        keys = [f"{i:0128x}" for i in range(64)]
        seeded(keys, rig)
        cached = rig.fresh_max(keys, rig.addrs)
        rig.net.unregister(silent)
        client = rig.client
        before, sent = skipped(), requests_sent()
        rounds = await _rounds(
            rig, keys, cached,
            qc.MAX_LATE_ROUNDS + client.cfg.breaker_threshold - 1)
        assert silent not in client.breakers or client.breakers[silent].settled
        assert skipped() == before
        rounds += await _rounds(rig, keys, cached, 1)
        # the eleventh closed the third silent window: open from now on
        assert client.breakers[silent].state == "open"
        assert sorted(client._probe_tasks) == [silent]
        assert len(client._late_tags) <= qc.MAX_LATE_ROUNDS
        first = since(sent, requests_sent())
        assert first["carried"] == 3 + len(rounds)      # never a holder
        sent = requests_sent()
        after = await _rounds(rig, keys, cached, 5)
        assert all(set(r) == set(rig.addrs) - {silent} for r in after)
        assert since(sent, requests_sent()) == {"named": 15.0}
        assert skipped() - before == 5
        assert rig.strikes() == {}
        await client.stop()

    rigged(body)


def test_a_replica_that_answers_last_is_never_taken_for_silent():
    """Three votes make the quorum and the fourth comes after it, every
    round: a verified late vote is a breaker success, so nothing opens."""
    async def body(rig):
        keys = [f"{i:0128x}" for i in range(64)]
        seeded(keys, rig)
        cached = rig.fresh_max(keys, rig.addrs)
        before = skipped()
        rounds = await _rounds(rig, keys, cached, 3 * qc.MAX_LATE_ROUNDS)
        assert all(len(r) == 4 for r in rounds)
        assert all(b.settled for b in rig.client.breakers.values())
        assert rig.client._probe_tasks == {} and skipped() == before
        assert rig.client._late_tags == {}

    rigged(body)


@pytest.mark.parametrize("open_", [1, 2])
def test_a_skipped_replica_lowers_no_quorum(open_):
    """With one breaker open the other three are asked and all three must
    vote; with two open fewer than a quorum would be left, so everyone is
    asked, as before."""
    async def body(rig):
        keys = [f"{i:0128x}" for i in range(32)]
        seeded(keys, rig)
        cached = rig.fresh_max(keys, rig.addrs)
        await _rounds(rig, keys, cached, 1)
        client = rig.client
        client.cfg.breaker_reset = 30.0
        down = rig.addrs[-open_:]
        for a in down:
            for _ in range(client.cfg.breaker_threshold):
                client._breaker_failed(a)
        before = skipped()
        (asked,) = await _rounds(rig, keys, cached, 1)
        if open_ == 1:
            assert set(asked) == set(rig.addrs) - set(down)
            assert skipped() - before == 1
            # the three are needed, all of them: one more silent and the
            # round fails as it must
            rig.net.unregister(rig.addrs[0])
            client.cfg.request_timeout = 0.2
            with pytest.raises(asyncio.TimeoutError):
                await client.read_tags(keys, fingerprint=sigs.tags_fingerprint(
                    cached), cached_tags=cached)
        else:
            assert set(asked) == set(rig.addrs)
            assert skipped() == before
        await client.stop()

    rigged(body)


def test_a_replica_back_behind_a_closed_breaker_is_taught_the_keys_once():
    async def body(rig):
        keys = [f"{i:0128x}" for i in range(32)]
        seeded(keys, rig)
        cached = rig.fresh_max(keys, rig.addrs)
        client = rig.client
        client.cfg.breaker_reset, client.cfg.breaker_probe_timeout = 0.1, 0.1
        gone = rig.addrs[2]
        handler = rig.net._handlers[gone]
        rig.net.unregister(gone)
        for _ in range(client.cfg.breaker_threshold):
            client._breaker_failed(gone)
        (asked,) = await _rounds(rig, keys, cached, 1)
        assert gone not in asked
        rig.net.register(gone, handler)                 # it is back
        for _ in range(100):
            if client.breakers[gone].settled:
                break
            await asyncio.sleep(0.02)
        assert client.breakers[gone].state == "closed"
        assert client._probe_tasks == {}
        sent = requests_sent()
        rounds = await _rounds(rig, keys, cached, 3)
        assert all(len(r) == 4 for r in rounds)
        assert [len(r[gone].keys) for r in rounds] == [32, 0, 0]
        assert since(sent, requests_sent()) == {"named": 11.0, "carried": 1.0}

    rigged(body)
