"""The replica's kept tag vectors (`replica._TagVector`): what a
`ReadTagBatch` reply is made of, patched by the keys stored since.

The safety argument of the tag round does not move: the reply for a key set
is, byte for byte, what a replica that looked every key up and formatted
every tag would send — never a vector from before a stored write. What
moves is the cost: O(keys stored since the last round), counted here by
patching the functions that cost O(K).
"""

import asyncio
import random

import pytest

from dds_tpu.core import messages as M
from dds_tpu.core import replica as replica_mod
from dds_tpu.core.replica import BFTABDNode, ReplicaConfig
from dds_tpu.core.transport import InMemoryNet
from dds_tpu.obs.metrics import metrics
from dds_tpu.utils import sigs
from dds_tpu.utils.trace import tracer

from tests.test_core import Cluster, run


class _Shard:
    """shard.ShardState duck-type whose map the test moves."""

    group_id, epoch = 0, 1

    def __init__(self):
        self.disowned: set = set()

    def owns(self, key: str) -> bool:
        return key not in self.disowned


def vector_of(node, keys):
    """The vector a replica keeps for a key set: found by the keys' digest."""
    return node._tag_vectors[sigs.key_from_set(list(keys))]


def kept_sets(node) -> list:
    """The key sets a replica keeps a vector for, oldest first."""
    return [v.keys for v in node._tag_vectors.values()]


class Rig:
    """One replica and a proxy's address that collects what it answers."""

    def __init__(self, shard=None):
        self.net = InMemoryNet()
        self.inbox: list = []
        self.net.register("proxy", self._collect)
        self.node = BFTABDNode("replica-0", ["replica-0"], "supervisor",
                               self.net, ReplicaConfig(quorum_size=1),
                               shard=shard)
        self.seq = 0

    async def _collect(self, sender, msg):
        self.inbox.append(msg)

    def store(self, key: str) -> M.ABDTag:
        self.seq += 1
        tag = M.ABDTag(self.seq, f"replica-{self.seq % 3}")
        self.node._store(key, tag, [self.seq])
        return tag

    def fresh(self, keys) -> tuple:
        """The tag vector looked up anew, as the memo-less replica did."""
        blank = (M.ABDTag(0, self.node.name), None)
        return tuple(self.node.repository.get(k, blank)[0] for k in keys)

    async def ask(self, keys, fingerprint=None, signature=None, base=None):
        nonce = sigs.generate_nonce()
        digest = sigs.key_from_set(list(keys))
        if signature is None:
            signature = sigs.proxy_signature(
                self.node.cfg.proxy_mac_secret, digest, nonce)
        self.inbox.clear()
        await self.node.handle(
            "proxy", M.ReadTagBatch(tuple(keys), nonce, signature, fingerprint,
                                    base=base, digest=digest,
                                    count=len(keys)))
        await self.net.quiesce()
        return nonce, digest, list(self.inbox)

    async def ask_delta(self, keys, base, held, fingerprint=b"\x01" * 32):
        """One round that names `base`, the fingerprint of `held` (the
        vector this replica answered with then). Returns the reply and
        whether it was a delta; a delta is held to: its positions are
        exactly where a fresh lookup differs from `held` or a tag was
        stored again, ascending and each once; applied to `held` it gives
        the fresh lookup; its MAC is the delta MAC over all of it."""
        nonce, digest, got = await self.ask(keys, fingerprint, base=base)
        (reply,) = got
        want = self.fresh(keys)
        assert reply.fingerprint == sigs.tags_fingerprint(want)
        if reply.base is None:
            return reply, False
        assert reply.base == base and not reply.unchanged
        at = list(reply.positions)
        assert at == sorted(set(at)) and len(at) == len(reply.tags)
        applied = list(held)
        for i, t in zip(at, reply.tags):
            applied[i] = t
        assert tuple(applied) == want
        assert reply.signature == sigs.abd_batch_delta_signature(
            self.node.cfg.abd_mac_secret, base, reply.fingerprint, at,
            [sigs.tag_field(t) for t in reply.tags], digest, nonce)
        assert sigs.validate_abd_batch_delta_signature(
            self.node.cfg.abd_mac_secret, base, reply.fingerprint, at,
            reply.tags, digest, nonce, reply.signature)
        return reply, True

    async def ask_and_check(self, keys, fingerprint=None):
        """One authenticated round, held to the reply computed afresh."""
        nonce, digest, got = await self.ask(keys, fingerprint)
        assert len(got) == 1
        (reply,) = got
        secret = self.node.cfg.abd_mac_secret
        want = self.fresh(keys)
        assert reply.digest == digest and reply.nonce == nonce
        assert reply.fingerprint == sigs.tags_fingerprint(want)
        if fingerprint is not None and fingerprint == reply.fingerprint:
            assert reply.unchanged and reply.tags == ()
            assert reply.signature == sigs.abd_batch_unchanged_signature(
                secret, reply.fingerprint, digest, nonce)
        else:
            assert not reply.unchanged
            assert reply.tags == want
            assert reply.signature == sigs.abd_batch_signature(
                secret, want, digest, nonce)
        return reply


def _counts() -> dict:
    out = {o: metrics.value("dds_replica_tag_vector_total", outcome=o) or 0.0
           for o in ("reused", "patched", "rebuilt")}
    out["keys"] = metrics.value("dds_replica_tag_vector_keys_total",
                                outcome="patched") or 0.0
    return out


def _delta(before: dict) -> dict:
    return {k: v - before[k] for k, v in _counts().items() if v != before[k]}


# ------------------------------------------------------------- equivalence


@pytest.mark.parametrize("seed", range(8))
def test_replies_equal_a_fresh_lookup_under_random_interleavings(seed):
    """(a) Whatever happened to the repository between two rounds — stored
    writes, a reseed, a wipe, a prune, a direct assignment with a bare
    version bump — the reply's tags, fingerprint and MAC are those computed
    afresh from the repository, for two overlapping key sets and a key
    never stored."""
    rng = random.Random(seed)
    universe = [f"key-{i:03d}" for i in range(24)]
    sets = (tuple(universe[:16]) + ("never-stored",), tuple(universe[8:]))

    async def go():
        shard = _Shard()
        rig = Rig(shard)
        node = rig.node
        last_fp = {ks: None for ks in sets}
        rounds = 0
        for _ in range(160):
            op = rng.choice(["store"] * 6 + ["ask"] * 6 + [
                "install", "wipe", "prune", "bare", "state"])
            if op == "store":
                for _ in range(rng.randrange(1, 4)):
                    rig.store(rng.choice(universe))
            elif op == "install":
                kept = rng.sample(universe, rng.randrange(0, len(universe)))
                rig.seq += 1
                node._install_repository(
                    {k: (M.ABDTag(rig.seq, "seed"), [0]) for k in kept})
            elif op == "wipe":
                node._wipe()
            elif op == "prune":
                shard.disowned = set(rng.sample(universe, 3))
                _, _, got = await rig.ask(sets[0])
                if shard.disowned & set(sets[0]):
                    assert [type(m) for m in got] == [M.WrongShard]
                node.drop_unowned()
                shard.disowned = set()
            elif op == "bare":
                k = rng.choice(universe)
                rig.seq += 1
                node.repository[k] = (M.ABDTag(rig.seq, "direct"), None)
                node.repo_version += 1
            elif op == "state":
                # ReadTag on an unknown key materializes a blank entry
                # without a version bump: the same tag the vector holds
                node._state(rng.choice(universe))
            else:
                ks = rng.choice(sets)
                fp = rng.choice([None, last_fp[ks], b"\x00" * 32])
                reply = await rig.ask_and_check(ks, fp)
                last_fp[ks] = reply.fingerprint
                rounds += 1
        assert rounds > 20
        for ks in sets:   # and once more at the end, both ways
            await rig.ask_and_check(ks)
            await rig.ask_and_check(ks, last_fp[ks])

    run(go())


def test_a_store_between_two_rounds_is_in_the_second():
    """Never a vector from before a stored write: the round right after
    `_store` carries the new tag, under `unchanged` only if the proxy's
    fingerprint already covers it."""

    async def go():
        rig = Rig()
        keys = ("a", "b", "c")
        for k in keys:
            rig.store(k)
        first = await rig.ask_and_check(keys)
        tag = rig.store("b")
        second = await rig.ask_and_check(keys, first.fingerprint)
        assert not second.unchanged and second.tags[1] == tag
        third = await rig.ask_and_check(keys, second.fingerprint)
        assert third.unchanged

    run(go())


# -------------------------------------------------------------------- cost


@pytest.mark.parametrize("m", [1, 3, 7])
def test_a_round_after_m_stores_formats_m_tags_and_hashes_no_keys(
        m, monkeypatch):
    """(b) After the first round for a key set, m stored keys then a round
    cost m `tag_field`s, no `key_from_set` and no `tags_blob` on the
    replica; the counters read `patched` / m; the span says so."""
    calls = {"key_from_set": 0, "tag_field": 0, "tags_blob": 0}

    def counting(name):
        real = getattr(sigs, name)

        def wrapped(*a, **kw):
            calls[name] += 1
            return real(*a, **kw)

        monkeypatch.setattr(sigs, name, wrapped)

    async def go():
        rig = Rig()
        keys = tuple(f"key-{i:03d}" for i in range(64))
        for k in keys:
            rig.store(k)
        digest = sigs.key_from_set(list(keys))
        await rig.ask_and_check(keys)          # the first round builds

        async def quiet_round():
            """A round that computes nothing of its own around the
            replica: what is counted is the replica's."""
            nonce = sigs.generate_nonce()
            sig = sigs.proxy_signature(
                rig.node.cfg.proxy_mac_secret, digest, nonce)
            for name in calls:
                counting(name)
            before = _counts()
            spans_before = len(tracer.events("replica.tag_vector"))
            await rig.node.handle(
                "proxy", M.ReadTagBatch(keys, nonce, sig, None,
                                        digest=digest, count=len(keys)))
            monkeypatch.undo()
            spans = tracer.events("replica.tag_vector")[spans_before:]
            return _delta(before), spans

        for k in random.Random(m).sample(keys, m):
            rig.store(k)
        rig.store("not-in-the-set")
        delta, spans = await quiet_round()
        assert calls == {"key_from_set": 0, "tag_field": m, "tags_blob": 0}
        assert delta == {"patched": 1.0, "keys": float(m)}
        assert [(s.meta["outcome"], s.meta["changed"], s.meta["k"])
                for s in spans] == [("patched", m, len(keys))]

        # nothing stored: nothing formatted, joined or counted as patched,
        # and no span
        for name in calls:
            calls[name] = 0
        delta, spans = await quiet_round()
        assert calls == {"key_from_set": 0, "tag_field": 0, "tags_blob": 0}
        assert delta == {"reused": 1.0} and spans == []

        # a store outside the set alone: still reused
        rig.store("not-in-the-set")
        delta, spans = await quiet_round()
        assert delta == {"reused": 1.0} and spans == []
        assert calls["tag_field"] == 0

        await rig.net.quiesce()
        await rig.ask_and_check(keys)

    run(go())


@pytest.mark.parametrize("event", ["install", "wipe", "prune", "bare"])
def test_a_change_that_names_no_key_is_rebuilt_and_counted(event):
    """A reseed, a wipe, a prune and a bare version bump drop the vectors:
    the next round builds anew, reads `rebuilt`, and is exact."""

    async def go():
        shard = _Shard()
        rig = Rig(shard)
        node = rig.node
        keys = tuple(f"key-{i}" for i in range(12))
        for k in keys:
            rig.store(k)
        await rig.ask_and_check(keys)
        rig.store(keys[3])           # logged, then overtaken by the event
        if event == "install":
            node._install_repository(
                {k: (M.ABDTag(99, "seed"), [1]) for k in keys[:5]})
        elif event == "wipe":
            node._wipe()
        elif event == "prune":
            shard.disowned = {keys[0]}
            assert node.drop_unowned() == 1
            shard.disowned = set()
        else:
            node.repository[keys[1]] = (M.ABDTag(77, "direct"), None)
            node.repo_version += 1
        before = _counts()
        spans_before = len(tracer.events("replica.tag_vector"))
        await rig.ask_and_check(keys)
        assert _delta(before) == {"rebuilt": 1.0}
        (span,) = tracer.events("replica.tag_vector")[spans_before:]
        assert span.meta["outcome"] == "rebuilt"
        before = _counts()
        await rig.ask_and_check(keys)
        assert _delta(before) == {"reused": 1.0}

    run(go())


def test_key_sets_and_the_store_log_stay_bounded(monkeypatch):
    """Past `MAX_TAG_VECTORS` key sets the oldest vector goes; a vector
    that trails the log by more than `MAX_TAG_VECTOR_LAG` stores goes
    instead of holding the log; with no vector kept, nothing is logged."""
    monkeypatch.setattr(replica_mod, "MAX_TAG_VECTOR_LAG", 10)

    async def go():
        rig = Rig()
        node = rig.node
        for i in range(30):
            rig.store(f"key-{i}")
        assert node._stored_since == []       # no vector: no log
        sets = [tuple(f"key-{j}" for j in range(i, i + 3))
                for i in range(replica_mod.MAX_TAG_VECTORS + 3)]
        for ks in sets:
            await rig.ask_and_check(ks)
        assert len(node._tag_vectors) == replica_mod.MAX_TAG_VECTORS
        assert sets[0] not in kept_sets(node)
        assert sets[-1] in kept_sets(node)
        hot = sets[-1]
        for round_ in range(6):               # only `hot` is asked about
            for _ in range(3):
                rig.store(hot[round_ % 3])
            await rig.ask_and_check(hot)
            assert len(node._stored_since) <= 10 + 3
        assert kept_sets(node) == [hot]
        assert node._stored_since == []
        for ks in sets:                       # the dropped ones build anew
            await rig.ask_and_check(ks)

    run(go())


# ---------------------------------------------------------- authentication


def test_unauthenticated_rounds_on_a_kept_key_set_leave_it_alone():
    """(d) A bogus request for the very key set that is kept probes the
    vector for its digest and nothing more: no reply, no nonce burned, no
    patch applied, no counter moved; the next authentic round patches."""

    async def go():
        rig = Rig()
        node = rig.node
        keys = ("a", "b")
        for k in keys:
            rig.store(k)
        await rig.ask_and_check(keys)
        tag = rig.store("a")
        vec = vector_of(node, keys)
        state = (vec.seen, vec.tags[:], vec.fields[:], vec.blob,
                 vec.fingerprint, node._stored_since[:], dict(node.incoming))
        before = _counts()
        for i in range(12):
            _, _, got = await rig.ask(keys, signature=b"bogus")
            assert got == []
            _, _, got = await rig.ask((f"bogus-{i}",) * 3, signature=b"bogus")
            assert got == []
        assert kept_sets(node) == [keys]
        assert (vec.seen, vec.tags, vec.fields, vec.blob, vec.fingerprint,
                node._stored_since, node.incoming) == state
        assert _delta(before) == {}
        reply = await rig.ask_and_check(keys)
        assert reply.tags[0] == tag
        assert _delta(before) == {"patched": 1.0, "keys": 1.0}

    run(go())


def test_replayed_nonce_is_refused_before_the_vector_is_touched():
    async def go():
        rig = Rig()
        keys = ("a",)
        rig.store("a")
        nonce, digest, _ = await rig.ask(keys)
        rig.store("a")
        vec = vector_of(rig.node, keys)
        seen = vec.seen
        rig.inbox.clear()
        sig = sigs.proxy_signature(rig.node.cfg.proxy_mac_secret, digest, nonce)
        await rig.node.handle("proxy", M.ReadTagBatch(
            keys, nonce, sig, None, digest=digest, count=len(keys)))
        await rig.net.quiesce()
        assert not any(isinstance(m, M.TagBatchReply) for m in rig.inbox)
        assert vec.seen == seen

    run(go())


# ------------------------------------------------------------- delta replies


@pytest.mark.parametrize("seed", range(6))
def test_a_delta_applied_to_its_base_is_the_fresh_lookup(seed, monkeypatch):
    """A round that names a base the vector remembers is answered with the
    positions replaced since, whatever was stored meanwhile and however
    many rounds old the base is; a base it never sealed, one from before a
    reseed and one trimmed away are owed the full reply, byte for byte the
    parent's."""
    monkeypatch.setattr(replica_mod, "MIN_DELTA_HISTORY", 8)
    rng = random.Random(seed)

    async def go():
        rig = Rig()
        keys = tuple(f"key-{i:02d}" for i in range(20))
        for k in keys:
            rig.store(k)
        first = await rig.ask_and_check(keys)
        bases = [(first.fingerprint, first.tags)]    # states it answered from
        deltas = fulls = 0
        for step in range(80):
            op = rng.choice(["store"] * 5 + ["ask"] * 4 + ["burst", "reseed"])
            if op == "store":
                rig.store(rng.choice(keys + ("outside",)))
            elif op == "burst":
                for _ in range(10):
                    rig.store(rng.choice(keys))
            elif op == "reseed":
                rig.node._install_repository(dict(rig.node.repository))
            else:
                base, held = rng.choice(bases[-4:])
                reply, was_delta = await rig.ask_delta(keys, base, held)
                if was_delta:
                    deltas += 1
                else:
                    fulls += 1
                    assert reply.tags == rig.fresh(keys)
                bases.append((reply.fingerprint, rig.fresh(keys)))
        assert deltas > 5 and fulls > 0
        # a base it never sealed, and no base: the parent's full reply
        reply, was_delta = await rig.ask_delta(keys, b"\x07" * 32, ())
        assert not was_delta
        await rig.ask_and_check(keys)
        # `unchanged` outranks a delta
        fp = sigs.tags_fingerprint(rig.fresh(keys))
        _, _, (reply,) = await rig.ask(keys, fp, base=fp)
        assert reply.unchanged and reply.base is None

    run(go())


def test_a_base_that_is_the_vector_itself_is_an_empty_delta():
    """The proxy's own list differs from this replica's vector, which has
    not moved since the proxy last verified it: nothing to ship but the
    MAC."""

    async def go():
        rig = Rig()
        keys = ("a", "b", "c")
        for k in keys:
            rig.store(k)
        first = await rig.ask_and_check(keys)
        reply, was_delta = await rig.ask_delta(keys, first.fingerprint,
                                               first.tags)
        assert was_delta and reply.positions == () and reply.tags == ()

    run(go())


def test_a_trimmed_history_forgets_old_bases_and_keeps_new_ones(monkeypatch):
    """The positions kept are bounded by the key set (a quarter of it, a
    floor for small sets): past it the older half goes with the marks that
    pointed into it, and never by a setting."""
    monkeypatch.setattr(replica_mod, "MIN_DELTA_HISTORY", 8)

    async def go():
        rig = Rig()
        keys = tuple(f"key-{i:02d}" for i in range(16))
        for k in keys:
            rig.store(k)
        old = await rig.ask_and_check(keys)
        vec = vector_of(rig.node, keys)
        recent = None
        for step in range(30):
            rig.store(keys[step % 16])
            reply = await rig.ask_and_check(keys)
            if step == 27:
                recent = (reply.fingerprint, reply.tags)
            assert len(vec.moved) <= 8 and len(vec.marks) <= 9
        _, was_delta = await rig.ask_delta(keys, old.fingerprint, old.tags)
        assert not was_delta
        reply, was_delta = await rig.ask_delta(keys, *recent)
        assert was_delta and len(reply.positions) == 2

    run(go())


def test_a_delta_round_ships_and_formats_what_moved_not_k(monkeypatch):
    """(c) After m stores a delta round formats m tags (the patch), no key
    digest, no `tags_blob`, and ships m tags, not K."""
    calls = {"key_from_set": 0, "tag_field": 0, "tags_blob": 0}

    async def go():
        rig = Rig()
        keys = tuple(f"key-{i:03d}" for i in range(256))
        for k in keys:
            rig.store(k)
        first = await rig.ask_and_check(keys)
        digest = sigs.key_from_set(list(keys))
        for k in random.Random(5).sample(keys, 3):
            rig.store(k)
        nonce = sigs.generate_nonce()
        sig = sigs.proxy_signature(rig.node.cfg.proxy_mac_secret, digest,
                                   nonce)
        for name in calls:
            real = getattr(sigs, name)

            def wrapped(*a, _real=real, _name=name, **kw):
                calls[_name] += 1
                return _real(*a, **kw)

            monkeypatch.setattr(sigs, name, wrapped)
        rig.inbox.clear()
        await rig.node.handle("proxy", M.ReadTagBatch(
            keys, nonce, sig, b"\x01" * 32, base=first.fingerprint,
            digest=digest, count=len(keys)))
        monkeypatch.undo()
        await rig.net.quiesce()
        (reply,) = rig.inbox
        assert reply.base == first.fingerprint
        assert len(reply.tags) == len(reply.positions) == 3
        assert calls == {"key_from_set": 0, "tag_field": 3, "tags_blob": 0}

    run(go())


# ------------------------------------------------------------ through a quorum


@pytest.mark.parametrize("n_keys", [1, 5])
def test_acknowledged_writes_are_in_the_next_quorum_round(n_keys):
    """(c) A write acknowledged by a quorum is in the very next
    `read_tags`, from vectors every replica patched at its own store."""

    async def go():
        c = Cluster()
        keys = [f"k{i}" for i in range(n_keys)]
        tags = {}
        for k in keys:
            _, tags[k] = await c.client.write_set_tagged(k, [0])
        assert await c.client.read_tags(keys) == [tags[k] for k in keys]
        rng = random.Random(n_keys)
        for step in range(12):
            k = rng.choice(keys)
            _, tags[k] = await c.client.write_set_tagged(k, [step])
            got = await c.client.read_tags(keys)
            assert got == [tags[k] for k in keys]
        await c.net.quiesce()
        for name in c.active:
            node = c.replicas[name]
            vec = vector_of(node, tuple(keys))
            assert vec.fingerprint == sigs.tags_fingerprint(
                tuple(node.repository[k][0] for k in keys))

    run(go())


def test_unchanged_fast_path_survives_writes_to_other_keys():
    """The proxy's fingerprint stays good across stores outside the key
    set: every vote is `unchanged` and the caller's list comes back by
    identity."""

    async def go():
        c = Cluster()
        await c.client.write_set("in", [1])
        cached = await c.client.read_tags(["in"])
        fp = sigs.tags_fingerprint(cached)
        await c.client.write_set("out", [2])
        again = await c.client.read_tags(["in"], fingerprint=fp,
                                         cached_tags=cached)
        assert again is cached

    run(go())
