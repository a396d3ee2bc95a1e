"""Delta replies to `ReadTagBatch`, and the proxy that keeps each replica's
verified tag vector patched by them (`quorum_client._KeptVectors`).

What must hold: `read_tags` returns, element for element, the per-key max
over the same quorum's vectors taken whole, and the caller's own list by
identity when no vote differs from it; a delta is applied to the record of
the replica that sent it and to no other, so a liar misstates no vote but
its own; and a delta round costs the proxy what it carries, not K.
"""

import asyncio
import random

import pytest

from dds_tpu.core import messages as M
from dds_tpu.core import quorum_client as qc
from dds_tpu.core import replica as replica_mod
from dds_tpu.core.quorum_client import AbdClient, AbdClientConfig
from dds_tpu.obs.metrics import metrics
from dds_tpu.utils import sigs
from dds_tpu.utils.trace import tracer

from tests.test_core import Cluster, run

SECRET = AbdClientConfig().abd_mac_secret
KINDS = ("unchanged", "delta", "full")


def votes_by_kind() -> dict:
    return {k: metrics.value("dds_tag_round_votes_total", kind=k) or 0.0
            for k in KINDS}


def discarded() -> dict:
    return {r: metrics.value("dds_tag_round_delta_discarded_total",
                             reason=r) or 0.0
            for r in ("unknown_base", "bad_positions", "bad_mac")}


def since(before: dict, now: dict) -> dict:
    return {k: now[k] - before[k] for k in now if now[k] != before[k]}


class Voters(AbdClient):
    """An AbdClient that notes whose votes each round counted."""

    def __init__(self, *a, **kw):
        super().__init__(*a, **kw)
        self.voters: list[str] = []

    async def read_tags(self, *a, **kw):
        self.voters = []
        return await super().read_tags(*a, **kw)

    def _on_tag_batch_reply(self, sender, msg):
        rnd = self._pending_tags[msg.nonce]
        had = sender in rnd.votes
        super()._on_tag_batch_reply(sender, msg)
        if not had and sender in rnd.votes:
            self.voters.append(sender)


class Bench:
    """n replicas, a proxy under test that passes its cached vector and a
    reference proxy that never does (so every reply to it is the full one:
    the path the parent took). Writes are stored straight into chosen
    replicas, as a write in flight reaches them one by one."""

    def __init__(self, n=5, quorum=3, k=40):
        self.c = Cluster(n_active=n, n_sentinent=0, quorum=quorum)
        # replies are lost here at random, round by round, to choose who
        # votes: not the silence of a dead replica, which a breaker would
        # answer by asking fewer (tests/test_tag_round_keyset.py holds that)
        cfg = AbdClientConfig(request_timeout=1.0, quorum_size=quorum,
                              breaker_threshold=10**6)
        self.client = Voters("proxy-t", self.c.net, self.c.active, cfg)
        self.reference = Voters("proxy-ref", self.c.net, self.c.active, cfg)
        self.keys = [f"key-{i:03d}" for i in range(k)]
        self.digest = sigs.key_from_set(self.keys)
        self.seq = 0
        self.down: set = set()
        for a in self.c.active:
            self.c.net.link_filters[a] = self._gate(a)

    def _gate(self, addr):
        async def gate(msg):
            return None if addr in self.down else msg
        return gate

    def node(self, addr):
        return self.c.replicas[addr]

    def store(self, key, at) -> M.ABDTag:
        self.seq += 1
        tag = M.ABDTag(self.seq, f"replica-{self.seq % 3}")
        for a in at:
            self.node(a)._store(key, tag, [self.seq])
        return tag

    def held(self, addr) -> list:
        node = self.node(addr)
        blank = (M.ABDTag(0, node.name), None)
        return [node.repository.get(k, blank)[0] for k in self.keys]

    async def round(self, cached, reachable):
        """One round of the proxy under test over `reachable`, held to the
        max over its voters' vectors looked up anew and to the reference
        proxy's answer over the same replicas."""
        self.down = set(self.c.active) - set(reachable)
        fp = sigs.tags_fingerprint(cached)
        got = await self.client.read_tags(
            self.keys, digest=self.digest, fingerprint=fp,
            cached_tags=cached)
        voters = list(self.client.voters)
        await self.c.net.quiesce()
        want = [max(col) for col in zip(*(self.held(a) for a in voters))]
        assert list(got) == want
        # the reference over the same voters and no others: a replica that
        # was reseeded answers the first proxy to name the set to it one
        # exchange later (`KeySetUnknown`, then the keys), so which replies
        # come first is no longer the same for two proxies in turn
        self.down = set(self.c.active) - set(voters)
        ref = await self.reference.read_tags(self.keys, digest=self.digest)
        await self.c.net.quiesce()
        assert sorted(self.reference.voters) == sorted(voters)
        assert list(got) == ref
        assert (got is cached) == (want == cached)
        self.down = set()
        return got


# --------------------------------------------------------------- (a) property


@pytest.mark.parametrize("seed", range(10))
def test_read_tags_equals_the_full_path_under_random_interleavings(
        seed, monkeypatch):
    """(a) Over seeded interleavings of writes in flight, rounds over
    changing quorum subsets (replies past the quorum are lost), a caller
    that takes the round's tags in wholly, partly or not at all, a reseed,
    a trimmed history and a replica struck from the trusted set:
    `read_tags` returns what the full path returns, and the caller's list
    by identity exactly when nothing differs from it."""
    monkeypatch.setattr(replica_mod, "MIN_DELTA_HISTORY", 6)
    monkeypatch.setattr(qc, "MIN_KEPT_DIFF", 6)
    rng = random.Random(seed)

    async def go():
        b = Bench()
        active = b.c.active
        for k in b.keys:
            b.store(k, active)
        cached = await b.client.read_tags(b.keys, digest=b.digest)
        await b.c.net.quiesce()
        before = votes_by_kind()
        struck = False
        for step in range(140):
            op = rng.choice(["write"] * 5 + ["round"] * 6
                            + ["burst", "reseed", "strike"])
            if op == "write":
                at = rng.sample(active, rng.randrange(1, len(active) + 1))
                b.store(rng.choice(b.keys), at)
            elif op == "burst":
                # more stores at one replica than its history keeps
                a = rng.choice(active)
                for _ in range(12):
                    b.store(rng.choice(b.keys), [a])
            elif op == "reseed":
                node = b.node(rng.choice(active))
                node._install_repository(dict(node.repository))
            elif op == "strike" and not struck and step > 60:
                struck = True
                gone = rng.choice(active)
                for _ in range(3):
                    b.client.replicas.increment_suspicion(gone)
                    b.reference.replicas.increment_suspicion(gone)
                active = [a for a in active if a != gone]
            else:
                reach = rng.sample(active, rng.randrange(3, len(active) + 1))
                got = await b.round(cached, reach)
                take = rng.choice(["all", "some", "none"])
                if got is not cached and take != "none":
                    new = list(cached)
                    for i, t in enumerate(got):
                        if t != cached[i] and (take == "all"
                                               or rng.random() < 0.5):
                            new[i] = t
                    cached = new
        for _ in range(3):   # and settled, by identity
            got = await b.round(cached, active)
            cached = list(got) if got is not cached else cached
        assert (await b.round(cached, active)) is cached
        moved = since(before, votes_by_kind())
        assert moved.get("delta", 0) > 20 and moved.get("full", 0) > 0

    run(go())


# ----------------------------------------------------------- (b) Byzantine


class Liar:
    """A credentialed Byzantine replica: it holds the MAC secret, answers
    the tag round from a repository frozen in the past, and dresses the
    answer as `craft` says. Stands at a real replica's address."""

    def __init__(self, bench: Bench, addr: str):
        self.b, self.addr = bench, addr
        self.frozen = bench.held(addr)
        self.craft = "honest_fp_delta"
        self.sent: list = []
        bench.c.net.register(addr, self.handle)

    def honest_fingerprint(self) -> bytes:
        other = next(a for a in self.b.c.active if a != self.addr)
        return sigs.tags_fingerprint(self.b.held(other))

    async def handle(self, sender, msg):
        if not isinstance(msg, M.ReadTagBatch):
            return
        reply = self.reply_to(msg)
        if reply is not None:
            self.sent.append(reply)
            self.b.c.net.send(self.addr, sender, reply)

    def delta(self, msg, positions, tags, fingerprint, base=None, sign=True):
        base = msg.base if base is None else base
        digest = self.b.digest
        sig = sigs.abd_batch_delta_signature(
            SECRET, base, fingerprint, positions,
            [sigs.tag_field(t) for t in tags], digest, msg.nonce,
        ) if sign else b""
        return M.TagBatchReply(tuple(tags), digest, sig, msg.nonce,
                               fingerprint=fingerprint, base=base,
                               positions=tuple(positions))

    def full(self, msg, tags, fingerprint):
        digest = self.b.digest
        return M.TagBatchReply(
            tuple(tags), digest,
            sigs.abd_batch_signature(SECRET, tags, digest, msg.nonce),
            msg.nonce, fingerprint=fingerprint)

    def reply_to(self, msg):
        honest_fp = self.honest_fingerprint()
        old = self.frozen
        match self.craft:
            case "honest_fp_delta" if msg.base is not None:
                # "nothing of mine moved, and I am where the honest are"
                return self.delta(msg, (), (), honest_fp)
            case "honest_fp_delta" | "honest_fp_full":
                # old tags under a well-formed MAC, the honest replicas'
                # fingerprint claimed beside them
                return self.full(msg, old, honest_fp)
            case "out_of_range":
                return self.delta(msg, (len(old),), (old[0],), honest_fp)
            case "negative":
                return self.delta(msg, (-1,), (old[0],), honest_fp)
            case "descending":
                return self.delta(msg, (3, 1), (old[3], old[1]), honest_fp)
            case "repeated":
                return self.delta(msg, (2, 2), (old[2], old[2]), honest_fp)
            case "ragged":
                return self.delta(msg, (1, 2), (old[1],), honest_fp)
            case "wrong_base":
                return self.delta(msg, (), (), honest_fp, base=honest_fp)
            case "no_mac":
                return self.delta(msg, (1,), (old[1],), honest_fp,
                                  sign=False)
            case "delta_macd_as_full":
                r = self.delta(msg, (1,), (old[1],), honest_fp, sign=False)
                return M.TagBatchReply(
                    r.tags, r.digest,
                    sigs.abd_batch_signature(SECRET, r.tags, r.digest,
                                             msg.nonce),
                    r.nonce, fingerprint=r.fingerprint, base=r.base,
                    positions=r.positions)
            case "replay":
                # last round's delta, its nonce rewritten to this round's
                r = self.sent[-1]
                return M.TagBatchReply(
                    r.tags, r.digest, r.signature, msg.nonce,
                    fingerprint=r.fingerprint, base=msg.base,
                    positions=r.positions)
        raise AssertionError(self.craft)


async def _anchored(k=16):
    """4 replicas (f = 1, quorum 3), every key written everywhere, and two
    rounds made: the proxy holds a verified vector from the first three
    and every replica remembers the state it answered from."""
    b = Bench(n=4, quorum=3, k=k)
    for key in b.keys:
        b.store(key, b.c.active)
    cached = await b.client.read_tags(b.keys, digest=b.digest)
    await b.c.net.quiesce()
    # replica-0 unreachable, so that replica-3 is anchored too
    got = await b.round(cached, b.c.active[1:])
    assert got is cached
    got = await b.round(cached, b.c.active)
    assert got is cached
    return b, cached


def test_a_liar_claiming_the_honest_fingerprint_never_moves_an_honest_delta():
    """(b) The cross-sender trap. A Byzantine replica claims the honest
    replicas' true fingerprint over deflated tags. Records are kept by
    sender, so the honest replicas' later deltas against that very
    fingerprint resolve against their own vectors, never the liar's, and
    the completed write stays in the max."""

    async def go():
        b, cached = await _anchored()
        honest = b.c.active[:3]
        liar = Liar(b, "replica-3")
        kept = b.client._kept_vectors[b.digest]
        assert set(kept.senders) == set(b.c.active)

        # w2 completes at the honest quorum; the liar hides it and claims
        # the fingerprint the honest now have. Voters: two honest + liar
        w2 = b.store(b.keys[4], honest)
        fp = sigs.tags_fingerprint(cached)
        b.down = {"replica-0"}
        got = await b.client.read_tags(b.keys, digest=b.digest,
                                       fingerprint=fp, cached_tags=cached)
        await b.c.net.quiesce()
        assert set(b.client.voters) == {"replica-1", "replica-2", "replica-3"}
        assert got[4] == w2
        honest_fp = liar.honest_fingerprint()
        assert kept.senders["replica-3"] == (honest_fp, {})   # the lie, kept
        assert kept.senders["replica-1"] == (honest_fp, {4: w2})

        # the caller has not taken w2 in yet. Same cached list again: the
        # honest answer EMPTY deltas against honest_fp, the very
        # fingerprint the liar's deflated record claims
        for _ in range(3):
            got = await b.client.read_tags(
                b.keys, digest=b.digest, fingerprint=fp, cached_tags=cached)
            await b.c.net.quiesce()
            assert "replica-3" in b.client.voters
            assert got[4] == w2, "a completed write was served stale"
            assert kept.senders["replica-1"] == (honest_fp, {4: w2})
            assert kept.senders["replica-2"] == (honest_fp, {4: w2})
            assert kept.senders["replica-3"][1] == {}
        # ... and once the caller has it, with a further write in flight
        cached2 = list(got)
        w3 = b.store(b.keys[9], honest)
        got = await b.client.read_tags(
            b.keys, digest=b.digest,
            fingerprint=sigs.tags_fingerprint(cached2), cached_tags=cached2)
        await b.c.net.quiesce()
        assert got[4] == w2 and got[9] == w3

    run(go())


@pytest.mark.parametrize("seed", range(6))
def test_a_completed_write_is_never_served_stale_past_a_lying_replica(seed):
    """(b) n = 4, f = 1: writes complete at any 3 replicas (the liar may be
    one of them and drops it); rounds count any 3 votes. Whatever the liar
    dresses its frozen vector as, the max never falls below a completed
    write's tag."""
    rng = random.Random(seed)

    async def go():
        b, cached = await _anchored()
        liar = Liar(b, "replica-3")
        honest = b.c.active[:3]
        completed = list(cached)
        for _ in range(60):
            for _ in range(rng.randrange(0, 3)):
                i = rng.randrange(len(b.keys))
                # a write quorum of 3: all honest, or two honest + the liar
                at = honest if rng.random() < 0.5 else rng.sample(honest, 2)
                completed[i] = b.store(b.keys[i], at)
                if len(at) == 2:   # the third honest replica gets it late
                    late = next(a for a in honest if a not in at)
                    if rng.random() < 0.5:
                        b.node(late)._store(b.keys[i], completed[i], [0])
            liar.craft = rng.choice(["honest_fp_delta", "honest_fp_full"])
            b.down = {rng.choice(honest)} if rng.random() < 0.7 else set()
            got = await b.client.read_tags(
                b.keys, digest=b.digest,
                fingerprint=sigs.tags_fingerprint(cached),
                cached_tags=cached)
            await b.c.net.quiesce()
            assert all(g >= c for g, c in zip(got, completed))
            if rng.random() < 0.6:
                cached = list(got)

    run(go())


@pytest.mark.parametrize("craft,reason", [
    ("out_of_range", "bad_positions"),
    ("negative", "bad_positions"),
    ("descending", "bad_positions"),
    ("repeated", "bad_positions"),
    ("ragged", "bad_positions"),
    ("wrong_base", "unknown_base"),
    ("no_mac", "bad_mac"),
    ("delta_macd_as_full", "bad_mac"),
    ("replay", "bad_mac"),
])
def test_a_malformed_delta_is_refused_counted_and_a_strike(craft, reason):
    """(b) Positions out of range, descending or repeated, a base other
    than the one this request named to this sender, a missing MAC, a delta
    MAC'd as a full reply and a replayed delta under a rewritten nonce:
    the vote is not counted, the sender earns a strike, the reason is
    counted, and the round still completes on the honest three."""

    async def go():
        b, cached = await _anchored()
        liar = Liar(b, "replica-3")
        fp = sigs.tags_fingerprint(cached)
        w = b.store(b.keys[2], b.c.active[:3])
        if craft == "replay":
            liar.craft = "honest_fp_delta"     # one accepted delta to replay
            b.down = {"replica-0"}
            await b.client.read_tags(b.keys, digest=b.digest, fingerprint=fp,
                                     cached_tags=cached)
            await b.c.net.quiesce()
            assert "replica-3" in b.client.voters
        liar.craft = craft
        b.down = set()
        before, strikes = discarded(), b.client.replicas.suspicions().get(
            "replica-3", 0)
        # the liar's reply must be looked at: it goes first
        b.client.replicas.reset(["replica-3"] + b.c.active[:3])
        got = await b.client.read_tags(b.keys, digest=b.digest,
                                       fingerprint=fp, cached_tags=cached)
        await b.c.net.quiesce()
        assert "replica-3" not in b.client.voters
        assert len(b.client.voters) == 3 and got[2] == w
        assert since(before, discarded()) == {reason: 1.0}
        assert b.client.replicas.suspicions()["replica-3"] == strikes + 1

    run(go())


def test_a_delta_to_a_round_that_named_no_base_is_refused():
    """A sender the proxy holds nothing from is owed the full reply: a
    delta from it has no record to be applied to."""

    async def go():
        b = Bench(n=4, quorum=3, k=8)
        for key in b.keys:
            b.store(key, b.c.active)
        cached = await b.client.read_tags(b.keys, digest=b.digest)
        await b.c.net.quiesce()
        liar = Liar(b, "replica-0")
        liar.craft = "wrong_base"
        before = discarded()
        got = await b.client.read_tags(
            b.keys, digest=b.digest,
            fingerprint=sigs.tags_fingerprint(cached), cached_tags=cached)
        assert got is cached and "replica-0" not in b.client.voters
        assert since(before, discarded()) == {"unknown_base": 1.0}

    run(go())


# ---------------------------------------------------------------- (c) cost


@pytest.mark.parametrize("m", [1, 4])
def test_a_delta_round_formats_and_compares_what_it_carries_not_k(
        m, monkeypatch):
    """(c) K = 600 keys, m of them written between two rounds. The proxy
    calls `tag_field` once per entry of the deltas it verified (the
    quorum's and the one that came after it), `tags_blob` never, and orders
    tags only at the positions some vote of the quorum moved."""
    calls = {"tag_field": 0, "tags_blob": 0, "lt": 0}
    real_field, real_blob = sigs.tag_field, sigs.tags_blob

    def tag_field(t):
        calls["tag_field"] += 1
        return real_field(t)

    def tags_blob(ts):
        calls["tags_blob"] += 1
        return real_blob(ts)

    real_max = max

    def counting_max(it):
        vals = list(it)
        calls["lt"] += len(vals)
        return real_max(vals)

    async def go():
        b = Bench(n=4, quorum=3, k=600)
        for key in b.keys:
            b.store(key, b.c.active)
        cached = await b.client.read_tags(b.keys, digest=b.digest)
        await b.c.net.quiesce()
        fp = sigs.tags_fingerprint(cached)
        assert (await b.client.read_tags(
            b.keys, digest=b.digest, fingerprint=fp,
            cached_tags=cached)) is cached
        await b.c.net.quiesce()
        written = {}
        for i in random.Random(m).sample(range(600), m):
            written[i] = b.store(b.keys[i], b.c.active)
        # the replicas take the writes into their vectors on another
        # proxy's round, so that what is counted below is this proxy's
        await b.reference.read_tags(b.keys, digest=b.digest)
        await b.c.net.quiesce()
        entries = metrics.value("dds_tag_round_delta_entries_total") or 0.0
        tracer.reset()
        monkeypatch.setattr(sigs, "tag_field", tag_field)
        monkeypatch.setattr(sigs, "tags_blob", tags_blob)
        monkeypatch.setattr(qc, "max", counting_max, raising=False)
        got = await b.client.read_tags(b.keys, digest=b.digest,
                                       fingerprint=fp, cached_tags=cached)
        await b.c.net.quiesce()
        monkeypatch.undo()
        assert {i: got[i] for i in written} == written
        carried = (metrics.value("dds_tag_round_delta_entries_total")
                   - entries)
        assert carried == 3 * m               # q deltas of m entries each
        # the fourth replica's delta, after the quorum, is verified and
        # kept too (PR 34): m more fields formatted, no order taken
        assert calls == {"tag_field": 4 * m, "tags_blob": 0, "lt": 3 * m}
        spans = tracer.summary()
        assert spans["abd.read_tags.verify"]["count"] == 1
        assert spans["abd.read_tags.merge"]["count"] == 1
        assert spans["abd.read_tags"]["count"] == 1

    run(go())


def test_the_rounds_spans_and_counters_tell_the_reply_kinds_apart():
    """One count per accepted vote by kind; `abd.read_tags` carries the
    kinds of its own round; verify and merge are recorded once a round."""

    async def go():
        b = Bench(n=4, quorum=3, k=10)
        for key in b.keys:
            b.store(key, b.c.active)
        before = votes_by_kind()
        cached = await b.client.read_tags(b.keys, digest=b.digest)
        fp = sigs.tags_fingerprint(cached)
        await b.client.read_tags(b.keys, digest=b.digest, fingerprint=fp,
                                 cached_tags=cached)
        b.store(b.keys[3], b.c.active)
        tracer.reset()
        await b.client.read_tags(b.keys, digest=b.digest, fingerprint=fp,
                                 cached_tags=cached)
        assert since(before, votes_by_kind()) == {
            "full": 3.0, "unchanged": 3.0, "delta": 3.0}
        (span,) = tracer.events("abd.read_tags")
        assert (span.meta["delta"], span.meta["full"],
                span.meta["unchanged"]) == (3, 0, 0)
        for name in ("abd.read_tags.verify", "abd.read_tags.merge"):
            (child,) = tracer.events(name)
            assert child.parent_id == span.span_id

    run(go())


# ------------------------------------------------------- kept state goes


def test_kept_vectors_go_with_the_sender_the_epoch_and_the_oldest_key_set():
    """A sender struck from the trusted set, a shard-map epoch change and
    a fifth key set each drop what was kept: the next round is full."""

    async def go():
        b, cached = await _anchored(k=6)
        client = b.client
        fp = sigs.tags_fingerprint(cached)
        kept = client._kept_vectors[b.digest]
        for _ in range(3):
            client.replicas.increment_suspicion("replica-2")
        await client.read_tags(b.keys, digest=b.digest, fingerprint=fp,
                               cached_tags=cached)
        assert "replica-2" not in kept.senders
        epoch = [7]
        client.shard_epoch = lambda: epoch[0]
        await client.read_tags(b.keys, digest=b.digest, fingerprint=fp,
                               cached_tags=cached)
        kept7 = client._kept_vectors[b.digest]
        assert kept7 is not kept and kept7.epoch == 7
        epoch[0] = 8
        b.store(b.keys[0], b.c.active)
        before = votes_by_kind()
        await client.read_tags(b.keys, digest=b.digest, fingerprint=fp,
                               cached_tags=cached)
        assert since(before, votes_by_kind()) == {"full": 3.0}
        for j in range(qc.MAX_TAG_ROUNDS):
            ks = b.keys[: j + 1]
            tags = await client.read_tags(ks)
            await client.read_tags(
                ks, fingerprint=sigs.tags_fingerprint(tags), cached_tags=tags)
        assert len(client._kept_vectors) == qc.MAX_TAG_ROUNDS
        assert b.digest not in client._kept_vectors

    run(go())


def _records_match_their_fingerprints(client, digest) -> None:
    kept = client._kept_vectors[digest]
    assert len(kept.senders) >= 3
    for sender, (sfp, diff) in kept.senders.items():
        vector = [diff.get(i, t) for i, t in enumerate(kept.ref)]
        assert sigs.tags_fingerprint(vector) == sfp, (
            f"{sender}: the record kept is not the vector its "
            "fingerprint names")


@pytest.mark.parametrize("moved_on", [False, True])
def test_concurrent_rounds_over_one_key_set_leave_a_consistent_record(
        moved_on):
    """Two aggregates validate at once: both rounds name the same base to
    each replica and the replies interleave; with `moved_on` the caller's
    list moves (the kept reference is re-based, as the start of a second
    round does) while the first round's replies are still to come. Either
    way each record kept is a vector its sender attested under the
    fingerprint it is kept by, and later rounds stay exact."""

    async def go():
        b, cached = await _anchored(k=12)

        async def one(tags):
            return await b.client.read_tags(
                b.keys, digest=b.digest,
                fingerprint=sigs.tags_fingerprint(tags), cached_tags=tags)

        for r in range(6):
            # replica-0 votes in every round and gets this write late
            newest = b.store(b.keys[r], b.c.active[1:])
            cached2 = list(cached)
            cached2[r] = newest
            first = asyncio.ensure_future(one(cached))
            await asyncio.sleep(0)      # requests out, replies not yet in
            b.store(b.keys[r + 6], b.c.active[:2])
            if moved_on:
                b.client._kept_for(
                    b.digest, sigs.tags_fingerprint(cached2), cached2,
                    b.client.replicas.get_trusted())
                got1 = await first
                await b.c.net.quiesce()
                _records_match_their_fingerprints(b.client, b.digest)
                got2 = await one(cached2)
            else:
                got2 = await one(cached)
                got1 = await first
            await b.c.net.quiesce()
            for got in (got1, got2):
                assert all(g >= c for g, c in zip(got, cached2))
            _records_match_their_fingerprints(b.client, b.digest)
            cached = cached2
            await b.round(cached, b.c.active)
            b.node("replica-0")._store(b.keys[r], newest, [0])

    run(go())
