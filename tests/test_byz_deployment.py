"""The deployment with one of its four replicas compromised from launch
(`attacks.at_launch`, the upstream's `intruder-attacks` / Trudy, no spare).

A tolerated fault changes no answer. What must hold: `run.launch` alone arms
the attack, once, at f victims that follow from `attacks.chaos_seed`; the
same rows and operations through a healthy deployment and through one with a
liar agree bit for bit, whichever of the four replicas lies; a write that
was acknowledged on the liar's unsigned `WriteAck` is read back while an
honest replica still lags; the liar's forged `TagBatchReply`s and replayed
`TagReply`s move no tag anybody adopts, and each refusal is counted; once
struck out the liar is sent nothing and nothing is kept for it; and what
the vote stream leaves at the supervisor stays bounded.
"""

import asyncio
import functools
import json
import os
import random

import pytest

from dds_tpu.core import messages as M
from dds_tpu.core.quorum_client import AbdClient, AbdClientConfig
from dds_tpu.core.replica import BFTABDNode, ReplicaConfig
from dds_tpu.core.supervisor import BFTSupervisor, SupervisorConfig
from dds_tpu.core.transport import InMemoryNet
from dds_tpu.obs.metrics import metrics
from dds_tpu.utils import sigs
from dds_tpu.utils.trace import tracer

from tests.test_core import run
from tests.test_tag_round_keyset import Spans, requests_sent, since
from tests.test_tcp_deployment import (MULT_MOD, SUM_MOD, _deployment_cfg,
                                       _script)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
NAMES = [f"replica-{i}" for i in range(4)]
# `random.Random(seed).sample(NAMES, 1)`: the seed that draws each victim
SEED_OF = {"replica-3": 0, "replica-1": 1, "replica-0": 2, "replica-2": 5}
REASONS = ("bad_mac", "unknown_nonce", "repeated_nonce", "wrong_phase")


def attacks() -> float:
    return metrics.value("dds_attacks_total", type="byzantine") or 0.0


def rejected() -> dict:
    return {r: metrics.value("dds_replica_rejected_total", reason=r) or 0.0
            for r in REASONS}


def votes(suspect: str) -> float:
    return metrics.value("dds_suspect_votes_total", suspect=suspect) or 0.0


def late_votes() -> float:
    return sum(metrics.value("dds_tag_round_late_votes_total", kind=k) or 0.0
               for k in ("unchanged", "delta", "full"))


def _cfg(seed: int | None = 0, enabled: bool = True):
    cfg = _deployment_cfg("memory")
    cfg.proxy.crypto_backend = "cpu"
    cfg.proxy.intranet_request_timeout = 1.0
    cfg.attacks.enabled = enabled
    if seed is not None:
        cfg.attacks.at_launch = True
        cfg.attacks.chaos_seed = seed
    return cfg


# ------------------------------------------ (a) the normal path arms the attack


@pytest.mark.parametrize("victim", sorted(SEED_OF))
def test_launch_fires_one_attack_at_the_victim_its_seed_draws(victim):
    from dds_tpu.run import launch

    async def go():
        seen = []
        for _ in range(2):                      # the same seed, twice
            before = attacks()
            dep = await launch(_cfg(SEED_OF[victim]))
            try:
                assert attacks() - before == 1
                seen.append(dep.launch_victims)
                lying = {n for n, node in dep.replicas.items()
                         if node.behavior == "byzantine"}
                assert lying == {victim}        # f = 1 of them, no more
            finally:
                await dep.stop()
        assert seen == [[victim], [victim]]

    run(go())


def test_launch_fires_nothing_unless_the_field_says_so():
    """`attacks.enabled` alone is consent, not a trigger: the hand-fired
    tests set it and choose their own moment."""
    from dds_tpu.run import launch

    async def go():
        before = attacks()
        dep = await launch(_cfg(seed=None))
        try:
            await asyncio.sleep(0.05)
            assert attacks() == before and dep.launch_victims is None
            assert {n.behavior for n in dep.replicas.values()} == {"healthy"}
        finally:
            await dep.stop()

    run(go())


def test_at_launch_without_consent_is_refused_loudly():
    from dds_tpu.run import launch

    with pytest.raises(ValueError, match="attacks.enabled"):
        run(launch(_cfg(0, enabled=False)))


@pytest.mark.parametrize("armed", [True, False])
def test_run_workload_fires_only_where_launch_has_not(armed):
    """The repo's own client driver keeps its trigger, and fires no second
    attack (two draws could name more than f victims) after an armed
    launch."""
    from dds_tpu.run import launch, run_workload

    async def go():
        cfg = _cfg(0 if armed else None)
        cfg.client.nr_of_local_clients = 0
        dep = await launch(cfg)
        try:
            before = attacks()
            assert await run_workload(dep, provider=object(), seed=3) == []
            await asyncio.sleep(0.3)            # past its 0.1 s timer
            assert attacks() - before == (0 if armed else 1)
            lying = [n for n, node in dep.replicas.items()
                     if node.behavior == "byzantine"]
            assert len(lying) == 1
        finally:
            await dep.stop()

    run(go())


def test_the_field_is_off_by_default_and_loads_from_a_file(tmp_path):
    from dds_tpu.utils.config import DDSConfig

    shipped = DDSConfig.load(os.path.join(ROOT, "configs", "default.toml"))
    assert shipped.attacks.at_launch is False is DDSConfig().attacks.at_launch
    assert shipped.attacks.enabled is False and shipped.attacks.chaos_seed == 0
    path = tmp_path / "byz.toml"
    path.write_text('[attacks]\nenabled = true\nat-launch = true\n'
                    'chaos-seed = 5\n')
    got = DDSConfig.load(path).attacks
    assert got.at_launch and got.enabled and got.chaos_seed == 5


# --------------------------------- (b) the same answers, whoever of the four lies


@pytest.fixture(scope="module")
def healthy():
    return run(_script("memory", _cfg(seed=None, enabled=False)))


@pytest.mark.parametrize("victim", sorted(SEED_OF))
def test_a_liar_changes_no_answer(healthy, victim):
    """replica-3 and replica-2 are the coordinators the supervisor's
    freshest half names to the proxy, replica-0 and replica-1 are met as
    participants only: both ways of meeting the liar are held."""
    before = votes(victim)
    said, model = run(_script("memory", _cfg(SEED_OF[victim])))
    well, _ = healthy
    for op in ("PutSet", "GetSet", "WriteElement", "SumAll", "MultAll"):
        assert said[op] and all(st == 200 for st, _ in said[op]), op
        assert said[op] == well[op], op                 # bit for bit

    def fold(col, mod):
        return functools.reduce(lambda a, b: a * b % mod,
                                (int(r[col]) for r in model["rows"]), 1)

    assert int(json.loads(said["SumAll"][-1][1])["result"]) == fold(2, SUM_MOD)
    assert int(json.loads(said["MultAll"][-1][1])["result"]) == fold(
        3, MULT_MOD)
    assert len({body for _, body in said["SumAll"]}) >= 4
    assert votes(victim) > before           # it lied, and was seen lying


# ------------------------------------------------- a cluster taken apart


class Cluster:
    """Four replicas, a supervisor and one proxy-side client on an
    `InMemoryNet`; `liar` answers as `core/replica._byzantine` does."""

    def __init__(self, liar: str = "replica-3"):
        self.net = InMemoryNet()
        rcfg = ReplicaConfig(quorum_size=3)
        self.nodes = {n: BFTABDNode(n, NAMES, "supervisor", self.net, rcfg)
                      for n in NAMES}
        self.sup = BFTSupervisor("supervisor", NAMES, [], self.net,
                                 SupervisorConfig(
                                     quorum_size=3,
                                     proactive_recovery_enabled=False))
        self.abd = AbdClient("proxy-0", self.net, NAMES, AbdClientConfig(
            quorum_size=3, request_timeout=1.0))
        self.liar = liar
        self.nodes[liar].behavior = "byzantine"
        self.honest = [n for n in NAMES if n != liar]
        self.sent_to_liar: list = []

        async def watch(msg):
            self.sent_to_liar.append(msg)
            return msg

        self.net.link_filters[liar] = watch

    def through(self, coordinator: str) -> None:
        self.abd._preferred = [coordinator]

    def stored(self, name: str, key: str):
        return self.nodes[name].repository.get(key, (None, None))


# --------------- (c) acknowledged on the liar's word, read back all the same


@pytest.mark.parametrize("lagging", NAMES[:3])
def test_a_write_acked_with_the_liars_ack_is_read_back(lagging):
    """The liar acks a `Write` it never stores, unsigned like every
    `WriteAck`, so a write can close on two honest replicas and the liar.
    Whichever honest replica is the one that lags, a read returns the
    write: through another coordinator, and through the laggard itself."""

    async def go():
        c = Cluster()
        hold = asyncio.Event()

        async def held(msg):
            if isinstance(msg, M.Write):
                await hold.wait()
            return msg

        first = next(n for n in c.honest if n != lagging)
        c.through(first)
        await c.abd.write_set("K", ["old"])
        await c.net.quiesce()
        c.net.link_filters[lagging] = held
        _, tag = await c.abd.write_set_tagged("K", ["new"])
        # the quorum of three was two honest replicas and the liar
        assert c.stored(lagging, "K")[1] == ["old"]
        assert c.stored(c.liar, "K")[1] is None
        assert [c.stored(n, "K")[1] for n in c.honest
                if n != lagging] == [["new"], ["new"]]
        for coordinator in (first, lagging):
            c.through(coordinator)
            value, got = await c.abd.fetch_set_tagged("K")
            assert value == ["new"] and got == tag
        assert c.stored(lagging, "K")[1] == ["old"]     # still lagging
        hold.set()
        await c.net.quiesce()
        assert c.stored(lagging, "K") == (tag, ["new"])
        assert c.abd.replicas.suspicions()[c.liar] == 0  # never a coordinator

    run(go())


# ------------------------- (d) forged tags move nothing, and each is counted


def test_replayed_tag_replies_move_no_tag_a_coordinator_mints():
    async def go():
        c = Cluster()
        c.through("replica-1")
        _, t1 = await c.abd.write_set_tagged("K", [1])
        await c.net.quiesce()
        before, voted = rejected(), votes(c.liar)
        with Spans("supervisor.handle") as handled:
            _, t2 = await c.abd.write_set_tagged("K", [2])
            await c.net.quiesce()
        # the next tag after the honest quorum's, whatever the liar said
        assert (t2.seq, t2.id) == (t1.seq + 1, "replica-1")
        for n in c.honest:
            assert c.stored(n, "K") == (t2, [2])
        # four replays refused, one vote each, one message each at the
        # supervisor, which tallies them and can never act: one voter
        assert since(before, rejected()) == {"bad_mac": 4}
        assert votes(c.liar) - voted == 4
        assert [s.meta["msg"] for s in handled] == ["Suspect"] * 4
        assert c.sup.quorum == {c.liar: {"replica-1"}}
        assert not metrics.value("dds_suspicion_quorums_total",
                                 replica=c.liar)

    run(go())


def test_a_forged_read_reply_moves_no_read():
    async def go():
        c = Cluster()
        c.through("replica-2")
        _, tag = await c.abd.write_set_tagged("K", ["row"])
        await c.net.quiesce()
        before = rejected()
        assert await c.abd.fetch_set_tagged("K") == (["row"], tag)
        await c.net.quiesce()
        assert since(before, rejected()) == {"bad_mac": 1}

    run(go())


@pytest.mark.parametrize("liar", ["replica-0", "replica-3"])
def test_forged_tag_batch_replies_move_no_tag_the_proxy_adopts(liar):
    """Inflated tags under an empty signature, twice a round. Asked first
    (replica-0) the liar answers before the quorum and is struck for each;
    asked last (replica-3) it answers a round that is over, and a late vote
    that fails is dropped. Either way the proxy adopts the honest tags."""

    async def go():
        c = Cluster(liar)
        c.through(c.honest[0])
        keys = [f"k{i}" for i in range(40)]
        tags = [(await c.abd.write_set_tagged(k, [k]))[1] for k in keys]
        await c.net.quiesce()
        late = late_votes()
        fp = sigs.tags_fingerprint(tags)
        for _ in range(2):
            got = await c.abd.read_tags(keys, fingerprint=fp, cached_tags=tags)
            await c.net.quiesce()
            assert got is tags                      # nothing moved
        _, moved = await c.abd.write_set_tagged(keys[7], ["again"])
        await c.net.quiesce()
        got = await c.abd.read_tags(keys, fingerprint=fp, cached_tags=tags)
        assert got == tags[:7] + [moved] + tags[8:]
        assert max(got).seq < 1 << 30               # no inflated tag
        strikes = c.abd.replicas.suspicions()[liar]
        assert strikes >= 3 if liar == "replica-0" else strikes == 0
        digest = sigs.key_from_set(keys)
        assert liar not in c.abd._kept_vectors[digest].senders
        assert liar not in c.abd._keyset_holders[digest]
        assert late_votes() - late <= 2 * 3         # honest late votes only

    run(go())


# --------------------------- (e) struck out: sent nothing, nothing kept of it


def test_once_struck_out_the_liar_is_asked_nothing_and_nothing_is_kept():
    async def go():
        c = Cluster()
        c.through(c.liar)           # a coordinator the supervisor named
        for i in range(3):          # three bare replies, three strikes
            with pytest.raises(Exception):
                await c.abd.write_set(f"x{i}", [i])
        assert c.liar not in c.abd.replicas.get_trusted()
        assert (metrics.value("dds_coordinator_violations_total",
                              node=c.liar) or 0) >= 3
        keys = [f"k{i}" for i in range(24)]
        tags = [(await c.abd.write_set_tagged(k, [k]))[1] for k in keys]
        await c.net.quiesce()
        # every point operation now goes through an honest coordinator,
        # which still asks the liar: that is the honest replicas' business
        c.sent_to_liar.clear()
        late, sent = late_votes(), requests_sent()
        fp = sigs.tags_fingerprint(tags)
        for _ in range(4):
            assert await c.abd.read_tags(
                keys, fingerprint=fp, cached_tags=tags) is tags
            await c.net.quiesce()
        assert not [m for m in c.sent_to_liar
                    if isinstance(m, (M.ReadTagBatch, M.Envelope))]
        # a round is three requests, and from the second on none carries
        assert since(sent, requests_sent()) == {"named": 9.0, "carried": 3.0}
        # quorum 3 of 3 trusted: nobody is left to be late
        assert late_votes() == late
        digest = sigs.key_from_set(keys)
        assert set(c.abd._kept_vectors[digest].senders) == set(c.honest)
        assert c.abd._keyset_holders[digest] == set(c.honest)
        assert not c.abd._late_tags

    run(go())


# ------------------------------ (f) the vote stream leaves nothing that grows


def test_ten_thousand_votes_leave_the_supervisor_bounded():
    """Four `Suspect`s a write, for as long as the liar is there: a day of
    this deployment is tens of millions of votes. What the supervisor keeps
    of them is the last `MAX_VOTE_NONCES` nonces and one set of voters a
    suspect."""

    async def go():
        net = InMemoryNet()
        sup = BFTSupervisor("supervisor", NAMES, [], net, SupervisorConfig(
            quorum_size=3, proactive_recovery_enabled=False))
        cap = sup.MAX_VOTE_NONCES
        assert 1000 <= cap < 10_000
        nonces = [sigs.generate_nonce() for _ in range(10_000)]
        for i, nonce in enumerate(nonces):
            await sup.handle(f"replica-{i % 2}", M.Suspect("replica-3", nonce))
        assert len(sup.nonces) == cap
        assert sup.quorum == {"replica-3": {"replica-0", "replica-1"}}
        assert not sup._pending and not sup._manifest_collects
        # a vote seen lately is still refused when it comes again: the
        # third voter's replayed vote is not a second vote
        await sup.handle("replica-2", M.Suspect("replica-2", nonces[-1]))
        assert "replica-2" not in sup.quorum
        assert len(sup.nonces) == cap

    run(go())


@pytest.mark.parametrize("reason", REASONS)
def test_a_refused_message_is_counted_once_by_its_reason(reason):
    """`dds_replica_rejected_total{reason}`: one count a message, from the
    branches of `_healthy` that refuse one."""

    async def go():
        net = InMemoryNet()
        node = BFTABDNode("replica-0", NAMES, "supervisor", net,
                          ReplicaConfig(quorum_size=3))
        secret, nonce = node.cfg.abd_mac_secret, sigs.generate_nonce()
        tag = M.ABDTag(3, "replica-1")
        good = sigs.abd_signature(secret, [1], tag, nonce)
        msg = {
            "bad_mac": M.Write(tag, "K", [1], b"", nonce),
            "unknown_nonce": M.Write(tag, "K", [1], good, nonce),
            "repeated_nonce": M.Read("K", nonce),
            "wrong_phase": M.TagReply(tag, "K", [1], good, nonce),
        }[reason]
        if reason == "repeated_nonce":
            await node.handle("replica-1", M.Read("K", nonce))
        if reason == "wrong_phase":     # a TagReply to a read's nonce
            await node.handle("proxy-0", M.Envelope(
                M.IRead("K"), nonce, sigs.proxy_signature(
                    node.cfg.proxy_mac_secret, "K", nonce)))
        before, voted = rejected(), votes("replica-1")
        events = []
        tracer.subscribe(events.append)
        try:
            await node.handle("replica-1", msg)
        finally:
            tracer.unsubscribe(events.append)
        assert since(before, rejected()) == {reason: 1}
        assert votes("replica-1") - voted == 1
        vote = next(e for e in events if e.name == "replica.suspect")
        assert vote.meta["reason"] == reason
        assert vote.meta["msg"] == type(msg).__name__
        assert "K" not in node.repository or node.repository["K"][1] is None

    run(go())


# ------------- (e) the batched read (IReadBatch): the liar keeps lying, and
# every refusal of a batch message is counted and voted on like its kin's


def test_a_liar_in_a_batch_forges_every_entry_and_moves_none():
    """As a participant the liar answers `ReadBatch` as it answers `Read`:
    a random tag and garbage per key under a bad signature. One message
    refused (`bad_mac`), one vote, and every key's answer is the honest
    quorum's."""

    async def go():
        c = Cluster()
        c.through("replica-2")
        tags = {}
        for k in ("A", "B", "C"):
            _, tags[k] = await c.abd.write_set_tagged(k, [k])
        await c.net.quiesce()
        before, voted = rejected(), votes(c.liar)
        out = await c.abd.fetch_sets_attributed(["A", "B", "C"])
        await c.net.quiesce()
        assert out == [([k], tags[k], "replica-2") for k in ("A", "B", "C")]
        assert since(before, rejected()) == {"bad_mac": 1}
        assert votes(c.liar) - voted == 1
        asked = [m for m in c.sent_to_liar if isinstance(m, M.ReadBatch)]
        assert len(asked) == 1 and asked[0].keys == ("A", "B", "C")
        assert c.abd.replicas.suspicions()[c.liar] == 0

    run(go())


@pytest.mark.parametrize("lagging", NAMES[:3])
def test_a_batch_written_back_with_the_liars_ack_is_read_back(lagging):
    """The liar acks a `WriteBatch` it never stores. The write-back can
    close on the liar and two honest replicas, one of them the
    coordinator, whose own copy the write-back has then repaired: the
    value is at two honest replicas, and every later quorum of three
    holds one of them."""

    async def go():
        c = Cluster()
        first = next(n for n in c.honest if n != lagging)
        c.through(first)
        _, tag = await c.abd.write_set_tagged("K", ["new"])
        await c.abd.write_set("L", ["same"])
        await c.net.quiesce()
        # one honest replica trails on K: the batch's quorum disagrees
        c.nodes[lagging].repository["K"] = (M.ABDTag(0, lagging), None)
        out = await c.abd.fetch_sets_attributed(["K", "L"])
        await c.net.quiesce()
        assert [r[:2] for r in out][0] == (["new"], tag)
        acks = [m for m in c.sent_to_liar if isinstance(m, M.WriteBatch)]
        assert len(acks) == 1 and [e.key for e in acks[0].entries] == ["K"]
        assert c.stored(c.liar, "K")[1] is None          # acked, not stored
        assert all(c.stored(n, "K") == (tag, ["new"]) for n in c.honest)
        for coordinator in c.honest:
            c.through(coordinator)
            assert await c.abd.fetch_set_tagged("K") == (["new"], tag)

    run(go())


def test_a_liar_coordinating_a_batch_is_struck_and_the_batch_goes_elsewhere():
    """As a coordinator the liar answers `IReadBatch` with its bare reply:
    a protocol violation, a strike, and the retry reads through an honest
    one."""
    from dds_tpu.core.errors import ByzUnknownReplyError

    async def go():
        c = Cluster()
        c.through("replica-1")
        _, tag = await c.abd.write_set_tagged("K", ["row"])
        c.through(c.liar)
        with pytest.raises(ByzUnknownReplyError):
            await c.abd.fetch_sets_attributed(["K"])
        assert c.abd.replicas.suspicions()[c.liar] == 1
        c.through("replica-1")
        assert await c.abd.fetch_sets_attributed(["K"]) == [
            (["row"], tag, "replica-1")]

    run(go())


def _batch_refusals():
    """(reason, case) -> (the message a healthy replica must refuse, what
    it was sent first); built per test, so that the nonce is fresh."""
    cfg = ReplicaConfig(quorum_size=3)
    secret, psecret = cfg.abd_mac_secret, cfg.proxy_mac_secret
    nonce = sigs.generate_nonce()
    tag = M.ABDTag(3, "replica-1")
    good = M.BatchEntry(tag, "K", [1], sigs.abd_signature(secret, [1], tag, nonce))
    forged = M.BatchEntry(tag, "K", [1], b"")
    iread = M.Envelope(M.IRead("K"), nonce,
                       sigs.proxy_signature(psecret, "K", nonce))
    ibatch = M.Envelope(M.IReadBatch(("K",)), nonce, sigs.proxy_signature(
        psecret, sigs.key_from_set(["K"]), nonce))
    asked = [("replica-1", M.ReadBatch(("K",), nonce))]
    return {
        ("bad_mac", "ReadBatchReply"): (
            M.ReadBatchReply((good, forged), nonce), [("proxy-0", ibatch)]),
        ("bad_mac", "WriteBatch"): (M.WriteBatch((forged,), nonce), asked),
        ("unknown_nonce", "ReadBatchReply"): (
            M.ReadBatchReply((good,), nonce), []),
        ("unknown_nonce", "WriteBatch"): (M.WriteBatch((good,), nonce), []),
        ("unknown_nonce", "WriteBatchAck"): (M.WriteBatchAck(nonce), []),
        ("repeated_nonce", "ReadBatch"): (M.ReadBatch(("K",), nonce), asked),
        ("wrong_phase", "ReadBatchReply_to_a_single_read"): (
            M.ReadBatchReply((good,), nonce), [("proxy-0", iread)]),
        ("wrong_phase", "WriteBatchAck_to_a_single_read"): (
            M.WriteBatchAck(nonce), [("proxy-0", iread)]),
        ("wrong_phase", "ReadReply_to_a_batch"): (
            M.ReadReply(tag, "K", [1], good.signature, nonce),
            [("proxy-0", ibatch)]),
        ("wrong_phase", "WriteAck_to_a_batch"): (
            M.WriteAck("K", nonce), [("proxy-0", ibatch)]),
        ("wrong_phase", "ReadBatchReply_for_other_keys"): (
            M.ReadBatchReply((M.BatchEntry(
                tag, "other", [1], good.signature),), nonce),
            [("proxy-0", ibatch)]),
        ("wrong_phase", "WriteBatchAck_before_the_read_quorum"): (
            M.WriteBatchAck(nonce), [("proxy-0", ibatch)]),
    }


@pytest.mark.parametrize("reason,case", sorted(_batch_refusals()))
def test_a_refused_batch_message_is_counted_and_voted_on_like_its_kin(
        reason, case):
    """`ReadBatchReply` is refused where `ReadReply` is, `WriteBatch` where
    `Write` is, `WriteBatchAck` where `WriteAck` is (one count by reason,
    one vote naming the class), a single read's replies do not answer a
    batch's nonce nor a batch's a single read's, and nothing is stored."""

    async def go():
        net = InMemoryNet()
        node = BFTABDNode("replica-0", NAMES, "supervisor", net,
                          ReplicaConfig(quorum_size=3))
        msg, first = _batch_refusals()[reason, case]
        for sender, m in first:
            await node.handle(sender, m)
        before, voted = rejected(), votes("replica-1")
        events = []
        tracer.subscribe(events.append)
        try:
            await node.handle("replica-1", msg)
        finally:
            tracer.unsubscribe(events.append)
        assert since(before, rejected()) == {reason: 1}
        assert votes("replica-1") - voted == 1
        vote = next(e for e in events if e.name == "replica.suspect")
        assert vote.meta["reason"] == reason
        assert vote.meta["msg"] == type(msg).__name__
        assert "K" not in node.repository or node.repository["K"][1] is None
        assert "other" not in node.repository

    run(go())
