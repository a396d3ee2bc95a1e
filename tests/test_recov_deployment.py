"""The upstream's own deployment with its proactive recovery on: nine
endpoints, two of them asleep at any instant, quorum 5, the oldest active
replica rejuvenated through a spare on a timer (`ref8col-bft9-recov`).

A rotation changes no answer. What must hold while the supervisor swaps
replicas under the proxy: every `SumAll` is the plain fold of the rows as
written and every acknowledged write is read back, a write acknowledged
while a swap is in flight included and through every coordinator, the
promoted spare among them; the membership history is a function of the
configuration (`attacks.chaos_seed` draws the spare); the reseed that takes
its chunks in as they arrive keeps and refuses exactly the entries the
whole-state install kept and refused; a replica that slept and was woken is
found by the proxy's probe and taught the key set once; and no callback of
a rotation holds the one loop for long.
"""

import asyncio
import functools
import gc
import json
import random
import time

import pytest

from dds_tpu.core import messages as M
from dds_tpu.core.replica import (BFTABDNode, ReplicaConfig,
                                  verified_manifest)
from dds_tpu.core.supervisor import BFTSupervisor, SupervisorConfig
from dds_tpu.core.transport import InMemoryNet
from dds_tpu.obs.metrics import metrics
from dds_tpu.utils import sigs
from dds_tpu.utils.retry import CircuitBreaker
from dds_tpu.utils.trace import tracer

from tests.test_core import run
from tests.test_tcp_deployment import SUM_MOD

pytestmark = pytest.mark.recovery

NAMES = [f"replica-{i}" for i in range(9)]
K = 64
SECRET = ReplicaConfig().abd_mac_secret


def _cfg(interval: float | None, seed: int = 0):
    """n = 9, two spares, quorum 5; `interval` None leaves the rotations
    to the test (`dep.supervisor.recover`)."""
    from dds_tpu.utils.config import DDSConfig

    cfg = DDSConfig()
    cfg.replicas.endpoints = list(NAMES)
    cfg.replicas.sentinent = NAMES[7:]
    cfg.replicas.byz_quorum_size = 5
    cfg.replicas.byz_max_faults = 2
    cfg.recovery.enabled = interval is not None
    cfg.recovery.warm_up = cfg.recovery.interval = interval or 7.0
    if interval is not None:
        # anti-entropy keeps its pace against the rotation (5 +- 2 s
        # against 7 s): what a reseed refused is repaired before the
        # replica is woken again
        cfg.recovery.anti_entropy_interval = 0.7 * interval
        cfg.recovery.anti_entropy_jitter = 0.3 * interval
    cfg.proxy.port = 0
    cfg.proxy.crypto_backend = "cpu"
    cfg.proxy.replica_refresh_interval = 0.1
    cfg.proxy.intranet_request_timeout = 1.0
    cfg.proxy.breaker_reset = 0.1
    cfg.proxy.breaker_probe_timeout = 0.1
    cfg.attacks.chaos_seed = seed
    return cfg


def _rows(k: int = K):
    rng = random.Random(f"recov/{k}")
    return [[str(i), "x", str(rng.randrange(2, SUM_MOD)), "3", "y", "z", "w",
             None] for i in range(k)]


def _fold(rows) -> int:
    """The plain reference: python ints, nothing of the program."""
    return functools.reduce(lambda a, b: a * b % SUM_MOD,
                            (int(r[2]) for r in rows), 1)


class Rest:
    def __init__(self, cfg, dep):
        self.host, self.port = cfg.proxy.host, dep.server.cfg.port

    async def call(self, method, target, body=None):
        from dds_tpu.http.miniserver import http_request

        st, raw = await http_request(
            self.host, self.port, method, target,
            None if body is None else json.dumps(body).encode())
        return st, raw.decode()

    async def load(self, rows):
        keys = []
        for r in rows:
            st, body = await self.call("POST", "/PutSet", {"contents": r})
            assert st == 200, (st, body)
            keys.append(body)
        return keys

    async def sum_all(self):
        st, body = await self.call("GET", f"/SumAll?position=2&nsqr={SUM_MOD}")
        return st, (int(json.loads(body)["result"]) if st == 200 else body)

    async def update(self, key, value):
        return (await self.call("PUT", f"/WriteElement/{key}?position=2",
                                {"value": value}))[0]

    async def get(self, key):
        st, body = await self.call("GET", f"/GetSet/{key}")
        return st, (json.loads(body)["contents"] if st == 200 else body)


def _recovers(into: list):
    def on_record(rec):
        if rec.name == "supervisor.recover":
            into.append(dict(rec.meta))
    return on_record


# ------------------------------------------- a rotation changes no answer


async def _story() -> dict:
    """Reads, updates and `SumAll`s through REST while the supervisor
    rotates every 0.3 s, for at least six rotations; then a write
    acknowledged while a swap is in flight, read back through every
    coordinator once the swap is over."""
    from dds_tpu.run import launch

    from dds_tpu.obs.watchtower import watchtower

    cfg = _cfg(0.3)
    rotations: list = []
    on_record = _recovers(rotations)
    tracer.subscribe(on_record)
    watchtower.reset()     # what it knows of these keys is another store's
    dep = await launch(cfg)
    rest, rows, rng = Rest(cfg, dep), _rows(), random.Random(45)
    seen = {"wrong": [], "steps": 0, "rotations": rotations}
    try:
        keys = await rest.load(rows)
        t0 = time.perf_counter()
        while len(rotations) < 7 and time.perf_counter() - t0 < 60:
            i, new = rng.randrange(K), str(rng.randrange(2, SUM_MOD))
            if await rest.update(keys[i], new) == 200:
                rows[i][2] = new
            else:
                seen["wrong"].append("update refused")
            if await rest.sum_all() != (200, _fold(rows)):
                seen["wrong"].append("SumAll")
            if await rest.get(keys[i]) != (200, rows[i]):
                seen["wrong"].append("GetSet")
            seen["steps"] += 1
        # a write acknowledged while `supervisor.recover` is in flight
        sup = dep.supervisor
        t0 = time.perf_counter()
        while sup._idle.is_set() and time.perf_counter() - t0 < 5:
            await asyncio.sleep(0.002)
        seen["in_flight"] = not sup._idle.is_set()
        i, new = 7, str(rng.randrange(2, SUM_MOD))
        seen["ack"] = await rest.update(keys[i], new)
        seen["still_in_flight"] = not sup._idle.is_set()
        rows[i][2] = new
        await sup.stop()                  # no further rotation
        assert await sup.wait_recovery_idle()
        abd = dep.server.abd
        active = [a for a, _ in sup.active]
        seen["active"], seen["promoted"] = active, rotations[-1]["seeder"]
        abd.replicas.merge(active)
        seen["read_back"] = {}
        for coordinator in active:
            abd._preferred = [coordinator]
            got, _, who = await abd.fetch_set_attributed(keys[i])
            seen["read_back"][coordinator] = (who, got == rows[i])
        seen["sum_after"] = await rest.sum_all() == (200, _fold(rows))
        seen["verdicts"] = [v.invariant for v in watchtower.verdicts()]
        seen["audited"] = watchtower.stats()["ops_audited"]
    finally:
        await dep.stop()
        tracer.unsubscribe(on_record)
    return seen


@pytest.fixture(scope="module")
def story():
    return run(_story())


def test_every_answer_under_six_rotations_is_exact(story):
    assert story["wrong"] == []
    assert len(story["rotations"]) >= 6 and story["steps"] >= 6
    assert all(r["verified"] for r in story["rotations"])
    assert story["sum_after"]
    # the auditor that watches every committed operation's tags and
    # quorums saw nothing go back, whoever was asleep
    assert story["verdicts"] == [] and story["audited"] > story["steps"]


def test_the_oldest_active_replica_goes_and_a_spare_comes(story):
    victims = [r["victim"] for r in story["rotations"]]
    # the seven that were active at launch go in the order of their age,
    # then the first spare promoted
    assert victims[:7] == NAMES[:7]
    assert story["rotations"][0]["seeder"] in NAMES[7:]
    for r in story["rotations"]:
        # a spare may lack the entries its own reseed refused (a write in
        # flight while the manifests were signed) until anti-entropy has
        # repaired them: a few, at rotations twenty times the upstream's
        assert r["seeder"] != r["victim"] and K // 2 < r["keys"] <= K


def test_a_write_acknowledged_during_a_swap_is_read_back_everywhere(story):
    assert story["in_flight"] and story["ack"] == 200
    assert len(story["active"]) == 7
    assert story["promoted"] in story["active"]
    for coordinator, (who, same) in story["read_back"].items():
        assert who == coordinator and same, coordinator


# --------------------------- the membership history is the configuration's


async def _history(seed: int, n: int = 6) -> list:
    from dds_tpu.run import launch

    rotations: list = []
    on_record = _recovers(rotations)
    tracer.subscribe(on_record)
    dep = await launch(_cfg(0.03, seed))
    try:
        t0 = time.perf_counter()
        while len(rotations) < n and time.perf_counter() - t0 < 30:
            await asyncio.sleep(0.01)
    finally:
        await dep.stop()
        tracer.unsubscribe(on_record)
    return [(r["victim"], r["seeder"]) for r in rotations[:n]]


def test_the_same_seed_gives_the_same_victims_and_seeders():
    first, again = run(_history(0)), run(_history(0))
    assert len(first) == 6 and first == again
    others = [run(_history(seed)) for seed in (1, 2, 3)]
    assert any(o != first for o in others)
    # the victim is never drawn: the oldest active replica goes
    for h in [first] + others:
        assert [v for v, _ in h] == NAMES[:6]


# ------------------- chunk by chunk keeps what the whole-state install kept


def _entry(tag, value):
    return {"tag": [tag.seq, tag.id], "value": value}


def _whole_state_install(digests, support, chunks, total):
    """What `_try_complete_recovery` did before the chunks were taken in
    as they came: nothing until every chunk is there, then every entry
    against the settled manifests."""
    if set(range(total)) - set(chunks):
        return None
    verified = verified_manifest(digests, support, SECRET)
    kept, refused = {}, []
    for seq in range(total):
        for key, e in chunks[seq].items():
            try:
                tag = M.ABDTag(int(e["tag"][0]), str(e["tag"][1]))
                value = e["value"]
            except (KeyError, TypeError, ValueError, IndexError):
                refused.append(key)
                continue
            if verified.get(key) == (tag.seq, tag.id,
                                     sigs.value_digest(value)):
                kept[key] = (tag, value)
            else:
                refused.append(key)
    return kept, sorted(refused)


def _seed_material(n_keys=40, chunk=8):
    """An honest state, six signers' manifests of it, and its chunks."""
    state = {f"key-{i:03d}": (M.ABDTag(1 + i % 3, f"replica-{i % 7}"),
                              [i, f"row-{i}", "9" * 40])
             for i in range(n_keys)}
    manifest = {k: [t.seq, t.id, sigs.value_digest(v)]
                for k, (t, v) in state.items()}
    digests = []
    for signer in NAMES[1:7]:
        nonce = 77
        sig = sigs.manifest_signature(SECRET, signer, manifest, nonce)
        digests.append([signer, manifest, nonce, sig.hex()])
    items = sorted(state.items())
    chunks = {seq: {k: _entry(t, v) for k, (t, v) in items[at:at + chunk]}
              for seq, at in enumerate(range(0, n_keys, chunk))}
    return state, digests, chunks


def _forged_value(chunks):
    chunks[1]["key-009"] = dict(chunks[1]["key-009"], value=["tampered"])


def _forged_tag(chunks):
    chunks[2]["key-017"] = dict(chunks[2]["key-017"], tag=[1 << 20, "trudy"])
    chunks[2]["FORGED-KEY"] = _entry(M.ABDTag(1 << 20, "trudy"), ["evil"])


def _malformed(chunks):
    chunks[0]["key-001"] = {"tag": ["x"], "value": 1}
    chunks[3]["key-030"] = {"value": [1]}


def _missing_chunk(chunks):
    del chunks[3]


CASES = {
    "honest": (lambda chunks: None, "in_order"),
    "forged_value": (_forged_value, "in_order"),
    "forged_tag": (_forged_tag, "in_order"),
    "malformed": (_malformed, "in_order"),
    "missing_chunk": (_missing_chunk, "in_order"),
    "out_of_order": (_forged_value, "reversed"),
    "chunks_before_header": (_forged_tag, "header_last"),
    "header_in_the_middle": (_forged_value, "header_middle"),
}


async def _reseed(case: str):
    tamper, order = CASES[case]
    state, digests, chunks = _seed_material()
    total = len(chunks)
    tamper(chunks)
    net = InMemoryNet()
    node = BFTABDNode("replica-0", NAMES, "supervisor", net)
    node._store("stale-key", M.ABDTag(9, "replica-0"), ["old"])
    complied: list = []

    async def supervisor(sender, msg):
        complied.append(type(msg).__name__)

    net.register("supervisor", supervisor)
    session = 4242
    frames = [M.StateChunk(session, seq, entries, "recovery", (1000 + seq,))
              for seq, entries in sorted(chunks.items())]
    begin = M.SleepBegin(digests, session, total, 3, [5, 6])
    if order == "reversed":
        frames = [begin] + frames[::-1]
    elif order == "header_last":
        frames = frames + [begin]
    elif order == "header_middle":
        frames = frames[:2] + [begin] + frames[2:]
    else:
        frames = [begin] + frames
    for f in frames:
        net.send("supervisor", "replica-0", f)
    await net.quiesce()
    want = _whole_state_install(digests, 3, chunks, total)
    return node, want, complied, state


@pytest.mark.parametrize("case", sorted(CASES))
def test_the_chunked_install_keeps_and_refuses_what_the_whole_one_did(case):
    node, want, complied, state = run(_reseed(case))
    if want is None:
        # a chunk never came: nothing installed, nobody told; asleep from
        # the header on, voting in nobody's quorum with what it has
        assert node.behavior == "sentinent" and complied == []
        assert set(node.repository) == {"stale-key"}
        return
    kept, refused = want
    assert node.behavior == "sentinent" and complied == ["Complying"]
    assert node.repository == kept
    assert "stale-key" not in node.repository
    assert set(kept) <= set(state) and all(
        kept[k] == state[k] for k in kept)        # nothing forged is kept
    if case != "honest":
        assert len(kept) < len(state)
    # the index built beside the repository is the one a rebuild gives
    from dds_tpu.core.antientropy import MerkleIndex

    fresh = MerkleIndex()
    fresh.rebuild(kept)
    assert node.merkle.root() == fresh.root()
    assert node.merkle.manifest() == fresh.manifest()
    # the nonces came with the chunks and with the header
    assert {5, 6, 1000, 1001} <= set(node.incoming)


def test_the_seeded_entries_are_counted_by_what_the_quorum_said():
    before = {o: metrics.value("dds_recovery_seeded_entries_total",
                               outcome=o) or 0.0
              for o in ("accepted", "rejected")}
    node, (kept, refused), _, _ = run(_reseed("forged_tag"))
    after = {o: metrics.value("dds_recovery_seeded_entries_total",
                              outcome=o) or 0.0
             for o in ("accepted", "rejected")}
    assert after["accepted"] - before["accepted"] == len(kept)
    assert after["rejected"] - before["rejected"] == len(refused) == 2


def test_a_second_session_racing_the_first_installs_one_state_whole():
    """Two reseeds interleaved frame by frame: the one whose last chunk
    lands first puts the replica to sleep with ITS state, whole; what is
    left of the other is never taken in."""

    async def go():
        state, digests, chunks = _seed_material()
        other = {k: (M.ABDTag(t.seq + 10, t.id), v + ["later"])
                 for k, (t, v) in state.items()}
        manifest = {k: [t.seq, t.id, sigs.value_digest(v)]
                    for k, (t, v) in other.items()}
        digests_b = [[s, manifest, 78, sigs.manifest_signature(
            SECRET, s, manifest, 78).hex()] for s in NAMES[1:7]]
        items = sorted(other.items())
        chunks_b = {seq: {k: _entry(t, v) for k, (t, v) in items[at:at + 8]}
                    for seq, at in enumerate(range(0, len(items), 8))}
        net = InMemoryNet()
        node = BFTABDNode("replica-0", NAMES, "supervisor", net)
        complied: list = []

        async def supervisor(sender, msg):
            complied.append(msg)

        net.register("supervisor", supervisor)
        a = [M.SleepBegin(digests, 1, len(chunks), 3, [])] + [
            M.StateChunk(1, s, e) for s, e in sorted(chunks.items())]
        b = [M.SleepBegin(digests_b, 2, len(chunks_b), 3, [])] + [
            M.StateChunk(2, s, e) for s, e in sorted(chunks_b.items())]
        for fa, fb in zip(a, b):
            net.send("supervisor", "replica-0", fa)
            net.send("supervisor", "replica-0", fb)
        await net.quiesce()
        return node, complied, state, other

    node, complied, state, other = run(go())
    assert node.behavior == "sentinent" and len(complied) == 1
    assert node.repository in (state, other)
    assert node._recovery_sessions == {} or all(
        s.begin is not None for s in node._recovery_sessions.values())


def test_what_was_written_since_the_kill_outlives_the_reseed():
    """Between `Kill` and the last chunk the replica is sent writes like
    anybody: the one it acknowledged awake, before the header, and the one
    it stored asleep, after it, are newer than the seed and stay; what it
    held before the `Kill` does not."""

    async def go():
        state, digests, chunks = _seed_material()
        net = InMemoryNet()
        node = BFTABDNode("replica-0", NAMES, "supervisor", net)
        node._store("before-kill", M.ABDTag(9, "replica-0"), ["gone"])
        said: list = []

        async def peer(sender, msg):
            said.append(type(msg).__name__)

        net.register("supervisor", peer)
        net.register("replica-1", peer)
        secret = node.cfg.abd_mac_secret

        def write(key, seq, value, nonce):
            tag = M.ABDTag(seq, "replica-1")
            return M.Write(tag, key, value,
                           sigs.abd_signature(secret, value, tag, nonce),
                           nonce)

        net.send("supervisor", "replica-0", M.Kill())
        await net.quiesce()
        node.incoming[31] = False                 # its ReadTag was seen
        net.send("replica-1", "replica-0", write("key-003", 50, ["a"], 31))
        await net.quiesce()
        awake = (node.behavior, list(said))
        frames = [M.SleepBegin(digests, 5, len(chunks), 3, [])] + [
            M.StateChunk(5, s, e) for s, e in sorted(chunks.items())]
        for f in frames[:3]:
            net.send("supervisor", "replica-0", f)
        await net.quiesce()
        asleep = node.behavior
        net.send("replica-1", "replica-0", write("key-020", 60, ["b"], 32))
        net.send("replica-1", "replica-0", write("new-key", 1, ["c"], 33))
        net.send("replica-1", "replica-0", M.Read("key-001", 34))
        await net.quiesce()
        for f in frames[3:]:
            net.send("supervisor", "replica-0", f)
        await net.quiesce()
        return node, state, awake, asleep, said

    node, state, awake, asleep, said = run(go())
    assert awake == ("healthy", ["WriteAck"]) and asleep == "sentinent"
    assert said == ["WriteAck", "Complying"]      # asleep: no ack, no reply
    assert node.behavior == "sentinent" and node._since_kill is None
    want = dict(state)
    want["key-003"] = (M.ABDTag(50, "replica-1"), ["a"])
    want["key-020"] = (M.ABDTag(60, "replica-1"), ["b"])
    want["new-key"] = (M.ABDTag(1, "replica-1"), ["c"])
    assert node.repository == want and "before-kill" not in want
    from dds_tpu.core.antientropy import MerkleIndex

    fresh = MerkleIndex()
    fresh.rebuild(want)
    assert node.merkle.root() == fresh.root()


def test_anti_entropy_waits_for_the_reseed_and_then_runs_at_once():
    """Emptied by a `Kill`, a replica pulls nothing (every key would read
    as stale and be fetched into a repository about to be replaced); the
    reseed that refused an entry starts its next round at once."""

    async def go():
        state, digests, chunks = _seed_material()
        _forged_value(chunks)
        net = InMemoryNet()
        nodes = {a: BFTABDNode(a, NAMES[:3], "supervisor", net)
                 for a in NAMES[:3]}
        for a in NAMES[1:3]:
            for k, (t, v) in state.items():
                nodes[a]._store(k, t, v)
        node = nodes["replica-0"]
        net.register("supervisor", lambda s, m: asyncio.sleep(0))
        node.antientropy.configure(interval=0.05, jitter=0.0,
                                   rng=random.Random(1))
        node.antientropy.start()
        net.send("supervisor", "replica-0", M.Kill())
        await asyncio.sleep(0.25)              # four rounds' worth of timer
        pulled_while_empty = (node.antientropy.rounds, len(node.repository),
                              node.reseeding)
        node.antientropy.configure(interval=30.0)
        await asyncio.sleep(0.06)              # the loop now waits 30 s
        frames = [M.SleepBegin(digests, 5, len(chunks), 3, [])] + [
            M.StateChunk(5, s, e) for s, e in sorted(chunks.items())]
        for f in frames:
            net.send("supervisor", "replica-0", f)
        await net.quiesce()
        seeded = len(node.repository)
        await asyncio.sleep(0.2)
        await net.quiesce()
        await node.antientropy.stop()
        return node, state, pulled_while_empty, seeded

    node, state, pulled_while_empty, seeded = run(go())
    assert pulled_while_empty == (0, 0, True)
    assert seeded == len(state) - 1 and not node.reseeding
    # the hole is repaired by the round the reseed asked for, 30 s early
    assert node.antientropy.rounds >= 1
    assert {k: v for k, (_, v) in node.repository.items()} == {
        k: v for k, (_, v) in state.items()}


def test_a_kill_in_the_middle_of_a_reseed_ends_it():
    async def go():
        _, digests, chunks = _seed_material()
        net = InMemoryNet()
        node = BFTABDNode("replica-0", NAMES, "supervisor", net)
        net.register("supervisor", lambda s, m: asyncio.sleep(0))
        net.send("supervisor", "replica-0",
                 M.SleepBegin(digests, 9, len(chunks), 3, []))
        for seq, e in sorted(chunks.items())[:2]:
            net.send("supervisor", "replica-0", M.StateChunk(9, seq, e))
        net.send("supervisor", "replica-0", M.Kill())
        await net.quiesce()
        for seq, e in sorted(chunks.items())[2:]:
            net.send("supervisor", "replica-0", M.StateChunk(9, seq, e))
        await net.quiesce()
        return node

    node = run(go())
    assert node.behavior == "healthy" and node.repository == {}


# ----------------------------- the woken spare's answer, chunked and whole


def test_a_woken_spare_streams_its_state_and_the_legacy_form_stands():
    async def go(verified: bool):
        net = InMemoryNet()
        nodes = {a: BFTABDNode(a, NAMES, "supervisor", net) for a in NAMES}
        for a in NAMES[7:]:
            nodes[a].behavior = "sentinent"
        for n in nodes.values():
            for i in range(100):
                n._store(f"k{i}", M.ABDTag(1, "replica-0"), [i])
            n.incoming.update({n_: True for n_ in range(50)})
        sup = BFTSupervisor("supervisor", NAMES[:7], NAMES[7:], net,
                            SupervisorConfig(
                                quorum_size=5, state_chunk_keys=16,
                                proactive_recovery_enabled=False,
                                verified_transfer=verified),
                            rng=random.Random(0))
        seen: list = []
        for a in NAMES:
            inner = nodes[a].handle

            async def spy(sender, msg, inner=inner, a=a):
                seen.append((a, msg))
                await inner(sender, msg)

            net.unregister(a)
            net.register(a, spy)
        await sup.recover("replica-0")
        return nodes, sup, seen

    nodes, sup, seen = run(go(True))
    to_victim = [m for a, m in seen if a == "replica-0"]
    chunks = [m for m in to_victim if isinstance(m, M.StateChunk)]
    assert len(chunks) == 7 and all(c.kind == "recovery" for c in chunks)
    assert sum(len(c.entries) for c in chunks) == 100
    assert all(len(c.entries) <= 16 for c in chunks)
    assert sum(len(c.nonces) for c in chunks) == 50
    begin = next(m for m in to_victim if isinstance(m, M.SleepBegin))
    assert begin.total == 7 and begin.nonces == []
    awake = [m for a, m in seen if isinstance(m, M.Awake)]
    assert len(awake) == 1 and awake[0].chunk_keys == 16
    assert nodes["replica-0"].behavior == "sentinent"
    assert len(nodes["replica-0"].repository) == 100
    assert set(range(50)) <= set(nodes["replica-0"].incoming)
    # `verified_transfer` off: one `Awake()`, one `State`, one `Sleep`
    nodes, sup, seen = run(go(False))
    assert [m for _, m in seen if isinstance(m, M.Awake)] == [M.Awake()]
    sleeps = [m for a, m in seen if isinstance(m, M.Sleep)]
    assert len(sleeps) == 1 and len(sleeps[0].data) == 100
    assert len(sleeps[0].nonces) == 50
    assert not any(isinstance(m, (M.StateChunk, M.SleepBegin))
                   for _, m in seen)
    assert nodes["replica-0"].behavior == "sentinent"


def test_the_new_fields_cross_the_wire_and_an_older_frame_still_reads():
    for msg in (M.Awake(7, 256), M.Awake(), M.State({}, [], 7, 32),
                M.StateChunk(7, 3, {"k": _entry(M.ABDTag(1, "r"), [1])},
                             "state", (1, 2, 3))):
        assert M.loads(M.dumps(msg)) == msg
    old = {"__msg__": "StateChunk", "session": 1, "seq": 0, "entries": {}}
    assert M.from_dict(old) == M.StateChunk(1, 0, {})
    assert M.from_dict({"__msg__": "Awake"}) == M.Awake()
    assert M.from_dict({"__msg__": "State", "data": {}, "nonces": [1]}) == \
        M.State({}, [1])


# ------------------------- slept, woken, probed, answered, taught the keys once


def _counts() -> dict:
    out = {k: metrics.value("dds_tag_round_requests_total", keys=k) or 0.0
           for k in ("named", "carried")}
    out["answered"] = metrics.value("dds_breaker_probes_total",
                                    outcome="answered") or 0.0
    out["silent"] = metrics.value("dds_breaker_probes_total",
                                  outcome="silent") or 0.0
    out["joined"] = metrics.value("dds_membership_changes_total",
                                  kind="joined") or 0.0
    out["skipped"] = metrics.value("dds_tag_round_skipped_total") or 0.0
    return out


async def _sleeper_story() -> dict:
    """The rotations are the test's own: replica-0 is put to sleep, found
    silent, probed; woken again three rotations later, found by its probe
    and taught the key set once."""
    from dds_tpu.run import launch

    from dds_tpu.obs.watchtower import watchtower

    cfg = _cfg(None)
    watchtower.reset()
    dep = await launch(cfg)
    rest, rows = Rest(cfg, dep), _rows()
    abd, sup = dep.server.abd, dep.supervisor
    seen: dict = {"wrong": 0}

    async def rounds(n):
        for _ in range(n):
            if await rest.sum_all() != (200, _fold(rows)):
                seen["wrong"] += 1

    async def since(before, what):
        now = _counts()
        seen[what] = {k: now[k] - before[k] for k in now}

    try:
        await rest.load(rows)
        await rounds(3)
        before = _counts()
        await sup.recover("replica-0")           # replica-0 sleeps
        first = next(a for a, _ in sup.active if a in NAMES[7:])
        await asyncio.sleep(0.25)                # a refresh: the spare joins
        await rounds(20)
        await asyncio.sleep(0.35)                # a probe, unanswered
        await since(before, "asleep")
        seen["asleep_breaker"] = abd.breaker_states().get("replica-0")
        seen["sentinent_answers_probe"] = (
            metrics.value("dds_breaker_probes_total", outcome="answered")
            or 0.0) - before["answered"]
        # wake replica-0: recover until the supervisor draws it
        for victim in NAMES[1:7]:
            if "replica-0" in (a for a, _ in sup.active):
                break
            await sup.recover(victim)
        seen["woken"] = "replica-0" in (a for a, _ in sup.active)
        seen["behavior"] = dep.replicas["replica-0"].behavior
        before = _counts()
        t0 = time.perf_counter()
        while (abd.breakers["replica-0"].state != CircuitBreaker.CLOSED
               and time.perf_counter() - t0 < 5.0):
            await asyncio.sleep(0.01)
        seen["closed_after_s"] = time.perf_counter() - t0
        others = _counts()
        to_it: list = []

        async def watch(msg):
            if isinstance(msg, M.ReadTagBatch) and msg.count:
                to_it.append(len(msg.keys))
            return msg

        dep.net.link_filters[(abd.addr, "replica-0")] = watch
        await rounds(12)
        seen["to_it"] = to_it
        await since(others, "awake")
        seen["first_spare"] = first
    finally:
        await dep.stop()
    return seen


@pytest.fixture(scope="module")
def sleeper():
    return run(_sleeper_story())


def test_a_sleeping_replica_is_found_by_its_silence_and_never_waited_for(
        sleeper):
    assert sleeper["wrong"] == 0
    asleep = sleeper["asleep"]
    assert sleeper["asleep_breaker"] != CircuitBreaker.CLOSED
    # a `sentinent` replica answers no probe
    assert sleeper["sentinent_answers_probe"] == 0 and asleep["silent"] >= 1
    # asked until its breaker opened, then skipped: once a round
    assert 0 < asleep["skipped"] < 20
    # the promoted spare joined by the refresh and was taught the keys once
    assert asleep["joined"] == 1
    assert asleep["carried"] == 1


def test_a_woken_replica_is_probed_answers_and_is_carried_the_keys_once(
        sleeper):
    assert sleeper["woken"] and sleeper["behavior"] == "healthy"
    assert sleeper["closed_after_s"] < 2.0
    awake = sleeper["awake"]
    # every round asks it again; the key set travels in one of them
    assert len(sleeper["to_it"]) >= 12
    assert sorted(sleeper["to_it"], reverse=True)[:2] == [K, 0]
    assert sleeper["to_it"].count(K) == 1
    assert awake["carried"] <= 2       # it, and a spare woken just before


# --------------------------------- no callback of a rotation holds the loop

HOLD_K, HOLD_LIMIT_MS = 4096, 100.0


def test_no_callback_of_a_rotation_holds_the_loop_for_long():
    """K = 4,096 rows of 2 KB on the CPU: the whole-state install held the
    loop some 190 ms here (one `StateChunk` callback: six manifests
    verified and tallied, K values digested twice, the index rebuilt);
    taken in a signer and a chunk at a time the longest callback of a
    rotation is a manifest answered or tallied, some 15 ms."""
    import asyncio.events as events

    async def go():
        net = InMemoryNet()
        nodes = {a: BFTABDNode(a, NAMES, "supervisor", net) for a in NAMES}
        for a in NAMES[7:]:
            nodes[a].behavior = "sentinent"
        rng = random.Random(1)
        rows = []
        for i in range(HOLD_K):
            row = [i, "A" * 44, str(rng.getrandbits(4096)),
                   str(rng.getrandbits(1024)), "B" * 44, "C" * 44, None]
            rows.append((sigs.key_from_set(row), row))
        tag = M.ABDTag(1, "replica-0")
        for n in nodes.values():
            for k, row in rows:
                n._store(k, tag, row)
            n.incoming.update({rng.getrandbits(63): True
                               for _ in range(5000)})
        sup = BFTSupervisor("supervisor", NAMES[:7], NAMES[7:], net,
                            SupervisorConfig(
                                quorum_size=5,
                                proactive_recovery_enabled=False),
                            rng=random.Random(3))
        held: list = []
        inner = events.Handle._run

        def timed(self):
            t0 = time.perf_counter()
            try:
                return inner(self)
            finally:
                held.append((time.perf_counter() - t0) * 1e3)

        gc.collect()
        gc.disable()
        events.Handle._run = timed
        try:
            await asyncio.sleep(0)
            del held[:]
            await sup.recover("replica-0")
            await net.quiesce()
        finally:
            events.Handle._run = inner
            gc.enable()
        return held, nodes["replica-0"]

    held, victim = run(go())
    assert victim.behavior == "sentinent"
    assert len(victim.repository) == HOLD_K == len(victim.merkle)
    assert max(held) < HOLD_LIMIT_MS, sorted(held)[-5:]
