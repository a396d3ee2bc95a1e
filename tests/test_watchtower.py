"""Watchtower auditing, SLO engine, and perf-sentry tests.

Unit layer: synthetic traces fed through a private Tracer must produce
exactly the expected verdicts (dropped-ack quorums, stale tags, illegal
breaker transitions, non-converging repairs) and NO verdicts on clean
shapes. End-to-end layer: a seeded ChaosNet cluster with a Trudy-style
forging coordinator MUST yield the tag_monotonicity + quorum_intersection
verdicts with the offending trace_id and a flight incident, while the
identical schedule without the attack audits clean. Plus: SLO burn math
on a fake clock, the `GET /slo` route, sentry baseline round-trip and the
CLI's non-zero exit on a synthetically-inflated kernel timing.
"""

import asyncio
import json
import os
import random
import subprocess
import sys
import time

import pytest

from dds_tpu.core import messages as M
from dds_tpu.core.chaos import ChaosNet, LinkFaults
from dds_tpu.core.quorum_client import AbdClient, AbdClientConfig
from dds_tpu.core.replica import BFTABDNode, ReplicaConfig
from dds_tpu.core.transport import InMemoryNet
from dds_tpu.http.miniserver import http_request
from dds_tpu.http.server import DDSRestServer, ProxyConfig
from dds_tpu.obs import sentry
from dds_tpu.obs.flight import flight
from dds_tpu.obs.slo import RouteSlo, SloEngine
from dds_tpu.obs.watchtower import Watchtower
from dds_tpu.utils import sigs
from dds_tpu.utils.trace import Tracer, tracer

pytestmark = pytest.mark.audit

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run(coro):
    return asyncio.run(coro)


def make_wt(**kw):
    kw.setdefault("quorum_size", 5)
    kw.setdefault("n_replicas", 7)
    wt = Watchtower(**kw)
    t = Tracer()
    wt.attach(t)
    return wt, t


def commit_op(t, name, key, seq, tid, read_replicas=(), write_replicas=(),
              coordinator="replica-0", op=None):
    """Synthesize one committed quorum op trace: root -> abd span (ok,
    tagged) -> replica.handle children per phase."""
    with t.span(f"http.{name}"):
        with t.span(
            "abd.write" if name == "write" else "abd.fetch",
            coordinator=coordinator, ok=True,
            op=op or ("write" if name == "write" else "read"),
            key=key, seq=seq, tag_id=tid,
        ):
            for r in read_replicas:
                with t.span("replica.handle", replica=r,
                            msg="ReadTag" if name == "write" else "Read",
                            key=key):
                    pass
            for r in write_replicas:
                with t.span("replica.handle", replica=r, msg="Write", key=key):
                    pass


R7 = [f"replica-{i}" for i in range(7)]


# ------------------------------------------------------------ unit: quorum


def test_clean_write_trace_audits_without_verdicts():
    wt, t = make_wt()
    commit_op(t, "write", "k1", 1, "replica-0",
              read_replicas=R7[:5], write_replicas=R7[1:6])
    assert wt.verdicts() == []
    assert wt.stats()["traces_audited"] == 1
    assert wt.stats()["ops_audited"] == 1


def test_dropped_ack_quorum_is_flagged():
    wt, t = make_wt()
    # coordinator answered after only 2 Write handlers: a forged quorum
    commit_op(t, "write", "k1", 1, "replica-0",
              read_replicas=R7[:5], write_replicas=R7[:2])
    vs = wt.verdicts()
    assert [v.invariant for v in vs] == ["quorum_intersection"]
    assert any("write_phase=2<5" in p for p in vs[0].detail["problems"])


def test_quorum_intersection_bound_is_checked():
    wt, t = make_wt()
    # both phases reach quorum size but share only 2 < 2q-n = 3 replicas
    # (physically impossible with n=7 honest replicas — exactly what the
    # auditor exists to notice)
    extra = [f"replica-{i}" for i in range(7, 10)]
    commit_op(t, "write", "k1", 1, "replica-0",
              read_replicas=R7[:5], write_replicas=R7[3:5] + extra)
    vs = wt.verdicts()
    assert [v.invariant for v in vs] == ["quorum_intersection"]
    assert any("intersection=2<3" in p for p in vs[0].detail["problems"])


def test_read_fast_path_skips_write_phase_legally():
    wt, t = make_wt()
    commit_op(t, "read", "k1", 1, "replica-0", read_replicas=R7[:5])
    assert wt.verdicts() == []


def commit_batch(t, reads, read_replicas=(), write_replicas=(),
                 coordinator="replica-0"):
    """One committed batched read: root -> abd.fetch_batch (ok, its
    `reads` facts per key) -> replica.handle children per phase."""
    with t.span("http.SumAll"):
        with t.span("abd.fetch_batch", k=len(reads), coordinator=coordinator,
                    ok=True, op="read",
                    reads=[[k, seq, tid] for k, seq, tid in reads]):
            for r in read_replicas:
                with t.span("replica.handle", replica=r, msg="ReadBatch"):
                    pass
            for r in write_replicas:
                with t.span("replica.handle", replica=r, msg="WriteBatch"):
                    pass


@pytest.mark.parametrize("case,reads,writes,problems", [
    ("settled", R7[:5], (), []),
    ("written_back", R7[:5], R7[2:7], []),
    ("no_read_quorum", R7[:3], (), ["read_phase=3<5"]),
    ("forged_answer", (), (), ["read_phase=0<5"]),
    ("write_back_short", R7[:5], R7[:2], ["write_phase=2<5"]),
    ("phases_apart", R7[:5], R7[3:5] + ["replica-7", "replica-8",
                                        "replica-9"], ["intersection=2<3"]),
])
def test_a_batched_reads_quorums_are_audited_once_for_all_its_keys(
        case, reads, writes, problems):
    wt, t = make_wt()
    commit_batch(t, [("k1", 1, "replica-0"), ("k2", 4, "replica-3")],
                 read_replicas=reads, write_replicas=writes)
    vs = wt.verdicts()
    assert [v.invariant for v in vs] == (
        ["quorum_intersection"] if problems else [])
    for v in vs:
        assert v.detail["op"] == "abd.fetch_batch"
        assert all(any(p in q for q in v.detail["problems"])
                   for p in problems)
    assert wt.stats()["ops_audited"] == 2   # one op a key


@pytest.mark.parametrize("stale_key", ["k1", "k2", None])
def test_every_key_of_a_batch_is_held_to_its_own_tag_history(stale_key):
    """A batched read that returns a key at a tag below one committed
    before it began is `tag_monotonicity` for THAT key, as a single
    read's would be; the other keys of the batch are clean."""
    wt, t = make_wt(check_quorum=False)
    commit_op(t, "write", "k1", 5, "replica-1")
    commit_op(t, "write", "k2", 7, "replica-2")
    time.sleep(0.002)
    seqs = {"k1": 5, "k2": 7}
    if stale_key:
        seqs[stale_key] = 2
    commit_batch(t, [("k1", seqs["k1"], "replica-1"),
                     ("k2", seqs["k2"], "replica-2")],
                 coordinator="replica-6")
    vs = wt.verdicts()
    if stale_key is None:
        assert vs == []
    else:
        assert [v.invariant for v in vs] == ["tag_monotonicity"]
        assert vs[0].detail["key"] == stale_key
        assert vs[0].detail["tag"][0] == 2
        assert vs[0].detail["coordinator"] == "replica-6"
    assert wt.stats()["ops_audited"] == 4


def test_a_batch_without_its_facts_audits_nothing_and_flags_nothing():
    wt, t = make_wt(check_quorum=False)
    with t.span("abd.fetch_batch", k=2, coordinator="replica-0"):
        pass                      # a failed attempt: no `ok`, never a commit
    with t.span("abd.fetch_batch", k=2, ok=True, reads=[["k", "x"], 7, None]):
        pass                      # facts of no shape are skipped, not raised on
    assert wt.verdicts() == [] and wt.stats()["ops_audited"] == 0


# ------------------------------------------------------- unit: tag ordering


def test_tag_monotonicity_across_traces():
    wt, t = make_wt(check_quorum=False)
    commit_op(t, "write", "k", 2, "replica-1")
    time.sleep(0.005)  # strict real-time order between the two commits
    commit_op(t, "read", "k", 1, "replica-0")
    vs = wt.verdicts()
    assert [v.invariant for v in vs] == ["tag_monotonicity"]
    assert vs[0].detail["tag"] == [1, "replica-0"]
    assert vs[0].detail["prior_tag"] == [2, "replica-1"]
    assert vs[0].trace_id is not None


def test_duplicate_tag_mint_is_flagged():
    wt, t = make_wt(check_quorum=False)
    commit_op(t, "write", "k", 3, "replica-1")
    time.sleep(0.005)
    commit_op(t, "write", "k", 3, "replica-1")
    vs = wt.verdicts()
    assert [v.invariant for v in vs] == ["tag_monotonicity"]
    assert vs[0].detail["violation_kind"] == "duplicate_mint"


def test_forward_tags_and_other_keys_stay_clean():
    wt, t = make_wt(check_quorum=False)
    commit_op(t, "write", "k", 1, "replica-0")
    time.sleep(0.002)
    commit_op(t, "write", "k", 2, "replica-1")
    time.sleep(0.002)
    commit_op(t, "read", "k", 2, "replica-1")
    commit_op(t, "write", "other", 1, "replica-0")
    assert wt.verdicts() == []


def test_read_sees_latest_within_one_trace():
    wt, t = make_wt(check_quorum=False)
    with t.span("http.GET.agg"):
        with t.span("abd.write", coordinator="replica-0", ok=True, op="write",
                    key="k", seq=5, tag_id="replica-0"):
            pass
        time.sleep(0.005)
        with t.span("abd.fetch", coordinator="replica-1", ok=True, op="read",
                    key="k", seq=4, tag_id="replica-1"):
            pass
    vs = wt.verdicts()
    assert [v.invariant for v in vs] == ["read_sees_latest"]
    assert vs[0].detail["read_tag"] == [4, "replica-1"]


# ------------------------------------------------- unit: state machines


def test_breaker_half_open_requires_open():
    wt, t = make_wt()
    t.event("breaker.open", target="replica-1")
    t.event("breaker.half_open", target="replica-1")
    t.event("breaker.closed", target="replica-1")
    assert wt.verdicts() == []
    t.event("breaker.half_open", target="replica-2")  # closed -> half_open
    vs = wt.verdicts()
    assert [v.invariant for v in vs] == ["breaker_legality"]
    assert vs[0].detail["transition"] == "closed->half_open"


def test_suspicion_excluded_coordinator_must_not_commit():
    wt, t = make_wt(check_quorum=False)
    for _ in range(3):
        t.event("abd.coordinator_violation", node="replica-3")
    time.sleep(0.005)
    commit_op(t, "read", "k", 1, "replica-0", coordinator="replica-3")
    vs = wt.verdicts()
    assert [v.invariant for v in vs] == ["suspicion_legality"]
    assert vs[0].detail["coordinator"] == "replica-3"


def test_repair_convergence_checks_installed_vs_advertised():
    wt, t = make_wt()
    with t.span("antientropy.sync", replica="replica-0"):
        t.event("audit.repair", replica="replica-0", peer="replica-1",
                key="good", src_seq=4, src_id="a", seq=4, tag_id="a")
        t.event("audit.repair", replica="replica-0", peer="replica-1",
                key="bad", src_seq=9, src_id="z", seq=3, tag_id="a")
    vs = wt.verdicts()
    assert [v.invariant for v in vs] == ["repair_convergence"]
    assert vs[0].detail["key"] == "bad"
    assert vs[0].detail["advertised"] == [9, "z"]
    assert vs[0].detail["installed"] == [3, "a"]


# --------------------------------------------------- e2e: clusters + attacks


class StaleForgerNode(BFTABDNode):
    """Trudy-style coordinator: holds the real proxy MAC secret and
    answers reads with a properly-signed FORGED stale (tag, value) —
    undetectable to the client's cryptographic checks, detectable only by
    auditing the committed tag sequence."""

    forged_tag = (1, "forged")
    forged_value = ["stale"]
    forging = True

    async def _healthy(self, sender, msg):
        match msg:
            case M.Envelope(M.IRead(key), nonce, _sig) if self.forging:
                tag = M.ABDTag(*self.forged_tag)
                challenge = nonce + self.cfg.nonce_increment
                sig = sigs.proxy_signature(
                    self.cfg.proxy_mac_secret, key, challenge,
                    [self.forged_value, sigs.tag_payload(tag)],
                )
                self._send(sender, M.Envelope(
                    M.IReadReply(key, self.forged_value, tag=tag),
                    challenge, sig,
                ))
            case _:
                await super()._healthy(sender, msg)


class CheatingCoordinator(BFTABDNode):
    """Answers a write instantly with a valid proxy MAC — no quorum ever
    ran. The client cannot tell; the trace can."""

    async def _healthy(self, sender, msg):
        match msg:
            case M.Envelope(M.IWrite(key, _v), nonce, _sig):
                self._seq_floor += 1
                tag = M.ABDTag(self._seq_floor, self.name)
                challenge = nonce + self.cfg.nonce_increment
                sig = sigs.proxy_signature(
                    self.cfg.proxy_mac_secret, key, challenge,
                    sigs.tag_payload(tag),
                )
                self._send(sender, M.Envelope(
                    M.IWriteReply(key, tag=tag), challenge, sig,
                ))
            case _:
                await super()._healthy(sender, msg)


def _chaos_cluster(seed, special_cls=None, special_addr="replica-6"):
    net = ChaosNet(InMemoryNet(), seed=seed)
    net.default_faults = LinkFaults(delay=0.001, jitter=0.002)
    replicas = {}
    for a in R7:
        cls = special_cls if (special_cls and a == special_addr) else BFTABDNode
        replicas[a] = cls(a, R7, "supervisor", net,
                          ReplicaConfig(quorum_size=5))
    client = AbdClient(
        "proxy-0", net, R7,
        AbdClientConfig(request_timeout=2.0, quorum_size=5),
    )
    client.replicas._rng = random.Random(5)
    return net, client, replicas


async def _forged_tag_schedule(seed, attack: bool):
    """Two honest writes, then a read steered through replica-6. With
    `attack` the read is served a forged stale tag; without, replica-6
    answers honestly — the identical schedule minus the forgery."""
    net, client, replicas = _chaos_cluster(
        seed, special_cls=StaleForgerNode
    )
    replicas["replica-6"].forging = attack
    others = tuple(a for a in R7 if a != "replica-6")
    try:
        await client.write_set("KEY", ["v1"], )
        await client.write_set("KEY", ["v2"], )
        await asyncio.sleep(0.01)  # strict real-time order before the read
        value, tag, coord = await client.fetch_set_attributed(
            "KEY", exclude=others
        )
        assert coord == "replica-6"
        if attack:
            assert value == ["stale"] and tag.seq == 1  # the forgery landed
        else:
            assert value == ["v2"]
        await net.quiesce()
    finally:
        await net.stop()


def test_forged_tag_under_chaos_yields_exact_verdicts(tmp_path):
    """Acceptance: seeded ChaosNet + forging coordinator -> the auditor
    reports tag_monotonicity (stale committed tag) AND quorum_intersection
    (no read quorum ever served the forged reply), both carrying the
    offending read's trace_id, and files flight incidents with the trace."""
    wt = Watchtower(quorum_size=5, n_replicas=7)
    wt.attach(tracer)
    flight.configure(dir=str(tmp_path), min_interval=0.0)
    try:
        run(_forged_tag_schedule(seed=21, attack=True))
    finally:
        flight.configure(dir="")
        wt.detach()
    vs = wt.verdicts()
    by_inv = {v.invariant: v for v in vs}
    assert set(by_inv) == {"tag_monotonicity", "quorum_intersection"}
    mono = by_inv["tag_monotonicity"]
    assert mono.detail["key"] == "KEY"
    assert mono.detail["tag"] == [1, "forged"]
    assert mono.detail["coordinator"] == "replica-6"
    # both verdicts blame the SAME offending trace: the forged read
    assert mono.trace_id is not None
    assert by_inv["quorum_intersection"].trace_id == mono.trace_id

    incidents = sorted(tmp_path.glob("incident-*audit_tag_monotonicity*.jsonl"))
    assert incidents
    lines = [json.loads(l) for l in open(incidents[0])]
    header = lines[0]
    assert header["trace_id"] == mono.trace_id
    trace_lines = [l for l in lines[1:] if l.get("section") == "trace"]
    assert any(l["name"] == "abd.fetch" for l in trace_lines)
    # the index names the incident without globbing
    idx = [json.loads(l) for l in open(tmp_path / "index.jsonl")]
    assert any(e["kind"] == "audit_tag_monotonicity"
               and e["trace_id"] == mono.trace_id for e in idx)


async def _forged_batch_schedule(seed, attack: bool):
    """The schedule above with the read an aggregate's batched re-read:
    two keys written twice each, then one `IReadBatch` steered through
    replica-6, Trudy's `StaleTagForger`."""
    from dds_tpu.malicious.trudy import StaleTagForger

    net, client, replicas = _chaos_cluster(seed, special_cls=StaleTagForger)
    replicas["replica-6"].forging = attack
    others = tuple(a for a in R7 if a != "replica-6")
    try:
        for k in ("KA", "KB"):
            await client.write_set(k, ["v1"])
            await client.write_set(k, ["v2"])
        await asyncio.sleep(0.01)
        out = await client.fetch_sets_attributed(["KA", "KB"], exclude=others)
        assert {r[2] for r in out} == {"replica-6"}
        if attack:
            assert [r[0] for r in out] == [["stale"], ["stale"]]
        else:
            assert [r[0] for r in out] == [["v2"], ["v2"]]
        await net.quiesce()
    finally:
        await net.stop()


@pytest.mark.parametrize("attack", [True, False])
def test_a_forged_batched_read_yields_a_verdict_a_key(attack):
    """The auditor reads a batch's per-key facts: a forging coordinator's
    stale tags are `tag_monotonicity` once for EACH key of the batch and
    `quorum_intersection` once for the round no quorum served, all under
    the batch's trace; the same schedule served honestly audits clean."""
    wt = Watchtower(quorum_size=5, n_replicas=7)
    wt.attach(tracer)
    try:
        run(_forged_batch_schedule(seed=23, attack=attack))
    finally:
        wt.detach()
    vs = wt.verdicts()
    if not attack:
        assert vs == []
        assert wt.stats()["ops_audited"] >= 6      # four writes, two reads
        return
    mono = [v for v in vs if v.invariant == "tag_monotonicity"]
    assert sorted(v.detail["key"] for v in mono) == ["KA", "KB"]
    assert all(v.detail["tag"] == [1, "forged"]
               and v.detail["coordinator"] == "replica-6" for v in mono)
    quorum = [v for v in vs if v.invariant == "quorum_intersection"]
    assert len(quorum) == 1 and quorum[0].detail["op"] == "abd.fetch_batch"
    assert {v.trace_id for v in vs} == {mono[0].trace_id}
    assert len(vs) == 3


def test_identical_schedule_without_attack_is_clean():
    wt = Watchtower(quorum_size=5, n_replicas=7)
    wt.attach(tracer)
    try:
        run(_forged_tag_schedule(seed=21, attack=False))
    finally:
        wt.detach()
    assert wt.verdicts() == []
    assert wt.stats()["traces_audited"] >= 3  # both writes + the read


def test_dropped_ack_quorum_e2e():
    """A committed write whose coordinator never ran a quorum -> exactly
    one quorum_intersection verdict."""
    wt = Watchtower(quorum_size=5, n_replicas=7)
    wt.attach(tracer)
    try:
        async def go():
            net, client, _ = _chaos_cluster(9, special_cls=CheatingCoordinator)
            # force the cheater to coordinate: strike every other replica
            # out of the trusted set for this client
            for a in R7:
                if a != "replica-6":
                    for _ in range(3):
                        client.replicas.increment_suspicion(a)
            try:
                await client.write_set("Q", ["v"])
                await net.quiesce()
            finally:
                await net.stop()

        run(go())
    finally:
        wt.detach()
    vs = [v for v in wt.verdicts() if v.invariant == "quorum_intersection"]
    assert len(vs) == 1
    assert vs[0].detail["key"] == "Q"
    assert vs[0].detail["read_phase"] == [] and vs[0].detail["write_phase"] == []


def test_clean_chaos_run_zero_violations_property():
    """Property: a clean seeded-chaos run (no attack) audits every trace
    and yields ZERO violations."""
    wt = Watchtower(quorum_size=5, n_replicas=7)
    wt.attach(tracer)
    try:
        async def go():
            net, client, _ = _chaos_cluster(33)
            rng = random.Random(4)
            try:
                keys = [f"pk-{i}" for i in range(4)]
                for i in range(12):
                    k = rng.choice(keys)
                    if rng.random() < 0.5:
                        await client.write_set(k, [f"v{i}"])
                    else:
                        await client.fetch_set(k)
                await net.quiesce()
            finally:
                await net.stop()

        run(go())
    finally:
        wt.detach()
    assert wt.verdicts() == []
    st = wt.stats()
    assert st["traces_audited"] >= 12 and st["ops_audited"] >= 12


def test_launch_attaches_and_stop_detaches_watchtower():
    """launch() wires the global auditor to the deployment's quorum
    geometry; stop() detaches it so a later deployment (or test cluster)
    is never audited against stale q/n."""
    from dds_tpu.obs.watchtower import watchtower as global_wt
    from dds_tpu.run import launch
    from dds_tpu.utils.config import DDSConfig

    async def go():
        cfg = DDSConfig()
        cfg.proxy.port = 0
        cfg.recovery.enabled = False
        cfg.recovery.anti_entropy_enabled = False
        dep = await launch(cfg)
        try:
            assert global_wt.attached
            assert global_wt.quorum_size == 5
            assert global_wt.n_replicas == 7  # 9 endpoints - 2 sentinent
            assert global_wt.check_quorum
        finally:
            await dep.stop()
        assert not global_wt.attached

    run(go())


# ------------------------------------------------------------------ SLO


def test_slo_burn_math_and_windows():
    clk = [0.0]
    eng = SloEngine(default=RouteSlo(objective=0.9, latency_ms=100.0),
                    windows=(60.0, 600.0), burn_alert=2.0,
                    clock=lambda: clk[0])
    for _ in range(8):
        eng.observe("GetSet", 200, 0.010)
    eng.observe("GetSet", 200, 0.500)   # too slow: burns budget
    eng.observe("GetSet", 503, 0.010)   # server error: burns budget
    eng.observe("GetSet", 404, 0.010)   # client error, fast: GOOD
    r = eng.report()["routes"]["GetSet"]
    w = r["windows"]["60s"]
    assert w["total"] == 11 and w["bad"] == 2
    assert w["bad_latency"] == 1 and w["bad_error"] == 1
    # bad fraction 2/11 over budget 0.1 -> burn ~1.82 < alert 2.0
    assert abs(w["burn_rate"] - (2 / 11) / 0.1) < 1e-3
    assert r["alert"] is False

    # a cliff: 10 straight errors pushes burn over the alert line in BOTH
    # windows
    for _ in range(10):
        eng.observe("GetSet", 503, 0.010)
    r = eng.report()["routes"]["GetSet"]
    assert r["alert"] is True
    assert r["windows"]["60s"]["burn_rate"] >= 2.0

    # the fast window forgets, the slow one remembers
    clk[0] = 120.0
    r = eng.report()["routes"]["GetSet"]
    assert r["windows"]["60s"]["total"] == 0
    assert r["windows"]["600s"]["total"] == 21
    assert r["alert"] is False  # fast window no longer corroborates


def test_slo_per_route_overrides_and_gauges():
    clk = [0.0]
    eng = SloEngine(
        default=RouteSlo(0.99, 100.0),
        routes={"SumAll": RouteSlo(0.95, 1000.0)},
        windows=(60.0, 600.0), clock=lambda: clk[0],
    )
    eng.observe("SumAll", 200, 0.5)  # slow for default, fine for SumAll
    r = eng.report()["routes"]["SumAll"]
    assert r["objective"] == 0.95
    assert r["windows"]["60s"]["bad"] == 0

    from dds_tpu.obs.metrics import Registry
    reg = Registry()
    eng.export_gauges(reg)
    assert reg.value("dds_slo_objective", route="SumAll") == 0.95
    assert reg.value("dds_slo_burn_rate", route="SumAll", window="60s") == 0.0
    assert reg.value("dds_slo_error_budget_remaining", route="SumAll") == 1.0
    text = reg.render()
    assert "# TYPE dds_slo_burn_rate gauge" in text
    assert "# HELP dds_slo_burn_rate" in text


async def _rest_stack(**proxy_kw):
    net = ChaosNet(InMemoryNet(), seed=11)
    net.default_faults = LinkFaults(delay=0.001, jitter=0.002)
    replicas = {
        a: BFTABDNode(a, R7, "supervisor", net, ReplicaConfig(quorum_size=5))
        for a in R7
    }
    abd = AbdClient("proxy-0", net, R7,
                    AbdClientConfig(request_timeout=2.0, quorum_size=5))
    server = DDSRestServer(
        abd,
        ProxyConfig(host="127.0.0.1", port=0, request_budget=10.0, **proxy_kw),
    )
    await server.start()
    return net, server


def test_slo_route_serves_parseable_burn_state():
    """Acceptance: GET /slo returns parseable per-route objective/burn
    state (and the audit summary riding along)."""

    async def go():
        net, server = await _rest_stack()
        try:
            status, _ = await http_request(
                "127.0.0.1", server.cfg.port, "POST", "/PutSet",
                json.dumps({"contents": ["a"]}).encode(), timeout=10.0,
            )
            assert status == 200
            status, body = await http_request(
                "127.0.0.1", server.cfg.port, "GET", "/slo", timeout=10.0,
            )
            assert status == 200
            await net.quiesce()
            return json.loads(body)
        finally:
            await server.stop()

    out = run(go())
    routes = out["slo"]["routes"]
    assert "PutSet" in routes
    put = routes["PutSet"]
    assert 0 < put["objective"] <= 1
    for wname in put["windows"]:
        assert set(put["windows"][wname]) >= {
            "total", "bad", "burn_rate", "bad_fraction",
        }
    assert put["windows"][f"{int(out['slo']['windows_s'][0])}s"]["total"] >= 1
    assert "budget_remaining" in put and "alert" in put
    assert "violations" in out["audit"]


# ---------------------------------------------------------------- sentry


def _fake_kernel_trace():
    t = Tracer()
    for d in (1.0, 1.1, 1.2, 1.3, 1.4):
        t.record("kernel.foldmany.dispatch", d, R=2, P2=2)
        t.record("kernel.foldmany.execute", d * 2, R=2, P2=2)
    return t


# pin the baseline namespace so these tests (and their CLI subprocesses,
# which inherit the env) agree on keys regardless of the host's backend
@pytest.fixture(autouse=True)
def _pin_sentry_platform(monkeypatch):
    monkeypatch.setenv("DDS_SENTRY_PLATFORM", "cpu")


def test_sentry_collect_keys_by_platform_name_and_shape():
    stats = sentry.collect(_fake_kernel_trace())
    assert list(stats) == ["cpu::foldmany[R=2,P2=2]"]
    d = stats["cpu::foldmany[R=2,P2=2]"]["dispatch"]
    assert d["count"] == 5 and d["p50_ms"] == 1.2 and d["p95_ms"] == 1.4


def test_sentry_platform_namespacing_never_crosses_environments():
    """Satellite-f: a CPU-fabric run's rows must not gate (or ratchet)
    against an on-chip baseline's rows — the platform prefix keeps the
    key sets disjoint, so compare() has an empty intersection."""
    cpu_stats = sentry.collect(_fake_kernel_trace())
    os.environ["DDS_SENTRY_PLATFORM"] = "tpu"
    try:
        tpu_stats = sentry.collect(_fake_kernel_trace())
    finally:
        os.environ["DDS_SENTRY_PLATFORM"] = "cpu"
    assert set(cpu_stats).isdisjoint(tpu_stats)
    # a 10x-slower CPU run vs a TPU baseline: no findings, nothing shared
    slow_cpu = {k: {ph: {**s, "p50_ms": s["p50_ms"] * 10}
                    for ph, s in e.items()} for k, e in cpu_stats.items()}
    assert sentry.compare(tpu_stats, slow_cpu) == []
    # and a merge into one shared file keeps both environments' rows
    merged = dict(tpu_stats)
    merged.update(slow_cpu)
    assert sentry.compare(merged, slow_cpu) == []  # only cpu rows compare


def test_sentry_baseline_roundtrip_and_merge(tmp_path):
    p = str(tmp_path / "base.json")
    stats = sentry.collect(_fake_kernel_trace())
    sentry.save_baseline(stats, p)
    assert sentry.load_baseline(p) == stats
    # merge keeps the committed baseline unless overwrite
    slower = {k: {ph: {**s, "p50_ms": s["p50_ms"] * 10}
                  for ph, s in e.items()} for k, e in stats.items()}
    sentry.save_baseline(slower, p)
    assert sentry.load_baseline(p) == stats
    sentry.save_baseline(slower, p, overwrite=True)
    assert sentry.load_baseline(p) == slower
    # malformed file -> typed error, not garbage comparisons
    (tmp_path / "bad.json").write_text('{"kernels": {"k": {"dispatch": "x"}}}')
    with pytest.raises(ValueError):
        sentry.load_baseline(str(tmp_path / "bad.json"))


def test_sentry_compare_flags_inflated_timings():
    base = sentry.collect(_fake_kernel_trace())
    fresh = {k: {ph: dict(s) for ph, s in e.items()} for k, e in base.items()}
    assert sentry.compare(base, fresh) == []
    fresh["cpu::foldmany[R=2,P2=2]"]["execute"]["p50_ms"] *= 3  # 3x regression
    findings = sentry.compare(base, fresh, threshold=0.20)
    assert len(findings) == 1
    f = findings[0]
    assert (f["phase"], f["stat"]) == ("execute", "p50_ms")
    assert f["ratio"] >= 3.0
    # sub-floor jitter on a tiny kernel is not a regression
    tiny_b = {"k": {"dispatch": {"p50_ms": 0.01, "p95_ms": 0.01, "count": 5}}}
    tiny_f = {"k": {"dispatch": {"p50_ms": 0.03, "p95_ms": 0.03, "count": 5}}}
    assert sentry.compare(tiny_b, tiny_f) == []


def _run_sentry_cli(*args):
    return subprocess.run(
        [sys.executable, os.path.join(REPO, "benchmarks", "sentry.py"), *args],
        capture_output=True, text=True, timeout=120, cwd=REPO,
    )


def test_sentry_cli_gates_on_regression(tmp_path):
    """Acceptance: the sentry CLI exits non-zero when a fresh run's kernel
    timing is synthetically inflated past the stored baseline."""
    stats = sentry.collect(_fake_kernel_trace())
    base_path = str(tmp_path / "baseline.json")
    sentry.save_baseline(stats, base_path)
    inflated = {k: {ph: {**s, "p50_ms": s["p50_ms"] * 2, "p95_ms": s["p95_ms"] * 2}
                    for ph, s in e.items()} for k, e in stats.items()}
    fresh_path = tmp_path / "fresh.json"
    fresh_path.write_text(json.dumps(inflated))

    p = _run_sentry_cli("--baseline", base_path, "--fresh", str(fresh_path))
    assert p.returncode == 1, p.stdout + p.stderr
    row = json.loads(p.stdout.strip().splitlines()[-1])
    assert row["ok"] is False and row["regressions"]
    assert row["regressions"][0]["kernel"] == "cpu::foldmany[R=2,P2=2]"

    # identical stats pass the gate
    same = tmp_path / "same.json"
    same.write_text(json.dumps(stats))
    p = _run_sentry_cli("--baseline", base_path, "--fresh", str(same))
    assert p.returncode == 0, p.stdout + p.stderr


def test_sentry_cli_check_smoke(tmp_path):
    """The CPU-only CI smoke: --check parses the baseline (or reports a
    clean absence) with exit 0, and exits 2 on a corrupted file."""
    stats = sentry.collect(_fake_kernel_trace())
    base_path = str(tmp_path / "baseline.json")
    sentry.save_baseline(stats, base_path)
    p = _run_sentry_cli("--check", "--baseline", base_path)
    assert p.returncode == 0, p.stdout + p.stderr
    row = json.loads(p.stdout.strip().splitlines()[-1])
    assert row["ok"] is True and row["kernels"] == 1

    p = _run_sentry_cli("--check", "--baseline", str(tmp_path / "missing.json"))
    assert p.returncode == 0

    bad = tmp_path / "corrupt.json"
    bad.write_text("{not json")
    p = _run_sentry_cli("--check", "--baseline", str(bad))
    assert p.returncode == 2


def test_emit_persists_kernel_baseline(tmp_path, monkeypatch):
    from benchmarks import common

    path = tmp_path / "kb.json"
    monkeypatch.setenv("DDS_KERNEL_BASELINE", str(path))
    tracer.record("kernel.emit_probe.dispatch", 2.0, k=4)
    tracer.record("kernel.emit_probe.execute", 3.0, k=4)
    common.emit("m", 1.0, "ops/s", 1.0)
    kernels = sentry.load_baseline(str(path))
    assert "cpu::emit_probe[k=4]" in kernels
    assert kernels["cpu::emit_probe[k=4]"]["execute"]["p50_ms"] == 3.0


# ------------------------------------------------------- metrics satellite


def test_metrics_help_backfill_and_escaping():
    from dds_tpu.obs.metrics import Registry

    r = Registry()
    r.set("g_state", 1)                       # first touch: no help
    r.set("g_state", 2, help="state\nwith \\ tricky text")
    text = r.render()
    assert "# HELP g_state state\\nwith \\\\ tricky text" in text
    assert "# TYPE g_state gauge" in text
    # backfill never downgrades an existing help
    r.inc("c_total", help="first")
    r.inc("c_total", help="second")
    assert "# HELP c_total first" in r.render()


# --------------------------------------------------- flight index satellite


def test_flight_index_lines_and_prune_rewrite(tmp_path):
    from dds_tpu.obs.flight import FlightRecorder

    fr = FlightRecorder(dir=str(tmp_path), max_incidents=2, min_interval=0.0)
    for i in range(4):
        assert fr.record(f"kind_{i}", trace_id=f"t{i}") is not None
    files = {p.name for p in tmp_path.glob("incident-*.jsonl")}
    assert len(files) == 2
    idx = [json.loads(l) for l in open(tmp_path / "index.jsonl")]
    # pruned incidents were dropped from the index; survivors match files
    assert {e["path"] for e in idx} == files
    assert all({"ts", "kind", "trace_id", "path"} <= set(e) for e in idx)
    assert [e["kind"] for e in idx] == ["kind_2", "kind_3"]
