"""The deployment with one of its four replicas crashed from launch
(`attacks.type = "crash"`, `attacks.at_launch`; the upstream's
`intruder-attacks` of type crash, no spare).

A tolerated crash changes no answer, and after the timeouts that open the
victim's breaker it costs no user anything. What must hold, whichever way
the proxy meets the victim (as one of the two coordinators the supervisor
names, `chaos_seed` 0, or as a participant of tag rounds only, 1 and 2):
every `SumAll` is the plain fold of the rows as written, every
acknowledged write is read back, the answers are the healthy deployment's
bit for bit; once the breaker is open the proxy sends the victim its own
probes and nothing else, nobody waits on it, a tag round asks three and
carries nothing; the victim re-registered is found by the next probe, its
breaker closes, the next round asks four and teaches it the keys once; the
breaker's transitions stay legal; timeouts strike nobody; nothing of the
probe is left after `stop()`. With every breaker open today's degraded try
and fast-fail stay; with two replicas down every operation fails typed;
the liar of `ref8col-bft4-byz1` is struck out by evidence and never probed.
"""

import asyncio
import functools
import json
import os
import random
import time

import pytest

from dds_tpu.core import messages as M
from dds_tpu.core.errors import AllBreakersOpenError
from dds_tpu.core.quorum_client import AbdClient, AbdClientConfig
from dds_tpu.core.replica import BFTABDNode, ReplicaConfig
from dds_tpu.core.transport import InMemoryNet
from dds_tpu.obs.metrics import metrics
from dds_tpu.utils.retry import CircuitBreaker, Deadline
from dds_tpu.utils.trace import tracer

from tests.test_core import run
from tests.test_tcp_deployment import MULT_MOD, SUM_MOD, _deployment_cfg

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
NAMES = [f"replica-{i}" for i in range(4)]
# `random.Random(seed).sample(NAMES, 1)`: the seed that draws each victim.
# replica-3 is one of the two coordinators the freshest half names
SEED_OF = {"replica-3": 0, "replica-1": 1, "replica-0": 2}
K = 192
REQUEST_TIMEOUT, RESET, PROBE_TIMEOUT = 1.0, 0.3, 0.2
OPS = ("fetch", "write", "fetch_batch")


def _cfg(seed: int | None, kind: str = "crash"):
    cfg = _deployment_cfg("memory")
    cfg.proxy.crypto_backend = "cpu"
    cfg.proxy.intranet_request_timeout = REQUEST_TIMEOUT
    cfg.proxy.breaker_reset = RESET
    cfg.proxy.breaker_probe_timeout = PROBE_TIMEOUT
    if seed is not None:
        cfg.attacks.enabled = True
        cfg.attacks.type = kind
        cfg.attacks.at_launch = True
        cfg.attacks.chaos_seed = seed
    return cfg


def _counts() -> dict:
    out = {f"requests.{k}": metrics.value("dds_tag_round_requests_total",
                                          keys=k) or 0.0
           for k in ("named", "carried")}
    out["skipped"] = metrics.value("dds_tag_round_skipped_total") or 0.0
    for o in ("answered", "silent", "refused"):
        out[f"probes.{o}"] = metrics.value("dds_breaker_probes_total",
                                           outcome=o) or 0.0
    out["timeouts"] = sum(metrics.value("dds_request_timeouts_total", op=op)
                          or 0.0 for op in OPS)
    return out


def _since(before: dict) -> dict:
    now = _counts()
    return {k: now[k] - before[k] for k in now}


def _fold(rows, col, mod) -> int:
    """The plain reference: python ints, nothing of the program."""
    return functools.reduce(lambda a, b: a * b % mod,
                            (int(r[col]) for r in rows), 1)


async def _story(seed: int | None, kind: str = "crash") -> dict:
    """One seeded run of a tiny deployment through REST: load, 16 steps of
    aggregates, an update and its read-back (by when a victim's breaker is
    open), 6 more, the victim back, 3 more, stop. Returns what was seen."""
    from dds_tpu.http.miniserver import http_request
    from dds_tpu.obs.watchtower import watchtower
    from dds_tpu.run import launch

    rng = random.Random(f"crash/{K}")
    rows = [[str(i), "x", str(rng.randrange(2, SUM_MOD)),
             str(rng.randrange(2, MULT_MOD)), "y", "z", "w", None]
            for i in range(K)]
    cfg = _cfg(seed, kind)
    events: list = []

    def on_record(rec):
        if rec.name.startswith("breaker.") or rec.name == "abd.probe":
            events.append((rec.name, dict(rec.meta),
                           getattr(rec, "dur_ms", None)))

    tracer.subscribe(on_record)
    dep = await launch(cfg)
    abd = dep.server.abd
    victim = (dep.launch_victims or [None])[0]
    seen = {"victim": victim, "said": [], "phases": {}, "wrong": [],
            "events": events}
    to_victim: list = []

    async def watch(msg):
        to_victim.append(msg)
        return msg

    if victim is not None:
        dep.net.link_filters[(abd.addr, victim)] = watch
    host, port = cfg.proxy.host, dep.server.cfg.port
    lat: list = []

    async def call(method, target, body=None):
        t0 = time.perf_counter()
        st, raw = await http_request(
            host, port, method, target,
            None if body is None else json.dumps(body).encode())
        lat.append(time.perf_counter() - t0)
        seen["said"].append((target.split("?")[0], st, raw.decode()))
        return st, raw.decode()

    async def step():
        for route, col, mod, par in (("SumAll", 2, SUM_MOD, "nsqr"),
                                     ("MultAll", 3, MULT_MOD, "pubkey")):
            st, body = await call("GET", f"/{route}?position={col}&{par}={mod}")
            if st != 200 or int(json.loads(body)["result"]) != _fold(
                    rows, col, mod):
                seen["wrong"].append((route, st))
        i = rng.randrange(K)
        new = str(rng.randrange(2, SUM_MOD))
        st, _ = await call("PUT", f"/WriteElement/{keys[i]}?position=2",
                           {"value": new})
        if st == 200:
            rows[i][2] = new
        st, body = await call("GET", f"/GetSet/{keys[i]}")
        if st != 200 or json.loads(body)["contents"] != rows[i]:
            seen["wrong"].append(("GetSet", st))

    async def phase(name: str, steps: int, first=None):
        del to_victim[:], lat[:]
        before = _counts()
        if first is not None:
            await first
        for _ in range(steps):
            await step()
        seen["phases"][name] = {
            "to_victim": list(to_victim), "latency": list(lat),
            "counts": _since(before), "breakers": abd.breaker_states()}

    try:
        sem = asyncio.Semaphore(16)

        async def put(row):
            async with sem:
                st, body = await call("POST", "/PutSet", {"contents": row})
                assert st == 200, (st, body)
                return body

        before = _counts()
        keys = list(await asyncio.gather(*(put(r) for r in rows)))
        seen["load"] = {"counts": _since(before), "latency": list(lat),
                        "breakers": abd.breaker_states()}
        await phase("found", 16)
        async def back():
            node = dep.replicas[victim]
            dep.net.register(node.addr, node.handle)
            t0 = time.perf_counter()
            while (abd.breakers[victim].state != CircuitBreaker.CLOSED
                   and time.perf_counter() - t0 < 5.0):
                await asyncio.sleep(0.02)
            seen["closed_after_s"] = time.perf_counter() - t0

        if victim is not None:
            # a probe or two, then six steps beside them
            await phase("open", 6, asyncio.sleep(RESET + PROBE_TIMEOUT + 0.1))
            seen["probe_tasks"] = sorted(abd._probe_tasks)
            await phase("back", 3, back())
        seen["strikes"] = abd.replicas.suspicions()
        seen["verdicts"] = [v.invariant for v in watchtower.verdicts()]
    finally:
        await dep.stop()
        tracer.unsubscribe(on_record)
    await asyncio.sleep(0)
    seen["tasks_left"] = [t.get_name() for t in asyncio.all_tasks()
                          if t.get_name().startswith("abd.probe")]
    seen["probe_tasks_left"] = sorted(abd._probe_tasks)
    seen["rows"] = rows
    return seen


@pytest.fixture(scope="module")
def stories():
    got: dict = {}

    def of(victim):
        if victim not in got:
            got[victim] = run(_story(None if victim is None
                                     else SEED_OF[victim]))
        return got[victim]

    return of


VICTIMS = sorted(SEED_OF)


# ------------------------------------------------ a crash changes no answer


@pytest.mark.parametrize("victim", [None] + VICTIMS)
def test_every_aggregate_is_the_plain_fold_and_every_write_is_read_back(
        stories, victim):
    s = stories(victim)
    assert s["victim"] == victim
    assert s["wrong"] == []
    assert all(st == 200 for _, st, _ in s["said"])
    sums = [body for route, _, body in s["said"] if route == "/SumAll"]
    assert len(set(sums)) >= 16         # it moved with every update


@pytest.mark.parametrize("victim", VICTIMS)
def test_the_answers_are_the_healthy_deployments_bit_for_bit(stories, victim):
    well, said = stories(None)["said"], stories(victim)["said"]
    loaded = [x for x in said if x[0] == "/PutSet"]
    assert sorted(loaded) == sorted(x for x in well if x[0] == "/PutSet")
    rest = [x for x in said if x[0] != "/PutSet"]
    assert rest[:len(well) - K] == [x for x in well if x[0] != "/PutSet"]


# --------------------------------------- what the crash costs, and for how long


def test_a_dead_coordinator_costs_the_timeouts_that_open_its_breaker_once(
        stories):
    """replica-3 is a coordinator: the requests in flight at it when the
    load starts wait one `intranet_request_timeout`, three of them open
    the breaker, every retry goes through replica-2 and is answered."""
    s = stories("replica-3")
    load = s["load"]
    assert load["counts"]["timeouts"] >= 3
    assert load["breakers"]["replica-3"] != CircuitBreaker.CLOSED
    assert max(load["latency"]) < 2 * REQUEST_TIMEOUT + 1.0
    for name in ("found", "open", "back"):
        assert s["phases"][name]["counts"]["timeouts"] == 0, name


@pytest.mark.parametrize("victim", ["replica-0", "replica-1"])
def test_a_dead_participant_is_found_by_its_silence_and_costs_no_timeout(
        stories, victim):
    """Outside the freshest half the victim coordinates nothing, so no
    request ever waits on it: its breaker opens on `breaker_threshold` tag
    rounds whose late window closed without a word from it."""
    s = stories(victim)
    assert s["load"]["counts"]["timeouts"] == 0
    assert victim not in s["load"]["breakers"]
    found = s["phases"]["found"]
    assert found["breakers"][victim] != CircuitBreaker.CLOSED
    assert found["counts"]["timeouts"] == 0
    # asked and carried the keys until then, skipped from then on
    asked = [m for m in found["to_victim"] if m.count]
    assert asked and all(len(m.keys) == K for m in asked)
    assert found["counts"]["skipped"] > 0


@pytest.mark.parametrize("victim", VICTIMS)
def test_behind_an_open_breaker_the_victim_is_sent_probes_only(stories,
                                                               victim):
    open_ = stories(victim)["phases"]["open"]
    assert open_["breakers"][victim] != CircuitBreaker.CLOSED
    sent = open_["to_victim"]
    assert sent, "no probe in 6 steps and a breaker_reset"
    assert {type(m) for m in sent} == {M.ReadTagBatch}
    assert all(m.count == 0 and m.keys == () for m in sent)
    assert len({m.nonce for m in sent}) == len(sent)     # a nonce each


@pytest.mark.parametrize("victim", VICTIMS)
def test_nobody_waits_on_the_victim_once_its_breaker_is_open(stories, victim):
    open_ = stories(victim)["phases"]["open"]["latency"]
    well = stories(None)["phases"]["found"]["latency"]
    assert max(open_) < REQUEST_TIMEOUT / 2
    assert max(open_) < 10 * max(well) + 0.1


@pytest.mark.parametrize("victim", VICTIMS)
def test_a_tag_round_asks_the_three_and_carries_nothing(stories, victim):
    c = stories(victim)["phases"]["open"]["counts"]
    rounds = c["skipped"]
    assert rounds >= 6                       # one request not sent a round
    assert c["requests.named"] == 3 * rounds
    assert c["requests.carried"] == 0
    assert c["probes.answered"] == c["probes.refused"] == 0


@pytest.mark.parametrize("victim", VICTIMS)
def test_the_victim_back_is_found_by_a_probe_and_taught_the_keys_once(
        stories, victim):
    s = stories(victim)
    assert s["probe_tasks"] == [victim]
    assert s["closed_after_s"] < RESET + PROBE_TIMEOUT + 1.0
    back = s["phases"]["back"]
    assert back["breakers"][victim] == CircuitBreaker.CLOSED
    c = back["counts"]
    assert c["probes.answered"] == 1 and c["skipped"] == 0
    probes = [m for m in back["to_victim"] if isinstance(m, M.ReadTagBatch)
              and not m.count]
    assert len(probes) in (1, 2)      # the one that was answered last
    rounds = [m for m in back["to_victim"] if isinstance(m, M.ReadTagBatch)
              and m.count]
    assert len(rounds) >= 3
    assert [len(m.keys) for m in rounds] == [K] + [0] * (len(rounds) - 1)
    assert c["requests.carried"] == 1
    assert c["requests.named"] + 1 == 4 * len(rounds)     # four are asked


@pytest.mark.parametrize("victim", VICTIMS)
def test_the_probe_is_a_span_with_its_target_and_its_outcome(stories, victim):
    probes = [(meta, ms) for name, meta, ms in stories(victim)["events"]
              if name == "abd.probe"]
    assert probes and all(meta["target"] == victim for meta, _ in probes)
    assert [meta["ok"] for meta, _ in probes].count(True) == 1
    assert probes[-1][0]["ok"] is True
    silent = [ms for meta, ms in probes if not meta["ok"]]
    assert silent and all(
        PROBE_TIMEOUT * 1e3 <= ms < PROBE_TIMEOUT * 1e3 + 150 for ms in silent)


@pytest.mark.parametrize("victim", VICTIMS)
def test_the_breaker_keeps_its_legal_transitions(stories, victim):
    s = stories(victim)
    moves = [name.split(".", 1)[1] for name, meta, _ in s["events"]
             if name.startswith("breaker.") and meta["target"] == victim]
    assert moves[:2] == ["open", "half_open"] and moves[-1] == "closed"
    state = "closed"
    legal = {"closed": {"open"}, "open": {"half_open", "closed"},
             "half_open": {"open", "closed"}}
    for to in moves:
        assert to in legal[state], (state, to, moves)
        state = to
    assert moves.count("half_open") >= 2          # probed more than once
    assert "breaker_legality" not in s["verdicts"]


@pytest.mark.parametrize("victim", VICTIMS)
def test_timeouts_and_silence_strike_nobody(stories, victim):
    assert set(stories(victim)["strikes"].values()) == {0}


@pytest.mark.parametrize("victim", [None] + VICTIMS)
def test_nothing_of_the_probe_is_left_after_stop(stories, victim):
    s = stories(victim)
    assert s["tasks_left"] == [] and s["probe_tasks_left"] == []


def test_a_healthy_deployment_opens_no_breaker_and_sends_no_probe(stories):
    s = stories(None)
    assert not any(name.startswith("breaker.") or name == "abd.probe"
                   for name, _, _ in s["events"])
    c = s["phases"]["found"]["counts"]
    assert c["skipped"] == c["timeouts"] == 0
    assert c["probes.silent"] == c["probes.answered"] == 0


# ------------------------------------------------ the liar is not a crash


def test_the_liar_is_struck_out_by_evidence_and_never_probed():
    """`ref8col-bft4-byz1`'s replica-3: three bare replies strike it out
    and open its breaker in the same breath; a replica struck out is
    nobody's to probe, and the answers are right as before."""
    before = _counts()
    s = run(_story(0, kind="byzantine"))
    assert s["wrong"] == [] and all(st == 200 for _, st, _ in s["said"])
    assert s["strikes"]["replica-3"] >= 3
    assert not any(name == "abd.probe" for name, _, _ in s["events"])
    got = _since(before)
    assert got["probes.silent"] == got["probes.refused"] == 0
    assert got["timeouts"] == 0
    assert s["tasks_left"] == [] and s["probe_tasks_left"] == []


# --------------------------------------- a cluster taken apart: the edges


class Cluster:
    """Four replicas and one proxy-side client on an `InMemoryNet`."""

    def __init__(self, **cfg):
        self.net = InMemoryNet()
        rcfg = ReplicaConfig(quorum_size=3)
        self.nodes = {n: BFTABDNode(n, NAMES, "supervisor", self.net, rcfg)
                      for n in NAMES}
        self.abd = AbdClient("proxy-0", self.net, NAMES, AbdClientConfig(
            quorum_size=3, request_timeout=0.1, breaker_reset=0.15,
            breaker_probe_timeout=0.05, **cfg))
        self.sent: list = []        # (replica, what the proxy sent it)
        for n in NAMES:
            self.net.link_filters[("proxy-0", n)] = self._noting(n)

    def _noting(self, dest):
        async def note(msg):
            self.sent.append((dest, msg))
            return msg
        return note

    def crash(self, *names):
        for n in names:
            self.net.unregister(n)

    def back(self, *names):
        for n in names:
            self.net.register(n, self.nodes[n].handle)

    async def open_all(self):
        """Every coordinator's breaker open: three timeouts each."""
        self.crash(*NAMES)
        while any(self.abd.breakers.get(n) is None
                  or self.abd.breakers[n].state == CircuitBreaker.CLOSED
                  for n in NAMES):
            with pytest.raises(asyncio.TimeoutError):
                await self.abd.fetch_set("K")


@pytest.mark.parametrize("through", ["a user's request", "the probe"])
def test_with_every_breaker_open_the_degraded_try_stays(through):
    """Nobody is settled, so a user's request is routed all the same
    (half-open first), as before; the probes run beside it, and whichever
    gets a verified answer first closes the breaker."""

    async def go():
        c = Cluster()
        await c.open_all()
        assert set(c.abd.breaker_states().values()) <= {"open", "half_open"}
        assert sorted(c.abd._probe_tasks) == NAMES
        del c.sent[:]
        with pytest.raises(asyncio.TimeoutError):
            await c.abd.fetch_set("K")               # routed, not refused
        assert any(isinstance(m, M.Envelope) for _, m in c.sent)
        c.back(*NAMES)
        if through == "the probe":
            await asyncio.sleep(0.4)
            assert set(c.abd.breaker_states().values()) == {"closed"}
            assert c.abd._probe_tasks == {}
        assert await c.abd.fetch_set("K") is None    # serves again
        await c.abd.stop()

    run(go())


def test_with_every_breaker_open_past_the_budget_it_fails_fast():
    async def go():
        c = Cluster(breaker_threshold=1)
        c.abd.cfg.breaker_reset = 30.0
        c.crash(*NAMES)
        for _ in range(12):
            if len(c.abd.breakers) == 4 and not any(
                    b.allow() for b in c.abd.breakers.values()):
                break
            with pytest.raises(asyncio.TimeoutError):
                await c.abd.fetch_set("K")
        t0 = time.perf_counter()
        with pytest.raises(AllBreakersOpenError) as e:
            await c.abd.fetch_set("K", deadline=Deadline(1.0))
        assert time.perf_counter() - t0 < 0.05 and e.value.eta > 1.0
        with pytest.raises(AllBreakersOpenError):
            await c.abd.read_tags(["K"], deadline=Deadline(1.0))
        await c.abd.stop()
        assert c.abd._probe_tasks == {}

    run(go())


@pytest.mark.parametrize("down", [("replica-3", "replica-2"),
                                  ("replica-3", "replica-0"),
                                  ("replica-1", "replica-0")])
def test_two_replicas_down_fails_every_operation_typed(down):
    """f = 1 was bought: with two of four gone no quorum of three exists,
    whatever the breakers do; every operation ends in a timeout or the
    fast-fail's typed error, none in an answer, and nobody is struck."""

    async def go():
        c = Cluster()
        c.abd._preferred = ["replica-3", "replica-2"]
        await c.abd.write_set("K", ["row"])
        await c.net.quiesce()
        c.crash(*down)
        typed = (asyncio.TimeoutError, AllBreakersOpenError)
        for _ in range(8):
            with pytest.raises(typed):
                await c.abd.fetch_set("K", deadline=Deadline(0.3))
            with pytest.raises(typed):
                await c.abd.write_set("K", ["new"], deadline=Deadline(0.3))
            with pytest.raises(typed):
                await c.abd.read_tags(["K"], deadline=Deadline(0.3))
        assert set(c.abd.replicas.suspicions().values()) == {0}
        await c.abd.stop()

    run(go())


@pytest.mark.parametrize("field", ["default", "toml", "deployment"])
def test_the_probes_timeout_is_one_field_of_the_deployment(field, tmp_path):
    from dds_tpu.run import shard_configs
    from dds_tpu.utils.config import DDSConfig

    if field == "default":
        shipped = DDSConfig.load(os.path.join(ROOT, "configs", "default.toml"))
        assert (shipped.proxy.breaker_probe_timeout
                == DDSConfig().proxy.breaker_probe_timeout
                == AbdClientConfig().breaker_probe_timeout == 1.0)
        assert shipped.proxy.breaker_reset == 2.0
    elif field == "toml":
        path = tmp_path / "probe.toml"
        path.write_text("[proxy]\nbreaker-probe-timeout = 0.25\n")
        cfg = DDSConfig.load(path)
        assert cfg.proxy.breaker_probe_timeout == 0.25
        assert shard_configs(cfg)[2].breaker_probe_timeout == 0.25
    else:
        from dds_tpu.run import launch

        async def go():
            dep = await launch(_cfg(None))
            try:
                return dep.server.abd.cfg.breaker_probe_timeout
            finally:
                await dep.stop()

        assert run(go()) == PROBE_TIMEOUT
