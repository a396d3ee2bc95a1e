"""Every replica in a process of its own (`transport.replica_processes`).

The placement of `dds-system.conf:113-128` / `Main.scala:90-99` on one
machine: the launcher keeps the proxy and no replica, four children hold a
replica each and meet over `TcpNet`. Placing the replicas changes no answer:
every REST operation equals python ints', also while writers and readers
run concurrently against replicas that really run concurrently; a child
that is killed is the breaker's business; no child outlives its launcher,
however that ends; and what the children count about the protocol the
launcher's registry reads as their sum.

One deployment serves the whole module (a child imports the program: about
three seconds), so the tests run in the order they are written in: the ones
that damage it come last.
"""

import asyncio
import functools
import json
import math
import os
import random
import shutil
import signal
import subprocess
import sys
import time

import pytest

from dds_tpu.core import messages as M
from dds_tpu.core.transport import TcpNet
from dds_tpu.obs.metrics import PROTOCOL_FAMILIES, metrics
from dds_tpu.obs.panopticon import parse_samples
from dds_tpu.utils import sigs
from dds_tpu.utils.trace import tracer

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
NAMES = [f"replica-{i}" for i in range(4)]
ROWS = 24
STEP = 1 << 32               # an update adds it: above every initial total
FRAME_SECRET = "a-fixed-frame-secret"

# toy keys, for exactness and not for secrecy: Paillier over two Mersenne
# primes, RSA over two others
P, Q = (1 << 61) - 1, (1 << 89) - 1
N, NSQ = P * Q, (P * Q) ** 2
LAM = math.lcm(P - 1, Q - 1)
MU = pow(LAM, -1, N)
RP, RQ = (1 << 107) - 1, (1 << 127) - 1
RN, E = RP * RQ, 65537
D = pow(E, -1, (RP - 1) * (RQ - 1))


def enc(m: int, rng) -> int:
    return pow(1 + N, m, NSQ) * pow(rng.randrange(2, N), N, NSQ) % NSQ


def dec(c: int) -> int:
    return (pow(c, LAM, NSQ) - 1) // N * MU % N


def fold(values, modulus: int) -> int:
    """The plain reference: python ints, nothing of the program."""
    return functools.reduce(lambda a, b: a * b % modulus, values, 1)


def alive(pid: int) -> bool:
    """Running: there, and not a zombie waiting to be reaped."""
    try:
        with open(f"/proc/{pid}/stat", "rb") as f:
            return f.read().rsplit(b") ", 1)[1].split()[0] != b"Z"
    except OSError:
        return False


def children_of(pid: int) -> set[int]:
    out = set()
    for entry in os.listdir("/proc"):
        if entry.isdigit():
            try:
                with open(f"/proc/{entry}/stat", "rb") as f:
                    fields = f.read().rsplit(b") ", 1)[1].split()
            except OSError:
                continue
            if int(fields[1]) == pid and fields[0] != b"Z":
                out.add(int(entry))
    return out


def _cfg():
    from dds_tpu.utils.config import DDSConfig

    cfg = DDSConfig()
    cfg.replicas.endpoints = list(NAMES)
    cfg.replicas.sentinent = []
    cfg.replicas.byz_quorum_size = 3
    cfg.replicas.byz_max_faults = 1
    cfg.recovery.enabled = False
    cfg.transport.kind = "tcp"
    cfg.transport.port = 0
    cfg.transport.replica_processes = True
    cfg.security.transport_frame_secret = FRAME_SECRET
    cfg.proxy.port = 0
    cfg.proxy.crypto_backend = "cpu"
    cfg.proxy.intranet_request_timeout = 0.4
    cfg.proxy.breaker_reset = 0.3
    cfg.proxy.breaker_probe_timeout = 0.2
    return cfg


class World:
    """The module's deployment on a loop of its own, its rows as the
    plain model holds them, and the launcher's counters before it."""

    def __init__(self):
        from dds_tpu.run import launch

        self.loop = asyncio.new_event_loop()
        self.before = {
            (name, tuple(sorted(labels.items()))): value
            for name, _, labels, value in metrics.counters(PROTOCOL_FAMILIES)}
        self.loop_before = self.replica_tenant_seconds()
        self.cfg = _cfg()
        self.launch_spans = []

        def on_record(rec):
            if rec.name == "launch.children":
                self.launch_spans.append(rec)

        tracer.subscribe(on_record)
        try:
            self.dep = self.run(launch(self.cfg))
        finally:
            tracer.unsubscribe(on_record)
        self.pids = [c.proc.pid for c in self.dep.hosts.children]
        self.host, self.port = self.cfg.proxy.host, self.dep.server.cfg.port
        rng = self.rng = random.Random(49)
        self.plain = [rng.randrange(1 << 16) for _ in range(ROWS)]
        self.mplain = [rng.randrange(2, 1 << 16) for _ in range(ROWS)]
        self.rows = [[str(i), "x", str(enc(self.plain[i], rng)),
                      str(pow(self.mplain[i], E, RN)), "y", "z", "w", None]
                     for i in range(ROWS)]
        self.keys = self.run(self.load())

    def run(self, coro):
        return self.loop.run_until_complete(coro)

    @staticmethod
    def replica_tenant_seconds() -> float:
        return metrics.value("dds_event_loop_seconds_total",
                             tenant="replica") or 0.0

    async def call(self, method, target, body=None):
        from dds_tpu.http.miniserver import http_request

        st, raw = await http_request(
            self.host, self.port, method, target,
            None if body is None else json.dumps(body).encode())
        return st, raw.decode()

    async def load(self):
        keys = []
        for r in self.rows:
            st, body = await self.call("POST", "/PutSet", {"contents": r})
            assert st == 200, (st, body)
            keys.append(body)
        return keys

    async def sum_all(self) -> int:
        st, body = await self.call("GET", f"/SumAll?position=2&nsqr={NSQ}")
        assert st == 200, (st, body)
        return int(json.loads(body)["result"])

    async def mult_all(self) -> int:
        st, body = await self.call("GET", f"/MultAll?position=3&pubkey={RN}")
        assert st == 200, (st, body)
        return int(json.loads(body)["result"])

    async def get(self, i):
        st, body = await self.call("GET", f"/GetSet/{self.keys[i]}")
        assert st == 200, (st, body)
        return json.loads(body)["contents"]

    async def update(self, i, col, value) -> int:
        st, _ = await self.call(
            "PUT", f"/WriteElement/{self.keys[i]}?position={col}",
            {"value": value})
        return st

    def stop(self):
        if self.dep is not None:
            self.run(self.dep.stop())
            self.dep = None


@pytest.fixture(scope="module")
def world():
    w = World()
    try:
        yield w
    finally:
        w.stop()
        w.loop.close()


# --------------------------------------- (a) every operation, python ints'


def test_every_rest_operation_equals_python_ints(world):
    w = world

    async def go():
        total = await w.sum_all()
        assert total == fold((int(r[2]) for r in w.rows), NSQ)
        assert dec(total) == sum(w.plain)
        prod = await w.mult_all()
        assert prod == fold((int(r[3]) for r in w.rows), RN)
        assert pow(prod, D, RN) == fold(w.mplain, RN)
        for i in (3, 11, 3):
            w.plain[i] += 7
            new = str(enc(w.plain[i], w.rng))
            assert await w.update(i, 2, new) == 200
            w.rows[i][2] = new
            w.mplain[i] = w.mplain[i] * 5 % RN
            new = str(pow(w.mplain[i], E, RN))
            assert await w.update(i, 3, new) == 200
            w.rows[i][3] = new
            assert await w.get(i) == w.rows[i]
            total, prod = await w.sum_all(), await w.mult_all()
            assert total == fold((int(r[2]) for r in w.rows), NSQ)
            assert dec(total) == sum(w.plain)
            assert prod == fold((int(r[3]) for r in w.rows), RN)
            assert pow(prod, D, RN) == fold(w.mplain, RN)
        assert [await w.get(i) for i in range(ROWS)] == w.rows

    w.run(go())


# ------------------- (b) concurrent writers and readers, `check.py`'s rule


def test_answers_under_concurrent_writers_lie_inside_their_intervals(world):
    """Writers own disjoint rows and add STEP to one of them at a time, so a
    decrypted SumAll is the initial total plus a whole number of updates:
    between those acknowledged before it was sent and those sent before it
    was answered. A GetSet returns a version of its row inside the same
    kind of interval. (`yardstick/check.py`'s rule, restated.)"""
    w = world
    base = sum(w.plain)
    assert base < STEP
    sent, acked = [0], [0]
    row_sent, row_acked = [0] * ROWS, [0] * ROWS
    versions = [[r[2]] for r in w.rows]
    wrong: list[str] = []
    seen = {"aggregates": 0, "reads": 0}
    t_end = time.perf_counter() + 2.5

    async def writer(mine, rng):
        while time.perf_counter() < t_end:
            i = rng.choice(mine)
            new = str(enc(w.plain[i] + STEP * (row_sent[i] + 1), rng))
            versions[i].append(new)
            row_sent[i] += 1
            sent[0] += 1
            if await w.update(i, 2, new) != 200:
                wrong.append("an update was refused")
                return
            row_acked[i] += 1
            acked[0] += 1

    async def summer():
        while time.perf_counter() < t_end:
            lo = acked[0]
            got = dec(await w.sum_all()) - base
            hi = sent[0]
            seen["aggregates"] += 1
            if got % STEP or not lo <= got // STEP <= hi:
                wrong.append(f"SumAll of {got / STEP} updates, not in "
                             f"[{lo}, {hi}]")

    async def reader(rng):
        while time.perf_counter() < t_end:
            i = rng.randrange(ROWS)
            lo = row_acked[i]
            got = (await w.get(i))[2]
            hi = row_sent[i]
            seen["reads"] += 1
            if got not in versions[i][lo:hi + 1]:
                wrong.append(f"GetSet of row {i}: no version in [{lo}, {hi}]")

    async def go():
        await asyncio.gather(
            *(writer(list(range(k, ROWS, 4)), random.Random(k))
              for k in range(4)),
            summer(), summer(), reader(random.Random(8)),
            reader(random.Random(9)))
        for i in range(ROWS):
            w.plain[i] += STEP * row_acked[i]
            w.rows[i][2] = versions[i][row_acked[i]]
        assert row_acked == row_sent
        total = await w.sum_all()
        assert total == fold((int(r[2]) for r in w.rows), NSQ)
        assert dec(total) == sum(w.plain)

    w.run(go())
    assert wrong == []
    assert acked[0] > 8 and seen["aggregates"] > 2 and seen["reads"] > 8


# --------------------------------------------- (c) who runs in which process


def test_every_replica_has_a_process_and_the_launcher_holds_none(world):
    w = world
    dep = w.dep
    assert dep.replicas == {} and dep.supervisor is None
    assert dep.server.local_replicas == {}
    assert len(set(w.pids)) == 4 and os.getpid() not in w.pids
    assert set(w.pids) <= children_of(os.getpid())
    book = w.cfg.replicas.addresses
    assert sorted(book) == NAMES and len(set(book.values())) == 4
    assert w.cfg.replicas.supervisor_address == book["replica-0"]
    assert f"{w.cfg.transport.host}:{w.cfg.transport.port}" not in (
        book.values())
    for child in dep.hosts.children:
        with open(f"/proc/{child.proc.pid}/environ", "rb") as f:
            env = f.read().split(b"\0")
        assert b"JAX_PLATFORMS=cpu" in env
        with open(f"/proc/{child.proc.pid}/cmdline", "rb") as f:
            argv = f.read().split(b"\0")
        assert b"--die-with-parent" in argv and b"dds_tpu.run" in argv
        with open(os.path.join(dep.hosts.dir, f"{child.name}.json")) as f:
            conf = json.load(f)
        assert conf["replicas"]["local"] == [child.name]
        assert conf["replicas"]["addresses"] == book
        assert conf["transport"]["replica_processes"] is False
        assert conf["proxy"]["crypto_backend"] == "cpu"
        assert conf["client"]["nr_of_operations"] == 0


def test_what_launching_the_children_took_is_a_span(world):
    (span,) = world.launch_spans
    assert span.meta["children"] == 4 and 0 < span.dur_ms < 120_000


# ---------------- (f) the children's protocol counters, summed in one place


async def _write_in_flight(w, key: str, value: list) -> None:
    """A write that has reached replica-0 and nobody else yet: what a
    coordinator's `Write` leaves while its other frames are on their way.
    Sent as a coordinator sends it: the tag asked first, then one above."""
    cfg = w.cfg
    net = TcpNet("127.0.0.1", 0, frame_secret=FRAME_SECRET.encode())
    await net.start()
    secret = cfg.security.abd_mac_secret.encode()
    target = f"{cfg.replicas.addresses['replica-0']}/replica-0"
    me = net.local_addr("writer-in-flight")
    tagged: asyncio.Future = asyncio.get_event_loop().create_future()

    async def handle(sender, msg):
        if isinstance(msg, M.TagReply) and not tagged.done():
            tagged.set_result(msg.tag)

    net.register(me, handle)
    try:
        nonce = sigs.generate_nonce()
        net.send(me, target, M.ReadTag(key, nonce))
        held = await asyncio.wait_for(tagged, 5.0)
        tag = M.ABDTag(held.seq + 1, "writer-in-flight")
        net.send(me, target, M.Write(
            tag, key, value, sigs.abd_signature(secret, value, tag, nonce),
            nonce))
        await asyncio.sleep(0.2)
    finally:
        await net.stop()


async def _child_counters(w) -> dict:
    """Each child's own `/metrics`, summed: (family, labels) -> value."""
    from dds_tpu.http.miniserver import http_request

    total: dict = {}
    for child in w.dep.hosts.children:
        with open(child.log_path) as f:
            said = [ln for ln in f if ln.startswith("serving on ")]
        host, port = said[0].split()[2].rsplit(":", 1)
        st, raw = await http_request(host, int(port), "GET", "/metrics")
        assert st == 200
        for name in PROTOCOL_FAMILIES:
            for labels, value in parse_samples(raw.decode(), name):
                at = (name, tuple(sorted(labels.items())))
                total[at] = total.get(at, 0.0) + value
    return total


def test_the_launcher_reads_the_sum_of_the_childrens_protocol_counters(world):
    w = world
    at = ("dds_read_batch_keys_total", (("outcome", "written_back"),))

    def here(key):
        name, labels = key
        return (metrics.value(name, **dict(labels)) or 0.0) - w.before.get(
            key, 0.0)

    async def go():
        i = 5
        w.plain[i] += 3
        row = list(w.rows[i])
        row[2] = str(enc(w.plain[i], w.rng))
        for _ in range(12):
            # the quorum that answers the re-read is the first three of
            # four: replica-0 is among them three times in four
            await _write_in_flight(w, w.keys[i], row)
            w.rows[i] = list(row)
            total = await w.sum_all()
            await w.dep.hosts.pull()
            if here(at) > 0:
                break
            # the round closed without replica-0: the write is still in
            # flight, and the next one goes one tag above it
        assert here(at) > 0
        # the write a read has seen is the store's: every later answer has it
        assert await w.get(i) == row
        total = await w.sum_all()
        assert total == fold((int(r[2]) for r in w.rows), NSQ)
        assert dec(total) == sum(w.plain)
        await w.dep.hosts.pull()
        theirs = await _child_counters(w)
        assert theirs[at] > 0
        # every SumAll's tag round reached all four replicas
        assert sum(v for (name, _), v in theirs.items()
                   if name == "dds_replica_tag_vector_total") >= 4 * 8
        assert {key: here(key) for key in theirs} == theirs
        assert metrics.value("dds_process_cpu_seconds_total",
                             role="replica") > 0
        assert metrics.value("dds_process_cpu_seconds_total",
                             role="proxy") > 0

    w.run(go())
    # what describes a process stays in it: no replica ran on this loop
    assert w.replica_tenant_seconds() == w.loop_before


# ---------------------------------- (d) a child that is killed, (e) the end


def test_a_killed_child_opens_its_breaker_and_three_answer_exactly(world):
    w = world
    victim = w.dep.hosts.children[3]
    os.kill(victim.proc.pid, signal.SIGKILL)

    def state():
        return next((s for n, s in w.dep.server.abd.breaker_states().items()
                     if n.endswith("/replica-3")), "closed")

    async def go():
        t0 = time.perf_counter()
        while state() == "closed" and time.perf_counter() - t0 < 30:
            i = w.rng.randrange(ROWS)
            assert await w.get(i) == w.rows[i]
        assert state() in ("open", "half_open")
        for i in (1, 2, 20):
            w.plain[i] += 11
            new = str(enc(w.plain[i], w.rng))
            assert await w.update(i, 2, new) == 200
            w.rows[i][2] = new
            assert await w.get(i) == w.rows[i]
            total = await w.sum_all()
            assert total == fold((int(r[2]) for r in w.rows), NSQ)
            assert dec(total) == sum(w.plain)
        assert await w.mult_all() == fold((int(r[3]) for r in w.rows), RN)
        assert state() in ("open", "half_open")
        await w.dep.hosts.pull()     # a round goes on without the dead one

    w.run(go())
    assert not alive(victim.proc.pid)
    assert all(alive(pid) for pid in w.pids[:3])


def test_after_stop_no_child_is_alive(world):
    directory = world.dep.hosts.dir
    assert os.path.isdir(directory)
    world.stop()
    assert not any(alive(pid) for pid in world.pids)
    assert not set(world.pids) & children_of(os.getpid())   # reaped too
    assert not os.path.exists(directory)


_LAUNCHER = """
import asyncio, json, sys
sys.path.insert(0, {root!r})
from tests.test_replica_processes import _cfg
from dds_tpu.run import launch

async def go():
    dep = await launch(_cfg())
    print(json.dumps([dep.hosts.dir] + [c.proc.pid
                                        for c in dep.hosts.children]),
          flush=True)
    await asyncio.Event().wait()

asyncio.run(go())
"""


def test_a_launcher_killed_with_sigkill_leaves_no_child_alive():
    proc = subprocess.Popen(
        [sys.executable, "-c", _LAUNCHER.format(root=ROOT)], cwd=ROOT,
        stdout=subprocess.PIPE, stderr=subprocess.DEVNULL, text=True,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    directory = None
    try:
        directory, *pids = json.loads(proc.stdout.readline())
        assert len(pids) == 4 and all(alive(pid) for pid in pids)
        proc.kill()
        proc.wait()
        t0 = time.perf_counter()
        while any(alive(p) for p in pids) and time.perf_counter() - t0 < 2:
            time.sleep(0.02)
        assert not any(alive(pid) for pid in pids)
    finally:
        proc.kill()
        proc.wait()
        proc.stdout.close()
        if directory:   # nobody was left to take the children's files away
            shutil.rmtree(directory, ignore_errors=True)


# ------------------------------------------ (g) the field off changes nothing


def test_the_field_off_starts_no_process():
    from dds_tpu.run import launch
    from dds_tpu.utils.config import DDSConfig

    assert DDSConfig().transport.replica_processes is False

    async def go():
        cfg = DDSConfig()
        cfg.proxy.port = 0
        before = children_of(os.getpid())
        dep = await launch(cfg)
        try:
            assert dep.hosts is None and len(dep.replicas) == 9
            assert children_of(os.getpid()) == before
        finally:
            await dep.stop()

    asyncio.run(go())


@pytest.mark.parametrize("spoil,words", [
    (lambda cfg: setattr(cfg.transport, "kind", "memory"), "tcp"),
    (lambda cfg: setattr(cfg.replicas, "local", ["replica-0"]),
     "replicas.local"),
    (lambda cfg: setattr(cfg.shard, "enabled", True), "sharded"),
    (lambda cfg: setattr(cfg.security, "node_public_keys", {"a:1": "00"}),
     "node_public_keys"),
])
def test_what_the_launcher_cannot_place_is_refused_before_any_child(
        spoil, words):
    from dds_tpu.run import launch

    cfg = _cfg()
    spoil(cfg)
    before = children_of(os.getpid())
    with pytest.raises(ValueError, match=words):
        asyncio.run(launch(cfg))
    assert children_of(os.getpid()) == before


# ----------------------------------------------- the transport between them


def test_a_send_to_a_peer_that_is_not_up_fails_and_the_next_one_arrives():
    """A frame to a port nobody listens on is a failed send and no
    exception; once a peer listens there the next frame opens a connection
    and arrives; when that peer goes its connection goes with it, and a
    peer that returns is reached by the first frame sent to it."""

    async def go():
        a = TcpNet("127.0.0.1", 0)
        await a.start()
        b = TcpNet("127.0.0.1", 0)
        await b.start()
        port = b.port
        await b.stop()
        got = []

        async def handle(sender, msg):
            got.append(msg)

        async def until(cond):
            for _ in range(300):
                if cond():
                    return
                await asyncio.sleep(0.01)
            assert cond()

        dest = f"127.0.0.1:{port}/peer"
        src = a.local_addr("me")
        try:
            a.send(src, dest, M.Read("k", 1))         # nobody there
            await asyncio.sleep(0.1)
            assert a._conns == {} and got == []
            for nonce in (2, 3):
                b = TcpNet("127.0.0.1", port)
                await b.start()
                b.register(b.local_addr("peer"), handle)
                a.send(src, dest, M.Read("k", nonce))
                await until(lambda: [m.nonce for m in got] == list(
                    range(2, nonce + 1)))
                assert list(a._conns) == [f"127.0.0.1:{port}"]
                await b.stop()                        # the peer goes
                await until(lambda: a._conns == {})
        finally:
            await a.stop()
        assert not a._watchers

    asyncio.run(go())
