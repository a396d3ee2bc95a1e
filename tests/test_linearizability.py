"""Randomized linearizability / atomicity properties of the BFT-ABD core.

The reference verifies its protocol only operationally (SURVEY.md §4);
these are the property tests it never had. ABD with the read write-back
phase implements an *atomic* (linearizable) multi-writer register: we
record operation intervals in real time and check the two violations a
register can exhibit:

- a read returning a value whose write started after the read ended
  (reading from the future), and
- new/old inversion: once a read returns a write W2 that is real-time
  ordered after W1, no later read may return W1 again.

Also exercises Trudy mid-workload: crashes and compromises within the
f=2 budget must not break the properties or liveness.

The chaos suite at the bottom re-runs the same history checker under
seeded ChaosNet fault schedules (partition during writes, delay storms
during proactive recovery, duplicate/reorder during tag reads, lossy and
corrupting links, mixed Nemesis attacks): linearizability must hold
THROUGH the faults and the cluster must converge after heal. Schedules
are capped by short intervals (ms-scale delays, sub-second partitions)
and per-op deadline budgets, so the whole suite stays inside the tier-1
time budget.
"""

import asyncio
import itertools
import random
import time

import pytest

from dds_tpu.core.chaos import ChaosNet, LinkFaults
from dds_tpu.core.transport import InMemoryNet
from dds_tpu.malicious.trudy import Nemesis, Trudy
from dds_tpu.utils.retry import Deadline, RetryPolicy, retry, retry_deadline
from tests.test_core import Cluster, run


KEY = "LINREG"


class Recorder:
    def __init__(self):
        self.ops = []

    def record(self, kind, value, start, end):
        self.ops.append({"kind": kind, "value": value, "start": start, "end": end})


def check_atomic_register(ops):
    """Assert the recorded history is consistent with an atomic register.

    Conservative (sound, incomplete) checks that need no search:
    1. every read's value was None or written by some write that STARTED
       before the read ENDED;
    2. if write W1 ENDED before write W2 STARTED (real-time ordered) then
       after any read returns W2's value, no read that STARTS after that
       read ENDS may return W1's value (new/old inversion).
    """
    writes = {o["value"]: o for o in ops if o["kind"] == "write"}
    reads = sorted(
        (o for o in ops if o["kind"] == "read"), key=lambda o: o["start"]
    )
    for r in reads:
        if r["value"] is None:
            continue
        w = writes.get(r["value"])
        assert w is not None, f"read returned a never-written value {r['value']}"
        assert w["start"] <= r["end"], "read returned a value from the future"

    for r1, r2 in itertools.combinations(reads, 2):
        # reads sorted by start; require real-time ordering r1 before r2
        if r1["end"] > r2["start"]:
            continue
        if r1["value"] is None or r2["value"] is None:
            continue
        w1, w2 = writes[r1["value"]], writes[r2["value"]]
        if w2["end"] < w1["start"]:
            raise AssertionError(
                f"new/old inversion: read@{r1['start']:.4f} saw {r1['value']} "
                f"but later read@{r2['start']:.4f} saw older {r2['value']}"
            )


async def _writer(cluster, rec, wid, n_writes, rng):
    """Writes with the proxy's retry discipline (the reference wraps every
    writeSet in FutureRetry — crashed coordinators are retried elsewhere
    while suspicion accrues, `DDSRestServer.scala:178`)."""
    for i in range(n_writes):
        value = [f"w{wid}-{i}"]
        t0 = time.monotonic()
        await retry(lambda: cluster.client.write_set(KEY, value), 0.01, 5)
        rec.record("write", f"w{wid}-{i}", t0, time.monotonic())
        await asyncio.sleep(rng.uniform(0, 0.002))


async def _reader(cluster, rec, n_reads, rng):
    for _ in range(n_reads):
        t0 = time.monotonic()
        got = await retry(lambda: cluster.client.fetch_set(KEY), 0.01, 5)
        rec.record("read", got[0] if got else None, t0, time.monotonic())
        await asyncio.sleep(rng.uniform(0, 0.002))


def test_concurrent_writers_atomic_register():
    async def go():
        rng = random.Random(11)
        c = Cluster()
        rec = Recorder()
        await asyncio.gather(
            _writer(c, rec, 0, 6, rng),
            _writer(c, rec, 1, 6, rng),
            _writer(c, rec, 2, 6, rng),
            _reader(c, rec, 12, rng),
            _reader(c, rec, 12, rng),
        )
        check_atomic_register(rec.ops)
        # convergence: a final read agrees with a quorum of replicas
        final = await c.client.fetch_set(KEY)
        await c.net.quiesce()
        holders = [
            r for r in c.replicas.values()
            if r.repository.get(KEY, (None, None))[1] == final
        ]
        assert len(holders) >= 5

    run(go())


def test_atomicity_checker_catches_inversion():
    """The checker itself must reject a known-bad history."""
    bad = [
        {"kind": "write", "value": "old", "start": 0.0, "end": 0.1},
        {"kind": "write", "value": "new", "start": 0.2, "end": 0.3},
        {"kind": "read", "value": "new", "start": 0.4, "end": 0.5},
        {"kind": "read", "value": "old", "start": 0.6, "end": 0.7},
    ]
    try:
        check_atomic_register(bad)
    except AssertionError:
        return
    raise AssertionError("checker accepted a new/old inversion")


def test_crash_faults_mid_workload():
    """Trudy crashes f=2 replicas between writes; properties + liveness hold."""

    async def go():
        rng = random.Random(23)
        c = Cluster()
        c.client.cfg.request_timeout = 0.2  # fast retry on crashed coordinators
        rec = Recorder()
        trudy = Trudy(c.net, c.active, max_faults=2, rng=random.Random(5))

        async def attacker():
            await asyncio.sleep(0.01)
            trudy.trigger("crash")

        await asyncio.gather(
            _writer(c, rec, 0, 8, rng),
            _reader(c, rec, 16, rng),
            attacker(),
        )
        check_atomic_register(rec.ops)
        # single writer: its last write is the register's final value
        assert await c.client.fetch_set(KEY) == ["w0-7"]

    run(go())


def test_byzantine_faults_mid_workload():
    """Compromised replicas (valid MAC keys, garbage behavior) within f=2
    cannot corrupt reads: every read still satisfies the register checks
    and returns only genuinely-written values."""

    async def go():
        rng = random.Random(31)
        c = Cluster()
        rec = Recorder()
        trudy = Trudy(c.net, c.active, max_faults=2, rng=random.Random(9))

        async def attacker():
            await asyncio.sleep(0.005)
            trudy.trigger("byzantine")

        await asyncio.gather(
            _writer(c, rec, 0, 6, rng),
            _writer(c, rec, 1, 6, rng),
            _reader(c, rec, 14, rng),
            attacker(),
        )
        check_atomic_register(rec.ops)

    run(go())


# ---------------------------------------------------------------------------
# the batched read (IReadBatch): per key still an atomic register
# ---------------------------------------------------------------------------

KEYS = ["LINREG-0", "LINREG-1", "LINREG-2"]


async def _key_writer(cluster, recs, wid, n_writes, rng):
    for i in range(n_writes):
        k = rng.randrange(len(KEYS))
        value = [f"w{wid}-{i}"]
        t0 = time.monotonic()
        await retry(lambda: cluster.client.write_set(KEYS[k], value), 0.01, 5)
        recs[k].record("write", f"w{wid}-{i}", t0, time.monotonic())
        await asyncio.sleep(rng.uniform(0, 0.002))


async def _batch_reader(cluster, recs, n_reads, rng, single_every=0):
    """Reads all keys as one batch; every `single_every`-th read is a
    single read of one key instead, so both entry points share a history."""
    for n in range(n_reads):
        t0 = time.monotonic()
        if single_every and n % single_every == 0:
            k = rng.randrange(len(KEYS))
            got = await retry(
                lambda: cluster.client.fetch_set(KEYS[k]), 0.01, 5)
            recs[k].record("read", got[0] if got else None, t0,
                           time.monotonic())
        else:
            out = await retry(
                lambda: cluster.client.fetch_sets_attributed(KEYS), 0.01, 5)
            t1 = time.monotonic()
            for rec, (got, _tag, _coord) in zip(recs, out):
                rec.record("read", got[0] if got else None, t0, t1)
        await asyncio.sleep(rng.uniform(0, 0.002))


@pytest.mark.parametrize("seed,fault,single_every", [
    (41, None, 0), (42, None, 3), (43, "crash", 0), (44, "byzantine", 0),
    (45, "byzantine", 2),
])
def test_batched_reads_keep_every_key_an_atomic_register(
        seed, fault, single_every):
    """Concurrent writers over three keys and readers that read all three
    as one batch (write-backs of the keys in flight included): each key's
    history passes the register checks, with crashes or compromised
    replicas within f = 2 mid-workload too, and a final batch agrees with
    a quorum of replicas on every key."""

    async def go():
        rng = random.Random(seed)
        c = Cluster()
        recs = [Recorder() for _ in KEYS]
        jobs = [
            _key_writer(c, recs, 0, 8, rng),
            _key_writer(c, recs, 1, 8, rng),
            _key_writer(c, recs, 2, 8, rng),
            _batch_reader(c, recs, 12, rng, single_every),
            _batch_reader(c, recs, 12, rng, single_every),
        ]
        if fault:
            trudy = Trudy(c.net, c.active, max_faults=2,
                          rng=random.Random(seed))

            async def attacker():
                await asyncio.sleep(0.005)
                trudy.trigger(fault)

            jobs.append(attacker())
        await asyncio.gather(*jobs)
        for rec in recs:
            check_atomic_register(rec.ops)
        final = await retry(
            lambda: c.client.fetch_sets_attributed(KEYS), 0.01, 5)
        await c.net.quiesce()
        for k, (value, tag, _coord) in zip(KEYS, final):
            holders = [r for r in c.replicas.values()
                       if r.behavior == "healthy"
                       and r.repository.get(k, (None, None)) == (tag, value)]
            assert len(holders) >= 3, (k, len(holders))

    run(go())


# ---------------------------------------------------------------------------
# chaos suite: the SAME atomic-register checker under seeded fault schedules
# ---------------------------------------------------------------------------

# fast, deadline-governed retry for chaos workloads: ops keep retrying
# through a fault window and must complete once it heals, within budget
_CHAOS_POLICY = RetryPolicy(base=0.01, multiplier=2.0, max_delay=0.08)


def chaos_cluster(seed, request_timeout=0.25, **kw):
    net = ChaosNet(InMemoryNet(), seed=seed)
    c = Cluster(net=net, **kw)
    c.client.cfg.request_timeout = request_timeout
    c.client.cfg.breaker_reset = 0.15
    return c, net


async def _chaos_writer(cluster, rec, wid, n_writes, seed, budget=15.0):
    rng = random.Random(seed)
    for i in range(n_writes):
        value = [f"w{wid}-{i}"]
        t0 = time.monotonic()
        dl = Deadline(budget)
        await retry_deadline(
            lambda: cluster.client.write_set(KEY, value, deadline=dl),
            dl, _CHAOS_POLICY, rng=rng,
        )
        rec.record("write", f"w{wid}-{i}", t0, time.monotonic())
        await asyncio.sleep(rng.uniform(0, 0.002))


async def _chaos_reader(cluster, rec, n_reads, seed, budget=15.0):
    rng = random.Random(seed)
    for _ in range(n_reads):
        t0 = time.monotonic()
        dl = Deadline(budget)
        got = await retry_deadline(
            lambda: cluster.client.fetch_set(KEY, deadline=dl),
            dl, _CHAOS_POLICY, rng=rng,
        )
        rec.record("read", got[0] if got else None, t0, time.monotonic())
        await asyncio.sleep(rng.uniform(0, 0.002))


async def _converged_holders(c, expect):
    await c.net.quiesce()
    return [
        r for r in c.replicas.values()
        if r.repository.get(KEY, (None, None))[1] == expect
    ]


@pytest.mark.chaos
def test_chaos_minority_partition_during_writes_linearizable():
    """Schedule 1: a minority partition (2 of 7) opens mid-workload and
    heals on a timer; the remaining quorum keeps serving, every recorded
    history linearizes, and a quorum converges on the final value."""

    async def go():
        c, net = chaos_cluster(seed=101)
        rec = Recorder()

        async def attacker():
            await asyncio.sleep(0.01)
            net.partition(["replica-5", "replica-6"], duration=0.15)

        await asyncio.gather(
            _chaos_writer(c, rec, 0, 5, seed=1),
            _chaos_writer(c, rec, 1, 5, seed=2),
            _chaos_reader(c, rec, 10, seed=3),
            attacker(),
        )
        check_atomic_register(rec.ops)
        final = await c.client.fetch_set(KEY)
        assert len(await _converged_holders(c, final)) >= 5

    run(go())


@pytest.mark.chaos
def test_chaos_quorum_breaking_partition_stalls_then_heals():
    """Schedule 2: partitioning 3 of 7 leaves 4 < quorum — writes STALL
    (no wrong answers) until the timed heal, then complete within their
    deadline budgets; the history stays linearizable throughout."""

    async def go():
        c, net = chaos_cluster(seed=202, request_timeout=0.15)
        rec = Recorder()

        async def attacker():
            await asyncio.sleep(0.01)
            net.partition(
                ["replica-0", "replica-1", "replica-2"], duration=0.3
            )

        await asyncio.gather(
            _chaos_writer(c, rec, 0, 4, seed=4),
            _chaos_reader(c, rec, 6, seed=5),
            attacker(),
        )
        check_atomic_register(rec.ops)
        # single writer: its last write is the register's final value
        assert await c.client.fetch_set(KEY) == ["w0-3"]

    run(go())


@pytest.mark.chaos
def test_chaos_delay_storm_during_proactive_recovery():
    """Schedule 3: jittered delays on EVERY link while the proactive
    recovery timer swaps replicas mid-workload. Linearizability holds,
    and after heal the supervisor converges back to full membership.

    Event-driven (deflaked): the membership assertion waits on the
    supervisor's recovery-complete hook instead of racing stop() against
    an in-flight swap — cancelling recover() mid-swap left a spare
    promoted with the offender not yet demoted (8 active / 1 sentinent),
    the pre-existing 8/10 isolation failure. stop() itself is now
    graceful (awaits the shielded in-flight recovery), and the explicit
    wait asserts the hook resolves within the recovery timeouts."""

    async def go():
        c, net = chaos_cluster(seed=303, proactive=True)
        net.default_faults = LinkFaults(delay=0.002, jitter=0.008)
        c.supervisor.start()
        rec = Recorder()
        await asyncio.gather(
            _chaos_writer(c, rec, 0, 6, seed=6),
            _chaos_reader(c, rec, 10, seed=7),
        )
        net.heal_all()
        assert await c.supervisor.wait_recovery_idle(10.0), (
            "recovery never quiesced after heal"
        )
        await c.supervisor.stop()
        await net.quiesce()
        check_atomic_register(rec.ops)
        # supervisor converged after heal: membership sizes intact
        active = [a for a, _ in c.supervisor.active]
        assert len(active) == len(set(active)) == 7
        assert len(c.supervisor.sentinent) == 2

    run(go())


@pytest.mark.chaos
def test_chaos_duplicate_reorder_during_tag_reads():
    """Schedule 4: duplication + reordering on the proxy<->replica links
    while writes interleave with batched tag reads. Duplicated replies
    must not stuff quorums (votes key by sender), reordered replies must
    not corrupt correlation, and the final tag round agrees with the last
    completed write."""

    async def go():
        c, net = chaos_cluster(seed=404)
        for i in range(7):
            net.set_pair(
                "proxy-0", f"replica-{i}",
                LinkFaults(duplicate=0.3, reorder=0.3),
            )
        rec = Recorder()
        tag_rounds = {"n": 0}

        async def tag_reader():
            rng = random.Random(8)
            for _ in range(8):
                dl = Deadline(15.0)
                tags = await retry_deadline(
                    lambda: c.client.read_tags([KEY], deadline=dl),
                    dl, _CHAOS_POLICY, rng=rng,
                )
                assert len(tags) == 1
                tag_rounds["n"] += 1
                await asyncio.sleep(rng.uniform(0, 0.003))

        await asyncio.gather(
            _chaos_writer(c, rec, 0, 6, seed=9),
            _chaos_reader(c, rec, 8, seed=10),
            tag_reader(),
        )
        check_atomic_register(rec.ops)
        assert tag_rounds["n"] == 8
        await net.quiesce()
        # the quorum-max tag now equals the last completed write's tag
        value, tag = await c.client.fetch_set_tagged(KEY)
        assert value == ["w0-5"]
        assert (await c.client.read_tags([KEY])) == [tag]

    run(go())


@pytest.mark.chaos
def test_chaos_lossy_corrupting_links_linearizable():
    """Schedule 5: 5% drop + 3% payload corruption + jitter on every link.
    Corrupted protocol messages must die at the HMAC/codec layers (never
    surface as values), lost messages are absorbed by retries, and the
    history still linearizes."""

    async def go():
        c, net = chaos_cluster(seed=505)
        net.default_faults = LinkFaults(drop=0.05, corrupt=0.03, jitter=0.003)
        rec = Recorder()
        await asyncio.gather(
            _chaos_writer(c, rec, 0, 5, seed=11),
            _chaos_writer(c, rec, 1, 5, seed=12),
            _chaos_reader(c, rec, 8, seed=13),
        )
        check_atomic_register(rec.ops)
        # every read surfaced a genuinely-written value (checker asserts
        # this) and the workload completed despite the loss schedule
        assert sum(1 for o in rec.ops if o["kind"] == "write") == 10
        net.heal_all()
        final = await c.client.fetch_set(KEY)
        assert len(await _converged_holders(c, final)) >= 5

    run(go())


@pytest.mark.chaos
def test_chaos_nemesis_mixed_attack_schedule():
    """Schedule 6: Nemesis drives a mixed attack — one replica compromised
    (byzantine), one partitioned, junk floods at a third — all within the
    f=2 budget, healed mid-workload. Linearizability and liveness hold."""

    async def go():
        c, net = chaos_cluster(seed=606)
        rec = Recorder()
        nem = Nemesis(net, c.active, max_faults=1, rng=random.Random(42),
                      flood_messages=15)

        async def attacker():
            await asyncio.sleep(0.005)
            byz = nem.trigger("byzantine")
            # partition a DIFFERENT replica so total faults stay at f=2
            nem.replicas = [a for a in c.active if a not in byz]
            cut = nem.trigger("partition")
            nem.replicas = [a for a in c.active if a not in byz + cut]
            nem.trigger("flood")
            await asyncio.sleep(0.12)
            nem.trigger("heal")

        await asyncio.gather(
            _chaos_writer(c, rec, 0, 5, seed=14),
            _chaos_reader(c, rec, 8, seed=15),
            attacker(),
        )
        check_atomic_register(rec.ops)
        assert await c.client.fetch_set(KEY) == ["w0-4"]

    run(go())


def test_concurrent_sumalls_under_a_write_see_the_old_or_the_new_total():
    """Aggregate linearizability under concurrent folds: while a stored
    key's value is rewritten (v_old -> v_new), a storm of concurrent
    SumAlls must each decrypt to sum_old or sum_new, never anything
    else. Each request's operand snapshot comes from its own
    quorum-validated read, which this test pins down."""
    import json

    from dds_tpu.models import HEKeys
    from dds_tpu.models.backend import TpuBackend
    from tests.test_rest import call, rest_stack

    keys = HEKeys.generate(paillier_bits=512, rsa_bits=512)
    pk = keys.psse.public

    async def go():
        async with rest_stack(n=4, quorum=3) as (server, _, _):
            server.backend = TpuBackend(pallas=False, min_device_batch=8)
            base_vals = [10, 20, 30, 40]
            row_keys = []
            for v in base_vals:
                st, body = await call(
                    server, "POST", "/PutSet", {"contents": [str(pk.encrypt(v))]}
                )
                assert st == 200
                row_keys.append(body.decode())

            old_total = sum(base_vals)
            new_last = 999
            new_total = old_total - base_vals[-1] + new_last
            target = f"/SumAll?position=0&nsqr={pk.nsquare}"

            async def storm(n):
                rs = await asyncio.gather(*(call(server, "GET", target)
                                            for _ in range(n)))
                out = []
                for st, data in rs:
                    assert st == 200
                    out.append(keys.psse.decrypt(int(json.loads(data)["result"])))
                return out

            async def rewrite():
                st, _ = await call(
                    server, "PUT",
                    f"/WriteElement/{row_keys[-1]}?position=0",
                    {"value": str(pk.encrypt(new_last))},
                )
                assert st == 200

            sums, _ = await asyncio.gather(storm(12), rewrite())
            allowed = {old_total, new_total}
            assert set(sums) <= allowed, (sums, allowed)
            # afterwards every aggregate sees the new value
            settled = await storm(4)
            assert set(settled) == {new_total}

    asyncio.run(go())
