"""Constellation sharding-plane tests.

Covers the acceptance surface of the shard plane: deterministic signed
shard maps and split-locality, point-op routing isolation, epoch fencing
(typed WrongShard rejections at coordinator, storage, and tag-batch
layers), scatter-gather aggregate equivalence (bit-for-bit vs a single
shard over IDENTICAL ciphertexts), a live Aegis-verified split under a
seeded ChaosNet schedule with a partition healing mid-reshard (zero
stale-epoch writes accepted, anti-entropy convergence, per-group
linearizability, zero Watchtower quorum-intersection violations per
group), the abort path (old map restored + flight incident), and the
/shards + /health + /metrics operator surface.
"""

import asyncio
import json
import random
import time

import pytest

from dds_tpu.core import messages as M
from dds_tpu.core.chaos import ChaosNet
from dds_tpu.core.errors import WrongShardError
from dds_tpu.core.transport import InMemoryNet
from dds_tpu.http.miniserver import http_request
from dds_tpu.http.server import DDSRestServer, ProxyConfig
from dds_tpu.shard import (
    ReshardAborted,
    ShardMap,
    build_constellation,
    moved_keys,
)
from dds_tpu.utils.retry import Deadline, RetryPolicy, retry_deadline
from tests.test_core import run
from tests.test_linearizability import Recorder, check_atomic_register

pytestmark = pytest.mark.shard

SECRET = b"intranet-abd-secret"
_POLICY = RetryPolicy(base=0.01, multiplier=2.0, max_delay=0.08)


def constellation(S=2, net=None, seed=7, **kw):
    net = net or InMemoryNet()
    kw.setdefault("n_active", 4)
    kw.setdefault("n_sentinent", 1)
    kw.setdefault("quorum", 3)
    return build_constellation(net, shard_count=S, vnodes_per_group=8,
                               seed=seed, **kw), net


# ---------------------------------------------------------------- shard map


def test_shardmap_deterministic_signed_and_tamperproof():
    m1 = ShardMap.build(["s0", "s1", "s2"], 8).sign(SECRET)
    m2 = ShardMap.build(["s2", "s1", "s0"], 8).sign(SECRET)
    assert m1.vnodes == m2.vnodes  # group order never changes the ring
    keys = [f"K{i}" for i in range(256)]
    assert [m1.owner(k) for k in keys] == [m2.owner(k) for k in keys]
    assert m1.verify(SECRET) and not m1.verify(b"forged-secret")
    # wire round-trip preserves the signature
    assert ShardMap.from_wire(m1.to_wire()).verify(SECRET)
    # a tampered map (vnode re-homed) fails verification
    forged = ShardMap(m1.epoch, tuple(
        (p, "s0") for p, _ in m1.vnodes), m1.groups, m1.signature)
    assert not forged.verify(SECRET)
    # epochs only move forward at the manager
    from dds_tpu.shard import ShardManager

    mgr = ShardManager(m1, SECRET)
    with pytest.raises(ValueError):
        mgr.activate(m1)  # same epoch


def test_shardmap_split_moves_only_victim_keys():
    m1 = ShardMap.build(["s0", "s1"], 8).sign(SECRET)
    m2 = m1.split("s1", "s2").sign(SECRET)
    assert m2.epoch == m1.epoch + 1
    keys = [f"K{i}" for i in range(512)]
    moved = moved_keys(m1, m2, keys)
    assert moved  # a split that moves nothing split nothing
    for k in moved:
        assert m1.owner(k) == "s1" and m2.owner(k) == "s2"
    # everything that didn't move kept its exact owner
    for k in keys:
        if k not in moved:
            assert m1.owner(k) == m2.owner(k)


def test_shardmap_merge_inverts_split_on_random_rings():
    """Property: merge(split(m)) == m (epoch aside) for random rings —
    the new group's vnodes are retired and every key it briefly owned
    falls back to its original arc, so ownership is bit-identical."""
    rng = random.Random(0xD5)
    keys = [f"P{i}" for i in range(256)]
    for trial in range(24):
        n_groups = rng.randint(1, 5)
        groups = [f"g{trial}x{i}" for i in range(n_groups)]
        # a one-vnode ring has no splittable arc (its own predecessor)
        vpg = rng.choice([2, 4, 8, 16] if n_groups == 1 else [1, 2, 4, 8])
        m = ShardMap.build(groups, vpg)
        victim = rng.choice(groups)
        m2 = m.split(victim, "sNEW")
        m3 = m2.merge("sNEW")
        assert m3.epoch == m.epoch + 2
        assert m3.vnodes == m.vnodes
        assert m3.groups == m.groups
        assert [m3.owner(k) for k in keys] == [m.owner(k) for k in keys]


def test_shardmap_merge_moves_only_victim_keys():
    """Merge locality: the only keys whose owner changes are those the
    victim owned, and they land exactly on the ring-successor absorbers
    the map itself advertises."""
    rng = random.Random(0xA7)
    keys = [f"M{i}" for i in range(512)]
    for trial in range(16):
        n_groups = rng.randint(2, 6)
        groups = [f"h{trial}x{i}" for i in range(n_groups)]
        m1 = ShardMap.build(groups, rng.choice([2, 4, 8]))
        victim = rng.choice(groups)
        m2 = m1.merge(victim)
        assert m2.epoch == m1.epoch + 1
        assert victim not in m2.groups
        moved = moved_keys(m1, m2, keys)
        absorbers = m1.absorbers(victim)
        for k in moved:
            assert m1.owner(k) == victim
            assert m2.owner(k) in absorbers
        for k in keys:
            if k not in moved:
                assert m1.owner(k) == m2.owner(k)
    # degenerate shapes refuse instead of corrupting the ring
    lone = ShardMap.build(["s0"], 4)
    with pytest.raises(ValueError):
        lone.merge("s0")
    with pytest.raises(ValueError):
        ShardMap.build(["s0", "s1"], 4).merge("sX")


def test_shardmap_merge_signed_manifest_across_epoch_bump():
    """The merge result signs/verifies like any other map, survives a
    wire round-trip, rejects tampering, and activates at the manager
    across the epoch bump — while the unsigned intermediate does not."""
    from dds_tpu.shard import ShardManager

    m1 = ShardMap.build(["s0", "s1", "s2"], 8).sign(SECRET)
    merged = m1.merge("s2")
    assert not merged.verify(SECRET)  # unsigned intermediate
    signed = merged.sign(SECRET)
    assert signed.verify(SECRET) and not signed.verify(b"forged")
    rt = ShardMap.from_wire(signed.to_wire())
    assert rt.verify(SECRET) and rt.epoch == m1.epoch + 1
    mgr = ShardManager(m1, SECRET)
    mgr.activate(rt)
    assert mgr.epoch == m1.epoch + 1
    with pytest.raises(ValueError):
        mgr.activate(rt)  # epochs only move forward


def test_shardmap_relabel_is_arc_identical_takeover():
    m1 = ShardMap.build(["s0", "s1", "s2"], 8).sign(SECRET)
    m2 = m1.relabel("s1", "s9")
    assert m2.epoch == m1.epoch + 1
    assert "s1" not in m2.groups and "s9" in m2.groups
    assert [p for p, _ in m2.vnodes] == [p for p, _ in m1.vnodes]
    keys = [f"T{i}" for i in range(256)]
    for k in keys:
        old, new = m1.owner(k), m2.owner(k)
        assert new == ("s9" if old == "s1" else old)
    with pytest.raises(ValueError):
        m1.relabel("sX", "s9")
    with pytest.raises(ValueError):
        m1.relabel("s1", "s0")


# ------------------------------------------------------------ point routing


def test_point_ops_route_to_exactly_one_group():
    async def go():
        const, net = constellation(S=2)
        r = const.router
        keys = [f"ROUTE-{i}" for i in range(12)]
        for k in keys:
            assert await r.write_set(k, [k]) == k
        for k in keys:
            assert await r.fetch_set(k) == [k]
        await net.quiesce()
        owners = {r.owner(k) for k in keys}
        assert owners == {"s0", "s1"}  # the sample spans both groups
        for k in keys:
            owner = r.owner(k)
            for g in const.groups:
                holders = [
                    n for n in g.replicas.values()
                    if n.repository.get(k, (None, None))[1] == [k]
                ]
                if g.gid == owner:
                    assert len(holders) >= g.quorum_size
                else:
                    assert not holders, (k, g.gid)
        await const.stop()

    run(go())


def test_router_read_tags_scatter_and_unchanged_identity():
    async def go():
        const, net = constellation(S=2)
        r = const.router
        keys = sorted(f"TAGS-{i}" for i in range(8))
        for k in keys:
            await r.write_set(k, [k])
        assert len(r.partition_keys(keys)) == 2
        tags = await r.read_tags(keys)
        # scattered per-group rounds agree with per-key quorum reads
        for k, t in zip(keys, tags):
            _, tag = await r.fetch_set_tagged(k)
            assert t == tag
        # all-fresh cached vector comes back BY IDENTITY even though each
        # group only attested its own slice
        cached = list(tags)
        again = await r.read_tags(keys, cached_tags=cached,
                                  fingerprint=b"ignored-by-router")
        assert again is cached
        await const.stop()

    run(go())


# ------------------------------------------------------------ epoch fencing


def _remap_all_to(smap, gid, epoch=None):
    """A forged-free epoch+1 map assigning every vnode to `gid`."""
    return ShardMap(
        epoch if epoch is not None else smap.epoch + 1,
        tuple((p, gid) for p, _ in smap.vnodes), (gid,),
    ).sign(SECRET)


@pytest.mark.parametrize("S", [1, 2, 3])
def test_router_batched_read_partitions_by_owner_and_keeps_request_order(S):
    """`fetch_sets_attributed` on the router: one `IReadBatch` a group that
    owns a key of the batch, results in the caller's order, each key's
    coordinator one of its OWN group's replicas."""

    async def go():
        const, net = constellation(S=S)
        r = const.router
        keys = [f"BATCH-{i}" for i in range(10)]
        for k in keys:
            await r.write_set(k, [k])
        groups = r.partition_keys(keys)
        assert len(groups) == S
        from dds_tpu.obs.metrics import metrics

        def rounds():
            return sum(metrics.value("dds_read_batch_rounds_total",
                                     shard=g) or 0.0 for g in r.clients)

        before = rounds()
        out = await r.fetch_sets_attributed(keys)
        assert rounds() - before == S
        for k, (value, tag, coord) in zip(keys, out):
            assert value == [k]
            assert (value, tag) == await r.fetch_set_tagged(k)
            assert coord in const.group(r.owner(k)).replicas
        assert await r.fetch_sets_attributed([]) == []
        await const.stop()

    run(go())


def test_epoch_fence_rejects_a_batch_that_holds_a_foreign_key():
    """The coordinator fences `IReadBatch` per key as it fences `IRead`:
    one key the group no longer owns and the batch is answered
    `WrongShard` (signed, no suspicion), and nothing of it is served."""

    async def go():
        const, net = constellation(S=2, n_sentinent=0)
        r = const.router
        smap = const.manager.current()
        mine = [k for k in (f"F{i}" for i in range(64))
                if smap.owner(k) == "s1"][:3]
        for k in mine:
            await r.write_set(k, ["v0"])
        const.group("s1").state.install(_remap_all_to(smap, "s0"))
        with pytest.raises(WrongShardError) as err:
            await r.fetch_sets_attributed(mine)
        assert err.value.key in mine
        assert not any(const.group("s1").client.replicas.suspicions().values())
        await const.stop()

    run(go())


def test_epoch_fence_rejects_stale_route_then_retry_lands():
    async def go():
        const, net = constellation(S=2, n_sentinent=0)
        r = const.router
        smap = const.manager.current()
        key = next(k for k in (f"F{i}" for i in range(64))
                   if smap.owner(k) == "s1")
        await r.write_set(key, ["v0"])
        m2 = _remap_all_to(smap, "s0")
        const.group("s1").state.install(m2)  # freeze: s1 fences, router stale
        with pytest.raises(WrongShardError):
            await r.write_set(key, ["v1"])
        with pytest.raises(WrongShardError):
            await r.read_tags([key])
        from dds_tpu.obs.metrics import metrics

        assert (metrics.value("dds_wrong_shard_retries_total", shard="s1")
                or 0) >= 2
        # no suspicion accrued: the fencing replicas stay fully trusted
        assert not any(const.group("s1").client.replicas.suspicions().values())
        # activation makes the SAME logical op succeed on the new owner
        const.group("s0").state.install(m2)
        const.manager.activate(m2)
        await r.write_set(key, ["v1"])
        assert await r.fetch_set(key) == ["v1"]
        await net.quiesce()
        for n in const.group("s1").replicas.values():
            assert n.repository.get(key, (None, None))[1] != ["v1"]
        await const.stop()

    run(go())


def test_storage_layer_fence_blocks_raced_write_broadcast():
    """A Write broadcast minted before the freeze must not land after it:
    the storage-layer fence drops it unstored and unacked on every
    replica, so zero stale-epoch writes are ever accepted."""

    async def go():
        const, net = constellation(S=1, n_sentinent=0)
        g = const.group("s0")
        smap = const.manager.current()
        key = "RACED"
        # freeze s0 out of the whole keyspace, then hand-deliver a Write
        # that a pre-freeze coordinator would have broadcast
        g.state.install(_remap_all_to(smap, "sX"))
        from dds_tpu.utils import sigs

        nonce = sigs.generate_nonce()
        tag = M.ABDTag(5, "s0-replica-0")
        sig = sigs.abd_signature(SECRET, ["stale"], tag, nonce)
        victim = g.replicas["s0-replica-1"]
        victim.incoming[nonce] = False  # phase already opened pre-freeze
        await victim.handle("s0-replica-0",
                            M.Write(tag, key, ["stale"], sig, nonce))
        assert key not in victim.repository
        await const.stop()

    run(go())


# ------------------------------------------------- scatter-gather aggregates


def test_scatter_gather_sumall_bit_for_bit_vs_single_shard():
    from dds_tpu.models import HEKeys

    he = HEKeys.generate(paillier_bits=512, rsa_bits=512)
    pk = he.psse.public
    vals = [7, 21, 301, 44, 5, 600]
    rows = [[str(pk.encrypt(v))] for v in vals]  # ONE encryption for both runs

    async def serve(S):
        const, net = constellation(S=S, n_sentinent=0, seed=3)
        server = DDSRestServer(const.router,
                               ProxyConfig(port=0, crypto_backend="cpu"))
        await server.start()
        scatters = {"n": 0}
        orig = server._owner_operands

        def spy(pairs, pos):
            out = orig(pairs, pos)
            if len(out) > 1:
                scatters["n"] += 1
            return out

        server._owner_operands = spy
        for row in rows:
            st, _ = await http_request(
                "127.0.0.1", server.cfg.port, "POST", "/PutSet",
                json.dumps({"contents": row}).encode(), timeout=10.0,
            )
            assert st == 200
        if S > 1:  # the sample must genuinely span shards
            assert len(const.router.partition_keys(
                sorted(server.stored_keys))) > 1
        st, body = await http_request(
            "127.0.0.1", server.cfg.port, "GET",
            f"/SumAll?position=0&nsqr={pk.nsquare}", timeout=30.0,
        )
        assert st == 200
        result = json.loads(body)["result"]
        await server.stop()
        await const.stop()
        return result, scatters["n"]

    async def go():
        single, _ = await serve(1)
        sharded, scattered = await serve(4)
        assert scattered >= 1  # the scatter path really ran
        assert sharded == single  # bit-for-bit: shared modulus, assoc product
        assert he.psse.decrypt(int(sharded)) == sum(vals)

    asyncio.run(go())


# ----------------------------------------------------------- live resharding


async def _retrying_writer(router, rec, key, wid, n, seed, budget=10.0):
    rng = random.Random(seed)
    committed = []
    for i in range(n):
        value = [f"w{wid}-{i}"]
        t0 = time.monotonic()
        dl = Deadline(budget)
        await retry_deadline(
            lambda: router.write_set(key, value, deadline=dl),
            dl, _POLICY, rng=rng, retry_on=(Exception,),
        )
        committed.append((f"w{wid}-{i}", t0))  # value, attempt START time
        rec.record("write", f"w{wid}-{i}", t0, time.monotonic())
        await asyncio.sleep(rng.uniform(0, 0.004))
    return committed


@pytest.mark.chaos
def test_live_split_chaos_partition_heals_mid_reshard():
    """The flagship schedule: a seeded ChaosNet partition cuts one future
    new-group replica while a live split runs, healing mid-reshard; a
    writer hammers a MOVING key throughout. Asserts: the history
    linearizes; zero writes were accepted under the stale epoch (no
    post-freeze value ever appears in the source group, whose pre-split
    state is retained via prune=False); the new group holds the final
    value at quorum; the partitioned straggler converges via Merkle
    anti-entropy; and a Watchtower with per-group geometry reports zero
    quorum-intersection violations."""
    from dds_tpu.obs.watchtower import Watchtower
    from dds_tpu.utils.trace import tracer

    async def go():
        net = ChaosNet(InMemoryNet(), seed=909)
        const, _ = constellation(S=2, net=net, n_sentinent=1, seed=11,
                                 prune=False, ack_timeout=8.0)
        wt = Watchtower(quorum_size=3, n_replicas=4)
        wt.configure(group_geometry={"s0": (3, 4), "s1": (3, 4),
                                     "s2": (3, 4)})
        wt.attach(tracer)
        try:
            r = const.router
            smap = const.manager.current()
            m2 = smap.split("s1", "s2")
            moving = next(k for k in (f"MOVE-{i}" for i in range(128))
                          if smap.owner(k) == "s1" and m2.owner(k) == "s2")
            stable = next(k for k in (f"STAY-{i}" for i in range(128))
                          if smap.owner(k) == "s0")
            await r.write_set(moving, ["w0--1"])
            rec = Recorder()
            split_done = asyncio.Event()
            frozen_at = {"t": None}
            # capture the EXACT fence instant: the moment the source
            # group's state adopts the epoch+1 map
            src_state = const.group("s1").state
            orig_install = src_state.install

            def spy_install(m, force=False):
                orig_install(m, force=force)
                if frozen_at["t"] is None and m.epoch > smap.epoch:
                    frozen_at["t"] = time.monotonic()

            src_state.install = spy_install

            async def do_split():
                await asyncio.sleep(0.03)
                # cut a replica of the FUTURE group s2 so it misses the
                # migration stream; heal mid-reshard on a timer
                net.partition(["s2-replica-2"], duration=0.12)
                await const.split("s1")
                split_done.set()

            writes, _, _ = await asyncio.gather(
                _retrying_writer(r, rec, moving, 0, 10, seed=21),
                _retrying_writer(r, rec, stable, 1, 6, seed=22),
                do_split(),
            )
            assert split_done.is_set()
            assert const.manager.epoch == smap.epoch + 1
            net.heal_all()
            await net.quiesce()
            check_atomic_register(
                [o for o in rec.ops if o["kind"] == "write"]
            )
            final = await r.fetch_set(moving)
            assert final == ["w0-9"]
            # zero stale-epoch writes: a write whose attempt STARTED after
            # the fence installed can only ever commit through the new
            # group (every source-group Write phase fences), so its value
            # must never appear in the (unpruned) source group
            assert frozen_at["t"] is not None
            post_freeze = {v for v, t in writes if t > frozen_at["t"]}
            assert post_freeze  # some writes really landed post-freeze
            src = const.group("s1")
            for n in src.replicas.values():
                held = n.repository.get(moving, (None, None))[1]
                assert held is None or held[0] not in post_freeze, (
                    n.name, held)
            # the new group holds the final value at quorum
            new = const.group("s2")
            await net.quiesce()
            holders = [
                n for n in new.replicas.values()
                if n.repository.get(moving, (None, None))[1] == final
            ]
            assert len(holders) >= new.quorum_size
            # the partitioned straggler converges via anti-entropy pulls
            straggler = new.replicas["s2-replica-2"]
            donors = [e for e in new.active if e != straggler.addr]
            for donor in donors:
                await straggler.antientropy.sync_once(donor)
            assert straggler.repository.get(moving, (None, None))[1] == final
            # per-group audit: no quorum-intersection violations anywhere
            bad = [v for v in wt.verdicts()
                   if v.invariant == "quorum_intersection"]
            assert not bad, bad
        finally:
            wt.detach()
            await const.stop()

    run(go())


def test_reshard_abort_restores_old_map_and_records_incident(tmp_path):
    from dds_tpu.obs.flight import flight

    async def go():
        net = ChaosNet(InMemoryNet(), seed=77)
        const, _ = constellation(S=2, net=net, n_sentinent=0, seed=5,
                                 manifest_timeout=0.3, ack_timeout=0.5)
        flight.configure(dir=str(tmp_path), max_incidents=8,
                         min_interval=0.0)
        try:
            old = const.manager.current()
            key = next(k for k in (f"A{i}" for i in range(64))
                       if old.owner(k) == "s1")
            await const.router.write_set(key, ["pre"])
            # the whole source group is unreachable: no manifest quorum
            net.partition([f"s1-replica-{i}" for i in range(4)])
            with pytest.raises(ReshardAborted):
                await const.split("s1")
            assert const.manager.current() is old
            assert const.manager.state == "stable"
            assert const.group("s1").state.epoch == old.epoch  # rolled back
            incidents = [p for p in tmp_path.iterdir()
                         if "reshard_abort" in p.name]
            assert incidents
            # heal: the old owner serves again, nothing was lost
            net.heal_all()
            assert await const.router.fetch_set(key) == ["pre"]
        finally:
            flight.configure(dir="")
            await const.stop()

    run(go())


# ------------------------------------------------------------ REST surface


def test_shards_health_metrics_routes():
    async def go():
        const, net = constellation(S=2, n_sentinent=0)
        server = DDSRestServer(const.router, ProxyConfig(port=0))
        await server.start()
        try:
            st, body = await http_request(
                "127.0.0.1", server.cfg.port, "POST", "/PutSet",
                json.dumps({"contents": ["x"]}).encode(), timeout=5.0)
            assert st == 200
            st, body = await http_request(
                "127.0.0.1", server.cfg.port, "GET", "/shards", timeout=5.0)
            assert st == 200
            d = json.loads(body)
            assert d["state"] == "stable"
            # the served map is the SIGNED map: verifiable by an operator
            assert ShardMap.from_wire(d["map"]).verify(SECRET)
            assert set(d["groups"]) == {"s0", "s1"}
            st, body = await http_request(
                "127.0.0.1", server.cfg.port, "GET", "/health", timeout=5.0)
            h = json.loads(body)
            assert st == 200 and h["status"] == "ok"
            assert set(h["shards"]) == {"s0", "s1"}
            assert h["shard_epoch"] == 1
            st, body = await http_request(
                "127.0.0.1", server.cfg.port, "GET", "/metrics", timeout=5.0)
            text = body.decode()
            for fam in ("dds_shard_epoch", "dds_shard_groups",
                        "dds_shard_keys", "dds_shard_reshard_state"):
                assert fam in text, fam
        finally:
            await server.stop()
            await const.stop()

    run(go())


def test_launch_constellation_end_to_end():
    from dds_tpu.run import launch
    from dds_tpu.utils.config import DDSConfig

    async def go():
        cfg = DDSConfig()
        cfg.shard.enabled = True
        cfg.shard.count = 2
        cfg.proxy.port = 0
        cfg.recovery.enabled = False
        dep = await launch(cfg)
        try:
            st, key = await http_request(
                "127.0.0.1", dep.server.cfg.port, "POST", "/PutSet",
                json.dumps({"contents": ["a", "b"]}).encode(), timeout=5.0)
            assert st == 200
            st, body = await http_request(
                "127.0.0.1", dep.server.cfg.port, "GET",
                f"/GetSet/{key.decode()}", timeout=5.0)
            assert st == 200 and json.loads(body)["contents"] == ["a", "b"]
            # tcp + shard routes through Meridian, which refuses an
            # unknown fabric role without leaking the bound listener
            bad = DDSConfig()
            bad.shard.enabled = True
            bad.transport.kind = "tcp"
            bad.transport.port = 0
            bad.fabric.role = "bogus"
            with pytest.raises(ValueError):
                await launch(bad)
        finally:
            await dep.stop()

    asyncio.run(go())
