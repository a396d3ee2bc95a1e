"""Lodestone resident-plane tests (dds_tpu/resident).

Covers the ISSUE 9 acceptance surface: per-group pools (content
addressing, doubling, reset-epoch semantics), the fused single-dispatch
sharded fold (bit-for-bit vs the host reference fold, S=4 vs S=1 over
IDENTICAL ciphertexts, exactly one kernel.resident_fold dispatch span
per warm aggregate), write-path incremental ingest (a warm fleet's first
post-write aggregate pays zero ingest; ingest racing an aggregate over
the same values stays bit-for-bit and deadlock-free), the concurrency
races around capacity resets and the epoch that voids kept rows, the
direct-fallback metric accounting fix, the /metrics + /health surface,
and the sentry `resident fold` record contract.
"""

import asyncio
import json
import random
import threading

import pytest

from dds_tpu.http.miniserver import http_request
from dds_tpu.http.server import DDSRestServer, ProxyConfig
from dds_tpu.models import HEKeys
from dds_tpu.obs.metrics import metrics
from dds_tpu.resident import ResidentPlane, ResidentPool
from dds_tpu.utils.config import ResidentConfig
from dds_tpu.utils.trace import tracer

pytestmark = pytest.mark.resident

rng = random.Random(0x10DE)
KEYS = HEKeys.generate(paillier_bits=512, rsa_bits=512)
MODULUS = rng.getrandbits(256) | (1 << 255) | 1


def pyfold(cs, n=MODULUS):
    acc = 1
    for c in cs:
        acc = acc * c % n
    return acc


def _metric(name, **labels):
    return metrics.value(name, **labels) or 0


# ------------------------------------------------------------------- pools


def test_direct_fallback_accounts_direct_not_resident():
    """Satellite fix: an aggregate wider than max_rows host-marshals every
    limb for a direct fold — it must report outcome="direct", not claim
    the operands were resident."""
    pool = ResidentPool(MODULUS, initial_rows=4, max_rows=8, gid="sX")
    cs = [rng.randrange(1, MODULUS) for _ in range(12)]  # > max_rows
    before = {
        o: _metric("dds_cipher_store_total", outcome=o)
        for o in ("resident", "ingested", "direct")
    }
    assert pool.fold(cs) == pyfold(cs)
    assert _metric("dds_cipher_store_total", outcome="direct") \
        == before["direct"] + len(cs)
    assert _metric("dds_cipher_store_total", outcome="resident") \
        == before["resident"]
    assert _metric("dds_cipher_store_total", outcome="ingested") \
        == before["ingested"]
    assert pool.hit_ratio() == 0.0


def test_epoch_invalidates_kept_rows_across_reset():
    """A capacity reset must void row indices resolved against the old
    placement: `patch_rows` refuses an index array of another epoch, and
    the SAME operand list folds correctly after rows were dropped and
    re-placed, its track then naming the new epoch."""
    from dds_tpu.resident.pool import Operands

    pool = ResidentPool(MODULUS, initial_rows=4, max_rows=8)
    cs = Operands(rng.randrange(1, MODULUS) for _ in range(4))
    assert pool.fold(cs) == pyfold(cs)
    kept = cs.track.rows[id(pool)]
    epoch0 = pool.epoch
    assert kept[0] is pool and kept[1] == epoch0
    # overflow with fresh values: forces the reset path, bumping the epoch
    flood = [rng.randrange(1, MODULUS) for _ in range(7)]
    assert pool.fold(flood) == pyfold(flood)
    assert pool.epoch > epoch0 and pool.resets >= 1
    # the old index array must NOT be patched or served
    assert pool.patch_rows(kept[3], epoch0, [], []) is None
    assert pool.patch_rows(kept[3], epoch0, [2], [cs[2]]) is None
    # same list object again: resolved whole against the new placement
    assert pool.fold(cs) == pyfold(cs)
    assert cs.track.rows[id(pool)][1] == pool.epoch


def test_capacity_reset_racing_concurrent_folds():
    """Folds on worker threads racing overflow-induced resets must always
    return the correct product (and never deadlock)."""
    pool = ResidentPool(MODULUS, initial_rows=4, max_rows=16)
    stable = [rng.randrange(1, MODULUS) for _ in range(5)]
    expect = pyfold(stable)
    errors = []

    def folder():
        for _ in range(12):
            try:
                if pool.fold(stable) != expect:
                    errors.append("wrong fold result")
            except Exception as e:  # pragma: no cover - failure surface
                errors.append(repr(e))

    def flooder(seed):
        r = random.Random(seed)
        for _ in range(12):
            flood = [r.randrange(1, MODULUS) for _ in range(13)]
            try:
                if pool.fold(flood) != pyfold(flood):
                    errors.append("wrong flood result")
            except Exception as e:  # pragma: no cover
                errors.append(repr(e))

    threads = [threading.Thread(target=folder) for _ in range(2)] + [
        threading.Thread(target=flooder, args=(i,)) for i in range(2)
    ]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=60)
        assert not t.is_alive(), "fold/reset race deadlocked"
    assert not errors, errors
    assert pool.resets >= 1  # the race actually exercised resets


def test_write_ingest_racing_aggregate_bit_for_bit():
    """Write-path ingest racing a fused fold over the same ciphertexts:
    content addressing means both sides converge on identical rows —
    results stay bit-for-bit the host fold, nothing deadlocks."""
    plane = ResidentPlane(initial_rows=8, max_rows=256)
    parts = [
        (f"s{i}", [rng.randrange(1, MODULUS) for _ in range(6)])
        for i in range(3)
    ]
    allops = [c for _, ops in parts for c in ops]
    expect = pyfold(allops)
    plane.fold_groups(parts, MODULUS)  # establish the pools
    errors = []

    def writer():
        for _ in range(10):
            for gid, ops in parts:
                assert plane.note_write(gid, list(ops)) >= 0
            plane.ingest_pending()

    def folder():
        for _ in range(10):
            try:
                if plane.fold_groups(parts, MODULUS) != expect:
                    errors.append("fused fold diverged under ingest race")
            except Exception as e:  # pragma: no cover
                errors.append(repr(e))

    threads = [threading.Thread(target=writer),
               threading.Thread(target=folder)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=60)
        assert not t.is_alive(), "ingest/fold race deadlocked"
    assert not errors, errors


def test_group_sharding_single_device_is_plain_buffer():
    from dds_tpu.parallel.mesh import group_sharding, make_mesh

    assert group_sharding(None, 0) is None
    assert group_sharding(make_mesh(1), 2) is None  # single device = today


# --------------------------------------------------- fused sharded aggregates


def _rest_constellation(S, resident=True):
    from dds_tpu.core.transport import InMemoryNet
    from dds_tpu.shard import build_constellation

    net = InMemoryNet()
    const = build_constellation(net, shard_count=S, vnodes_per_group=8,
                                seed=3, n_active=4, n_sentinent=0, quorum=3)
    cfg = ProxyConfig(
        port=0, crypto_backend="cpu",
        resident=(ResidentConfig(enabled=True, min_fold=1)
                  if resident else None),
    )
    server = DDSRestServer(const.router, cfg)
    return server, const


def test_warm_sharded_aggregate_bit_for_bit_and_single_dispatch():
    """Acceptance (ISSUE 9): warm sharded SumAll/MultAll over resident
    pools is bit-for-bit the host reference fold (S=4 vs S=1 over
    IDENTICAL ciphertexts) and dispatches exactly ONE fused fold per
    aggregate (kernel.resident_fold spans), ingesting nothing."""
    pk = KEYS.psse.public
    rsa_n = KEYS.mse.n
    vals = [7, 21, 301, 44, 5, 600, 13, 99]
    rows = [[str(pk.encrypt(v)), str(v + 2)] for v in vals]  # pos 1: mod-n ints
    expect_sum = pyfold([int(r[0]) for r in rows], pk.nsquare)
    expect_mult = pyfold([int(r[1]) for r in rows], rsa_n)

    async def serve(S):
        server, const = _rest_constellation(S)
        await server.start()
        try:
            for row in rows:
                st, _ = await http_request(
                    "127.0.0.1", server.cfg.port, "POST", "/PutSet",
                    json.dumps({"contents": row}).encode(), timeout=10.0,
                )
                assert st == 200
            if S > 1:  # the sample must genuinely span shards
                assert len(server.abd.partition_keys(
                    sorted(server.stored_keys))) > 1
            out = {}
            for route, mod in (("SumAll", f"nsqr={pk.nsquare}"),
                               ("MultAll", f"pubkey={rsa_n}")):
                # cold pass ingests; warm pass must gather resident rows
                # in ONE dispatch
                pos = 0 if route == "SumAll" else 1
                target = f"/{route}?position={pos}&{mod}"
                st, _ = await http_request(
                    "127.0.0.1", server.cfg.port, "GET", target, timeout=30.0)
                assert st == 200
                ingested = _metric("dds_cipher_store_total",
                                   outcome="ingested")
                tracer.reset()
                st, body = await http_request(
                    "127.0.0.1", server.cfg.port, "GET", target, timeout=30.0)
                assert st == 200
                spans = tracer.summary()
                assert spans.get("kernel.resident_fold.dispatch",
                                 {}).get("count") == 1, spans
                assert _metric("dds_cipher_store_total",
                               outcome="ingested") == ingested
                out[route] = json.loads(body)["result"]
            return out
        finally:
            await server.stop()
            await const.stop()

    async def go():
        single = await serve(1)
        sharded = await serve(4)
        assert sharded == single  # bit-for-bit across shard counts
        assert int(single["SumAll"]) == expect_sum  # == host reference fold
        assert int(single["MultAll"]) == expect_mult
        assert KEYS.psse.decrypt(int(single["SumAll"])) == sum(vals)

    asyncio.run(go())


def test_write_path_ingest_warms_first_post_write_aggregate():
    """A committed write ingests into the established pools off the
    request path: the FIRST post-write aggregate finds every row resident
    (zero fold-path ingest)."""
    pk = KEYS.psse.public
    vals = [31, 17, 255]

    async def go():
        server, const = _rest_constellation(4)
        await server.start()
        try:
            target = f"/SumAll?position=0&nsqr={pk.nsquare}"
            for v in vals:
                st, _ = await http_request(
                    "127.0.0.1", server.cfg.port, "POST", "/PutSet",
                    json.dumps({"contents": [str(pk.encrypt(v))]}).encode(),
                    timeout=10.0,
                )
                assert st == 200
            st, _ = await http_request("127.0.0.1", server.cfg.port, "GET",
                                       target, timeout=30.0)
            assert st == 200  # pools established for this modulus
            # the write: ingest must happen NOW, not at the next
            # aggregate. Only groups that already own an operand have a
            # pool, so pick an encryption whose (content-addressed) key
            # lands in a pooled group — blinding re-randomizes the
            # ciphertext, hence the key, every attempt.
            from dds_tpu.utils import sigs

            pooled = {p["shard"]
                      for p in server._resident.stats()["pools"]}
            extra = 777
            while True:
                row = [str(pk.encrypt(extra))]
                if server.abd.owner(sigs.key_from_set(row)) in pooled:
                    break
            st, _ = await http_request(
                "127.0.0.1", server.cfg.port, "POST", "/PutSet",
                json.dumps({"contents": row}).encode(), timeout=10.0,
            )
            assert st == 200
            assert server._ingest_task is not None
            await server._ingest_task  # event-driven: the debounced drain
            assert server._resident.pending_ingest() == 0
            rows_now = sum(p["rows"]
                           for p in server._resident.stats()["pools"])
            assert rows_now == len(vals) + 1  # the new row already landed
            fold_ingest = _metric("dds_resident_ingest_total", path="fold")
            st, body = await http_request("127.0.0.1", server.cfg.port,
                                          "GET", target, timeout=30.0)
            assert st == 200
            # zero fold-path ingest on the first post-write aggregate
            assert _metric("dds_resident_ingest_total",
                           path="fold") == fold_ingest
            assert KEYS.psse.decrypt(int(json.loads(body)["result"])) \
                == sum(vals) + extra
        finally:
            await server.stop()
            await const.stop()

    asyncio.run(go())


def test_metrics_and_health_surface():
    pk = KEYS.psse.public

    async def go():
        server, const = _rest_constellation(2)
        await server.start()
        try:
            for v in (5, 6, 7, 8):
                await http_request(
                    "127.0.0.1", server.cfg.port, "POST", "/PutSet",
                    json.dumps({"contents": [str(pk.encrypt(v))]}).encode(),
                    timeout=10.0,
                )
            await http_request(
                "127.0.0.1", server.cfg.port, "GET",
                f"/SumAll?position=0&nsqr={pk.nsquare}", timeout=30.0)
            st, body = await http_request("127.0.0.1", server.cfg.port,
                                          "GET", "/metrics", timeout=10.0)
            assert st == 200
            text = body.decode()
            for fam in ("dds_resident_rows", "dds_resident_bytes",
                        "dds_resident_hit_ratio"):
                assert f'{fam}{{shard="s' in text, fam
            st, body = await http_request("127.0.0.1", server.cfg.port,
                                          "GET", "/health", timeout=10.0)
            health = json.loads(body)
            assert "resident" in health
            assert health["resident"]["pools"], health["resident"]
            assert all(p["bytes"] == p["capacity"] * 64 * 4  # L=64 @ 1024b
                       for p in health["resident"]["pools"])
        finally:
            await server.stop()
            await const.stop()

    asyncio.run(go())


# ------------------------------------------------------------- prism + bench


def test_fold_weighted_resident_rows_bit_for_bit():
    """fold_weighted fed pre-gathered resident rows must equal the
    marshaling path (same kernel, same result)."""
    from dds_tpu.ops.foldmany import fold_weighted

    plane = ResidentPlane(initial_rows=16)
    cs = [rng.randrange(1, MODULUS) for _ in range(5)]
    weights = [[rng.randrange(0, 50) for _ in range(5)] for _ in range(3)]
    from dds_tpu.ops.montgomery import ModCtx

    rows = plane.rows_for("s0", MODULUS, cs)
    assert rows is not None and rows.shape == (5, ModCtx.make(MODULUS).L)
    assert fold_weighted(cs, weights, MODULUS, rows=rows) \
        == fold_weighted(cs, weights, MODULUS)


def test_sentry_resident_record_contract(tmp_path):
    from benchmarks.sentry import _check_resident_records

    bench = tmp_path / "benchmarks"
    bench.mkdir()
    good = {
        "metric": "resident fold (S=4, K=64)", "value": 900.0,
        "unit": "folds/s", "vs_baseline": 2.4,
        "detail": {"shards": 4, "rows": 64, "cold_ms": 2.7, "warm_ms": 1.1},
    }
    (bench / "results.json").write_text(json.dumps([good]))
    assert _check_resident_records(str(tmp_path)) == {"rows": 1}
    bad = dict(good, detail={"shards": 4, "rows": 64, "cold_ms": 2.7})
    (bench / "results.json").write_text(json.dumps([good, bad]))
    with pytest.raises(ValueError, match="malformed resident-fold record"):
        _check_resident_records(str(tmp_path))
