"""The aggregate's operand table (http/operand_table) and the pool's
patch entry point (resident/pool.patch_rows).

A `SumAll` after writes parses and looks up the rows that moved and no
others; whatever the delta cannot describe (a change of the key set, a
cache flush, a pool reset, a new column, a row gaining or losing its
operand) rebuilds and still answers exactly; a write landing inside an
aggregate is wholly in or wholly out of it.
"""

import asyncio
import json
import random

import numpy as np
import pytest

from dds_tpu.core.quorum_client import AbdClient, AbdClientConfig
from dds_tpu.http.operand_table import MAX_COLUMNS, OperandTable
from dds_tpu.obs.metrics import metrics
from dds_tpu.resident import ResidentPool
from dds_tpu.resident.pool import Operands
from dds_tpu.utils.trace import tracer

from tests.test_rest import call, rest_stack

NSQR = ((1 << 61) - 1) ** 2        # SumAll's modulus here: column 2
PUB = (1 << 89) - 1                # MultAll's: column 0
SUM = f"/SumAll?position=2&nsqr={NSQR}"
MULT = f"/MultAll?position=0&pubkey={PUB}"
OUTCOMES = ("reused", "patched", "rebuilt", "grown")


def product(rows, pos, mod):
    acc = 1
    for v in rows:
        if v is not None and pos < len(v):
            acc = acc * int(v[pos]) % mod
    return acc


def counters():
    c = {o: metrics.value("dds_operand_table_total", outcome=o) or 0
         for o in OUTCOMES}
    c["rows"] = metrics.value("dds_operand_table_rows_total",
                              outcome="patched") or 0
    c["ingested"] = metrics.value("dds_cipher_store_total",
                                  outcome="ingested") or 0
    return c


def delta(before):
    now = counters()
    return {k: now[k] - before[k] for k in now if now[k] != before[k]}


async def load(server, n=24):
    """n rows [i+2, "x", 1000+i] through PutSet; {key: row}."""
    rows = {}
    for i in range(n):
        row = [str(i + 2), "x", str(1000 + i)]
        st, key = await call(server, "POST", "/PutSet", {"contents": row})
        assert st == 200
        rows[key.decode()] = row
    return rows


async def write(server, rows, key, value, pos=2):
    st, _ = await call(server, "PUT", f"/WriteElement/{key}?position={pos}",
                       {"value": value})
    assert st == 200
    row = list(rows[key])
    if pos < len(row):
        row[pos] = value
    else:
        row.append(value)
    rows[key] = row


async def agg(server, target=SUM):
    st, body = await call(server, "GET", target)
    assert st == 200, body
    return int(json.loads(body)["result"])


def check_table(server):
    """Every entry is the cache's, every kept column what parsing the
    entries whole would give: never a stale operand under a new tag."""
    t = server._table
    assert t.keys == sorted(server.stored_keys)
    for k, e in zip(t.keys, t.entries):
        assert e is server._cache[k]
    for pos, col in t.columns.items():
        assert list(col.operands) == [
            int(e[1][pos]) for e in t.entries
            if e[1] is not None and pos < len(e[1])
        ]


@pytest.fixture
def on_pool(monkeypatch):
    """The tpu backend (on the CPU here) with every fold through the pool."""
    monkeypatch.setenv("DDS_TPU_MIN_BATCH", "0")


# ------------------------------------------------ (a) O(changed rows)


def test_sumall_after_three_writes_parses_and_looks_up_three_rows(on_pool):
    async def go():
        async with rest_stack("tpu") as (server, _, _):
            rows = await load(server)
            for _ in range(2):      # builds the table, ingests, compiles
                assert await agg(server) == product(rows.values(), 2, NSQR)
            for n, key in enumerate(sorted(rows)[3:6]):
                await write(server, rows, key, str(7000 + n))
            before = counters()
            seen = []
            tracer.subscribe(seen.append)
            try:
                got = await agg(server)
            finally:
                tracer.unsubscribe(seen.append)
            assert got == product(rows.values(), 2, NSQR)
            assert delta(before) == {"patched": 1, "rows": 3, "ingested": 3}
            lookups = [r.meta for r in seen if r.name == "residency.lookup"]
            assert [m["looked_up"] for m in lookups] == [3, 3]
            assert all(m["k"] == len(rows) for m in lookups)
            ops = next(r.meta for r in seen if r.name == "assembly.operands")
            assert ops == {"k": len(rows), "memo": False}
            check_table(server)
            # nothing moved: the same list, no parse, no look-up
            before = counters()
            seen.clear()
            tracer.subscribe(seen.append)
            try:
                assert await agg(server) == got
            finally:
                tracer.unsubscribe(seen.append)
            assert delta(before) == {"reused": 1}
            (lm,) = [r.meta for r in seen if r.name == "residency.lookup"]
            assert lm["looked_up"] == 0 and lm["memo"] is True
            assert not [r for r in seen if r.name in (
                "assembly.state", "assembly.validate_tags", "assembly.pairs")]

    asyncio.run(go())


def test_external_write_is_reread_and_patched_as_one_row():
    """A write through another proxy: the tag round names the key, one
    full read refreshes it, one row is parsed."""

    async def go():
        async with rest_stack() as (server, replicas, _):
            rows = await load(server, 12)
            await agg(server)
            other = AbdClient("proxy-ext", server.abd.net, list(replicas),
                              AbdClientConfig(request_timeout=2.0))
            key = sorted(rows)[4]
            rows[key] = ["6", "x", "4242"]
            await other.write_set(key, rows[key])
            before = counters()
            assert await agg(server) == product(rows.values(), 2, NSQR)
            assert delta(before) == {"patched": 1, "rows": 1}
            check_table(server)

    asyncio.run(go())


def test_two_writes_to_one_key_parse_and_look_up_one_row(on_pool):
    """The table takes the key's newest entry once: the value between the
    two writes is never parsed, looked up or placed."""

    async def go():
        async with rest_stack("tpu") as (server, _, _):
            rows = await load(server)
            for _ in range(2):
                assert await agg(server) == product(rows.values(), 2, NSQR)
            key = sorted(rows)[9]
            await write(server, rows, key, "5150")
            await write(server, rows, key, "5151")
            before = counters()
            seen = []
            tracer.subscribe(seen.append)
            try:
                got = await agg(server)
            finally:
                tracer.unsubscribe(seen.append)
            assert got == product(rows.values(), 2, NSQR)
            assert delta(before) == {"patched": 1, "rows": 1, "ingested": 1}
            assert [r.meta["looked_up"] for r in seen
                    if r.name == "residency.lookup"] == [1, 1]
            pool = server.backend.store_for(NSQR)
            assert 5151 in pool._index and 5150 not in pool._index
            check_table(server)

    asyncio.run(go())


def test_a_patched_table_answers_what_a_fresh_proxy_answers(on_pool):
    """A second proxy over the same replicas, with no table and an empty
    cache, reads every row through full quorums: the same sums, products
    and order as the proxy whose table was patched write by write."""
    from dds_tpu.http.server import DDSRestServer, ProxyConfig

    async def go():
        async with rest_stack("tpu") as (server, replicas, _):
            rows = await load(server, 16)
            await agg(server)
            await agg(server, MULT)
            keys = sorted(rows)
            for n, key in enumerate(keys[2:7]):
                await write(server, rows, key, str(6100 + n))
                await write(server, rows, key, str(13 + 2 * n), pos=0)
                await agg(server)
            fresh = DDSRestServer(
                AbdClient("proxy-fresh", server.abd.net, list(replicas),
                          AbdClientConfig(request_timeout=2.0)),
                ProxyConfig(host="127.0.0.1", port=0, crypto_backend="cpu"),
            )
            await fresh.start()
            try:
                fresh.stored_keys.update(server.stored_keys)
                fresh._stored_version += 1
                assert fresh._table is None and not fresh._cache
                for target in (SUM, MULT, "/OrderLS?position=0"):
                    a = await call(server, "GET", target)
                    b = await call(fresh, "GET", target)
                    assert a == b and a[0] == 200
            finally:
                await fresh.stop()
            assert await agg(server) == product(rows.values(), 2, NSQR)
            assert await agg(server, MULT) == product(rows.values(), 0, PUB)

    asyncio.run(go())


ONCE = ("proxy.fetch_stored", "abd.read_tags", "assembly.state",
        "assembly.validate_tags", "assembly.pick_stale", "assembly.reread",
        "assembly.pairs", "assembly.operands", "proxy.fold",
        "residency.convert", "ingest.h2d")


@pytest.mark.parametrize("writes", [1, 4])
def test_every_assembly_span_appears_once_per_aggregate(writes, on_pool):
    """The spans the benchmark's per-layer metrics read keep their names
    and stay one per aggregate, however little they now cover;
    `residency.lookup` one per locked stretch."""

    async def go():
        async with rest_stack("tpu") as (server, _, _):
            rows = await load(server)
            for _ in range(2):
                await agg(server)
            for n, key in enumerate(sorted(rows)[:writes]):
                await write(server, rows, key, str(3000 + n))
            seen = []
            tracer.subscribe(seen.append)
            try:
                assert await agg(server) == product(rows.values(), 2, NSQR)
            finally:
                tracer.unsubscribe(seen.append)
            (root,) = [r for r in seen if r.name == "http.GET.SumAll"]
            mine = [r.name for r in seen if r.trace_id == root.trace_id]
            assert {n: mine.count(n) for n in ONCE} == dict.fromkeys(ONCE, 1)
            look = [r.meta for r in seen if r.name == "residency.lookup"
                    and r.trace_id == root.trace_id]
            assert [m["stretch"] for m in look] == [1, 2]
            assert all(m["looked_up"] == writes for m in look)

    asyncio.run(go())


def test_the_audit_draws_from_every_validated_key():
    """Patched or not, every key the tag round confirmed can be drawn,
    `aggregate_cache_audit` of them per aggregate and never a stale one
    twice over: over many seeded rounds every key is audited."""

    async def go():
        async with rest_stack() as (server, _, _):
            rows = await load(server, 12)
            await agg(server)
            keys = sorted(rows)
            patched = set(keys[:3])
            for n, key in enumerate(keys[:3]):
                await write(server, rows, key, str(2000 + n))
            await agg(server)
            audited: dict[str, int] = {}
            real = server._reread

            async def reread(ks, audit):
                for k in ks[len(ks) - audit:]:
                    audited[k] = audited.get(k, 0) + 1
                assert audit == server.cfg.aggregate_cache_audit
                assert len(set(ks)) == len(ks)
                return await real(ks, audit)

            server._reread = reread
            random.seed(0xA0D17)
            for n in range(60):
                if n % 10 == 5:     # a patch in between: its key is stale
                    await write(server, rows, keys[n % 3], str(4000 + n))
                await agg(server)
            assert set(audited) == set(keys)
            assert patched & set(audited) and set(audited) - patched
            assert sum(audited.values()) == 60 * 2

    asyncio.run(go())


# ------------------------------- (b) a write inside the aggregate's window


@pytest.mark.parametrize("who", ["own", "external"])
@pytest.mark.parametrize("when", ["after_tag_round", "during_reread"])
def test_write_inside_an_aggregate_is_wholly_in_or_out(who, when):
    async def go():
        async with rest_stack() as (server, replicas, _):
            rows = await load(server, 10)
            old = await agg(server)
            key = sorted(rows)[2]
            other = AbdClient("proxy-ext", server.abd.net, list(replicas),
                              AbdClientConfig(request_timeout=2.0))
            fired = []

            async def land():
                if fired:
                    return
                fired.append(1)
                new = list(rows[key])
                new[2] = "31337"
                rows[key] = new
                if who == "own":
                    await server._write(key, new)
                else:
                    await other.write_set(key, new)

            if when == "after_tag_round":
                real = server.abd.read_tags

                async def read_tags(*a, **kw):
                    out = await real(*a, **kw)
                    await land()
                    return out

                server.abd.read_tags = read_tags
            else:
                real = server._reread

                async def reread(keys, audit):
                    out = await real(keys, audit)
                    await land()
                    return out

                server._reread = reread
            got = await agg(server)
            assert fired
            new_total = product(rows.values(), 2, NSQR)
            assert got in (old, new_total) and old != new_total
            if who == "own":
                # completed before the fold took its snapshot, through the
                # proxy's own cache: in
                assert got == new_total
                check_table(server)
            # and whoever comes next sees it
            assert await agg(server) == new_total
            check_table(server)

    asyncio.run(go())


def test_concurrent_sumalls_and_writes_fold_only_whole_rows(on_pool):
    async def go():
        async with rest_stack("tpu") as (server, _, _):
            rows = await load(server, 16)
            await agg(server)
            keys = sorted(rows)
            base = product(rows.values(), 2, NSQR)
            inv = {k: pow(int(rows[k][2]), -1, NSQR) for k in keys[:4]}
            news = {k: str(9001 + 2 * n) for n, k in enumerate(keys[:4])}
            # every subset of the four writes applied whole is allowed
            allowed = set()
            for mask in range(16):
                acc = base
                for n, k in enumerate(keys[:4]):
                    if mask >> n & 1:
                        acc = acc * inv[k] * int(news[k]) % NSQR
                allowed.add(acc)

            async def writer():
                for k in keys[:4]:
                    await write(server, rows, k, news[k])
                    await asyncio.sleep(0)

            async def reader():
                return [await agg(server) for _ in range(6)]

            a, b, _ = await asyncio.gather(reader(), reader(), writer())
            assert set(a + b) <= allowed
            assert await agg(server) == product(rows.values(), 2, NSQR)
            check_table(server)

    asyncio.run(go())


# -------------------------- (c) what the delta cannot describe rebuilds


@pytest.mark.parametrize("event", ["putset", "removeset", "forged_audit",
                                   "pool_reset", "cache_off"])
def test_rebuild_events_answer_exactly(event, on_pool):
    async def go():
        async with rest_stack("tpu") as (server, _, _):
            rows = await load(server, 12)
            if event == "pool_reset":     # a pool of 16 rows for 12 operands
                from dds_tpu.resident.pool import ResidentPool

                be = server.backend
                pool = be._stores[NSQR] = ResidentPool(
                    NSQR, reduce=be.store_for(NSQR).reduce,
                    initial_rows=16, max_rows=16)
            for _ in range(2):
                assert await agg(server) == product(rows.values(), 2, NSQR)
            table = server._table
            keys = sorted(rows)
            if event == "putset":
                row = ["99", "x", "5555"]
                st, k = await call(server, "POST", "/PutSet", {"contents": row})
                rows[k.decode()] = row
            elif event == "removeset":
                st, _ = await call(server, "DELETE", f"/RemoveSet/{keys[1]}")
                assert st == 200
                del rows[keys[1]]
            elif event == "forged_audit":
                # what a Byzantine coordinator could plant: another value
                # under the true tag, taken into the table like any entry
                tag, value = server._cache[keys[0]]
                server._cache[keys[0]] = (tag, [value[0], "x", "666"])
                server._dirty.add(keys[0])
                server.cfg.aggregate_cache_audit = len(keys)
            elif event == "pool_reset":
                for n in range(3):
                    await write(server, rows, keys[n], str(80000 + n))
                assert await agg(server) == product(rows.values(), 2, NSQR)
                assert pool.resident == 15 and pool.resets == 0
                for n in range(3):     # 18 distinct rows do not fit
                    await write(server, rows, keys[n], str(90000 + n))
            elif event == "cache_off":
                server.cfg.aggregate_cache = False
            before = counters()
            assert await agg(server) == product(rows.values(), 2, NSQR)
            d = delta(before)
            if event == "pool_reset":
                # the table patches; the pool resolves the column whole
                assert pool.resets >= 1 and d["patched"] == 1
                assert server._table is table
            elif event == "forged_audit":
                assert server._table is None      # flushed, not yet rebuilt
                assert server._cache == {}
            else:
                # a new key among 12 grows the table (test_growing_keyset)
                built = "grown" if event == "putset" else "rebuilt"
                assert d[built] == 1 and "patched" not in d
                assert server._table is not table
            before = counters()
            assert await agg(server) == product(rows.values(), 2, NSQR)
            if event == "forged_audit":
                assert delta(before)["rebuilt"] == 1
            elif event != "cache_off":
                assert delta(before) == {"reused": 1}
                check_table(server)

    asyncio.run(go())


def test_key_sync_rebuilds_and_rereads_the_unread_key():
    async def go():
        async with rest_stack() as (server, replicas, _):
            rows = await load(server, 6)
            await agg(server)
            other = AbdClient("proxy-ext", server.abd.net, list(replicas),
                              AbdClientConfig(request_timeout=2.0))
            rows["K" * 128] = ["1", "x", "777"]
            await other.write_set("K" * 128, rows["K" * 128])
            st, _ = await call(server, "POST", "/_sync",
                               {"keyset": ["K" * 128]})
            assert st == 204
            before = counters()
            assert await agg(server) == product(rows.values(), 2, NSQR)
            assert delta(before)["rebuilt"] == 1
            assert server._table.uncached == 0
            check_table(server)

    asyncio.run(go())


# ------------------------------------------------- (d) one column per pos


def test_sumall_and_multall_keep_separate_columns(on_pool):
    async def go():
        async with rest_stack("tpu") as (server, _, _):
            rows = await load(server, 12)
            assert await agg(server) == product(rows.values(), 2, NSQR)
            before = counters()
            assert await agg(server, MULT) == product(rows.values(), 0, PUB)
            assert delta(before)["rebuilt"] == 1     # a first request for pos 0
            t = server._table
            assert sorted(t.columns) == [0, 2]
            assert t.columns[0].track is not t.columns[2].track
            key = sorted(rows)[7]
            await write(server, rows, key, "31", pos=0)
            before = counters()
            assert await agg(server, MULT) == product(rows.values(), 0, PUB)
            assert await agg(server) == product(rows.values(), 2, NSQR)
            d = delta(before)
            # the row was parsed into both columns; only column 0 changed
            assert d["rows"] == 2 and d["patched"] == 1 and d["reused"] == 1
            check_table(server)

    asyncio.run(go())


def test_columns_are_bounded():
    from dds_tpu.core.messages import ABDTag

    tag = ABDTag(1, "proxy-0")
    t = OperandTable(["a", "b"],
                     {"a": (tag, ["1"] * 40), "b": (tag, ["2"] * 40)}, 0)
    for pos in range(MAX_COLUMNS + 3):
        ops, outcome = t.column(pos)
        assert list(ops) == [1, 2] and outcome == "rebuilt"
    assert len(t.columns) == MAX_COLUMNS and 0 not in t.columns


# ----------------------------------- (e) rows without the operand


def test_short_and_removed_rows_are_skipped_and_may_come_back():
    async def go():
        async with rest_stack() as (server, replicas, _):
            rows = await load(server, 8)
            short = ["5", "y"]                       # no column 2
            st, k = await call(server, "POST", "/PutSet", {"contents": short})
            short_key = k.decode()
            rows[short_key] = short
            other = AbdClient("proxy-ext", server.abd.net, list(replicas),
                              AbdClientConfig(request_timeout=2.0))
            gone = sorted(rows)[0] if sorted(rows)[0] != short_key \
                else sorted(rows)[1]
            await other.write_set(gone, None)        # removed elsewhere
            rows[gone] = None
            assert await agg(server) == product(rows.values(), 2, NSQR)
            col = server._table.columns[2]
            assert col.where is not None and col.where.count(-1) == 2
            assert len(col.operands) == len(rows) - 2
            # a value moves beside them: patched by position in the column
            key = sorted(k for k in rows if k not in (gone, short_key))[-1]
            await write(server, rows, key, "4321")
            before = counters()
            assert await agg(server) == product(rows.values(), 2, NSQR)
            assert delta(before) == {"patched": 1, "rows": 1}
            assert server._table.columns[2] is col
            # the short row gains the column: this column cannot say so,
            # and is parsed whole again
            await write(server, rows, short_key, "11", pos=2)
            before = counters()
            assert await agg(server) == product(rows.values(), 2, NSQR)
            assert delta(before)["rebuilt"] == 1
            assert server._table.columns[2] is not col
            # what int() refuses is the asking request's 400, as ever
            await write(server, rows, key, "not-a-number")
            st, _ = await call(server, "GET", SUM)
            assert st == 400
            await write(server, rows, key, "8")
            assert await agg(server) == product(rows.values(), 2, NSQR)

    asyncio.run(go())


def test_pairs_routes_follow_the_table():
    """Order routes read `[(key, value)]` listed from the table on demand."""

    async def go():
        async with rest_stack() as (server, _, _):
            rows = await load(server, 6)
            st, body = await call(server, "GET", "/OrderSL?position=0")
            want = sorted(rows, key=lambda k: int(rows[k][0]))
            assert json.loads(body)["keyset"] == want
            pairs = server._table.pairs()
            assert server._table.pairs() is pairs    # same list until a move
            await write(server, rows, want[0], "500", pos=0)
            st, body = await call(server, "GET", "/OrderSL?position=0")
            assert json.loads(body)["keyset"] == want[1:] + want[:1]
            assert server._table.pairs() is not pairs

    asyncio.run(go())


# ------------------------------------------------------ the pool's half

rng = random.Random(0x7AB1E)
MODULUS = rng.getrandbits(256) | (1 << 255) | 1


def pyfold(cs, n=MODULUS):
    acc = 1
    for c in cs:
        acc = acc * c % n
    return acc


def test_patch_rows_looks_up_the_changed_positions_alone():
    pool = ResidentPool(MODULUS, initial_rows=16, max_rows=64)
    cs = [rng.randrange(1, MODULUS) for _ in range(8)]
    buf, idx = pool.rows_for(cs)
    new = [rng.randrange(1, MODULUS) for _ in range(2)]
    seen = []
    tracer.subscribe(seen.append)
    try:
        buf2, idx2 = pool.patch_rows(idx, pool.epoch, [1, 6], new)
    finally:
        tracer.unsubscribe(seen.append)
    assert [r.meta["looked_up"] for r in seen
            if r.name == "residency.lookup"] == [2, 2]
    assert idx2 is not idx and sorted(idx) == list(range(8))  # left as it was
    cs[1], cs[6] = new
    assert sorted((idx2[1], idx2[6])) == [8, 9]
    assert all(idx2[i] == idx[i] for i in (0, 2, 3, 4, 5, 7))
    assert np.array_equal(idx2, pool.rows_for(cs)[1])
    assert pool.fold(cs) == pyfold(cs)


def test_operands_track_resolves_any_version_by_the_log_between():
    from dds_tpu.http.operand_table import OperandColumn

    entries = [(1, [str(rng.randrange(1, MODULUS))]) for _ in range(6)]
    col = OperandColumn(0, entries)
    pool = ResidentPool(MODULUS, initial_rows=16, max_rows=64)
    v0 = col.operands
    assert pool.fold(v0) == pyfold(v0)
    for n, i in enumerate((4, 1, 4)):
        entries[i] = (2 + n, [str(rng.randrange(1, MODULUS))])
        assert col.patch([(i, entries[i])])
    v3 = col.operands
    assert v3 is not v0 and v3.version == 3 and v0.version == 0
    seen = []
    tracer.subscribe(seen.append)
    try:
        assert pool.fold(v3) == pyfold(v3)      # forward: positions 4, 1
        assert pool.fold(v0) == pyfold(v0)      # and back, from v3's rows
        assert pool.fold(v3) == pyfold(v3)      # the same object: nothing
    finally:
        tracer.unsubscribe(seen.append)
    looked = [r.meta["looked_up"] for r in seen
              if r.name == "residency.lookup" and r.meta["stretch"] == 1]
    assert looked == [2, 2, 0]
    assert col.track.rows[id(pool)][2] == 3      # the newest version stays


def test_a_plain_list_is_resolved_whole_every_time():
    pool = ResidentPool(MODULUS, initial_rows=16, max_rows=64)
    cs = [rng.randrange(1, MODULUS) for _ in range(5)]
    seen = []
    tracer.subscribe(seen.append)
    try:
        for _ in range(2):
            assert pool.fold(cs) == pyfold(cs)
        ops = Operands(cs)
        for _ in range(2):
            assert pool.fold(ops) == pyfold(cs)
    finally:
        tracer.unsubscribe(seen.append)
    assert [r.meta["looked_up"] for r in seen
            if r.name == "residency.lookup"] == [5, 5, 5, 5, 0]


def test_folds_on_threads_race_patches_and_resets_without_a_wrong_product():
    """The loop patches a column while worker threads fold its versions
    through one pool small enough to reset under them: every fold is the
    product of the very list it was given."""
    import sys
    import threading
    import time

    from dds_tpu.http.operand_table import OperandColumn

    r = random.Random(0xFACE)
    entries = [(1, [str(r.randrange(1, MODULUS))]) for _ in range(12)]
    col = OperandColumn(0, entries)
    pool = ResidentPool(MODULUS, initial_rows=16, max_rows=32)
    assert pool.fold(col.operands) == pyfold(col.operands)
    errors, folds = [], [0]
    stop = time.monotonic() + 2.0

    def folder():
        while time.monotonic() < stop:
            snap = col.operands
            try:
                if pool.fold(snap) != pyfold(snap):
                    errors.append(("wrong product", snap.version))
            except Exception as e:   # pragma: no cover - failure surface
                errors.append(repr(e))
            folds[0] += 1

    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        threads = [threading.Thread(target=folder) for _ in range(2 * 13)]
        for t in threads:
            t.start()
        n = 0
        while time.monotonic() < stop:
            i = r.randrange(len(entries))
            n += 1
            entries[i] = (1 + n, [str(r.randrange(1, MODULUS))])
            assert col.patch([(i, entries[i])])
            time.sleep(0.002)
        for t in threads:
            t.join(timeout=60)
    finally:
        sys.setswitchinterval(interval)
    assert not any(t.is_alive() for t in threads)
    assert not errors and folds[0] > len(threads)
    assert pool.resets >= 1 and col.operands.version == n
