"""A key set that grew is the old one plus its new keys
(`OperandTable.grown`, `OperandColumn.grown`, `http/server._sync_table`).

A table made by `grown` is the table built anew, list for list and tuple
for tuple; the table it was made from is left as it was; a pool that had
resolved a column looks up the added rows of the carried one and no
others; and whatever growth cannot describe (a key removed, a cache
flushed or off, too many keys added, an added row without the column)
builds anew and says so.
"""

import asyncio
import contextlib
import random

import numpy as np
import pytest

from dds_tpu.core.messages import ABDTag
from dds_tpu.http.operand_table import OperandColumn, OperandTable
from dds_tpu.obs.metrics import metrics
from dds_tpu.resident import ResidentPool
from dds_tpu.utils.trace import tracer

from tests.test_operand_table import (
    NSQR, agg, call, check_table, counters, delta, load, product, rest_stack,
)

MODULUS = random.Random(0x960).getrandbits(256) | (1 << 255) | 1
POSITIONS = (2, 3)

# name: (keys before, numbers of the added keys among the old ones'
# 100, 102, ..: odd in between, under 100 at the front, 90000 up at the end)
GROWTHS = {
    "one_key": (40, [131]),
    "several": (40, [103, 131, 133, 177]),
    "at_the_front": (40, [7, 8]),
    "at_the_end": (40, [90001, 90002, 90003]),
    "front_middle_end": (40, [5, 141, 90000]),
    "across_a_power_of_two": (30, [101, 121, 141, 151, 90000]),
    "across_the_pools_first_capacity": (250, [3, 111, 113, 301, 455, 457,
                                              90000, 90001, 90002]),
    "no_key": (40, []),
}


def key(n: int) -> str:
    return f"K{n:06d}"


def entry(rng, seq=1, short=False):
    row = ["1", "x", str(rng.randrange(2, MODULUS))]
    if not short:
        row.append(str(rng.randrange(2, MODULUS)))
    return (ABDTag(seq, "proxy-0"), row)


def store(name, short_every=0):
    """(cache of old and added keys, old keys sorted, added keys)."""
    k0, numbers = GROWTHS[name]
    rng = random.Random(f"growth:{name}:{short_every}")
    old = [key(100 + 2 * i) for i in range(k0)]
    added = [key(n) for n in numbers]
    cache = {k: entry(rng, short=bool(short_every) and i % short_every == 1)
             for i, k in enumerate(old + added)}
    return cache, old, added


def snapshot(table):
    """Everything of a table that growth must leave alone, by value and
    (entries, operands) by identity."""
    return (
        list(table.keys), [id(e) for e in table.entries], list(table.tags),
        list(table.fields), dict(table.index), table.uncached, table.version,
        {pos: (id(c.operands), list(c.operands), c.where and list(c.where),
               list(c.track.log), {p: (r[1], r[2], r[3].tolist())
                                   for p, r in c.track.rows.items()})
         for pos, c in table.columns.items()},
    )


def assert_same_table(grown, anew):
    assert grown.keys == anew.keys
    assert len(grown.entries) == len(anew.entries)
    assert all(a is b for a, b in zip(grown.entries, anew.entries))
    assert grown.tags == anew.tags and grown.fields == anew.fields
    assert grown.index == anew.index
    assert grown.uncached == anew.uncached
    assert grown.stored_version == anew.stored_version
    # but for the table's version: what the tag round is sent
    assert grown.round_args()[1:] == anew.round_args()[1:]
    assert grown.pairs() == anew.pairs()
    for pos, col in grown.columns.items():
        whole = OperandColumn(pos, anew.entries)
        assert list(col.operands) == list(whole.operands)
        assert col.where == whole.where
        assert col.operands.version == len(col.track.log)


# ------------------------------------------------ the table and its columns


@pytest.mark.parametrize("short_every", [0, 5], ids=["full_rows", "short_rows"])
@pytest.mark.parametrize("name", list(GROWTHS))
def test_a_grown_table_is_the_table_built_anew(name, short_every):
    cache, old_keys, added = store(name, short_every)
    old = OperandTable(sorted(old_keys), cache, 1)
    for pos in POSITIONS:
        assert old.column(pos)[1] == "rebuilt"
    # rows that moved before the growth: their log comes along
    rng = random.Random(name)
    moved = rng.sample(old_keys, 3)
    for k in moved:
        cache[k] = (ABDTag(2, "proxy-0"), [*cache[k][1][:2], "77",
                                           *cache[k][1][3:]])
    old.apply([(old.index[k], cache[k]) for k in moved])
    before = snapshot(old)

    grown = OperandTable.grown(old, set(added), cache, 2)
    anew = OperandTable(sorted(old_keys + added), cache, 2)
    assert_same_table(grown, anew)
    if short_every and any(len(cache[k][1]) < 4 for k in added):
        # an added row without column 3: that column is parsed whole
        assert set(grown.columns) == {2}
        assert grown.column(3)[1] == "rebuilt"
    else:
        assert set(grown.columns) == set(POSITIONS)
    assert grown.column(2)[1] == "grown"
    assert grown.column(2)[1] == "reused"
    assert snapshot(old) == before
    assert grown.keys is not old.keys and grown.entries is not old.entries

    # and it is patched like any other from here on
    k = grown.keys[len(grown.keys) // 2]
    cache[k] = (ABDTag(3, "proxy-0"), [*cache[k][1][:2], "99",
                                       *cache[k][1][3:]])
    assert grown.apply([(grown.index[k], cache[k])]) == 1
    assert grown.column(2)[1] == "patched"
    assert_same_table(grown, OperandTable(grown.keys, cache, 2))
    assert snapshot(old) == before


def test_a_table_grown_twice_before_an_aggregate_is_still_the_one_built_anew():
    cache, old_keys, added = store("several")
    old = OperandTable(sorted(old_keys), cache, 1)
    old.column(2)
    once = OperandTable.grown(old, set(added[:2]), cache, 2)
    twice = OperandTable.grown(once, set(added[2:]), cache, 3)
    assert_same_table(twice, OperandTable(sorted(old_keys + added), cache, 3))
    assert twice.column(2)[1] == "grown"


@pytest.mark.parametrize("what", ["never_read", "short_row", "not_a_number"])
def test_a_column_that_cannot_take_an_added_row_is_left_out(what):
    cache, old_keys, added = store("several")
    old = OperandTable(sorted(old_keys), cache, 1)
    for pos in POSITIONS:
        old.column(pos)
    tag, row = cache[added[1]]
    if what == "never_read":
        del cache[added[1]]             # a key learned by sync
    elif what == "short_row":
        cache[added[1]] = (tag, row[:3])
    else:
        cache[added[1]] = (tag, [*row[:3], "12x"])
    grown = OperandTable.grown(old, set(added), cache, 2)
    assert set(grown.columns) == (set() if what == "never_read" else {2})
    assert grown.uncached == (what == "never_read")
    assert_same_table(grown, OperandTable(sorted(old_keys + added), cache, 2))
    if what == "not_a_number":
        with pytest.raises(ValueError):   # raised for the request that asks
            grown.column(3)
    else:
        assert grown.column(3)[1] == "rebuilt"


def test_a_table_with_an_entry_without_a_tag_does_not_grow():
    cache, old_keys, added = store("one_key")
    old = OperandTable(sorted(old_keys), cache, 1)
    old.apply([(3, (None, cache[old.keys[3]][1]))])   # a read nobody cached
    assert old.uncached == 1
    assert OperandTable.grown(old, set(added), cache, 2) is None


# ------------------------------------------------------------ the pool's half


def pyfold(cs):
    acc = 1
    for c in cs:
        acc = acc * c % MODULUS
    return acc


@contextlib.contextmanager
def spans():
    """The span records made inside the block, as a list."""
    seen = []
    tracer.subscribe(seen.append)
    try:
        yield seen
    finally:
        tracer.unsubscribe(seen.append)


def lookups(seen):
    return [r.meta["looked_up"] for r in seen
            if r.name == "residency.lookup" and r.meta["stretch"] == 1]


@pytest.mark.parametrize("name", list(GROWTHS))
def test_a_pool_looks_up_the_added_rows_of_a_carried_column_alone(name):
    cache, old_keys, added = store(name)
    old = OperandTable(sorted(old_keys), cache, 1)
    # 256 rows: `across_the_pools_first_capacity` doubles the buffer
    pool = ResidentPool(MODULUS, initial_rows=256, max_rows=1024)
    other = ResidentPool(MODULUS, initial_rows=256, max_rows=1024)
    v0 = old.column(2)[0]
    assert pool.fold(v0) == pyfold(v0)
    # two rows move and only `other` has looked since: `pool` owes them
    for k in old_keys[3:5]:
        cache[k] = (ABDTag(2, "proxy-0"), [*cache[k][1][:2], "4242",
                                           cache[k][1][3]])
    old.apply([(old.index[k], cache[k]) for k in old_keys[3:5]])
    v1 = old.column(2)[0]
    assert other.fold(v1) == pyfold(v1)

    grown = OperandTable.grown(old, set(added), cache, 2)
    ops, outcome = grown.column(2)
    assert outcome == "grown" and len(ops) == len(v0) + len(added)
    with spans() as seen:
        assert pool.fold(ops) == pyfold(ops)
        assert other.fold(ops) == pyfold(ops)
        assert pool.fold(ops) == pyfold(ops)     # the same object: nothing
        # the old column still folds, from its own rows
        assert pool.fold(v1) == pyfold(v1)
    # "4242" twice is one ciphertext at two positions: both are looked up
    assert lookups(seen) == [len(added) + 2, len(added), 0, 2]
    assert pool.capacity == (512 if len(ops) > 256 else 256)
    assert pool.resets == 0
    rows = grown.columns[2].track.rows
    assert rows[id(pool)][2] == rows[id(other)][2] == ops.version
    assert np.array_equal(rows[id(pool)][3], pool.rows_for(list(ops))[1])


def test_a_pool_that_was_reset_resolves_the_carried_column_whole():
    cache, old_keys, added = store("several")
    old = OperandTable(sorted(old_keys), cache, 1)
    pool = ResidentPool(MODULUS, initial_rows=16, max_rows=64)
    v0 = old.column(2)[0]
    assert pool.fold(v0) == pyfold(v0)
    filler = [random.Random(n).randrange(2, MODULUS) for n in range(40)]
    assert pool.fold(filler) == pyfold(filler)      # 80 rows do not fit
    assert pool.resets >= 1
    ops = OperandTable.grown(old, set(added), cache, 2).column(2)[0]
    with spans() as seen:
        assert pool.fold(ops) == pyfold(ops)
    assert lookups(seen) == [len(added), len(ops)]   # another epoch: all


def test_a_pool_that_never_saw_the_column_resolves_it_whole():
    cache, old_keys, added = store("several")
    old = OperandTable(sorted(old_keys), cache, 1)
    old.column(2)
    ops = OperandTable.grown(old, set(added), cache, 2).column(2)[0]
    pool = ResidentPool(MODULUS, initial_rows=64, max_rows=64)
    with spans() as seen:
        assert pool.fold(ops) == pyfold(ops)
    assert lookups(seen) == [len(ops)]


def test_folds_on_threads_race_growth_and_patches_without_a_wrong_product():
    """The loop grows and patches the table while worker threads fold the
    columns it has handed out, of this table and of those before it,
    through one pool small enough to reset under them: every fold is the
    product of the very list it was given."""
    import sys
    import threading
    import time

    r = random.Random(0x6407)
    cache = {key(100 + 2 * i): entry(r) for i in range(24)}
    holder = [OperandTable(sorted(cache), cache, 0)]
    holder[0].column(2)
    pool = ResidentPool(MODULUS, initial_rows=16, max_rows=64)
    for width in range(24, 29):     # compile each width the race will fold
        pool.fold([r.randrange(2, MODULUS) for _ in range(width)])
    errors, folds = [], [0]
    stop = time.monotonic() + 2.0

    def folder():
        while time.monotonic() < stop:
            snap = holder[0].columns[2].operands
            try:
                if pool.fold(snap) != pyfold(snap):
                    errors.append(("wrong product", len(snap), snap.version))
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
            n += 1
            table = holder[0]
            if n % 50 == 0 and len(table.keys) < 28:
                k = key(r.randrange(100_000))
                if k not in cache:
                    cache[k] = entry(r, seq=n)
                    holder[0] = OperandTable.grown(table, {k}, cache, n)
            else:
                k = r.choice(table.keys)
                cache[k] = entry(r, seq=n)
                table.apply([(table.index[k], cache[k])])
            time.sleep(0.002)
        for t in threads:
            t.join(timeout=60)
    finally:
        sys.setswitchinterval(interval)
    assert not any(t.is_alive() for t in threads)
    assert not errors and folds[0] > len(threads)
    assert pool.resets >= 1 and len(holder[0].keys) == 28
    assert_same_table(holder[0], OperandTable(sorted(cache), cache,
                                              holder[0].stored_version))


# --------------------------------------------- the rule, on the served path


async def put(server, rows, row):
    st, k = await call(server, "POST", "/PutSet", {"contents": row})
    assert st == 200
    rows[k.decode()] = row
    return k.decode()


def built(seen):
    return [(r.meta["built"], r.meta["added"]) for r in seen
            if r.name == "assembly.state"]


@pytest.mark.parametrize("event", [
    "grows", "removal", "removal_and_insert", "flush", "cache_off",
    "too_many_added", "row_without_the_column",
])
def test_the_rule_that_chooses_grown_over_anew(event, monkeypatch):
    monkeypatch.setenv("DDS_TPU_MIN_BATCH", "0")

    async def go():
        async with rest_stack("tpu") as (server, _, _):
            rows = await load(server, 16)
            for _ in range(2):
                assert await agg(server) == product(rows.values(), 2, NSQR)
            table, keys = server._table, sorted(rows)
            expect = "anew"
            if event == "grows":
                for n in range(2):              # 2 * 8 <= 16
                    await put(server, rows, [str(90 + n), "x", str(5000 + n)])
                expect = "grown"
            elif event in ("removal", "removal_and_insert"):
                st, _ = await call(server, "DELETE", f"/RemoveSet/{keys[1]}")
                assert st == 200
                del rows[keys[1]]
                if event == "removal_and_insert":
                    await put(server, rows, ["90", "x", "5000"])
            elif event == "flush":
                await put(server, rows, ["90", "x", "5000"])
                server._flush_cache()
            elif event == "cache_off":
                await put(server, rows, ["90", "x", "5000"])
                server.cfg.aggregate_cache = False
            elif event == "too_many_added":
                for n in range(3):              # 3 * 8 > 16
                    await put(server, rows, [str(90 + n), "x", str(5000 + n)])
            elif event == "row_without_the_column":
                await put(server, rows, ["90", "x"])
                expect = "grown"                # the table is; the column not
            before = counters()
            with spans() as seen:
                assert await agg(server) == product(rows.values(), 2, NSQR)
            d = delta(before)
            assert server._table is not table
            assert [b for b, _ in built(seen)] == [expect]
            if event == "grows":
                assert built(seen) == [("grown", 2)]
                assert d["grown"] == 1 and "rebuilt" not in d
                assert d["ingested"] == 2
                assert lookups(seen) == [2]
            else:
                assert d["rebuilt"] == 1 and "grown" not in d
            memo = [r.meta["memo"] for r in seen
                    if r.name == "assembly.operands"]
            assert memo == [False]
            if event != "cache_off":
                before = counters()
                assert await agg(server) == product(rows.values(), 2, NSQR)
                assert delta(before) == {"reused": 1}
                check_table(server)

    asyncio.run(go())


def test_the_counter_names_the_grown_outcome():
    """The series the yardstick's `assembly.table_grown_share` reads, on
    the backend that folds without a pool."""
    async def go():
        async with rest_stack() as (server, _, _):
            rows = await load(server, 8)
            await agg(server)
            await put(server, rows, ["90", "x", "5000"])
            before = metrics.value("dds_operand_table_total",
                                   outcome="grown") or 0
            assert await agg(server) == product(rows.values(), 2, NSQR)
            assert metrics.value("dds_operand_table_total",
                                 outcome="grown") == before + 1

    asyncio.run(go())
