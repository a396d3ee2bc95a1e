"""A key set that grows between two aggregates, on the served path: what
the cell `bft4-ycsbd-sumall` relies on, and (unlike the yardstick's own
`yardstick/tests/test_growing_keyset.py`, whose four cases stand here
too) what it costs: the second aggregate folds a column carried from the
table before (`dds_operand_table_total{outcome="grown"}`), parses and
looks up the new rows alone, and falls back to a table built anew where
`http/server._sync_table`'s rule says so.

One deployment as `run.launch` builds it (n = 4, quorum 3, in memory),
the `tpu` backend on the CPU with every fold through the pool.
"""

import asyncio
import functools
import json
import random

import pytest

from dds_tpu.http.miniserver import http_request
from dds_tpu.obs.metrics import metrics

from tests.test_operand_table import OUTCOMES
from tests.test_operand_table_growth import lookups, spans
from tests.test_tcp_deployment import MULT_MOD, SUM_MOD, _deployment_cfg

SUM = f"/SumAll?position=2&nsqr={SUM_MOD}"
MULT = f"/MultAll?position=3&pubkey={MULT_MOD}"


def _row(rng, i):
    return [i, "x", str(rng.randrange(2, SUM_MOD)),
            str(rng.randrange(2, MULT_MOD)), "y", "z", "w", None]


def _fold(rows, col, modulus):
    return functools.reduce(lambda a, b: a * b % modulus,
                            (int(r[col]) for r in rows), 1)


def _outcomes() -> dict:
    return {o: metrics.value("dds_operand_table_total", outcome=o) or 0
            for o in OUTCOMES}


def _since(before: dict) -> dict:
    now = _outcomes()
    return {o: now[o] - before[o] for o in now if now[o] != before[o]}


class Store:
    """A launched deployment and the rows it should hold, by key."""

    def __init__(self, dep, cfg):
        self.dep, self.server = dep, dep.server
        self.at = (cfg.proxy.host, dep.server.cfg.port)
        self.rows: dict[str, list] = {}

    async def call(self, method, target, body=None, status=200):
        st, raw = await http_request(
            *self.at, method, target,
            None if body is None else json.dumps(body).encode(), timeout=30.0)
        assert st == status, (method, target, st, raw[:200])
        return raw.decode()

    async def put(self, row) -> str:
        key = await self.call("POST", "/PutSet", {"contents": row})
        self.rows[key] = row
        return key

    async def write(self, key, pos, value):
        await self.call("PUT", f"/WriteElement/{key}?position={pos}",
                        {"value": value})
        self.rows[key] = [*self.rows[key][:pos], value,
                          *self.rows[key][pos + 1:]]

    async def remove(self, key):
        await self.call("DELETE", f"/RemoveSet/{key}")
        del self.rows[key]

    async def aggregates(self) -> tuple[int, int]:
        s = json.loads(await self.call("GET", SUM))["result"]
        p = json.loads(await self.call("GET", MULT))["result"]
        return int(s), int(p)

    def expected(self) -> tuple[int, int]:
        rows = self.rows.values()
        return _fold(rows, 2, SUM_MOD), _fold(rows, 3, MULT_MOD)


def served(story):
    """Run `story(store)` against a fresh deployment."""
    async def go():
        from dds_tpu.run import launch

        cfg = _deployment_cfg("memory")
        dep = await launch(cfg)
        try:
            return await story(Store(dep, cfg))
        finally:
            await dep.stop()

    return asyncio.run(go())


@pytest.fixture(autouse=True)
def on_pool(monkeypatch):
    monkeypatch.setenv("DDS_TPU_MIN_BATCH", "0")


@pytest.mark.parametrize("k0,m", [
    (30, 1),     # 31: inside 32
    (30, 5),     # 35: across 32; 5 * 8 > 30, so this one builds anew
    (64, 1),     # 65: off an exact power of two
    (250, 9),    # 259: across the pool's first capacity, 256
])
def test_the_aggregate_after_m_inserts_is_the_product_over_all_rows(k0, m):
    rng = random.Random(k0 * 100 + m)
    rows = [_row(rng, i) for i in range(k0 + m)]

    async def story(store):
        keys = [await store.put(r) for r in rows[:k0]]
        first = await store.aggregates()
        assert first == store.expected()
        new = [await store.put(r) for r in rows[k0:]]
        # the same rows again: the same keys, and nothing more is stored
        assert [await store.put(r) for r in rows[k0:]] == new
        assert len(set(keys + new)) == k0 + m == len(store.server.stored_keys)
        before = _outcomes()
        with spans() as seen:
            second = await store.aggregates()
        assert second == store.expected() and second != first
        # one growth for SumAll's column and MultAll's, and none parsed
        # whole: unless the added are over an eighth of the keys there were
        how = "grown" if m * 8 <= k0 else "rebuilt"
        assert _since(before) == {how: 2}
        state = [r.meta for r in seen if r.name == "assembly.state"]
        assert [(s["built"], s["added"], s["k"]) for s in state] == [
            ("grown" if how == "grown" else "anew", m, k0 + m)]
        assert lookups(seen) == (
            [m, m] if how == "grown" else [k0 + m, k0 + m])
        for k, r in zip(new, rows[k0:]):
            got = json.loads(await store.call("GET", f"/GetSet/{k}"))
            assert got["contents"] == r          # bit for bit
        before = _outcomes()
        assert await store.aggregates() == second
        assert _since(before) == {"reused": 2}

    served(story)


def test_a_write_and_an_insert_between_two_aggregates_both_land():
    rng = random.Random(4601)
    rows = [_row(rng, i) for i in range(41)]

    async def story(store):
        keys = [await store.put(r) for r in rows[:40]]
        first = await store.aggregates()
        await store.write(keys[7], 2, str(rng.randrange(2, SUM_MOD)))
        await store.put(rows[40])
        await store.write(keys[23], 3, str(rng.randrange(2, MULT_MOD)))
        before = _outcomes()
        with spans() as seen:
            second = await store.aggregates()
        assert second == store.expected()
        assert second[0] != first[0] and second[1] != first[1]
        assert _since(before) == {"grown": 2}
        # the new row and the written one, each column: nothing else
        assert lookups(seen) == [2, 2]
        table = store.server._table
        assert table.keys == sorted(store.rows)
        assert all(e is store.server._cache[k]
                   for k, e in zip(table.keys, table.entries))

    served(story)


@pytest.mark.parametrize("then_insert", [False, True],
                         ids=["removal", "removal_then_insert"])
def test_a_removeset_falls_back_to_a_table_built_anew(then_insert):
    rng = random.Random(4602)
    rows = [_row(rng, i) for i in range(41)]

    async def story(store):
        keys = [await store.put(r) for r in rows[:40]]
        await store.aggregates()
        await store.remove(keys[11])
        if then_insert:
            await store.put(rows[40])
        before = _outcomes()
        with spans() as seen:
            assert await store.aggregates() == store.expected()
        assert _since(before) == {"rebuilt": 2}
        assert [r.meta["built"] for r in seen
                if r.name == "assembly.state"] == ["anew"]
        # and growth takes over again from the table built anew
        await store.put(_row(rng, 99))
        before = _outcomes()
        assert await store.aggregates() == store.expected()
        assert _since(before) == {"grown": 2}

    served(story)


def test_inserts_racing_aggregates_are_wholly_in_or_out():
    """Two aggregate loops and an insert loop on one proxy: every total is
    the product over the rows loaded and a prefix of the inserts, no
    earlier than those acknowledged when the aggregate was sent."""
    rng = random.Random(4603)
    rows = [_row(rng, i) for i in range(100 + 12)]

    async def story(store):
        for r in rows[:100]:
            await store.put(r)
        await store.aggregates()
        prefix = [_fold(rows[:100 + n], 2, SUM_MOD) for n in range(13)]
        acked = [0]

        async def inserter():
            for r in rows[100:]:
                await store.put(r)
                acked[0] += 1
                await asyncio.sleep(0.005)

        async def reader():
            out = []
            while acked[0] < 12:
                sent = acked[0]
                got = int(json.loads(await store.call("GET", SUM))["result"])
                out.append((sent, prefix.index(got), acked[0]))
            return out

        before = _outcomes()
        a, b, _ = await asyncio.gather(reader(), reader(), inserter())
        assert a and b
        for sent, n, done in a + b:
            assert sent <= n <= done + 1    # +1: stored, its answer in flight
        # at most 12 added to 100: never over an eighth
        assert "rebuilt" not in _since(before)
        assert await store.aggregates() == store.expected()

    served(story)
