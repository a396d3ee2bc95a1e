"""The positions path as it is served, over the wire (`transport.kind =
"tcp"` through `run.launch`).

`SumAll` under concurrent `WriteElement`s, and under writes by a proxy
this one knows nothing of, so that the quorum's tags move under its tag
rounds: every answer inside its interval by `yardstick/check.py`'s rule
against python ints, none wrong, and after the first round every tag round
that has tags to hold against the table is decided at positions
(`dds_operand_table_validate_total{path="positions"}`), none by the pass
over all K. The search routes, which call `read_tags` for a key set of
their own and compare its reply themselves, still find a row written
behind their back.
"""

import asyncio
import json
import random
import time

import pytest

from dds_tpu.core.quorum_client import AbdClient
from dds_tpu.obs.metrics import metrics
from dds_tpu.utils.trace import tracer

from tests.test_replica_processes import NSQ, STEP, dec, enc, fold
from tests.test_tcp_deployment import _deployment_cfg

ROWS = 24


def validated():
    return {p: metrics.value("dds_operand_table_validate_total", path=p) or 0
            for p in ("positions", "full")}


class World:
    def __init__(self):
        from dds_tpu.run import launch

        cfg = self.cfg = _deployment_cfg("tcp")
        cfg.proxy.crypto_backend = "cpu"
        # the index learns of a write from the tag round alone
        cfg.search.enabled = True
        cfg.search.write_ingest = False
        self.loop = asyncio.new_event_loop()
        self.dep = self.run(launch(cfg))
        self.host, self.port = cfg.proxy.host, self.dep.server.cfg.port
        rng = self.rng = random.Random(51)
        self.plain = [rng.randrange(1 << 16) for _ in range(ROWS)]
        self.rows = [[10 * i, "x", str(enc(self.plain[i], rng)), "1", "y",
                      "z", "w", None] for i in range(ROWS)]
        self.keys = self.run(self.load())
        abd = self.dep.server.abd
        # another proxy of the same deployment: what it writes, this one's
        # cache never sees
        self.other = AbdClient(
            abd.addr.replace("proxy-0", "proxy-ext"), self.dep.net,
            list(abd.replicas.get_trusted()), abd.cfg)

    def run(self, coro):
        return self.loop.run_until_complete(coro)

    async def call(self, method, target, body=None):
        from dds_tpu.http.miniserver import http_request

        st, raw = await http_request(
            self.host, self.port, method, target,
            None if body is None else json.dumps(body).encode(), timeout=30.0)
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

    def stop(self):
        self.run(self.dep.stop())


@pytest.fixture(scope="module")
def world():
    w = World()
    try:
        yield w
    finally:
        w.stop()
        w.loop.close()


def test_sumall_under_writes_over_the_wire_is_decided_at_positions(world):
    w = world
    base = sum(w.plain)
    assert base < STEP
    sent, acked = [0], [0]
    row_acked = [0] * ROWS
    wrong: list[str] = []
    seen = {"aggregates": 0}
    spans = []

    def on_record(rec):
        if rec.name == "assembly.validate_tags":
            spans.append(dict(rec.meta))

    async def write(i, rng, through_rest):
        new = str(enc(w.plain[i] + STEP * (row_acked[i] + 1), rng))
        row = w.rows[i][:2] + [new] + w.rows[i][3:]
        sent[0] += 1
        if through_rest:
            st, _ = await w.call(
                "PUT", f"/WriteElement/{w.keys[i]}?position=2",
                {"value": new})
            if st != 200:
                wrong.append("an update was refused")
        else:
            await w.other.write_set(w.keys[i], row)
        w.rows[i] = row
        row_acked[i] += 1
        acked[0] += 1

    async def writer(mine, rng, through_rest, t_end):
        while time.perf_counter() < t_end:
            await write(rng.choice(mine), rng, through_rest)
            if not through_rest:
                await asyncio.sleep(0.05)

    async def summer(t_end):
        while time.perf_counter() < t_end:
            lo = acked[0]
            got = dec(await w.sum_all()) - base
            hi = sent[0]
            seen["aggregates"] += 1
            if got % STEP or not lo <= got // STEP <= hi:
                wrong.append(f"SumAll of {got / STEP} updates, not in "
                             f"[{lo}, {hi}]")

    async def go():
        # the first round: the replicas learn the key set and answer whole
        total = await w.sum_all()
        assert total == fold((int(r[2]) for r in w.rows), NSQ)
        before = validated()
        t_end = time.perf_counter() + 2.5
        await asyncio.gather(
            *(writer(list(range(k, ROWS, 4)), random.Random(k), k != 3, t_end)
              for k in range(4)),
            summer(t_end), summer(t_end))
        # a write behind this proxy's back with nothing else in flight and
        # the other proxy's last one taken in: the next aggregate's round
        # finds that row and no other
        await w.sum_all()
        await write(5, w.rng, through_rest=False)
        total = await w.sum_all()
        assert total == fold((int(r[2]) for r in w.rows), NSQ)
        assert dec(total) == base + STEP * acked[0]
        return before

    tracer.subscribe(on_record)
    try:
        before = w.run(go())
    finally:
        tracer.unsubscribe(on_record)
    assert wrong == []
    assert acked[0] > 8 and seen["aggregates"] > 2
    after = validated()
    assert after["positions"] > before["positions"]
    assert after["full"] == before["full"]
    paths = [m["path"] for m in spans]
    assert "positions" in paths and "full" not in paths
    assert spans[-1]["path"] == "positions" and spans[-1]["stale"] == 1
    assert (after["positions"] - before["positions"]
            == paths.count("positions"))


def test_the_search_routes_still_compare_the_reply_themselves(world):
    w = world

    async def search():
        st, body = await w.call("POST", "/SearchGt?position=0",
                                {"value": 10 * ROWS})
        assert st == 200, (st, body)
        return json.loads(body)["keyset"]

    async def go():
        assert await search() == []
        stale = metrics.value("dds_search_index_total", outcome="stale") or 0
        # one row moves past the bound, through the other proxy: the tag
        # round's reply names it, the route's own comparison finds it
        w.rows[7] = [10 * ROWS + 1] + w.rows[7][1:]
        await w.other.write_set(w.keys[7], w.rows[7])
        assert await search() == [w.keys[7]]
        assert (metrics.value("dds_search_index_total", outcome="stale")
                == stale + 1)
        assert await search() == [w.keys[7]]      # and nothing moved since
        assert (metrics.value("dds_search_index_total", outcome="stale")
                == stale + 1)
        total = await w.sum_all()
        assert total == fold((int(r[2]) for r in w.rows), NSQ)

    w.run(go())
