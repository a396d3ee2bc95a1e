"""The one general traffic generator: it reads a mix from
`traffic/<name>.json` and knows no mix by name.

A mix is a list of client groups. A group has a `loop`: `closed` (`clients`
callers, each sending its next operation when the last was answered) or
`open` (`rate` operations a second on a Poisson or uniform schedule drawn
from the seed, whatever the system does; latency then counts from when an
operation was due). Its `ops` are drawn by `share`:

- `read`: `GET /GetSet/<key>`;
- `update`: `PUT /WriteElement/<key>?position=<column>` of the row's next
  version (column 2 additive, column 3 multiplicative; see `check.py`);
- `aggregate`: `GET /<route>?position=<column>&<modulus>` with `route`
  `SumAll` (column 2) or `MultAll` (column 3).

Keys are drawn over the loaded rows by `keys`: `{"dist": "zipf", "s": ...}`
through a seeded permutation (rank 0 is some row, not row 0), or
`{"dist": "uniform"}`.

Nothing here raises into the window: an operation that fails is recorded
as failed and the client goes on. Answers are kept as they arrived and
judged after the window, off the clock.
"""

from __future__ import annotations

import asyncio
import json
import random
import time
from dataclasses import dataclass, field

from yardstick import check, httpc
from yardstick.data import MSE, PSSE
from yardstick.zipf import Zipf

MODPARAM = {"SumAll": "nsqr", "MultAll": "pubkey"}
ROUTE_COLUMN = {"SumAll": PSSE, "MultAll": MSE}
OP_TIMEOUT = 20.0   # well above the proxy's 8 s request budget


@dataclass
class Op:
    """One operation as the client saw it."""

    kind: str            # read | update | aggregate
    group: str
    t_due: float         # when it was due (open loop) or sent (closed)
    t_sent: float
    t_done: float = 0.0
    status: int = 0
    body: bytes = b""
    col: int = -1
    row: int = -1
    lo: dict = field(default_factory=dict)   # acknowledged at send, by column
    hi: dict = field(default_factory=dict)   # sent at answer, by column
    verdict: str | None = None               # why it is wrong, once judged

    @property
    def ms(self) -> float:
        return (self.t_done - self.t_due) * 1e3


class Traffic:
    def __init__(self, mix: dict, data, host: str, port: int, seed: int):
        self.mix, self.data = mix, data
        self.host, self.port = host, port
        self.seed = seed
        self.ops: list[Op] = []
        perm = list(range(data.k))
        random.Random(seed ^ 0x5EED).shuffle(perm)
        self._perm = perm
        self._zipf: dict[float, Zipf] = {}
        self.late_ms: list[float] = []   # open loop: how late the generator ran

    # ------------------------------------------------------------ drawing

    def _pick_row(self, group: dict, rng: random.Random) -> int:
        spec = group.get("keys", {"dist": "uniform"})
        if spec["dist"] == "zipf":
            s = float(spec["s"])
            z = self._zipf.get(s)
            if z is None:
                z = self._zipf[s] = Zipf(self.data.k, s)
            return self._perm[z.pick(rng)]
        if spec["dist"] == "uniform":
            return rng.randrange(self.data.k)
        raise ValueError(f"unknown key distribution {spec['dist']!r}")

    @staticmethod
    def _pick_op(group: dict, rng: random.Random) -> dict:
        ops = group["ops"]
        u = rng.random() * sum(o["share"] for o in ops)
        for o in ops:
            u -= o["share"]
            if u < 0:
                return o
        return ops[-1]

    # ---------------------------------------------------------- one op

    async def do(self, group: dict, spec: dict, rng: random.Random,
                  t_due: float) -> Op:
        d = self.data
        kind = spec["op"]
        if kind not in ("aggregate", "read", "update"):
            raise ValueError(f"unknown operation {kind!r}")
        op = Op(kind, group["name"], t_due, time.perf_counter())
        body = None
        if kind == "aggregate":
            route = spec["route"]
            op.col = ROUTE_COLUMN[route]
            method = "GET"
            target = (f"/{route}?position={op.col}"
                      f"&{MODPARAM[route]}={d.moduli[op.col]}")
            op.lo = {op.col: d.acked[op.col]}
        elif kind == "read":
            op.row = self._pick_row(group, rng)
            method, target = "GET", f"/GetSet/{d.keys[op.row]}"
            op.lo = {c: d.row_acked[c][op.row] for c in (PSSE, MSE)}
        else:
            op.col = int(spec["column"])
            # never two updates of one key in flight: redraw the key
            for _ in range(64):
                op.row = self._pick_row(group, rng)
                if d.free(op.row):
                    break
            else:
                op.row = next(i for i in range(d.k) if d.free(i))
            method = "PUT"
            target = f"/WriteElement/{d.keys[op.row]}?position={op.col}"
            body = json.dumps(
                {"value": d.begin_update(op.col, op.row)}).encode()
        op.t_sent = time.perf_counter()
        op.status, op.body = await httpc.request(
            self.host, self.port, method, target, body, OP_TIMEOUT)
        op.t_done = time.perf_counter()
        if kind == "update":
            d.end_update(op.col, op.row, op.status == 200)
        elif kind == "aggregate":
            op.hi = {op.col: d.sent[op.col]}
        else:
            op.hi = {c: d.row_sent(c, op.row) for c in (PSSE, MSE)}
        self.ops.append(op)
        return op

    # ------------------------------------------------------------- loops

    async def _try(self, group: dict, spec: dict, rng: random.Random,
                   t_due: float) -> None:
        """`do`, with whatever it might raise turned into one more failed
        operation: a client loop never dies inside the window."""
        try:
            await self.do(group, spec, rng, t_due)
        except Exception as e:  # noqa: BLE001 — the window must not raise
            now = time.perf_counter()
            self.ops.append(Op(spec.get("op", "?"), group["name"], t_due, now,
                               now, 0, f"{type(e).__name__}: {e}".encode()))

    async def _closed_client(self, group: dict, idx: int, t_end: float):
        rng = random.Random(f"{self.seed}/{group['name']}/{idx}")
        while time.perf_counter() < t_end:
            await self._try(group, self._pick_op(group, rng), rng,
                            time.perf_counter())

    async def _open_group(self, group: dict, t0: float, t_end: float):
        rng = random.Random(f"{self.seed}/{group['name']}/arrivals")
        rate = float(group["rate"])
        tasks, t = [], t0
        while True:
            t += (rng.expovariate(rate)
                  if group.get("arrivals", "poisson") == "poisson"
                  else 1.0 / rate)
            if t >= t_end:
                break
            wait = t - time.perf_counter()
            if wait > 0:
                await asyncio.sleep(wait)
            self.late_ms.append(max(0.0, (time.perf_counter() - t) * 1e3))
            tasks.append(asyncio.ensure_future(
                self._try(group, self._pick_op(group, rng), rng, t)))
        if tasks:
            await asyncio.gather(*tasks)

    async def run(self, seconds: float) -> tuple[float, float]:
        """Drive the mix for `seconds`, then let what is in flight end.
        Returns the window's start and end on `time.perf_counter`."""
        t0 = time.perf_counter()
        t_end = t0 + seconds
        jobs = []
        for group in self.mix["groups"]:
            if group["loop"] == "closed":
                jobs += [self._closed_client(group, i, t_end)
                         for i in range(int(group["clients"]))]
            elif group["loop"] == "open":
                jobs.append(self._open_group(group, t0, t_end))
            else:
                raise ValueError(f"unknown loop {group['loop']!r}")
        await asyncio.gather(*jobs)
        return t0, t_end

    # ----------------------------------------------------------- judging

    def judge(self, ops: list[Op]) -> None:
        """Hold every answer in `ops` to the reference; sets `verdict` on
        the wrong ones. Each distinct aggregate answer is decrypted once."""
        d = self.data
        plains: dict[tuple[int, bytes], int | None] = {}
        for op in ops:
            if op.status != 200 or op.kind == "update":
                continue
            try:
                got = json.loads(op.body)
                if op.kind == "aggregate":
                    memo = (op.col, op.body)
                    if memo not in plains:
                        plains[memo] = d.decrypt(op.col, int(got["result"]))
                    op.verdict = check.judge_aggregate(
                        d.schemes[op.col], plains[memo], op.lo[op.col],
                        op.hi[op.col], d.sent[op.col])
                else:
                    op.verdict = check.judge_row(
                        got["contents"], d.rows[op.row],
                        {c: (d.versions[c][op.row], op.lo[c], op.hi[c])
                         for c in (PSSE, MSE)})
            except (ValueError, KeyError, TypeError) as e:
                op.verdict = f"unreadable answer: {type(e).__name__}: {e}"
