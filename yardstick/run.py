"""yardstick/run.py — one cell of the benchmark, once.

    python3 yardstick/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process, one deployment (`dds_tpu.run.launch`: in-process CPU replicas,
the proxy folding on the chip), then: load the cell's rows through
`POST /PutSet`, warm up every shape the window will use, measure for
`--seconds`, let what is in flight end, hold every answer to the plain
reference, stop the deployment, print one JSON object as the last line.

The cell, its deployment, its traffic and its per-layer metrics are found
by name: `BENCHMARK.json` -> `configs/<config>.json`,
`traffic/<traffic>.json`, `layers/<metric>.json` -> `reducers/<name>.py`.
Nothing in this file names a cell, a mix or a metric (see README.md).

It exits non-zero, printing no result, when the cell's files cannot be
found, when the program is not in the checkout, when jax has no TPU
(unless `JAX_PLATFORMS` names `cpu`, for rehearsal; `device` then says
`cpu`) or fewer chips than the cell asks for, or when set-up itself
fails. Inside the window nothing raises: a non-200, a timeout or a wrong
answer adds one to `failed`, and a wrong answer makes `correct` false.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()   # set-up is counted from here

import argparse   # noqa: E402
import asyncio   # noqa: E402
import faulthandler   # noqa: E402
import gc   # noqa: E402
import importlib   # noqa: E402
import json   # noqa: E402
import math   # noqa: E402
import os   # noqa: E402
import random   # noqa: E402
import shutil   # noqa: E402
import sys   # noqa: E402
from dataclasses import dataclass   # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
OUT_DIR = os.path.join(ROOT, ".yardstick_out")
SETUP_TIMEOUT = 1100.0   # a cold first aggregate compiles inside the request
LOAD_ATTEMPTS = 4        # tries of one PutSet during the load
GAP_NS = 20_000.0        # a longer pause of the device lies between programs
TRACE_SECONDS = 2.0      # the stretch of the window the profiler records


class SetupError(Exception):
    """Set-up failed: the run ends non-zero and prints no result."""


def say(tag: str, **fields) -> None:
    print(f"[{tag}] {json.dumps(fields)}", flush=True)


def _load(kind: str, name: str) -> dict:
    path = os.path.join(HERE, kind, f"{name}.json")
    try:
        with open(path) as f:
            return json.load(f)
    except (OSError, ValueError) as e:
        raise SetupError(f"cannot read {path}: {e}") from e


def find_cell(workload: str) -> dict:
    """The cell with its configuration, mix and per-layer metrics."""
    try:
        with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
            bench = json.load(f)
    except (OSError, ValueError) as e:
        raise SetupError(f"cannot read BENCHMARK.json: {e}") from e
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise SetupError(f"no workload {workload!r} in BENCHMARK.json "
                         f"(have {sorted(cells)})")
    cell = dict(cells[workload])

    def mine(metric: dict) -> bool:
        return "workloads" not in metric or workload in metric["workloads"]

    cell["end_to_end"] = [m for m in bench["end_to_end"] if mine(m)]
    cell["config_file"] = _load("configs", cell["config"])
    cell["mix"] = _load("traffic", cell["traffic"])
    cell["layers"] = {m["name"]: _load("layers", m["name"])
                      for m in bench["per_layer"] if mine(m)}
    return cell


def percentile(values: list[float], q: float) -> float:
    """Nearest-rank percentile."""
    s = sorted(values)
    return s[max(0, min(len(s) - 1, math.ceil(q * len(s)) - 1))]


# ------------------------------------------------------------- deployment


# what `build_config` sets from the configuration's named keys (and all of
# `replicas.`): a `settings` entry may not say it a second way
NAMED_SETTINGS = ("recovery.enabled", "proxy.port", "proxy.crypto_backend")


def build_config(conf: dict):
    from dds_tpu.utils.config import DDSConfig

    cfg = DDSConfig()
    n = int(conf["replicas"])
    cfg.replicas.endpoints = [f"replica-{i}" for i in range(n)]
    cfg.replicas.sentinent = [f"replica-{i}"
                              for i in range(n - int(conf["sentinels"]), n)]
    cfg.replicas.byz_quorum_size = int(conf["quorum"])
    cfg.replicas.byz_max_faults = int(conf["max_faults"])
    cfg.recovery.enabled = bool(conf["recovery"])
    cfg.proxy.port = 0
    cfg.proxy.crypto_backend = conf["crypto_backend"]
    for path, value in conf.get("settings", {}).items():
        apply_setting(cfg, path, value)
    return cfg


def apply_setting(cfg, path: str, value) -> None:
    """One entry of a configuration's `settings`: a dotted path into
    `DDSConfig` and the value it gets. Refused: a path the named keys
    already set, one that does not exist, a value of another type than
    the default's (a whole group is not a value)."""
    if path.startswith("replicas.") or path in NAMED_SETTINGS:
        raise SetupError(f"settings: {path!r} is set by the configuration's "
                         "named keys (replicas, sentinels, quorum, "
                         "max_faults, recovery, crypto_backend)")
    *groups, leaf = path.split(".")
    node = cfg
    for g in groups:
        node = getattr(node, g, None)
    if leaf not in getattr(node, "__dataclass_fields__", ()):
        raise SetupError(f"settings: DDSConfig has no {path!r}")
    default = getattr(node, leaf)
    if type(value) is not type(default):
        raise SetupError(f"settings: {path!r} is {type(default).__name__} "
                         f"({default!r}), not {type(value).__name__} "
                         f"({value!r})")
    setattr(node, leaf, value)


@dataclass
class Span:
    """One span of the program, with its end on `time.perf_counter`."""

    name: str
    dur_ms: float
    t_end: float
    span_id: str | None
    parent_id: str | None

    @property
    def t_start(self) -> float:
        return self.t_end - self.dur_ms / 1e3


class Window:
    """What a reducer may read: the window's spans, counters, operations,
    the reduced device trace, and the numbers taken during set-up."""

    def __init__(self, config: dict, device_kind: str, limbs: dict):
        self.config, self.device_kind, self.limbs = config, device_kind, limbs
        self.spans: list[Span] = []
        self.ops: list = []
        self.setup: dict[str, float] = {}
        self.trace: dict | None = None
        self.counts: dict[str, float] = {}   # the harness's own counts
        self._before: dict = {}
        self._metrics = None

    # spans ---------------------------------------------------------------

    def _on_span(self, rec) -> None:
        if rec.kind == "span":
            self.spans.append(Span(rec.name, rec.dur_ms, time.perf_counter(),
                                   rec.span_id, rec.parent_id))

    def open(self, layers: dict) -> None:
        from dds_tpu.obs.metrics import metrics
        from dds_tpu.utils.trace import tracer

        self._metrics = metrics
        for spec in layers.values():
            args = spec.get("args", {})
            if "counter" in args:
                labelsets = ([{args["label"]: v} for v in args["of"]]
                             if "of" in args else [args.get("labels", {})])
                for labels in labelsets:
                    key = (args["counter"], tuple(sorted(labels.items())))
                    self._before[key] = metrics.value(args["counter"],
                                                      **labels) or 0.0
        tracer.subscribe(self._on_span)

    def close(self) -> None:
        from dds_tpu.utils.trace import tracer

        tracer.unsubscribe(self._on_span)

    def counter_delta(self, counter: str, **labels) -> float:
        now = self._metrics.value(counter, **labels) or 0.0
        return now - self._before.get(
            (counter, tuple(sorted(labels.items()))), 0.0)


# ------------------------------------------------------------------ a run


class Run:
    def __init__(self, args, cell: dict, device: dict):
        from yardstick.data import MSE, PSSE, Dataset

        self.args, self.cell, self.device = args, cell, device
        self.conf = cell["config_file"]
        self.mix = cell["mix"]
        self.cfg = build_config(self.conf)   # refused before any row is made
        try:
            self.data = Dataset(args.seed, int(self.conf["rows"]),
                                int(self.conf["plain_bits"]),
                                int(self.conf["update_step_bits"]),
                                int(self.conf.get("paillier_bits", 2048)),
                                int(self.conf.get("rsa_bits", 1024)))
        except KeyError as e:
            raise SetupError(e.args[0]) from e
        # the deployment shares this process's heap: a full collection
        # walks every container alive, so the harness's rows would lengthen
        # the program's own collector pauses. Park them where it never looks.
        gc.collect()
        gc.freeze()
        self.limbs = {c: -(-m.bit_length() // 16)
                      for c, m in self.data.moduli.items()}
        self.window = Window(self.conf, device["kind"], self.limbs)
        self.checks: list[tuple[str, float, float]] = []  # name, value, limit
        self.failed_setup_ops = 0
        self.wrong_examples: list[str] = []
        self.fail_examples: list[str] = []
        # every XLA compilation of the process, as jax itself reports it:
        # (when it ended on perf_counter, seconds it took)
        self.compiles: list[tuple[float, float]] = []
        from jax import monitoring

        monitoring.register_event_duration_secs_listener(self._on_jax_event)
        routes = [o["route"] for g in self.mix["groups"] for o in g["ops"]
                  if o["op"] == "aggregate"]
        self.routes = sorted(set(routes)) or ["SumAll"]
        self._cols = {"SumAll": PSSE, "MultAll": MSE}

    def _on_jax_event(self, name: str, seconds: float, **_kw) -> None:
        if name.endswith("backend_compile_duration"):
            self.compiles.append((time.perf_counter(), seconds))

    # ------------------------------------------------------- small helpers

    async def call(self, method: str, target: str, body=None,
                   timeout: float = 60.0):
        from yardstick import httpc

        data = None if body is None else json.dumps(body).encode()
        return await httpc.request(self.host, self.port, method, target,
                                   data, timeout)

    def agg_target(self, route: str) -> str:
        from yardstick.traffic import MODPARAM

        col = self._cols[route]
        return (f"/{route}?position={col}"
                f"&{MODPARAM[route]}={self.data.moduli[col]}")

    def note_check(self, name: str, value: float, limit: float) -> None:
        self.checks.append((name, value, limit))

    def gc_pause_s(self) -> float:
        """Seconds the collector (jax's hook in it included) has held the
        process, by the program's own count."""
        from dds_tpu.obs.metrics import metrics

        return sum(metrics.value("dds_gc_pause_seconds_total",
                                 generation=str(g)) or 0.0 for g in range(3))

    def pool_stats(self) -> dict | None:
        """The device pool's own counts for the additive column, where the
        backend keeps one (a count of the program's, read not driven)."""
        be = self.dep.server.backend
        if not hasattr(be, "store_for"):
            return None
        return be.store_for(self.data.moduli[2]).stats()

    # --------------------------------------------------------- quiet point

    async def settle(self) -> bool:
        """Learn, by reading it back, the fate of every update whose
        answer never came. False when a row stays unknown."""
        d = self.data
        for col, i in sorted(d.unsure):
            status, body = await self.call("GET", f"/GetSet/{d.keys[i]}")
            if status != 200:
                return False
            got = json.loads(body)["contents"][col]
            if got == d.versions[col][i][-1]:
                d.row_acked[col][i] += 1
                d.acked[col] += 1
            elif got != d.versions[col][i][-2]:
                return False
            d.unsure.discard((col, i))
        return True

    async def quiet_point(self, label: str, timeout: float = 60.0) -> float:
        """With no write in flight, every aggregate the mix uses equals
        the reference's product over exactly the stored ciphertexts and
        decrypts to the plaintext total, and three seeded rows read back
        as acknowledged. Returns the first aggregate's seconds."""
        from yardstick import reference

        d = self.data
        wrong = 0
        if not await self.settle():
            self.failed_setup_ops += 1
            self.fail_examples.append(
                f"quiet point {label}: a row's last update stays unknown; "
                "exact comparison skipped")
            return 0.0
        first_s = 0.0
        for route in self.routes:
            col = self._cols[route]
            t0 = time.perf_counter()
            status, body = await self.call("GET", self.agg_target(route),
                                           timeout=timeout)
            first_s = first_s or time.perf_counter() - t0
            if status != 200:
                self.failed_setup_ops += 1
                self.fail_examples.append(
                    f"quiet point {label}: {route} answered {status} "
                    f"{body[:120]!r}")
                continue
            got = int(json.loads(body)["result"])
            if got != reference.fold(d.current(col), d.moduli[col]):
                wrong += 1
                self.wrong_examples.append(
                    f"quiet point {label}: {route} differs from the "
                    "reference's product of the stored ciphertexts")
            plain = d.decrypt(col, got)
            if d.schemes[col].count(plain, d.sent[col]) != d.acked[col]:
                wrong += 1
                self.wrong_examples.append(
                    f"quiet point {label}: {route} does not decrypt to the "
                    f"total after {d.acked[col]} updates")
        rng = random.Random(f"{self.args.seed}/readback/{label}")
        for i in rng.sample(range(d.k), min(3, d.k)):
            status, body = await self.call("GET", f"/GetSet/{d.keys[i]}")
            want = d.row_version(i, d.row_acked[2][i], d.row_acked[3][i])
            if status != 200:
                self.failed_setup_ops += 1
                self.fail_examples.append(
                    f"quiet point {label}: GetSet answered {status}")
            elif json.loads(body)["contents"] != want:
                wrong += 1
                self.wrong_examples.append(
                    f"quiet point {label}: GetSet of row {i} differs from "
                    "what was acknowledged")
        self.note_check(f"quiet_point.{label}.wrong_answers", wrong, 0)
        return first_s

    # --------------------------------------------------------------- phases

    async def load(self) -> None:
        d = self.data
        sem = asyncio.Semaphore(int(self.conf["load_in_flight"]))

        retried = [0]

        async def put(row):
            # PutSet names a row by its content, so sending it again is
            # safe: a client told 503 waits and retries, and so does this
            async with sem:
                for attempt in range(LOAD_ATTEMPTS):
                    status, body = await self.call("POST", "/PutSet",
                                                   {"contents": row})
                    if status == 200:
                        return body.decode()
                    retried[0] += 1
                    await asyncio.sleep(1.0)
                raise SetupError(f"PutSet answered {status} {body[:200]!r} "
                                 f"{LOAD_ATTEMPTS} times during the load")

        t0 = time.perf_counter()
        d.keys = list(await asyncio.gather(*(put(r) for r in d.rows)))
        load_s = time.perf_counter() - t0
        if len(set(d.keys)) != d.k:
            raise SetupError("PutSet returned duplicate keys")
        self.window.setup.update(load_s=load_s, putset_per_s=d.k / load_s)
        say("load", rows=d.k, load_s=load_s, putset_per_s=d.k / load_s,
            retried=retried[0])

    async def warm_up(self) -> None:
        """Every shape the window will use: the aggregate at the cell's
        operand count, the pool at the capacity it will reach, and the
        ingest of 1..`update_bursts` new rows between two aggregates."""
        from yardstick.traffic import Traffic

        spec = self.mix.get("warmup", {})
        update = next((o for g in self.mix["groups"] for o in g["ops"]
                       if o["op"] == "update"), None)
        t0 = time.perf_counter()
        warm = Traffic(self.mix, self.data, self.host, self.port,
                       self.args.seed ^ 0x3A3A)
        self.warm = warm
        if update is not None:
            group = next(g for g in self.mix["groups"] if update in g["ops"])
            agg = next(g for g in self.mix["groups"]
                       if any(o["op"] == "aggregate" for o in g["ops"]))
            agg_op = next(o for o in agg["ops"] if o["op"] == "aggregate")
            rng = random.Random(f"{self.args.seed}/bursts")
            for m in range(1, int(spec.get("update_bursts", 0)) + 1):
                await asyncio.gather(*(
                    warm.do(group, update, rng, time.perf_counter())
                    for _ in range(m)))
                await warm.do(agg, agg_op, rng, time.perf_counter())
        await warm.run(float(spec.get("seconds", 2.0)))
        say("warm_up", seconds=time.perf_counter() - t0, ops=len(warm.ops))

    async def _heartbeat(self) -> None:
        """How late the event loop runs (clients, proxy and replicas share
        it). A loop that falls silent is the program's to report: its own
        watchdog (`obs/runtime.LoopSampler`) logs every thread's stack,
        read under the interpreter lock. `faulthandler`'s timed dump reads
        them without it, and killed the run it was to explain."""
        tick = 0.05
        while True:
            t = time.perf_counter()
            await asyncio.sleep(tick)
            self.loop_lag_ms.append((time.perf_counter() - t - tick) * 1e3)

    async def measure(self, seed: int | None = None) -> None:
        """One window of the mix, then the quiet point after it."""
        from yardstick.traffic import Traffic

        args = self.args
        self.traffic = Traffic(self.mix, self.data, self.host, self.port,
                               args.seed if seed is None else seed)
        tracing = None
        if args.trace:
            self.window.open(self.cell["layers"])
            tracing = asyncio.ensure_future(self._profile(
                args.seconds, time.perf_counter() + args.seconds))
        self.pool_before = self.pool_stats()
        self.gc_before = self.gc_pause_s()
        self.loop_lag_ms: list[float] = []
        beat = asyncio.ensure_future(self._heartbeat())
        self.setup_s = time.perf_counter() - T_START
        self.t0, self.t_end = await self.traffic.run(args.seconds)
        beat.cancel()
        self.pool_after = self.pool_stats()
        if tracing is not None:
            await tracing
            self.window.close()
        await self.quiet_point("after_window")

    async def _profile(self, seconds: float, t_close: float) -> None:
        """Record the last TRACE_SECONDS of the window with jax's profiler,
        the python tracer off; stopping it (which is slow) falls after the
        window. Two marks put the host's clock on the trace's."""
        import jax

        tdir = os.path.join(OUT_DIR, "trace", self.cell["name"])
        shutil.rmtree(tdir, ignore_errors=True)
        span = min(TRACE_SECONDS, seconds / 2)
        await asyncio.sleep(max(0.0, t_close - span - time.perf_counter()))
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        await asyncio.to_thread(jax.profiler.start_trace, tdir,
                                profiler_options=opts)
        lo = time.perf_counter()
        with jax.profiler.TraceAnnotation("yardstick.sync",
                                          t_ns=int(lo * 1e9)):
            pass
        await asyncio.sleep(max(0.0, t_close - lo))
        hi = time.perf_counter()
        with jax.profiler.TraceAnnotation("yardstick.sync",
                                          t_ns=int(hi * 1e9)):
            pass
        await asyncio.to_thread(jax.profiler.stop_trace)
        self.trace_dir = tdir

    # ----------------------------------------------------------- the whole

    async def setup(self) -> None:
        """Launch, load, first aggregate, warm up. The caller stops the
        deployment (`stop`) whatever happens after this returns."""
        from dds_tpu.run import launch

        self.dep = await launch(self.cfg)
        self.host, self.port = self.cfg.proxy.host, self.dep.server.cfg.port
        be = self.dep.server.backend
        say("deployment", config=self.conf["name"],
            backend=getattr(be, "name", None),
            platform=getattr(be, "platform", None),
            pallas=getattr(be, "pallas", None),
            paillier_bits=self.data.paillier.n.bit_length(),
            rsa_bits=self.data.rsa.n.bit_length(), limbs=self.limbs,
            settings=self.conf.get("settings", {}))
        await self.load()
        first = await self.quiet_point("after_load", SETUP_TIMEOUT)
        self.window.setup["first_agg_s"] = first
        say("first_aggregate", seconds=first, pool=self.pool_stats())
        await self.warm_up()
        await self.quiet_point("after_warm_up")

    async def stop(self) -> None:
        import jax

        await self.dep.stop()
        self.device["memory_peak_bytes"] = max(
            (d.memory_stats() or {}).get("peak_bytes_in_use", 0)
            for d in jax.local_devices())

    async def run(self) -> dict:
        try:
            await self.setup()
            await self.measure()
        finally:
            if hasattr(self, "dep"):
                await self.stop()
        return self.report()

    # ------------------------------------------------------------ reporting

    def report(self) -> dict:
        from dds_tpu.obs.metrics import metrics

        args, tr = self.args, self.traffic
        every = self.warm.ops + tr.ops
        tr.judge(every)
        window_ops = tr.ops
        wrong = [op for op in every if op.verdict is not None]
        unanswered = [op for op in window_ops if op.status != 200]
        for op in wrong[:5]:
            self.wrong_examples.append(
                f"{op.kind} in group {op.group}: {op.verdict}")
        for op in unanswered[:5]:
            self.fail_examples.append(
                f"{op.kind} in group {op.group}: status {op.status} "
                f"{op.body[:120]!r}")
        self.note_check("window.wrong_aggregates",
                        sum(1 for o in wrong if o.kind == "aggregate"), 0)
        self.note_check("window.wrong_rows",
                        sum(1 for o in wrong if o.kind == "read"), 0)
        for name, value, limit in self.checks:
            say("check", number=name, value=value, limit=limit,
                ok=value <= limit)
        for line in self.wrong_examples[:8]:
            say("wrong", what=line)
        for line in self.fail_examples[:8]:
            say("failed", what=line)
        correct = all(value <= limit for _, value, limit in self.checks)

        good = [op for op in window_ops if op.status == 200
                and op.verdict is None and op.t_done <= self.t_end]
        aggs = [op.ms for op in good if op.kind == "aggregate"]
        points = [op.ms for op in good if op.kind in ("read", "update")]
        seconds = self.t_end - self.t0
        values = {"setup_s": self.setup_s,
                  "ops_per_s": len(good) / seconds}
        if aggs:
            values["agg_p50_ms"] = percentile(aggs, 0.50)
            values["agg_p95_ms"] = percentile(aggs, 0.95)
        if points:
            values["point_p95_ms"] = percentile(points, 0.95)
        compiled = [d for t, d in self.compiles if self.t0 <= t <= self.t_end]
        self.window.counts["xla_compiles_in_window"] = float(len(compiled))
        quants = (0.05, 0.25, 0.5, 0.75, 0.9, 0.95, 0.99, 1.0)
        say("latency_ms", quantiles=quants,
            aggregate=[percentile(aggs, q) for q in quants] if aggs else [],
            point=[percentile(points, q) for q in quants] if points else [],
            loop_lag=[percentile(self.loop_lag_ms, q) for q in quants]
            if self.loop_lag_ms else [])
        tenths = [0] * 10
        for op in good:
            tenths[min(9, int(10 * (op.t_done - self.t0) / seconds))] += 1
        say("window", seconds=seconds, aggregates=len(aggs),
            completed_by_tenth=tenths,
            xla_compiles_in_window=len(compiled),
            xla_compile_seconds_in_window=sum(compiled),
            points=len(points),
            updates_in_window=sum(1 for op in window_ops
                                  if op.kind == "update"),
            in_flight_at_close=sum(1 for op in window_ops
                                   if op.t_done > self.t_end),
            generator_late_ms_p95=(percentile(tr.late_ms, 0.95)
                                   if tr.late_ms else 0.0),
            loop_stalls_reported=metrics.value(
                "dds_event_loop_stalls_total") or 0.0,
            gc_pause_s_since_open=self.gc_pause_s() - self.gc_before,
            pool_before=self.pool_before, pool_after=self.pool_after)

        units = {m["name"]: m["unit"] for m in self.cell["end_to_end"]}
        out = {"correct": correct,
               "attempted": len(window_ops),
               "failed": (len(unanswered) + self.failed_setup_ops
                          + sum(1 for o in window_ops
                                if o.verdict is not None)),
               "metrics": {}, "device": self.device}
        if not args.trace:
            out["metrics"] = {n: {"value": values[n], "unit": u}
                              for n, u in units.items() if n in values}
        else:
            self.window.ops = [op for op in window_ops
                               if op.t_done <= self.t_end]
            breakdown = self._reduce_trace()
            for name, spec in self.cell["layers"].items():
                mod = importlib.import_module(
                    f"yardstick.reducers.{spec['reducer']}")
                value = mod.reduce(self.window, **spec.get("args", {}))
                if value is not None:
                    out["metrics"][name] = {"value": value,
                                            "unit": spec["unit"]}
            if breakdown:
                out["breakdown"] = breakdown
        # every number compared beside its limit, last in the line
        out["checks"] = {name: {"value": value, "limit": limit}
                         for name, value, limit in self.checks}
        return out

    def _reduce_trace(self) -> dict | None:
        """Read the profiler's file into `window.trace` and `device`, and
        name where the device's time and its idle gaps went."""
        import glob

        from yardstick import trace_reduce as trd

        files = glob.glob(os.path.join(
            getattr(self, "trace_dir", "/nonexistent"),
            "plugins", "profile", "*", "*.xplane.pb"))
        if not files:
            return None
        planes = trd.read_xplane(files[0])
        if self.args.keep_trace:
            os.makedirs(self.args.keep_trace, exist_ok=True)
            shutil.copy(files[0], self.args.keep_trace)
        shutil.rmtree(self.trace_dir, ignore_errors=True)
        marks = trd.sync_marks(planes)
        if len(marks) < 2:
            return None
        (lo_ns, lo_pc), (hi_ns, hi_pc) = marks[0], marks[-1]
        self.window.trace = {"planes": planes, "lo_ns": lo_ns,
                             "hi_ns": hi_ns, "lo_pc": lo_pc / 1e9,
                             "hi_pc": hi_pc / 1e9}
        busy = trd.busy_seconds(planes, lo_ns, hi_ns)
        if busy > 0:
            self.device["busy_s"] = busy
            self.device["window_s"] = (hi_ns - lo_ns) / 1e9
        ops = trd.op_seconds(planes, trd.OPS_LINE, lo_ns, hi_ns)
        top = sorted(ops.items(), key=lambda kv: -kv[1])[:10]
        # an idle gap between two programs belongs to the shortest program
        # span that covers its middle, once that is put on the trace's
        # clock; the pauses inside one program are told apart by length
        to_pc = lambda ns: (lo_pc + (ns - lo_ns)) / 1e9   # noqa: E731
        spans = [s for s in self.window.spans
                 if s.t_end >= lo_pc / 1e9 and s.t_start <= hi_pc / 1e9]
        long_gaps = trd.idle_gaps(planes, lo_ns, hi_ns, GAP_NS)
        by_span = {f"inside a program (pauses under {GAP_NS / 1e3:.0f} us)":
                   (hi_ns - lo_ns) / 1e9 - busy
                   - sum(b - a for a, b in long_gaps) / 1e9}
        for a, b in long_gaps:
            mid = to_pc((a + b) / 2)
            cover = [s for s in spans if s.t_start <= mid <= s.t_end]
            name = (min(cover, key=lambda s: s.dur_ms).name if cover
                    else "no program span")
            by_span[name] = by_span.get(name, 0.0) + (b - a) / 1e9
        gaps = sorted(by_span.items(), key=lambda kv: -kv[1])[:10]
        return {"device_ops": [[n, s] for n, s in top],
                "idle_gaps": [[n, s] for n, s in gaps]}


# -------------------------------------------------------------------- main


def find_device(chips: int) -> dict:
    """The device as jax reports it; refuses a CPU nobody asked for."""
    import jax

    devs = jax.local_devices()
    platform = devs[0].platform
    asked_cpu = "cpu" in os.environ.get("JAX_PLATFORMS", "").lower()
    if platform != "tpu" and not asked_cpu:
        raise SetupError(f"jax found platform {platform!r}, not 'tpu'")
    if platform == "tpu" and len(devs) < chips:
        raise SetupError(f"the cell asks for {chips} chips, jax found "
                         f"{len(devs)}")
    return {"platform": platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--keep-trace", default="", metavar="DIR",
                    help="copy the profiler's file there, to look at it")
    args = ap.parse_args(argv)
    sys.path.insert(0, ROOT)
    # a run that dies of a signal leaves its python stacks on stderr
    faulthandler.enable()
    # the compile cache at a fixed path inside the checkout, unless the
    # machine brings its own
    os.environ.setdefault("JAX_COMPILATION_CACHE_DIR",
                          os.path.join(ROOT, ".jax_cache"))
    try:
        cell = find_cell(args.workload)
        try:
            import dds_tpu  # noqa: F401 — the system under test
        except ImportError as e:
            raise SetupError(f"the program is not in this checkout: {e}")
        device = find_device(int(cell["chips"]))
        say("start", workload=args.workload, seed=args.seed,
            seconds=args.seconds, trace=args.trace, device=device,
            compile_cache=os.environ["JAX_COMPILATION_CACHE_DIR"])
        result = asyncio.run(Run(args, cell, device).run())
    except SetupError as e:
        print(f"yardstick: {e}", file=sys.stderr)
        return 2
    print(json.dumps(result), flush=True)
    for name, c in result["checks"].items():
        print(f"yardstick: check {name} = {c['value']} (limit {c['limit']})",
              file=sys.stderr)
    return 0


if __name__ == "__main__":
    if os.environ.get("PYTHONHASHSEED") != "0":
        # str hashes are salted per process, and the salt moves a whole run
        # by several per cent (dict and set layouts of the proxy and the
        # replicas). Runs are compared with each other, so start again with
        # the salt fixed; nothing has touched jax yet.
        os.environ["PYTHONHASHSEED"] = "0"
        os.execv(sys.executable, [sys.executable] + sys.argv)
    code = main()
    sys.stdout.flush()
    sys.stderr.flush()
    os._exit(code)   # jax's threads must not hold a finished run open
