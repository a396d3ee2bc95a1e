"""Every span of one benchmark window, written down and taken apart.

    python3 tools/span_tree.py run OUT.jsonl[.gz] --workload <cell> --seed <n> ...
    python3 tools/span_tree.py rest OUT.jsonl [--json]

`run` is `yardstick/run.py` with one more tracer subscriber: from the
start of the measured window it keeps every record (true end `t_end` on
`time.perf_counter`, thread, ids, meta) and writes them to OUT.jsonl after
the result line, headed by the window's two instants. `rest` reads such a
file and prints, for the spans that ended inside the window:

- mean and count by span name;
- for each container in `CONTAINERS`, what no child span covers: its mean
  remainder, the part of that which `runtime.loop_blocked` spans overlap
  (a coroutine that is ready while another holds the loop waits there),
  and what is left as a share of the container's mean;
- spans per completed `SumAll` (spans of the traces rooted in
  `http.GET.SumAll`, over those roots), and the messages the replicas
  handled (over sockets: the frames received) under such a trace, by
  class, per `SumAll`;
- the limb count and the product (`schoolbook`, `karatsuba1`, `cios`) the
  `kernel.fold` spans name, with their count;
- the window's ledger of the event loop (`obs/runtime`: counters
  `dds_event_loop_*_total{tenant}`, read by `run` as the window opens and
  at its end): seconds, callbacks and mean ready-wait by tenant, and
  seconds and callbacks a frame (TCP: `net.deserialize` spans) or a
  message handled (memory: `replica.handle` + `supervisor.handle` spans).
  A tenant's share is given of the window and, as the benchmark's
  `loop.*_share` metrics give it, of the loop's busy seconds. Left out for
  a program that keeps no ledger.

The benchmark's own per-layer metrics read the same spans through
`yardstick/reducers`; this is the builder's look at what they leave out.
"""

from __future__ import annotations

import gzip
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CONTAINERS = ("proxy.fetch_stored", "proxy.fold")
BLOCKED = "runtime.loop_blocked"
# the tenants that carry a message from a sender's `send` to a handler's
# first line, and the handlers themselves
CARRIERS = ("transport", "socket")
HANDLERS = ("replica", "proxy_inbox", "supervisor")


def read_ledger() -> dict | None:
    """The loop's ledger now: tenant -> [seconds, callbacks, ready-wait],
    with the instant it was read; None where the program keeps none."""
    from dds_tpu.obs import runtime
    from dds_tpu.obs.metrics import metrics

    series = getattr(runtime, "LEDGER_SERIES", ())
    got = {t: [metrics.value(n, tenant=t) or 0.0 for n in series]
           for t in getattr(runtime, "TENANTS", ())}
    if not any(v[0] for v in got.values()):
        return None
    return {"t": time.perf_counter(), "by_tenant": got}


def run(out: str, argv: list[str]) -> int:
    sys.path.insert(0, ROOT)
    import asyncio

    from yardstick import run as yr
    from yardstick import traffic as yt

    kept: list = []
    marks: dict = {}
    measure = yr.Run.measure
    drive = yt.Traffic.run

    async def driven(self, seconds):
        # the ledger as the window opens and at its end (`drive` returns
        # later, when what was in flight has ended)
        marks["ledger_before"] = read_ledger()
        asyncio.get_running_loop().call_later(
            seconds, lambda: marks.update(ledger_after=read_ledger()))
        return await drive(self, seconds)

    def keep(rec):
        # a program older than `t_end` is placed by when it told us
        kept.append((rec, time.perf_counter()))

    async def measured(self, seed=None):
        from dds_tpu.utils.trace import tracer

        tracer.subscribe(keep)
        try:
            await measure(self, seed)
        finally:
            tracer.unsubscribe(keep)
            marks.update(t0=self.t0, t_end=self.t_end)

    yr.Run.measure = measured
    yt.Traffic.run = driven
    code = yr.main(argv)
    os.makedirs(os.path.dirname(os.path.abspath(out)), exist_ok=True)
    with _open(out, "wt") as f:
        f.write(json.dumps({"window": marks}) + "\n")
        for r, seen in kept:
            f.write(json.dumps(
                {"name": r.name, "dur_ms": r.dur_ms,
                 "t_end": getattr(r, "t_end", None) or seen,
                 "tid": getattr(r, "tid", None), "kind": r.kind,
                 "trace_id": r.trace_id, "span_id": r.span_id,
                 "parent_id": r.parent_id,
                 "meta": {k: v for k, v in r.meta.items() if k != "key"}},
                default=str) + "\n")
    print(f"[span_tree] wrote {len(kept)} records to {out}", flush=True)
    return code


def _open(path: str, mode: str):
    return gzip.open(path, mode) if path.endswith(".gz") else open(path, mode)


def overlap(a, b):
    """Seconds that two sorted lists of disjoint intervals share."""
    i = j = 0
    total = 0.0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if hi > lo:
            total += hi - lo
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return total


def rest(path: str) -> dict:
    sys.path.insert(0, ROOT)
    from yardstick.trace_reduce import union

    with _open(path, "rt") as f:
        head = json.loads(f.readline())["window"]
        recs = [json.loads(line) for line in f]
    t0, t1 = head["t0"], head["t_end"]
    spans = [r for r in recs if r["kind"] == "span"
             and t0 <= r["t_end"] <= t1]
    for s in spans:
        s["t_start"] = s["t_end"] - s["dur_ms"] / 1e3
    by_name: dict[str, list] = {}
    children: dict[str, list] = {}
    for s in spans:
        by_name.setdefault(s["name"], []).append(s["dur_ms"])
        if s["parent_id"]:
            children.setdefault(s["parent_id"], []).append(s)
    blocked = union([(s["t_start"], s["t_end"]) for s in spans
                     if s["name"] == BLOCKED])
    out = {"window_s": t1 - t0,
           "mean_ms": {n: [sum(v) / len(v), len(v)]
                       for n, v in sorted(by_name.items())},
           "containers": {}}
    for name in CONTAINERS:
        rows = []
        for s in spans:
            if s["name"] != name:
                continue
            lo, hi = s["t_start"], s["t_end"]
            covered = union([(max(lo, c["t_start"]), min(hi, c["t_end"]))
                             for c in children.get(s["span_id"], [])
                             if c["t_end"] > lo and c["t_start"] < hi])
            bare, at = [], lo
            for a, b in covered:
                if a > at:
                    bare.append((at, a))
                at = max(at, b)
            if hi > at:
                bare.append((at, hi))
            uncovered = sum(b - a for a, b in bare)
            waited = overlap(bare, blocked)
            rows.append((s["dur_ms"], uncovered * 1e3, waited * 1e3))
        if rows:
            n = len(rows)
            mean, unc, wait = (sum(r[i] for r in rows) / n for i in range(3))
            out["containers"][name] = {
                "count": n, "mean_ms": mean, "uncovered_ms": unc,
                "under_loop_blocked_ms": wait,
                "left_share": (unc - wait) / mean if mean else 0.0}
    # which product each fold ran (the pool's span names it from the limb
    # count, ops/mont_mxu.product_for)
    folds: dict[str, int] = {}
    for s in spans:
        if s["name"] == "kernel.fold":
            m = s.get("meta", {})
            what = f"limbs={m.get('limbs')} product={m.get('product')}"
            folds[what] = folds.get(what, 0) + 1
    out["folds"] = folds
    before, after = head.get("ledger_before"), head.get("ledger_after")
    if before and after:
        gained = {t: [b - a for a, b in zip(before["by_tenant"][t], v)]
                  for t, v in after["by_tenant"].items()}
        frames = len(by_name.get("net.deserialize", ()))
        handled = (len(by_name.get("replica.handle", ()))
                   + len(by_name.get("supervisor.handle", ())))

        def per(what, tenants, n, unit):
            return [what, unit] + [
                sum(gained[t][i] for t in tenants if t in gained) / n
                for i in (0, 1)]

        out["ledger"] = {
            "stretch_s": after["t"] - before["t"], "by_tenant": gained,
            "frames": frames, "handled": handled,
            # [what, a unit of what, seconds a unit, callbacks a unit]
            "per_unit": [per(*row) for row in (
                ("transport + socket", CARRIERS, frames, "frame"),
                ("replica + proxy_inbox + supervisor", HANDLERS, handled,
                 "message handled"),
                ("every running tenant",
                 [t for t in gained if t != "idle"], frames or handled,
                 "frame" if frames else "message handled")) if row[2]]}
    roots = [s for s in spans if s["name"] == "http.GET.SumAll"]
    ids = {s["trace_id"] for s in roots}
    out["sumalls"] = len(roots)
    out["spans_per_sumall"] = (
        sum(1 for s in spans if s["trace_id"] in ids) / len(roots)
        if roots else None)
    # messages the replicas handled under a SumAll's trace, by class: what
    # one aggregate costs the loop in handlers (its tag round, its
    # re-reads); over sockets, the frames received under it likewise
    for key, span in (("handled_per_sumall", "replica.handle"),
                      ("frames_per_sumall", "net.deserialize")):
        by_msg: dict[str, int] = {}
        for s in spans:
            if s["name"] == span and s["trace_id"] in ids:
                msg = str(s.get("meta", {}).get("msg"))
                by_msg[msg] = by_msg.get(msg, 0) + 1
        out[key] = ({m: n / len(roots) for m, n in sorted(by_msg.items())}
                    if roots else None)
    return out


def main(argv: list[str]) -> int:
    if len(argv) >= 2 and argv[0] == "run":
        return run(argv[1], argv[2:])
    if len(argv) >= 2 and argv[0] == "rest":
        res = rest(argv[1])
        if "--json" in argv:
            print(json.dumps(res))
            return 0
        print(f"window {res['window_s']:.2f} s, {res['sumalls']} SumAll, "
              f"{res['spans_per_sumall']} spans per SumAll")
        for name, c in res["containers"].items():
            print(f"{name}: mean {c['mean_ms']:.3f} ms x {c['count']}, "
                  f"uncovered {c['uncovered_ms']:.3f}, of it under "
                  f"{BLOCKED} {c['under_loop_blocked_ms']:.3f}, left "
                  f"{100 * c['left_share']:.2f} %")
        for what, n in res["folds"].items():
            print(f"kernel.fold: {what} x {n}")
        for what, key in (("messages handled", "handled_per_sumall"),
                          ("frames received", "frames_per_sumall")):
            per = res.get(key)
            if per:
                print(f"{what} a SumAll: {sum(per.values()):.2f} ("
                      + ", ".join(f"{m} {n:.2f}" for m, n in per.items())
                      + ")")
        led = res.get("ledger")
        if led:
            total = sum(v[0] for v in led["by_tenant"].values())
            print(f"who holds the loop (ledger over {led['stretch_s']:.3f} s"
                  f", tenants sum to {total:.3f}):")
            busy = (total - led["by_tenant"]["idle"][0]) or 1.0
            for t, (sec, n, wait) in led["by_tenant"].items():
                print(f"  {t:12s} {sec:8.3f} s {100 * sec / total:6.2f} % "
                      + (f"{100 * sec / busy:6.2f} % of busy "
                         if t != "idle" else " " * 17)
                      + f"{int(n):9d} callbacks"
                      + (f" {1e6 * sec / n:8.1f} us each, ready-wait "
                         f"{1e3 * wait / n:7.3f} ms each" if n else ""))
            for what, unit, sec, n in led["per_unit"]:
                print(f"  {what}: {1e3 * sec:.4f} ms and {n:.2f} callbacks "
                      f"a {unit}")
            print(f"  ({led['frames']} frames, {led['handled']} messages "
                  "handled by a replica or the supervisor)")
        for name, (mean, n) in res["mean_ms"].items():
            print(f"  {name:36s} {mean:10.3f} ms x {n}")
        return 0
    print(__doc__, file=sys.stderr)
    return 2


if __name__ == "__main__":
    if os.environ.get("PYTHONHASHSEED") != "0":
        # as yardstick/run.py does: runs are compared with each other
        os.environ["PYTHONHASHSEED"] = "0"
        os.execv(sys.executable, [sys.executable] + sys.argv)
    code = main(sys.argv[1:])
    sys.stdout.flush()
    sys.stderr.flush()
    os._exit(code)
