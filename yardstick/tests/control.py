"""The controls: a cell at its own size with one stated guarantee broken
underneath the harness, which must come out `correct: false`.

    python3 yardstick/tests/control.py --workload <cell> --seeds 1,2,3 --seconds 5

One process and one load: for every seed a sound window (must be correct),
then one window per control (must not be). The store states no numeric
precision, so there is no lower precision to compute in; what stands in
its place is the step a later PR would be tempted by:

- `truncated_limb`: the device fold over operands with their top 16-bit
  limb zeroed, i.e. the aggregate computed at L - 1 limbs ("aggregates
  exact" broken);
- `lost_write`: every third write acknowledged before it reaches the
  replicas and then never sent ("an acknowledged write is read back"
  broken). Only in a mix that writes.

The comparisons are exact (limit 0), so a control passes when it gives
any wrong answer at all; the counts are printed for PERF.md.
"""

from __future__ import annotations

import argparse
import asyncio
import contextlib
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


@contextlib.contextmanager
def truncated_limb(run):
    """Every fold sees its operands without their top limb."""
    be = run.dep.server.backend
    modulus = run.data.moduli[2]
    top = 16 * (run.limbs[2] - 1)
    undo = []
    if hasattr(be, "store_for"):
        store = be.store_for(modulus)
        reduce = store.reduce
        store.reduce = lambda rows: reduce(rows.at[:, -1].set(0))
        undo.append(lambda: setattr(store, "reduce", reduce))
    host = be._host_fold if hasattr(be, "_host_fold") else be.modmul_fold
    name = "_host_fold" if hasattr(be, "_host_fold") else "modmul_fold"
    setattr(be, name,
            lambda cs, m: host([c & ((1 << top) - 1) for c in cs], m))
    undo.append(lambda: delattr(be, name))
    try:
        yield
    finally:
        for u in undo:
            u()


@contextlib.contextmanager
def lost_write(run):
    """Every third write is acknowledged and dropped."""
    server = run.dep.server
    write, n = server._write, [0]

    async def leaky(key, value):
        n[0] += 1
        if n[0] % 3 == 0:
            return None
        return await write(key, value)

    server._write = leaky
    try:
        yield
    finally:
        del server._write


CONTROLS = {"truncated_limb": truncated_limb, "lost_write": lost_write}


async def drive(run, seeds: list[int], seconds: float) -> list[dict]:
    """Sound and broken windows over one loaded deployment."""
    writes = any(o["op"] == "update" for g in run.mix["groups"]
                 for o in g["ops"])
    modes = [None, "truncated_limb"] + (["lost_write"] if writes else [])
    rows = []
    run.args.seconds = seconds
    try:
        await run.setup()
        for seed in seeds:
            for mode in modes:
                run.checks, run.wrong_examples = [], []
                run.fail_examples, run.failed_setup_ops = [], 0
                with (CONTROLS[mode](run) if mode
                      else contextlib.nullcontext()):
                    await run.measure(seed)
                out = run.report()
                wrong = sum(v for n, v, _ in run.checks)
                rows.append({"seed": seed, "control": mode,
                             "correct": out["correct"],
                             "wrong_answers": wrong,
                             "attempted": out["attempted"],
                             "failed": out["failed"]})
                print("[control] " + json.dumps(rows[-1]), flush=True)
                if mode == "lost_write":
                    # the harness's book now disagrees with the store for
                    # good; put it right before the next sound window
                    await resync(run)
    finally:
        if hasattr(run, "dep"):
            await run.stop()
    return rows


async def resync(run) -> None:
    """After `lost_write`, re-read every row and make the harness's book
    say what the store holds."""
    d = run.data
    for i in range(d.k):
        if max(len(d.versions[c][i]) for c in (2, 3)) == 1:
            continue   # never updated: nothing to disagree about
        status, body = await run.call("GET", f"/GetSet/{d.keys[i]}")
        got = json.loads(body)["contents"]
        for col in (2, 3):
            held = d.versions[col][i].index(got[col])
            del d.versions[col][i][held + 1:]
            d.acked[col] -= d.row_acked[col][i] - held
            d.row_acked[col][i] = held
    d.sent = dict(d.acked)
    d.busy.clear()
    d.unsure.clear()


def main(argv=None) -> int:
    from yardstick import run as yr

    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="1,2,3")
    ap.add_argument("--seconds", type=float, default=5.0)
    args = ap.parse_args(argv)
    seeds = [int(s) for s in args.seeds.split(",")]
    os.environ.setdefault("JAX_COMPILATION_CACHE_DIR",
                          os.path.join(ROOT, ".jax_cache"))
    cell = yr.find_cell(args.workload)
    import dds_tpu  # noqa: F401

    device = yr.find_device(int(cell["chips"]))
    ns = argparse.Namespace(workload=args.workload, seed=seeds[0],
                            seconds=args.seconds, trace=0, keep_trace="")
    rows = asyncio.run(drive(yr.Run(ns, cell, device), seeds, args.seconds))
    sound = [r for r in rows if r["control"] is None]
    broken = [r for r in rows if r["control"] is not None]
    ok = all(r["correct"] for r in sound) and not any(
        r["correct"] for r in broken)
    print(json.dumps({"controls_hold": ok, "device": device,
                      "sound_largest_wrong": max(r["wrong_answers"]
                                                 for r in sound),
                      "control_smallest_wrong": min(r["wrong_answers"]
                                                    for r in broken)}))
    return 0 if ok else 1


if __name__ == "__main__":
    code = main()
    sys.stdout.flush()
    os._exit(code)
