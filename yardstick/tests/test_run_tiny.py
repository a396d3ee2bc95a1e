"""Whole runs at a tiny size on the CPU.

The tiny cell, its mix and one per-layer metric live in `tests/files/` and
are named by no cell of `BENCHMARK.json`: the tests put them into a
scratch checkout as new files plus `BENCHMARK.json` entries and change no
file that was there, which is how a later PR adds a deployment, a mix or a
metric.
"""

import argparse
import asyncio
import contextlib
import json
import os
import shutil
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
LAST_LINE_KEYS = {"correct", "attempted", "failed", "metrics", "device"}


def make_checkout(tmp) -> str:
    """A checkout with the benchmark, the program, and the tiny cells."""
    tree = str(tmp)
    shutil.copytree(os.path.join(ROOT, "yardstick"),
                    os.path.join(tree, "yardstick"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    os.symlink(os.path.join(ROOT, "dds_tpu"), os.path.join(tree, "dds_tpu"))
    for kind, stem, name in (("configs", "config", "tiny-bft4"),
                             ("traffic", "traffic", "tiny-mixed"),
                             ("layers", "layer", "test.fetch_ms")):
        shutil.copy(
            os.path.join(HERE, "files", f"{stem}.{name}.json"),
            os.path.join(tree, "yardstick", kind, f"{name}.json"))
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    before = json.dumps(bench, sort_keys=True)
    bench["configs"].append({
        "name": "tiny-bft4", "source": "test",
        "file": "yardstick/configs/tiny-bft4.json", "reduced": ["rows"],
        "why": "test"})
    tiny = []
    for mix in ("tiny-mixed", "sumall-steady", "ycsbb-sumall", "ycsba-sumall"):
        tiny.append(f"tiny.{mix}")
        bench["workloads"].append({
            "name": tiny[-1], "config": "tiny-bft4", "traffic": mix,
            "chips": 1, "why": "test"})
    for metric in bench["end_to_end"] + bench["per_layer"]:
        if "workloads" in metric:
            metric["workloads"] = metric["workloads"] + tiny
    bench["per_layer"].append({
        "name": "test.fetch_ms", "unit": "ms", "better": "lower",
        "source": "program_span", "layer": "quorum round",
        "moves": "point_p95_ms", "workloads": ["tiny.tiny-mixed"]})
    # nothing that was there was edited: entries were only added
    kept = {k: (v[:len(json.loads(before)[k])] if isinstance(v, list) else v)
            for k, v in json.loads(json.dumps(bench)).items()}
    for metric in kept["end_to_end"] + kept["per_layer"]:
        if "workloads" in metric:
            metric["workloads"] = [w for w in metric["workloads"]
                                   if w not in tiny]
    assert json.dumps(kept, sort_keys=True) == before
    with open(os.path.join(tree, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    return tree


def run_cell(tree: str, workload: str, trace: int, seed: int = 2**31 + 5):
    env = dict(os.environ, JAX_PLATFORMS="cpu", DDS_TPU_MIN_BATCH="0",
               BENCH_RUN="ignored")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    return subprocess.run(
        [sys.executable, os.path.join(tree, "yardstick", "run.py"),
         "--workload", workload, "--seed", str(seed), "--seconds", "3",
         "--trace", str(trace)],
        cwd=tree, env=env, capture_output=True, text=True, timeout=300)


@pytest.fixture(scope="module")
def checkout(tmp_path_factory):
    return make_checkout(tmp_path_factory.mktemp("checkout"))


@pytest.mark.parametrize("workload,trace", [
    ("tiny.sumall-steady", 0), ("tiny.ycsbb-sumall", 0),
    ("tiny.ycsba-sumall", 0), ("tiny.tiny-mixed", 0),
    ("tiny.tiny-mixed", 1)])
def test_a_whole_run_ends_in_one_well_formed_line(checkout, workload, trace):
    done = run_cell(checkout, workload, trace)
    assert done.returncode == 0, done.stderr[-2000:]
    last = json.loads(done.stdout.strip().splitlines()[-1])
    assert LAST_LINE_KEYS <= set(last)
    assert last["correct"] is True and last["failed"] == 0
    assert last["attempted"] > 0
    assert last["device"]["platform"] == "cpu"   # never passed off as a chip
    names = set(last["metrics"])
    if trace:
        assert "setup_s" not in names
        # the metric that only the scratch checkout's new files define
        assert "test.fetch_ms" in names
        assert last["metrics"]["dispatch.compiles_in_window"]["value"] == 0
    else:
        assert {"setup_s", "ops_per_s", "agg_p50_ms", "agg_p95_ms"} <= names
        assert ("point_p95_ms" in names) == (workload != "tiny.sumall-steady")
    for m in last["metrics"].values():
        assert isinstance(m["value"], (int, float)) and m["unit"]
    # every number compared is printed beside its limit
    assert done.stdout.count("[check]") >= 5


def test_no_result_without_the_files_or_the_program(checkout, tmp_path):
    done = run_cell(checkout, "no-such-cell", 0)
    assert done.returncode != 0 and "{" not in done.stdout
    bare = tmp_path / "bare"
    shutil.copytree(os.path.join(checkout, "yardstick"), bare / "yardstick")
    shutil.copy(os.path.join(checkout, "BENCHMARK.json"), bare)
    done = run_cell(str(bare), "tiny.sumall-steady", 0)
    assert done.returncode != 0 and done.stdout.strip() == ""


# ------------------------------------------------- in one process, broken


@contextlib.contextmanager
def a_run(checkout, workload, seed, seconds):
    """A `Run` of a cell of the scratch checkout, past the look for a
    chip."""
    from yardstick import run as yr

    root, here = yr.ROOT, yr.HERE
    yr.ROOT, yr.HERE = checkout, os.path.join(checkout, "yardstick")
    try:
        args = argparse.Namespace(workload=workload, seed=seed,
                                  seconds=seconds, trace=0, keep_trace="")
        yield yr.Run(args, yr.find_cell(workload),
                     {"platform": "cpu", "kind": "cpu", "count": 1})
    finally:
        yr.ROOT, yr.HERE = root, here


def _in_process(checkout, workload, seed, breakage=None, patch_http=None):
    """Drive a run with something broken underneath; returns the result
    line's object."""
    from yardstick import httpc

    with a_run(checkout, workload, seed, 2.0) as run:

        async def go():
            try:
                await run.setup()
                real = httpc.request
                if patch_http is not None:
                    httpc.request = patch_http(real)
                try:
                    with (breakage(run) if breakage is not None
                          else contextlib.nullcontext()):
                        await run.measure()
                finally:
                    httpc.request = real
            finally:
                await run.stop()
            return run.report()

        return asyncio.run(go())


def test_a_failing_operation_is_counted_not_raised(checkout):
    def flaky(real):
        n = [0]

        async def request(host, port, method, target, body=None,
                          timeout=30.0):
            n[0] += 1
            if n[0] % 7 == 0:
                if method == "PUT":   # the write lands, its answer is lost
                    await real(host, port, method, target, body, timeout)
                return 0, b"injected: connection reset"
            if n[0] % 11 == 0:
                return 503, b"injected: unavailable"
            return await real(host, port, method, target, body, timeout)

        return request

    out = _in_process(checkout, "tiny.ycsbb-sumall", 41, patch_http=flaky)
    assert LAST_LINE_KEYS <= set(out)
    assert out["failed"] > 0 and out["attempted"] > out["failed"]
    # a failed operation is not a wrong answer: what did answer was right,
    # lost acknowledgements included
    assert out["correct"] is True


@pytest.mark.parametrize("control", ["truncated_limb", "lost_write"])
def test_the_controls_come_out_not_correct(checkout, control):
    from yardstick.tests import control as ctl

    out = _in_process(checkout, "tiny.ycsbb-sumall", 43,
                      breakage=ctl.CONTROLS[control])
    assert out["correct"] is False


def test_an_answer_altered_where_it_is_produced_is_caught(checkout):
    """The timed path broken underneath: the proxy's fold returns its
    result with one bit flipped."""
    @contextlib.contextmanager
    def flipped(run):
        be = run.dep.server.backend
        fold = be.modmul_fold_resident
        be.modmul_fold_resident = lambda cs, m: fold(cs, m) ^ (1 << 40)
        try:
            yield
        finally:
            del be.modmul_fold_resident

    out = _in_process(checkout, "tiny.sumall-steady", 47, breakage=flipped)
    assert out["correct"] is False and out["failed"] > 0


def test_the_control_driver_tells_sound_from_broken(checkout):
    from yardstick.tests import control as ctl

    with a_run(checkout, "tiny.ycsbb-sumall", 51, 1.5) as run:
        rows = asyncio.run(ctl.drive(run, [51, 52], 1.5))
    assert [r["control"] for r in rows] == [None, "truncated_limb",
                                            "lost_write"] * 2
    for r in rows:
        assert r["correct"] is (r["control"] is None), r
