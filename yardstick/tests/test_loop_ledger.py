"""The event loop's ledger as the benchmark reads it: every `loop.*`
metric is a data file over `counter_share` or `counter_sum_per_op`, a tiny
run reports each in the cells that list it, and on a program that keeps no
such counters (the parent of the PR that brought them; any program with
`DDS_OBS_TRACE=0`) each is left out and nothing raises."""

import json
import os
import types

import pytest

from yardstick.reducers import counter_share, counter_sum_per_op
from yardstick.run import Window
from yardstick.tests.test_run_tiny import make_checkout, run_cell
from yardstick.tests.test_tcp_deployment import (  # noqa: F401
    checkout as tcp_checkout)

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
TENANTS = ["idle", "loop", "socket", "request", "replica", "proxy_inbox",
           "supervisor", "transport", "background", "foreign"]
# each a share of the loop's BUSY seconds: `idle` is not in `of`, because
# the harness reads a counter's end when it reduces, long after the window
# (the quiet point, the profiler's stop, the deployment's), and that
# stretch is nearly all idle. The loop's headroom is `tools/span_tree.py`'s.
SHARES = {"loop.request_share": "request",
          "loop.replica_share": "replica",
          "loop.proxy_inbox_share": "proxy_inbox",
          "loop.foreign_share": "foreign",
          "loop.transport_share": "transport", "loop.socket_share": "socket"}
PER_AGG = {"loop.callbacks_per_agg": "dds_event_loop_callbacks_total",
           "loop.ready_wait_ms_per_agg":
               "dds_event_loop_ready_wait_seconds_total"}


def layer(name: str) -> dict:
    with open(os.path.join(ROOT, "yardstick", "layers", name + ".json")) as f:
        return json.load(f)


def listed() -> tuple:
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    return {m["name"]: m for m in bench["per_layer"]
            if m["name"].startswith("loop.")}, [
        w["name"] for w in bench["workloads"]]


def test_every_loop_metric_is_a_data_file_over_a_reducer_that_was_there():
    metrics, cells = listed()
    assert set(metrics) == set(SHARES) | set(PER_AGG)
    for name, m in metrics.items():
        spec = layer(name)
        assert spec["layer"] == m["layer"] == "host runtime"
        assert (spec["unit"], spec["better"], spec["moves"]) == (
            m["unit"], m["better"], m["moves"])
        assert m["source"] == spec["source"] == "program_counter"
        assert m["workloads"] == cells   # `transport` reads 0 in memory
        args = spec["args"]
        # `Window.open` takes its "before" readings by these three names
        assert args["label"] == "tenant" and "counter" in args
        if name in SHARES:
            assert spec["reducer"] == "counter_share"
            assert args["counter"] == "dds_event_loop_seconds_total"
            assert args["value"] == SHARES[name]
            assert args["of"] == TENANTS[1:]
            assert (m["unit"], m["better"], m["moves"]) == (
                "%", "lower", "ops_per_s")
        else:
            assert spec["reducer"] == "counter_sum_per_op"
            assert args["counter"] == PER_AGG[name]
            assert args["of"] == TENANTS[1:] and args["per"] == "aggregate"
    assert layer("loop.ready_wait_ms_per_agg")["args"]["scale"] == 1000.0
    assert metrics["loop.ready_wait_ms_per_agg"]["moves"] == "agg_p50_ms"


def test_the_table_in_the_files_is_the_programs():
    from dds_tpu.obs import runtime

    assert list(runtime.TENANTS) == TENANTS


@pytest.mark.parametrize("name", sorted(SHARES) + sorted(PER_AGG))
def test_a_program_without_the_counters_leaves_the_metric_out(
        monkeypatch, name):
    import importlib

    mod = importlib.import_module("dds_tpu.obs.metrics")
    monkeypatch.setattr(mod, "metrics", mod.Registry())
    spec = layer(name)
    w = Window({}, "test", {})
    w.ops = [types.SimpleNamespace(kind="aggregate", status=200)]
    w.open({name: spec})
    try:
        mod = counter_share if name in SHARES else counter_sum_per_op
        assert mod.reduce(w, **spec["args"]) is None
    finally:
        w.close()


def test_the_shares_are_of_the_windows_busy_gain_not_of_the_process():
    from dds_tpu.obs.metrics import metrics

    spec = layer("loop.replica_share")
    counter = spec["args"]["counter"]
    metrics.inc(counter, 100.0, tenant="foreign")    # set-up, before
    w = Window({}, "test", {})
    w.open({"loop.replica_share": spec})
    try:
        metrics.inc(counter, 50.0, tenant="idle")    # no part of any share
        metrics.inc(counter, 2.0, tenant="replica")
        metrics.inc(counter, 2.0, tenant="foreign")
        assert counter_share.reduce(w, **spec["args"]) == pytest.approx(50.0)
    finally:
        w.close()


# ---------------------------------------------------------------- tiny runs


@pytest.fixture(scope="module")
def memory_checkout(tmp_path_factory):
    return make_checkout(tmp_path_factory.mktemp("ledger_checkout"))


@pytest.fixture(scope="module")
def memory_line(memory_checkout):
    done = run_cell(memory_checkout, "tiny.ycsba-sumall", 1)
    assert done.returncode == 0, done.stderr[-2000:]
    return json.loads(done.stdout.strip().splitlines()[-1])


def test_a_tiny_run_in_memory_reports_every_loop_metric(memory_line):
    assert memory_line["correct"] is True and memory_line["failed"] == 0
    got = {n: m["value"] for n, m in memory_line["metrics"].items()
           if n.startswith("loop.")}
    assert set(got) == set(SHARES) | set(PER_AGG)
    assert got["loop.transport_share"] == 0.0    # no transport task here
    for name in ("loop.request_share", "loop.replica_share",
                 "loop.proxy_inbox_share", "loop.foreign_share",
                 "loop.socket_share"):
        assert 0.0 < got[name] < 100.0, name
    assert sum(got[n] for n in SHARES) <= 100.0 + 1e-6
    assert got["loop.callbacks_per_agg"] > 10
    assert got["loop.ready_wait_ms_per_agg"] > 0.0


def test_a_tiny_run_over_tcp_books_the_transport(tcp_checkout):
    done = run_cell(tcp_checkout, "tiny-tcp.ycsba-sumall", 1)
    assert done.returncode == 0, done.stderr[-2000:]
    last = json.loads(done.stdout.strip().splitlines()[-1])
    assert last["correct"] is True and last["failed"] == 0
    got = {n: m["value"] for n, m in last["metrics"].items()
           if n.startswith("loop.")}
    assert set(got) == set(SHARES) | set(PER_AGG)
    assert got["loop.transport_share"] > got["loop.replica_share"] > 0.0
    assert got["loop.socket_share"] > 0.0
    assert sum(got[n] for n in SHARES) <= 100.0 + 1e-6


def test_a_whole_run_of_a_program_that_keeps_no_ledger(
        memory_checkout, monkeypatch):
    # with the tracer off the sampler installs no ledger: this program's
    # registry then holds what the parent's holds, none of the series
    monkeypatch.setenv("DDS_OBS_TRACE", "0")
    done = run_cell(memory_checkout, "tiny.ycsba-sumall", 1)
    assert done.returncode == 0, done.stderr[-2000:]
    last = json.loads(done.stdout.strip().splitlines()[-1])
    assert last["correct"] is True and last["failed"] == 0
    assert not [n for n in last["metrics"] if n.startswith("loop.")]
    assert "dispatch.xla_compiles_in_window" in last["metrics"]
