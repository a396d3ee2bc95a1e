"""The reducers that read the program's spans against the device's idle
time, the event loop's blocked stretches and the collector's counter, on a
window written down by hand and on the trace recorded on the chip."""

import json
import os
import types

import pytest

from yardstick import trace_reduce as tr
from yardstick.reducers import (counter_sum_per_op, idle_by_leaf,
                                span_stretch_share)
from yardstick.run import Span, Window

HERE = os.path.dirname(os.path.abspath(__file__))
CONTAINERS = ["http.", "proxy.fetch_stored", "proxy.fold"]
PC0 = 100.0   # the host's clock at the trace's first mark


def span(name, start, end, span_id=None, parent_id=None):
    """A span over [start, end] seconds after the first mark."""
    return Span(name, (end - start) * 1e3, PC0 + end, span_id, parent_id)


def window(spans, busy, seconds=1.0):
    """One second of trace in which the device is busy over `busy`."""
    w = Window({}, "test", {})
    w.spans = spans
    ops = [["op", a * 1e9, (b - a) * 1e9, {}] for a, b in busy]
    w.trace = {"planes": [{"name": "/device:TPU:0", "lines": [
        {"name": tr.OPS_LINE, "events": ops}]}],
        "lo_ns": 0.0, "hi_ns": seconds * 1e9,
        "lo_pc": PC0, "hi_pc": PC0 + seconds}
    return w


BUSY = [(0.0, 0.1), (0.3, 0.4), (0.6, 0.7), (0.9, 1.0)]   # three gaps of 0.2 s
# an aggregate whose fold runs on a worker thread, and on the loop's
# thread a read of another request
HAND = [
    span("http.GET.SumAll", 0.05, 0.65, "r"),
    span("proxy.fold", 0.08, 0.5, "f", "r"),
    span("kernel.fold", 0.1, 0.2, "k", "f"),
    span("http.GET.GetSet", 0.12, 0.35, "g"),
    span("abd.fetch", 0.15, 0.3, "a", "g"),
]


def test_a_gap_is_split_among_the_leaves_that_lie_under_it(capsys):
    share = idle_by_leaf.reduce(window(HAND, BUSY), 20.0, CONTAINERS)
    line = next(ln for ln in capsys.readouterr().out.splitlines()
                if ln.startswith("[idle_by_leaf] "))
    table = json.loads(line.split(" ", 1)[1])
    assert table["idle_s"] == pytest.approx(0.6)
    # gap 0.1..0.3: kernel.fold alone to 0.15, with abd.fetch to 0.2
    # (halves), then abd.fetch beside proxy.fold, a container, to 0.3
    assert table["by_leaf"]["kernel.fold"] == pytest.approx(0.075)
    assert table["by_leaf"]["abd.fetch"] == pytest.approx(0.125)
    # gap 0.4..0.6 lies under containers only, gap 0.7..0.9 under nothing
    assert table["by_leaf"]["unattributed"] == pytest.approx(0.4)
    assert table["unattributed_under"] == {
        "proxy.fold": pytest.approx(0.1),
        "http.GET.SumAll": pytest.approx(0.1),
        "nothing": pytest.approx(0.2)}
    assert share == pytest.approx(100.0 * 0.2 / 0.6)
    assert sum(table["by_leaf"].values()) == pytest.approx(table["idle_s"])


def test_pauses_inside_a_program_are_not_gaps():
    # 10 us between two operations is under the 20 us that make a gap
    busy = [(0.0, 0.5), (0.50001, 1.0)]
    assert idle_by_leaf.reduce(window(HAND, busy), 20.0, CONTAINERS) is None
    assert idle_by_leaf.reduce(window(HAND, busy), 5.0,
                               CONTAINERS) == pytest.approx(0.0)


def test_nothing_to_read_gives_nothing():
    w = window([], BUSY)
    assert idle_by_leaf.reduce(w, 20.0, CONTAINERS) is None   # no spans
    assert span_stretch_share.reduce(w, "runtime.loop_blocked") is None
    w = window(HAND, BUSY)
    w.trace = None                                            # no trace
    assert idle_by_leaf.reduce(w, 20.0, CONTAINERS) is None
    assert span_stretch_share.reduce(w, "http.GET.SumAll") is None
    w = window(HAND, [])
    w.trace["planes"] = []                                    # a CPU's trace
    assert idle_by_leaf.reduce(w, 20.0, CONTAINERS) is None


def test_the_blocked_share_is_the_union_clipped_to_the_stretch():
    w = window([span("runtime.loop_blocked", -0.1, 0.1),
                span("runtime.loop_blocked", 0.3, 0.5),
                span("runtime.loop_blocked", 0.4, 0.6),
                span("runtime.loop_blocked", 1.2, 1.3),
                span("abd.fetch", 0.0, 1.0)], BUSY)
    assert span_stretch_share.reduce(
        w, "runtime.loop_blocked") == pytest.approx(40.0)


def test_a_counter_summed_over_a_label_per_operation():
    from dds_tpu.obs.metrics import metrics

    name = "yardstick_test_pause_seconds_total"
    args = {"counter": name, "label": "generation", "of": ["0", "1", "2"],
            "per": "aggregate", "scale": 1000.0}
    w = Window({}, "test", {})
    w.ops = [types.SimpleNamespace(kind=k, status=s) for k, s in
             (("aggregate", 200), ("aggregate", 200), ("aggregate", 503),
              ("read", 200))]
    w.open({"m": {"args": args}})
    try:
        assert counter_sum_per_op.reduce(w, **args) is None   # no series
        metrics.inc(name, 0.25, generation="0")
        w.open({"m": {"args": args}})    # what was there before the window
        metrics.inc(name, 0.004, generation="0")
        metrics.inc(name, 0.010, generation="2")
        metrics.inc(name, 5.0, generation="other")            # not asked for
        assert counter_sum_per_op.reduce(w, **args) == pytest.approx(7.0)
        w.ops = []
        assert counter_sum_per_op.reduce(w, **args) is None
    finally:
        w.close()


RECORDED = os.path.join(HERE, "files", "recorded_trace.json")


@pytest.mark.skipif(not os.path.exists(RECORDED),
                    reason="no recorded trace on file")
def test_idle_of_the_recorded_chip_trace_goes_to_hand_written_spans(capsys):
    with open(RECORDED) as f:
        rec = json.load(f)
    lo, hi = rec["lo_ns"], rec["hi_ns"]
    seconds = (hi - lo) / 1e9
    w = Window({}, "TPU v5 lite", {})
    w.trace = {"planes": rec["planes"], "lo_ns": lo, "hi_ns": hi,
               "lo_pc": PC0, "hi_pc": PC0 + seconds}
    gaps = tr.idle_gaps(rec["planes"], lo, hi, 20e3)
    idle = sum(b - a for a, b in gaps) / 1e9
    assert 0 < idle < seconds
    # one request over the whole stretch, its read over the first half
    half = seconds / 2
    w.spans = [span("http.GET.SumAll", -1.0, seconds + 1.0, "r"),
               span("proxy.fetch_stored", 0.0, seconds, "p", "r"),
               span("abd.fetch", 0.0, half, "a", "p")]
    share = idle_by_leaf.reduce(w, 20.0, CONTAINERS)
    first = sum(min(b, lo + half * 1e9) - a for a, b in gaps
                if a < lo + half * 1e9) / 1e9
    assert share == pytest.approx(100.0 * first / idle, rel=1e-6)
    table = json.loads(capsys.readouterr().out.split(" ", 1)[1])
    assert table["by_leaf"]["abd.fetch"] == pytest.approx(first, rel=1e-6)
    assert table["unattributed_under"] == {
        "proxy.fetch_stored": pytest.approx(idle - first, rel=1e-6)}
