"""The reduction from a profiler trace to numbers, on a trace small enough
to check by hand and on the one recorded on the chip."""

import json
import os

import pytest

from yardstick import trace_reduce as tr

HERE = os.path.dirname(os.path.abspath(__file__))


def plane(name, ops=(), modules=(), marks=()):
    lines = []
    if ops:
        lines.append({"name": tr.OPS_LINE,
                      "events": [[n, s, d, {}] for n, s, d in ops]})
    if modules:
        lines.append({"name": tr.MODULES_LINE,
                      "events": [[n, s, d, {}] for n, s, d in modules]})
    if marks:
        lines.append({"name": "python", "events": [
            [tr.SYNC_MARK, s, 1.0, {"t_ns": t}] for s, t in marks]})
    return {"name": name, "lines": lines}


HAND = [
    plane("/host:CPU", marks=[(100.0, 5_000_100.0), (1100.0, 5_001_100.0)]),
    plane("/device:TPU:0",
          # a loop 200..600 holding its body, then two separate operations
          ops=[("while.1", 200.0, 400.0), ("fusion.2", 250.0, 100.0),
               ("fusion.2", 400.0, 150.0), ("copy.3", 700.0, 100.0),
               ("fusion.9", 1050.0, 100.0)],
          modules=[("jit_run(123)", 200.0, 400.0),
                   ("jit_take(7)", 700.0, 100.0),
                   ("jit_run(123)", 1050.0, 100.0)]),
    plane("/device:TPU:1"),   # a chip that ran nothing is not averaged in
]


def test_union_merges_nested_and_touching_intervals():
    assert tr.union([(5, 7), (0, 2), (1, 3), (3, 4)]) == [(0, 4), (5, 7)]


def test_busy_time_is_the_union_inside_the_window():
    # inside [100, 1100]: 200..600, 700..800 and 1050..1100 = 550 ns
    assert tr.busy_seconds(HAND, 100.0, 1100.0) == pytest.approx(550e-9)
    gaps = tr.idle_gaps(HAND, 100.0, 1100.0)
    assert gaps == [(100.0, 200.0), (600.0, 700.0), (800.0, 1050.0)]
    assert sum(b - a for a, b in gaps) + 550.0 == 1000.0


def test_time_by_program_and_by_operation():
    mods = tr.op_seconds(HAND, tr.MODULES_LINE, 100.0, 1100.0, "^jit_run")
    assert mods == {"jit_run": pytest.approx(450e-9)}   # the last one clipped
    assert tr.op_count(HAND, tr.MODULES_LINE, 100.0, 1100.0, "^jit_run") == 2
    ops = tr.op_seconds(HAND, tr.OPS_LINE, 100.0, 1100.0)
    assert ops["fusion.2"] == pytest.approx(250e-9)
    assert ops["while.1"] == pytest.approx(400e-9)


def test_sync_marks_tie_the_two_clocks():
    assert tr.sync_marks(HAND) == [(100.0, 5_000_100.0),
                                   (1100.0, 5_001_100.0)]


RECORDED = os.path.join(HERE, "files", "recorded_trace.json")


@pytest.mark.skipif(not os.path.exists(RECORDED),
                    reason="no recorded trace on file")
def test_the_recorded_chip_trace_reduces_to_the_numbers_on_file():
    with open(RECORDED) as f:
        rec = json.load(f)
    planes, want = rec["planes"], rec["expected"]
    lo, hi = rec["lo_ns"], rec["hi_ns"]
    assert [p["name"] for p in tr.device_planes(planes)] == want["devices"]
    busy = tr.busy_seconds(planes, lo, hi)
    assert busy == pytest.approx(want["busy_s"], rel=1e-9)
    assert 0 < busy < (hi - lo) / 1e9
    idle = sum(b - a for a, b in tr.idle_gaps(planes, lo, hi)) / 1e9
    assert busy + idle == pytest.approx((hi - lo) / 1e9, rel=1e-9)
    mods = tr.op_seconds(planes, tr.MODULES_LINE, lo, hi, want["pattern"])
    assert sum(mods.values()) == pytest.approx(want["fold_s"], rel=1e-9)
    assert tr.op_count(planes, tr.MODULES_LINE, lo, hi,
                       want["pattern"]) == want["folds"]
    # a program's time is inside the device's busy time
    assert sum(mods.values()) <= busy * (1 + 1e-9)
