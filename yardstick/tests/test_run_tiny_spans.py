"""A whole traced run at a tiny size on the CPU gives every per-layer
metric that reads the spans and counters of the program's own layers
(`test_run_tiny.make_checkout` lists the tiny cells under every metric)."""

import json

import pytest

from yardstick.tests.test_run_tiny import make_checkout, run_cell

SPAN_FED = [
    "assembly.state_ms", "assembly.validate_tags_ms", "assembly.pairs_ms",
    "assembly.operands_ms", "assembly.reread_ms", "residency.lookup_ms",
    "residency.convert_ms", "residency.h2d_ms", "dispatch.thread_wait_ms",
    "dispatch.resume_wait_ms", "dispatch.d2h_ms", "edge.loop_blocked_share",
    "runtime.gc_pause_ms_per_agg"]


@pytest.fixture(scope="module")
def line(tmp_path_factory):
    tree = make_checkout(tmp_path_factory.mktemp("checkout"))
    done = run_cell(tree, "tiny.ycsba-sumall", 1)
    assert done.returncode == 0, done.stderr[-2000:]
    return json.loads(done.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("name", SPAN_FED)
def test_a_traced_writer_run_reports_the_metric(line, name):
    assert line["correct"] is True and line["failed"] == 0
    value = line["metrics"][name]["value"]
    assert isinstance(value, float) and value >= 0.0
    if name.endswith("_share"):
        assert value <= 100.0


def test_a_cpu_trace_has_no_device_idle_to_attribute(line):
    # no device plane: nothing stands under a device metric's name
    assert "device.idle_attributed_share" not in line["metrics"]
    assert "device.idle_share" not in line["metrics"]
