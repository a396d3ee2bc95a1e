"""The north-star deployment with every replica in a process of its own.

`ref8col-bft4-hosts` is `ref8col-bft4-tcp` with one more setting,
`transport.replica_processes`, and its cell reads what its twin reads, less
the two metrics of what only a replica's process holds (the span
`replica.tag_vector`, and the frames a replica SENDS: spans and the wire's
counters stay in their process), and `hosts.replica_cpu_share` besides.
The tiny cell is made as `test_tcp_deployment` makes its own: new files and
`BENCHMARK.json` entries in a scratch checkout. The controls at the cell's
own size are a chip run:

    python3 yardstick/tests/control.py --workload bft4-hosts-ycsba-sumall --seeds 1 --seconds 5
"""

import json
import os

import pytest

from yardstick.tests.test_run_tiny import _in_process, make_checkout, run_cell

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
CONFIGS = os.path.join(os.path.dirname(HERE), "configs")
CELL, TWIN = "bft4-hosts-ycsba-sumall", "bft4-tcp-ycsba-sumall"
TINY = "tiny-hosts.ycsba-sumall"
THE_SETTING = "transport.replica_processes"
REPLICA_SIDE = {"quorum.replica_tag_vector_ms",
                "wire.tag_reply_bytes_per_agg"}
NEW = {"hosts.replica_cpu_share"}


def read(name):
    with open(os.path.join(CONFIGS, f"{name}.json")) as f:
        return json.load(f)


def test_the_hosts_deployment_differs_from_tcp_by_the_one_setting():
    old, new = read("ref8col-bft4-tcp"), read("ref8col-bft4-hosts")
    told = {"name", "source", "deployment", "guarantees", "reduced",
            "assumed"}
    differs = {k for k in set(old) | set(new)
               if old.get(k) != new.get(k)} - told
    assert differs == {"settings"}
    assert dict(new["settings"]) == {**old["settings"], THE_SETTING: True}
    # the four guarantees, each restated as holding across processes
    assert list(new["guarantees"]) == list(old["guarantees"])
    for name, text in old["guarantees"].items():
        assert new["guarantees"][name].startswith(text)
        assert "process" in new["guarantees"][name]
    assert new["rows"] == old["rows"] == 16384
    assert set(new["reduced"]) == {"rows", "one_machine",
                                   "loadgen_in_proxy_process"}
    assert new["reduced"]["rows"] == old["reduced"]["rows"]
    assert "process of its own" in new["deployment"]
    assert "share one process" not in new["deployment"]
    assert len(new["source"]) <= 200


def test_the_harness_takes_the_setting_as_it_stands():
    from yardstick import run as yr

    cfg = yr.build_config(read("ref8col-bft4-hosts"))
    assert cfg.transport.replica_processes is True
    assert cfg.transport.kind == "tcp" and cfg.transport.port == 0
    tcp = yr.build_config(read("ref8col-bft4-tcp"))
    assert tcp.transport.replica_processes is False
    tcp.transport.replica_processes = True
    assert tcp == cfg


def test_the_cell_reads_what_its_twin_reads_less_the_replicas_side():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cells = {w["name"]: w for w in bench["workloads"]}
    assert cells[CELL]["config"] == "ref8col-bft4-hosts"
    assert cells[CELL]["traffic"] == cells[TWIN]["traffic"] == "ycsba-sumall"
    assert cells[CELL]["chips"] == 1 and len(cells[CELL]["why"]) <= 200
    conf = next(c for c in bench["configs"]
                if c["name"] == "ref8col-bft4-hosts")
    assert set(conf["reduced"]) == set(read("ref8col-bft4-hosts")["reduced"])
    assert conf["source"] == read("ref8col-bft4-hosts")["source"]

    def of(cell, kind):
        return {m["name"] for m in bench[kind]
                if "workloads" not in m or cell in m["workloads"]}

    assert of(CELL, "end_to_end") <= of(TWIN, "end_to_end")
    assert {"setup_s", "ops_per_s", "agg_p50_ms"} <= of(CELL, "end_to_end")
    # `point_p95_ms` is the cell's only where its spread allowed it
    # (`PERF.md` section 2); without it the mean `abd.write` is read by
    # the metric that moves `ops_per_s`, as in `bft9-recov-ycsba-sumall`
    if "point_p95_ms" in of(CELL, "end_to_end"):
        swapped_out, swapped_in = set(), set()
    else:
        swapped_out, swapped_in = ({"quorum.write_ms"},
                                   {"quorum.update_write_ms"})
    assert (of(TWIN, "per_layer") - of(CELL, "per_layer")
            == REPLICA_SIDE | swapped_out)
    assert (of(CELL, "per_layer") - of(TWIN, "per_layer")
            == NEW | swapped_in)
    ends = of(CELL, "end_to_end")
    for m in bench["per_layer"]:
        if CELL in m.get("workloads", ()):
            assert m["moves"] in ends, m["name"]
    (metric,) = [m for m in bench["per_layer"] if m["name"] in NEW]
    assert metric["workloads"] == [CELL]
    assert metric["layer"] == "replica hosts"
    with open(os.path.join(os.path.dirname(HERE), "layers",
                           "hosts.replica_cpu_share.json")) as f:
        spec = json.load(f)
    assert spec["reducer"] == "counter_share"
    assert {k: spec[k] for k in ("unit", "better", "source", "layer",
                                 "moves")} == {
        k: metric[k] for k in ("unit", "better", "source", "layer", "moves")}


# ------------------------------------------------------------- a tiny run


@pytest.fixture(scope="module")
def checkout(tmp_path_factory):
    """`make_checkout`'s tree with `tiny-bft4` once more, placed as
    `ref8col-bft4-hosts` places its replicas."""
    tree = make_checkout(tmp_path_factory.mktemp("hosts_checkout"))
    conf_dir = os.path.join(tree, "yardstick", "configs")
    with open(os.path.join(conf_dir, "tiny-bft4.json")) as f:
        conf = json.load(f)
    conf["name"] = "tiny-bft4-hosts"
    conf["settings"] = read("ref8col-bft4-hosts")["settings"]
    with open(os.path.join(conf_dir, "tiny-bft4-hosts.json"), "w") as f:
        json.dump(conf, f)
    with open(os.path.join(tree, "BENCHMARK.json")) as f:
        bench = json.load(f)
    bench["configs"].append({
        "name": "tiny-bft4-hosts", "source": "test",
        "file": "yardstick/configs/tiny-bft4-hosts.json",
        "reduced": ["rows"], "why": "test"})
    bench["workloads"].append({
        "name": TINY, "config": "tiny-bft4-hosts",
        "traffic": "ycsba-sumall", "chips": 1, "why": "test"})
    for metric in bench["end_to_end"] + bench["per_layer"]:
        if CELL in metric.get("workloads", ()):
            metric["workloads"].append(TINY)
    with open(os.path.join(tree, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    return tree


def replica_processes_of(tree: str) -> list[int]:
    """Replica processes started from this checkout that still run."""
    found = []
    for entry in os.listdir("/proc"):
        if not entry.isdigit():
            continue
        try:
            with open(f"/proc/{entry}/cmdline", "rb") as f:
                argv = f.read().split(b"\0")
            if (b"--die-with-parent" in argv
                    and os.readlink(f"/proc/{entry}/cwd") == tree):
                found.append(int(entry))
        except OSError:
            continue
    return found


def last_line(done):
    assert done.returncode == 0, done.stderr[-2000:]
    last = json.loads(done.stdout.strip().splitlines()[-1])
    assert last["correct"] is True and last["failed"] == 0
    assert last["attempted"] > 0
    assert all(c["value"] <= c["limit"] for c in last["checks"].values())
    return last


def test_a_tiny_hosts_run_is_correct_and_reads_the_replica_hosts(checkout):
    done = run_cell(checkout, TINY, 1)
    got = last_line(done)["metrics"]
    assert '"transport.replica_processes": true' in done.stdout
    # the replicas' CPU, summed over four processes, beside the proxy's
    assert 0 < got["hosts.replica_cpu_share"]["value"] < 100
    # shipped from the replica processes: a number, 0 when no round of
    # the window met a write half way
    assert 0 <= got["quorum.reread_written_back_share"]["value"] <= 100
    assert got["quorum.reread_rounds_per_agg"]["value"] >= 1
    assert got["quorum.tag_vector_rebuild_share"]["value"] >= 0
    # no replica runs on the proxy's loop; its frames' other ends are gone
    assert got["loop.replica_share"]["value"] == 0.0
    assert got["wire.serialize_ms"]["value"] > 0
    assert not REPLICA_SIDE & set(got)
    assert got["dispatch.compiles_in_window"]["value"] == 0
    assert replica_processes_of(checkout) == []


def test_a_tiny_hosts_run_reports_the_end_to_end_metrics(checkout):
    got = last_line(run_cell(checkout, TINY, 0))["metrics"]
    assert {"setup_s", "ops_per_s", "agg_p50_ms"} <= set(got)
    assert "agg_p95_ms" not in got
    assert replica_processes_of(checkout) == []


@pytest.mark.parametrize("control", ["truncated_limb", "lost_write"])
def test_the_controls_come_out_not_correct_across_processes(checkout,
                                                            control):
    from yardstick.tests import control as ctl

    out = _in_process(checkout, TINY, 43, breakage=ctl.CONTROLS[control])
    assert out["correct"] is False
