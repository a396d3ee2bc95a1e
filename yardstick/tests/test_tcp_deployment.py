"""The north-star deployment with its replicas behind sockets.

`ref8col-bft4-tcp` is `ref8col-bft4` with the transport named and nothing
else moved, and its cell reads the wire's own metrics where the in-memory
twin has none to read. The tiny pair is made as `test_run_tiny` makes its
cells: new files and `BENCHMARK.json` entries in a scratch checkout.
"""

import json
import os

import pytest

from yardstick.tests.test_run_tiny import make_checkout, run_cell

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
CONFIGS = os.path.join(os.path.dirname(HERE), "configs")
SETTINGS = {"transport.kind", "transport.port",
            "security.transport_frame_secret"}
WIRE = {"wire.serialize_ms", "wire.deserialize_ms",
        "wire.serialize_loop_share", "wire.deserialize_loop_share",
        "wire.tag_request_bytes_per_agg", "wire.tag_reply_bytes_per_agg"}
CELL, TWIN = "bft4-tcp-ycsba-sumall", "bft4-ycsba-sumall"


def read(name):
    with open(os.path.join(CONFIGS, f"{name}.json")) as f:
        return json.load(f)


def test_the_tcp_deployment_differs_from_bft4_by_its_transport_alone():
    old, new = read("ref8col-bft4"), read("ref8col-bft4-tcp")
    told = {"name", "source", "deployment", "reduced", "assumed"}
    differs = {k for k in set(old) | set(new)
               if old.get(k) != new.get(k)} - told
    assert differs == {"settings"}
    assert set(new["settings"]) == SETTINGS
    assert new["settings"]["transport.kind"] == "tcp"
    assert new["settings"]["security.transport_frame_secret"]
    assert new["guarantees"] == old["guarantees"] and len(
        new["guarantees"]) == 4
    assert new["rows"] == old["rows"] == 16384
    assert "TCP" in new["deployment"] and "in-memory" not in new["deployment"]
    assert set(new["reduced"]) == {"rows", "one_process"}
    assert new["reduced"]["rows"] == old["reduced"]["rows"]
    assert new["assumed"][:len(old["assumed"])] == old["assumed"]


def test_the_harness_takes_the_three_settings_as_they_stand():
    from yardstick import run as yr

    cfg = yr.build_config(read("ref8col-bft4-tcp"))
    assert cfg.transport.kind == "tcp" and cfg.transport.port == 0
    assert cfg.security.transport_frame_secret
    base = yr.build_config(read("ref8col-bft4"))
    assert base.transport.kind == "memory"
    assert cfg.replicas == base.replicas and cfg.proxy == base.proxy


def test_the_cell_reads_what_its_twin_reads_and_the_wire_besides():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cells = {w["name"]: w for w in bench["workloads"]}
    assert cells[CELL]["config"] == "ref8col-bft4-tcp"
    assert cells[CELL]["traffic"] == cells[TWIN]["traffic"]
    assert cells[CELL]["chips"] == 1

    def of(cell):
        return {m["name"] for m in bench["end_to_end"] + bench["per_layer"]
                if "workloads" not in m or cell in m["workloads"]}

    assert of(CELL) - of(TWIN) == WIRE and of(TWIN) <= of(CELL)
    for m in bench["per_layer"]:
        if m["name"] in WIRE:
            assert m["workloads"] == [CELL] and m["layer"] == "wire"


# ------------------------------------------------------------- a tiny pair


@pytest.fixture(scope="module")
def checkout(tmp_path_factory):
    """`make_checkout`'s tree with `tiny-bft4` a second time over TCP: one
    more configuration file, one more cell, and that cell's name beside
    the real one's in every list that has it."""
    tree = make_checkout(tmp_path_factory.mktemp("tcp_checkout"))
    conf_dir = os.path.join(tree, "yardstick", "configs")
    with open(os.path.join(conf_dir, "tiny-bft4.json")) as f:
        conf = json.load(f)
    conf["name"] = "tiny-bft4-tcp"
    conf["settings"] = read("ref8col-bft4-tcp")["settings"]
    with open(os.path.join(conf_dir, "tiny-bft4-tcp.json"), "w") as f:
        json.dump(conf, f)
    with open(os.path.join(tree, "BENCHMARK.json")) as f:
        bench = json.load(f)
    bench["configs"].append({
        "name": "tiny-bft4-tcp", "source": "test",
        "file": "yardstick/configs/tiny-bft4-tcp.json",
        "reduced": ["rows"], "why": "test"})
    bench["workloads"].append({
        "name": "tiny-tcp.ycsba-sumall", "config": "tiny-bft4-tcp",
        "traffic": "ycsba-sumall", "chips": 1, "why": "test"})
    for metric in bench["end_to_end"] + bench["per_layer"]:
        if metric["name"] in WIRE:   # `make_checkout` lists its cells everywhere
            metric["workloads"] = [CELL]
        if CELL in metric.get("workloads", ()):
            metric["workloads"].append("tiny-tcp.ycsba-sumall")
    with open(os.path.join(tree, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    return tree


def last_line(done):
    assert done.returncode == 0, done.stderr[-2000:]
    last = json.loads(done.stdout.strip().splitlines()[-1])
    assert last["correct"] is True and last["failed"] == 0
    assert last["attempted"] > 0
    assert all(c["value"] <= c["limit"] for c in last["checks"].values())
    return last


def test_a_tiny_tcp_run_is_correct_and_reads_the_wire(checkout):
    got = last_line(run_cell(checkout, "tiny-tcp.ycsba-sumall", 1))["metrics"]
    assert WIRE <= set(got)
    for name in WIRE:
        assert got[name]["value"] > 0, name
    # K keys of 128 hex characters to each of four replicas, every round
    assert got["wire.tag_request_bytes_per_agg"]["value"] > 4 * 192 * 128
    assert got["dispatch.compiles_in_window"]["value"] == 0
    assert got["quorum.tag_full_vote_share"]["value"] < 50


def test_the_in_memory_twin_has_no_wire_to_read(checkout):
    got = last_line(run_cell(checkout, "tiny.ycsba-sumall", 1))["metrics"]
    assert not WIRE & set(got)
    assert "quorum.read_tags_ms" in got


def test_a_tiny_tcp_run_reports_the_end_to_end_metrics(checkout):
    got = last_line(run_cell(checkout, "tiny-tcp.ycsba-sumall", 0))["metrics"]
    assert {"setup_s", "ops_per_s", "agg_p50_ms", "point_p95_ms"} <= set(got)
    assert "agg_p95_ms" not in got
