"""The north-star deployment with one of its four replicas compromised.

`ref8col-bft4-byz1` is `ref8col-bft4` with the attack armed at launch and
nothing else moved, and its cell reads the fault path's own metrics where
the healthy twin has nothing to read. The tiny pair is made as
`test_run_tiny` makes its cells: new files and `BENCHMARK.json` entries in
a scratch checkout.
"""

import json
import os

import pytest

from yardstick.tests.test_run_tiny import make_checkout, run_cell

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
CONFIGS = os.path.join(os.path.dirname(HERE), "configs")
SETTINGS = {"attacks.enabled": True, "attacks.type": "byzantine",
            "attacks.at_launch": True}
COUNTED = {"fault.suspect_votes_per_update", "fault.rejected_msgs_per_update",
           "fault.coordinator_violations_per_agg"}
SPANNED = {"fault.supervisor_loop_share", "fault.supervisor_handle_ms"}
FAULT = COUNTED | SPANNED
CELL, TWIN = "bft4-byz1-ycsba-sumall", "bft4-ycsba-sumall"


def read(name):
    with open(os.path.join(CONFIGS, f"{name}.json")) as f:
        return json.load(f)


def test_the_compromised_deployment_differs_from_bft4_by_its_attack_alone():
    old, new = read("ref8col-bft4"), read("ref8col-bft4-byz1")
    told = {"name", "source", "deployment", "guarantees", "assumed"}
    differs = {k for k in set(old) | set(new)
               if old.get(k) != new.get(k)} - told
    assert differs == {"settings"}
    assert new["settings"] == SETTINGS      # chaos_seed left at its default
    assert new["rows"] == old["rows"] == 16384
    assert new["reduced"] == old["reduced"]
    assert new["recovery"] is False and new["sentinels"] == 0
    # the four guarantees, each restated as holding under the fault
    assert set(new["guarantees"]) == set(old["guarantees"])
    assert len(new["guarantees"]) == 4
    for name, text in new["guarantees"].items():
        assert text.startswith(old["guarantees"][name]), name
        assert "with one of the four replicas compromised" in text, name
    assert "replica-3" in new["deployment"] and "no spare" in new["deployment"]
    assert new["assumed"][:len(old["assumed"])] == old["assumed"]
    assert any("chaos_seed" in a and "replica-3" in a
               for a in new["assumed"])
    assert len(new["source"]) <= 200
    for part in ("BASELINE.json config 4", "client.conf:50-61",
                 "dds-system.conf:144-148", "Trudy.scala:14-32"):
        assert part in new["source"], part


def test_the_harness_takes_the_three_settings_as_they_stand():
    import random

    from yardstick import run as yr

    cfg = yr.build_config(read("ref8col-bft4-byz1"))
    assert cfg.attacks.enabled and cfg.attacks.at_launch
    assert cfg.attacks.type == "byzantine" and cfg.attacks.chaos_seed == 0
    base = yr.build_config(read("ref8col-bft4"))
    assert not base.attacks.enabled and not base.attacks.at_launch
    assert cfg.replicas == base.replicas and cfg.proxy == base.proxy
    # the seed's draw, as `run.launch` makes it
    assert random.Random(cfg.attacks.chaos_seed).sample(
        cfg.replicas.endpoints, cfg.replicas.byz_max_faults) == ["replica-3"]


def test_the_cell_reads_what_its_twin_reads_and_the_fault_path_besides():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cells = {w["name"]: w for w in bench["workloads"]}
    assert cells[CELL]["config"] == "ref8col-bft4-byz1"
    assert cells[CELL]["traffic"] == cells[TWIN]["traffic"] == "ycsba-sumall"
    assert cells[CELL]["chips"] == 1

    def of(cell):
        return {m["name"] for m in bench["end_to_end"] + bench["per_layer"]
                if "workloads" not in m or cell in m["workloads"]}

    assert of(CELL) - of(TWIN) == FAULT and of(TWIN) <= of(CELL)
    assert not any(name.startswith("wire.") for name in of(CELL))
    for m in bench["per_layer"]:
        if m["name"] in FAULT:
            assert m["workloads"] == [CELL] and m["layer"] == "fault path"


# ------------------------------------------------------------- a tiny pair


@pytest.fixture(scope="module")
def checkout(tmp_path_factory):
    """`make_checkout`'s tree with `tiny-bft4` a second time under the
    attack: one more configuration file, one more cell, and that cell's
    name beside the real one's in every list that has it. The healthy
    tiny twin is asked for the fault path's metrics too, so that a test
    can see them left out of its line."""
    tree = make_checkout(tmp_path_factory.mktemp("byz_checkout"))
    conf_dir = os.path.join(tree, "yardstick", "configs")
    with open(os.path.join(conf_dir, "tiny-bft4.json")) as f:
        conf = json.load(f)
    conf["name"] = "tiny-bft4-byz1"
    conf["settings"] = read("ref8col-bft4-byz1")["settings"]
    with open(os.path.join(conf_dir, "tiny-bft4-byz1.json"), "w") as f:
        json.dump(conf, f)
    with open(os.path.join(tree, "BENCHMARK.json")) as f:
        bench = json.load(f)
    bench["configs"].append({
        "name": "tiny-bft4-byz1", "source": "test",
        "file": "yardstick/configs/tiny-bft4-byz1.json",
        "reduced": ["rows"], "why": "test"})
    bench["workloads"].append({
        "name": "tiny-byz1.ycsba-sumall", "config": "tiny-bft4-byz1",
        "traffic": "ycsba-sumall", "chips": 1, "why": "test"})
    for metric in bench["end_to_end"] + bench["per_layer"]:
        if metric["name"] in FAULT:   # `make_checkout` lists its cells everywhere
            metric["workloads"] = [CELL, "tiny.ycsba-sumall"]
        if CELL in metric.get("workloads", ()):
            metric["workloads"].append("tiny-byz1.ycsba-sumall")
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


def test_a_tiny_run_with_the_liar_is_correct_and_reads_the_fault_path(
        checkout):
    done = run_cell(checkout, "tiny-byz1.ycsba-sumall", 1)
    got = last_line(done)["metrics"]
    assert FAULT <= set(got)
    # the victim is on the record of the run
    deployment = next(line for line in done.stdout.splitlines()
                      if line.startswith("[deployment]"))
    assert '"attacks.at_launch": true' in deployment
    # every write is coordinated by an honest replica, which refuses the
    # liar's four replayed TagReplys and votes it suspect for each; every
    # full read refuses its forged ReadReply
    assert got["fault.suspect_votes_per_update"]["value"] >= 4
    assert got["fault.rejected_msgs_per_update"]["value"] >= 4
    assert got["fault.supervisor_handle_ms"]["value"] > 0
    assert 0 < got["fault.supervisor_loop_share"]["value"] < 100
    # struck out within the load, and never a coordinator again
    assert got["fault.coordinator_violations_per_agg"]["value"] == 0
    # a tag round goes to the three trusted, each named its key set
    assert got["quorum.tag_keys_carried_share"]["value"] == 0
    assert got["dispatch.compiles_in_window"]["value"] == 0


def test_the_healthy_twin_has_no_fault_path_to_read(checkout):
    got = last_line(run_cell(checkout, "tiny.ycsba-sumall", 1))["metrics"]
    # no series, no number: absent, not zero
    assert not COUNTED & set(got)
    # a healthy supervisor's only mail is the proxy's `RequestReplicas`,
    # once in 5 s: where one fell into the window, its span is all there
    # is to read
    if "fault.supervisor_loop_share" in got:
        assert got["fault.supervisor_loop_share"]["value"] < 1
    assert "quorum.read_tags_ms" in got


def test_a_tiny_run_with_the_liar_reports_the_end_to_end_metrics(checkout):
    got = last_line(run_cell(checkout, "tiny-byz1.ycsba-sumall", 0))["metrics"]
    assert {"setup_s", "ops_per_s", "agg_p50_ms", "point_p95_ms"} <= set(got)
    assert "agg_p95_ms" not in got
