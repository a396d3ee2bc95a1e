"""The north-star deployment with one of its four replicas crashed.

`ref8col-bft4-crash1` is `ref8col-bft4` with the crash armed at launch and
the failure detector's three periods written down, nothing else moved; its
cell reads the fault path's own metrics where the healthy twin has no probe
to read. The tiny pair is made as `test_byz_deployment` makes its own: new
files and `BENCHMARK.json` entries in a scratch checkout.
"""

import json
import os

import pytest

from yardstick.tests.test_run_tiny import make_checkout, run_cell

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
CONFIGS = os.path.join(os.path.dirname(HERE), "configs")
LAYERS = os.path.join(os.path.dirname(HERE), "layers")
SETTINGS = {"attacks.enabled": True, "attacks.type": "crash",
            "attacks.at_launch": True,
            "proxy.intranet_request_timeout": 5.0,
            "proxy.breaker_reset": 2.0, "proxy.breaker_probe_timeout": 1.0}
FAULT = {"fault.probes_per_agg", "fault.probe_ms",
         "fault.tag_requests_skipped_per_agg",
         "fault.request_timeouts_per_update"}
CELL, TWIN = "bft4-crash1-ycsba-sumall", "bft4-ycsba-sumall"
TINY, TINY_TWIN = "tiny-crash1.ycsba-sumall", "tiny.ycsba-sumall"


def read(name):
    with open(os.path.join(CONFIGS, f"{name}.json")) as f:
        return json.load(f)


def test_the_crashed_deployment_differs_from_bft4_by_attack_and_periods():
    old, new = read("ref8col-bft4"), read("ref8col-bft4-crash1")
    told = {"name", "source", "deployment", "guarantees", "assumed"}
    differs = {k for k in set(old) | set(new)
               if old.get(k) != new.get(k)} - told
    assert differs == {"settings"}
    assert new["settings"] == SETTINGS      # chaos_seed left at its default
    assert new["rows"] == old["rows"] == 16384
    assert new["reduced"] == old["reduced"]
    assert new["recovery"] is False and new["sentinels"] == 0
    # the four guarantees, each restated as holding with the replica down
    assert set(new["guarantees"]) == set(old["guarantees"])
    assert len(new["guarantees"]) == 4
    for name, text in new["guarantees"].items():
        assert text.startswith(old["guarantees"][name]), name
        assert "with one of the four replicas crashed" in text, name
    assert "no further fault is tolerated" in new["guarantees"][
        "acknowledged_write_read_back"]
    assert "replica-3" in new["deployment"] and "no spare" in new["deployment"]
    assert new["assumed"][:len(old["assumed"])] == old["assumed"]
    assert any("chaos_seed" in a and "replica-3" in a
               for a in new["assumed"])
    assert len(new["source"]) <= 200
    for part in ("BASELINE.json config 4", "client.conf:50-61",
                 "dds-system.conf:144-148", "type crash",
                 "Trudy.scala:14-32"):
        assert part in new["source"], part


def test_the_three_periods_stand_at_the_programs_defaults():
    """Written down because they define the deployment, not to move them:
    with the attack taken out the file builds `ref8col-bft4`'s config."""
    from yardstick import run as yr

    cfg = yr.build_config(read("ref8col-bft4-crash1"))
    base = yr.build_config(read("ref8col-bft4"))
    assert cfg.proxy == base.proxy and cfg.replicas == base.replicas
    assert (cfg.proxy.intranet_request_timeout, cfg.proxy.breaker_reset,
            cfg.proxy.breaker_probe_timeout) == (5.0, 2.0, 1.0)


@pytest.mark.parametrize("path", sorted(SETTINGS))
def test_the_harness_takes_each_of_the_six_settings(path):
    from dds_tpu.utils.config import DDSConfig
    from yardstick import run as yr

    cfg = DDSConfig()
    yr.apply_setting(cfg, path, SETTINGS[path])
    group, leaf = path.split(".")
    assert getattr(getattr(cfg, group), leaf) == SETTINGS[path]


def test_the_harness_draws_the_victim_the_file_names():
    import random

    from yardstick import run as yr

    cfg = yr.build_config(read("ref8col-bft4-crash1"))
    assert cfg.attacks.enabled and cfg.attacks.at_launch
    assert cfg.attacks.type == "crash" and cfg.attacks.chaos_seed == 0
    assert random.Random(cfg.attacks.chaos_seed).sample(
        cfg.replicas.endpoints, cfg.replicas.byz_max_faults) == ["replica-3"]


def test_a_program_without_the_field_ends_the_cell_at_set_up():
    """What the parent does with this file: the path is refused before a
    row is made, so the cell is measured on the change alone."""
    from dds_tpu.utils.config import ProxySettings
    from yardstick import run as yr

    fields = dict(ProxySettings.__dataclass_fields__)
    del fields["breaker_probe_timeout"]

    class Older:
        """`DDSConfig` as it was: a proxy group without the field."""
        proxy = type("Proxy", (), {"__dataclass_fields__": fields})()

    with pytest.raises(yr.SetupError, match="proxy.breaker_probe_timeout"):
        yr.apply_setting(Older(), "proxy.breaker_probe_timeout", 1.0)


def test_the_cell_reads_what_its_twin_reads_and_the_fault_path_besides():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cells = {w["name"]: w for w in bench["workloads"]}
    assert cells[CELL]["config"] == "ref8col-bft4-crash1"
    assert cells[CELL]["traffic"] == cells[TWIN]["traffic"] == "ycsba-sumall"
    assert cells[CELL]["chips"] == 1
    assert bench["workloads"][-1]["name"] == CELL       # appended, last
    assert bench["configs"][-1]["name"] == "ref8col-bft4-crash1"

    def of(cell):
        return {m["name"] for m in bench["end_to_end"] + bench["per_layer"]
                if "workloads" not in m or cell in m["workloads"]}

    assert of(CELL) - of(TWIN) == FAULT and of(TWIN) <= of(CELL)
    assert {"kernel.fold_roofline", "kernel.fold_device_ms",
            "quorum.tag_keys_carried_share", "point_p95_ms"} <= of(CELL)
    assert not any(name.startswith("wire.") for name in of(CELL))
    assert [m["name"] for m in bench["per_layer"][-4:]] == [
        "fault.probes_per_agg", "fault.probe_ms",
        "fault.tag_requests_skipped_per_agg",
        "fault.request_timeouts_per_update"]
    for m in bench["per_layer"]:
        if m["name"] in FAULT:
            assert m["workloads"] == [CELL] and m["layer"] == "fault path"
            with open(os.path.join(LAYERS, m["name"] + ".json")) as f:
                spec = json.load(f)
            assert {k: spec[k] for k in ("unit", "better", "source", "layer",
                                         "moves")} == {
                k: m[k] for k in ("unit", "better", "source", "layer",
                                  "moves")}
        elif CELL in m.get("workloads", ()):
            assert m["workloads"][-1] == CELL


# ------------------------------------------------------------- a tiny pair


@pytest.fixture(scope="module")
def checkout(tmp_path_factory):
    """`make_checkout`'s tree with `tiny-bft4` a second time with the
    crash: one more configuration file, one more cell, and that cell's
    name beside the real one's in every list that has it. The periods of
    the look are cut to fit a 3 s window (a probe every 0.75 s where the
    real cell has one every 3 s); the 5 s a user waits stay. The healthy
    tiny twin is asked for the fault path's metrics too, so that a test
    can see what it has of them."""
    tree = make_checkout(tmp_path_factory.mktemp("crash_checkout"))
    conf_dir = os.path.join(tree, "yardstick", "configs")
    with open(os.path.join(conf_dir, "tiny-bft4.json")) as f:
        conf = json.load(f)
    conf["name"] = "tiny-bft4-crash1"
    conf["settings"] = dict(SETTINGS, **{"proxy.breaker_reset": 0.5,
                                         "proxy.breaker_probe_timeout": 0.25})
    with open(os.path.join(conf_dir, "tiny-bft4-crash1.json"), "w") as f:
        json.dump(conf, f)
    with open(os.path.join(tree, "BENCHMARK.json")) as f:
        bench = json.load(f)
    bench["configs"].append({
        "name": "tiny-bft4-crash1", "source": "test",
        "file": "yardstick/configs/tiny-bft4-crash1.json",
        "reduced": ["rows"], "why": "test"})
    bench["workloads"].append({
        "name": TINY, "config": "tiny-bft4-crash1",
        "traffic": "ycsba-sumall", "chips": 1, "why": "test"})
    for metric in bench["end_to_end"] + bench["per_layer"]:
        if metric["name"] in FAULT:   # `make_checkout` lists its cells everywhere
            metric["workloads"] = [CELL, TINY_TWIN]
        if CELL in metric.get("workloads", ()):
            metric["workloads"].append(TINY)
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


def test_a_tiny_run_with_the_crash_is_correct_and_reads_the_fault_path(
        checkout):
    done = run_cell(checkout, TINY, 1)
    got = last_line(done)["metrics"]
    assert FAULT <= set(got)
    # the victim and the periods are on the record of the run
    deployment = next(line for line in done.stdout.splitlines()
                      if line.startswith("[deployment]"))
    assert '"attacks.type": "crash"' in deployment
    assert '"proxy.breaker_probe_timeout": 0.25' in deployment
    # the breaker opened inside the load: nobody waits in the window, and
    # the number says so by being there, not by being left out
    assert got["fault.request_timeouts_per_update"]["value"] == 0.0
    # a tag round goes to the three survivors, each named its key set
    # (one a round; the rounds of aggregates still in flight at the close
    # are counted, those aggregates are not)
    assert 1.0 <= got["fault.tag_requests_skipped_per_agg"]["value"] < 1.2
    assert got["quorum.tag_keys_carried_share"]["value"] == 0
    # the proxy looks, and each look is the probe's own timeout long
    assert got["fault.probes_per_agg"]["value"] > 0
    assert 250 <= got["fault.probe_ms"]["value"] < 400
    assert got["dispatch.compiles_in_window"]["value"] == 0


def test_the_healthy_twin_has_no_probe_to_read(checkout):
    got = last_line(run_cell(checkout, TINY_TWIN, 1))["metrics"]
    # no series, no span: absent, not zero
    assert not {"fault.probes_per_agg", "fault.probe_ms",
                "fault.request_timeouts_per_update"} & set(got)
    # and nothing skipped: every round asks the four
    assert got["fault.tag_requests_skipped_per_agg"]["value"] == 0.0
    assert "quorum.read_tags_ms" in got


def test_a_tiny_run_with_the_crash_reports_the_end_to_end_metrics(checkout):
    got = last_line(run_cell(checkout, TINY, 0))["metrics"]
    assert {"setup_s", "ops_per_s", "agg_p50_ms", "point_p95_ms"} <= set(got)
    assert "agg_p95_ms" not in got
    # three timeouts of a user's 5 s, once, inside the load
    assert 5.0 < got["setup_s"]["value"]
