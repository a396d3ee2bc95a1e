"""What a configuration file says of its key sizes and its settings.

The accepted cells must read what they read before the harness learned
this: the golden digests below were computed on the tree before it
(b59009a) and are written here, not recomputed.
"""

import hashlib
import json
import os

import pytest

from yardstick import keys, reference
from yardstick.data import MSE, PSSE, Dataset
from yardstick.tests.test_run_tiny import make_checkout, run_cell

HERE = os.path.dirname(os.path.abspath(__file__))
CONFIGS = os.path.join(os.path.dirname(HERE), "configs")


@pytest.fixture
def tiny_conf():
    with open(os.path.join(HERE, "files", "config.tiny-bft4.json")) as f:
        return json.load(f)


def digest(x) -> str:
    return hashlib.sha256(json.dumps(x, sort_keys=True).encode()).hexdigest()


# ------------------------------------------------- (a) nothing that was moved

MODULI = "ba90f315c3c460197e8e5e1e939599b41fd7734bb7c3fb79d477b05db5ec5579"
GOLDEN = {   # seed: (rows, first three updates, plaintext total)
    7: ("e929cca16909ef1899f3cbfe3a310c39d0633e6926fa43c662e18f3c5363aa6b",
        "2776ae7b15442a7514ca5e502ea60a61a60c4c3d8c0f62d30f30ad1d3a46cd18",
        559497),
    2**31 + 5: (
        "579a447b5c4a0edf1d47c94a4ba76828d41aa52fe6189870ef345ae31aa28a00",
        "574e8f34816750d34610562be39224974f53ee59942d89a262ae79cd1af6da1c",
        477971),
}


@pytest.mark.parametrize("seed", sorted(GOLDEN))
def test_the_default_dataset_is_the_parents_bit_for_bit(seed):
    d = Dataset(seed, 16, 16, 32)
    rows, updates, total = GOLDEN[seed]
    assert digest({str(c): m for c, m in d.moduli.items()}) == MODULI
    assert digest(d.rows) == rows
    assert d.schemes[PSSE].base == total
    assert digest([d.begin_update(PSSE, 0), d.begin_update(MSE, 1),
                   d.begin_update(PSSE, 2)]) == updates


def test_the_sizes_a_file_names_are_the_defaults_of_old():
    a = Dataset(7, 4, 16, 32)
    b = Dataset(7, 4, 16, 32, paillier_bits=2048, rsa_bits=1024)
    assert a.moduli == b.moduli and a.rows == b.rows


def parents_build_config(conf: dict):
    """`build_config` as it stood at b59009a, copied."""
    from dds_tpu.utils.config import DDSConfig

    cfg = DDSConfig()
    n = int(conf["replicas"])
    cfg.replicas.endpoints = [f"replica-{i}" for i in range(n)]
    cfg.replicas.sentinent = [f"replica-{i}"
                              for i in range(n - int(conf["sentinels"]), n)]
    cfg.replicas.byz_quorum_size = int(conf["quorum"])
    cfg.replicas.byz_max_faults = int(conf["max_faults"])
    cfg.recovery.enabled = bool(conf["recovery"])
    cfg.proxy.port = 0
    cfg.proxy.crypto_backend = conf["crypto_backend"]
    return cfg


@pytest.mark.parametrize("name", ["ref8col-bft4", "ref8col-bft9",
                                  "p4096-bft4"])
def test_build_config_of_a_file_without_settings_is_the_parents(name):
    import dataclasses

    from yardstick import run as yr

    with open(os.path.join(CONFIGS, f"{name}.json")) as f:
        conf = json.load(f)
    assert "settings" not in conf
    assert (dataclasses.asdict(yr.build_config(conf))
            == dataclasses.asdict(parents_build_config(conf)))


def test_the_new_deployment_differs_from_bft4_by_its_key_alone():
    def read(name):
        with open(os.path.join(CONFIGS, f"{name}.json")) as f:
            return json.load(f)

    old, new = read("ref8col-bft4"), read("p4096-bft4")
    told = {"name", "source", "deployment", "reduced", "assumed"}
    differs = {k for k in set(old) | set(new)
               if old.get(k) != new.get(k)} - told
    assert differs == {"paillier_bits"}
    assert new["paillier_bits"] == 4096 and new["rsa_bits"] == 1024
    assert new["guarantees"] == old["guarantees"] and len(
        new["guarantees"]) == 4


# ------------------------------------------------------------ (b) the 4096 key


def test_a_dataset_under_paillier_4096():
    d = Dataset(11, 6, 16, 32, paillier_bits=4096)
    n2 = d.moduli[PSSE]
    assert n2.bit_length() == 8192 and -(-n2.bit_length() // 16) == 512
    assert d.moduli[MSE].bit_length() == 1024
    assert d.paillier.n == keys.PAILLIER[4096][0] * keys.PAILLIER[4096][1]
    col = d.current(PSSE)
    plains = [d.decrypt(PSSE, c) for c in col]
    assert all(0 <= p < 1 << 16 for p in plains)
    assert sum(plains) == d.schemes[PSSE].base
    assert d.paillier.decrypt(col[0]) == plains[0]   # textbook agrees with CRT
    new = int(d.begin_update(PSSE, 3))
    d.end_update(PSSE, 3, True)
    assert d.decrypt(PSSE, new) == plains[3] + (1 << 32)
    total = d.decrypt(PSSE, reference.fold(d.current(PSSE), n2))
    assert total == sum(plains) + (1 << 32)
    assert d.schemes[PSSE].count(total, 1) == 1


@pytest.mark.parametrize("sizes,named", [
    ({"paillier_bits": 3072}, "[2048, 4096]"),
    ({"rsa_bits": 2048}, "[1024]")])
def test_a_size_not_on_file_is_refused_by_name(sizes, named):
    with pytest.raises(KeyError) as e:
        Dataset(1, 2, 16, 32, **sizes)
    assert named in str(e.value) and str(next(iter(sizes.values()))) in str(
        e.value)


def test_run_turns_an_unknown_size_into_a_setup_error(tiny_conf):
    import argparse

    from yardstick import run as yr

    conf = dict(tiny_conf, paillier_bits=3072)
    cell = {"config_file": conf, "mix": {"groups": []}, "name": "x"}
    args = argparse.Namespace(seed=1, seconds=1.0, trace=0)
    with pytest.raises(yr.SetupError, match=r"sizes on file: \[2048, 4096\]"):
        yr.Run(args, cell, {"platform": "cpu", "kind": "cpu", "count": 1})


# ---------------------------------------------------------------- (c) settings


def test_settings_are_applied_after_the_named_keys(tiny_conf):
    from yardstick import run as yr

    conf = dict(tiny_conf, settings={
        "search.enabled": True, "proxy.coalesce_window": 0.004,
        "obs.fleet.batch_max": 8, "proxy.remote_peers": ["a:1"]})
    cfg, plain = yr.build_config(conf), yr.build_config(tiny_conf)
    assert cfg.search.enabled is True and plain.search.enabled is False
    assert cfg.proxy.coalesce_window == 0.004
    assert cfg.obs.fleet.batch_max == 8
    assert cfg.proxy.remote_peers == ["a:1"]
    # and nothing else moved
    cfg.search.enabled, cfg.proxy.coalesce_window = False, 0.002
    cfg.obs.fleet.batch_max, cfg.proxy.remote_peers = 32, []
    assert cfg == plain


@pytest.mark.parametrize("settings,says", [
    ({"search.enabeld": True}, "no 'search.enabeld'"),
    ({"serach.enabled": True}, "no 'serach.enabled'"),
    ({"search": True}, "is SearchConfig"),
    ({"proxy.port.x": 1}, "no 'proxy.port.x'"),
    ({"search.enabled": 1}, "is bool"),
    ({"proxy.coalesce_window": 1}, "is float"),
    ({"recovery": {"enabled": True}}, "is RecoveryConfig"),
    ({"replicas.byz_quorum_size": 2}, "named keys"),
    ({"replicas.addresses": {}}, "named keys"),
    ({"recovery.enabled": True}, "named keys"),
    ({"proxy.port": 8080}, "named keys"),
    ({"proxy.crypto_backend": "cpu"}, "named keys")])
def test_a_setting_that_cannot_stand_is_refused(tiny_conf, settings, says):
    from yardstick import run as yr

    with pytest.raises(yr.SetupError) as e:
        yr.build_config(dict(tiny_conf, settings=settings))
    assert says in str(e.value)


# ---------------------------------- (d) a whole tiny run under the 4096-bit key


@pytest.fixture(scope="module")
def line_4096(tmp_path_factory):
    """A scratch cell at `paillier_bits` 4096 with one setting, added to a
    scratch checkout as files and entries only."""
    tree = make_checkout(tmp_path_factory.mktemp("checkout4096"))
    with open(os.path.join(HERE, "files", "config.tiny-bft4.json")) as f:
        conf = json.load(f)
    conf.update(name="tiny-p4096", paillier_bits=4096, rows=24,
                settings={"proxy.coalesce_window": 0.003})
    with open(os.path.join(tree, "yardstick", "configs", "tiny-p4096.json"),
              "w") as f:
        json.dump(conf, f)
    with open(os.path.join(tree, "BENCHMARK.json")) as f:
        bench = json.load(f)
    bench["configs"].append({
        "name": "tiny-p4096", "source": "test",
        "file": "yardstick/configs/tiny-p4096.json", "reduced": ["rows"],
        "why": "test"})
    bench["workloads"].append({
        "name": "tiny4096.ycsba-sumall", "config": "tiny-p4096",
        "traffic": "ycsba-sumall", "chips": 1, "why": "test"})
    for metric in bench["end_to_end"] + bench["per_layer"]:
        if "tiny.ycsba-sumall" in metric.get("workloads", []):
            metric["workloads"].append("tiny4096.ycsba-sumall")
    with open(os.path.join(tree, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    done = run_cell(tree, "tiny4096.ycsba-sumall", 0)
    assert done.returncode == 0, done.stderr[-2000:]
    return done


def test_a_whole_tiny_run_under_paillier_4096_is_correct(line_4096):
    last = json.loads(line_4096.stdout.strip().splitlines()[-1])
    assert last["correct"] is True and last["failed"] == 0
    assert last["attempted"] > 0
    assert {"setup_s", "ops_per_s", "agg_p50_ms",
            "point_p95_ms"} <= set(last["metrics"])
    # each number compared beside its limit: last in the line, and the last
    # lines of standard error
    assert list(last)[-1] == "checks" and len(last["checks"]) >= 5
    assert all(c["value"] <= c["limit"] for c in last["checks"].values())
    tail = line_4096.stderr.strip().splitlines()[-len(last["checks"]):]
    assert all(t.startswith("yardstick: check ") for t in tail)


def test_the_deployment_line_prints_the_key_and_the_settings(line_4096):
    said = next(json.loads(ln.split("] ", 1)[1])
                for ln in line_4096.stdout.splitlines()
                if ln.startswith("[deployment]"))
    assert said["config"] == "tiny-p4096"
    assert said["paillier_bits"] == 4096 and said["rsa_bits"] == 1024
    assert said["limbs"] == {"2": 512, "3": 64}
    assert said["settings"] == {"proxy.coalesce_window": 0.003}
