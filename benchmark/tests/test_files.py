"""The harness is driven by data: every file loads, ``BENCHMARK.json``
and the files agree, and a cell, a configuration, a traffic mix and a
metric added as NEW files are found without editing one that is there.
"""

import json
import re
import shutil
from pathlib import Path

import numpy as np
import pytest

from benchmark import cells
from benchmark.generators import closed_loop

HERE = Path(cells.HERE)
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


def _files(sub):
    return sorted((HERE / sub).glob("*.json"))


@pytest.mark.parametrize("path", _files("configs"), ids=lambda p: p.stem)
def test_config_states_its_deployment(path):
    c = json.loads(path.read_text())
    for key in ("source", "chips", "osds", "store", "pool", "stripe_unit",
                "settings", "guarantees", "assumed", "reduced"):
        assert key in c, key
    assert c["chips"] in (1, 4)
    assert c["settings"] == {"ec_backend": "jax"}   # defaults otherwise
    assert len(c["source"]) <= 200
    assert {"acknowledged_write", "read"} <= set(c["guarantees"])
    assert all(k in c for k in c["reduced"])


@pytest.mark.parametrize("path", _files("traffic"), ids=lambda p: p.stem)
def test_traffic_is_parameters_of_one_generator(path, bench):
    t = json.loads(path.read_text())
    assert (HERE / "generators" / f"{t['generator']}.py").exists()
    users = [w for w in bench["workloads"] if w["traffic"] == path.stem]
    assert users, "a traffic mix no cell uses"
    for w in users:
        conf = next(c for c in bench["configs"] if c["name"] == w["config"])
        resolved = cells.resolve(t, json.loads(
            (HERE.parent / conf["file"]).read_text()))
        small = dict(resolved, object_bytes=64,
                     payload=dict(resolved["payload"], pool=3))
        plan = closed_loop.make_plan(small, seed=3)
        assert plan.objects == resolved["objects"]
        assert resolved["verify"]["control"] in ("stale_version",
                                                 "no_decode")


@pytest.mark.parametrize("path", _files("metrics"), ids=lambda p: p.stem)
def test_metric_names_a_reader(path):
    m = json.loads(path.read_text())
    assert m["name"] == path.stem
    assert (HERE / "readers" / f"{m['reader']}.py").exists()
    assert NAME.match(m["name"]) and UNIT.match(m["unit"])


def test_manifest_and_files_agree(bench):
    assert bench["paths"] == ["benchmark"]
    assert bench["command"][:3] == ["python3", "-m", "benchmark.run"]
    e2e = {m["name"] for m in bench["end_to_end"]}
    assert "setup_s" in e2e
    cellnames = {w["name"] for w in bench["workloads"]}
    for m in bench["per_layer"]:
        spec = json.loads((HERE / "metrics" / f"{m['name']}.json")
                          .read_text())
        for key in ("layer", "unit", "moves", "source", "better"):
            assert spec[key] == m[key], (m["name"], key)
        assert m["moves"] in e2e
        assert set(m["workloads"]) <= cellnames
        assert set(m["workloads"]) <= set(spec["workloads"])
    for c in bench["configs"]:
        conf = json.loads((HERE.parent / c["file"]).read_text())
        assert conf["source"] == c["source"]
        assert sorted(conf["reduced"]) == c["reduced"]
    for w in bench["workloads"]:
        cell = cells.load_cell(w["name"], bench)
        assert cell["chips"] == w["chips"]
        assert cell["per_layer"] and len(cell["end_to_end"]) >= 2
        assert len(w["why"]) <= 200
    four = sum(w["chips"] == 4 for w in bench["workloads"])
    assert four <= max(1, len(bench["workloads"]) // 2)


def test_every_seed_gets_the_same_operations_in_another_order():
    t = json.loads((HERE / "traffic" / "ycsb_a.json").read_text())
    t = cells.resolve(t, json.loads(
        (HERE / "configs" / "ycsb_ec42_1k.json").read_text()))
    t = dict(t, object_bytes=64, payload=dict(t["payload"], pool=3))
    a = closed_loop.make_plan(t, seed=1)
    b = closed_loop.make_plan(t, seed=3_000_000_019)   # over 2**31
    assert int(a.kinds.sum()) == int(b.kinds.sum()) == len(a.kinds) // 2
    assert sorted(np.bincount(a.keys)) == sorted(np.bincount(b.keys))
    assert (a.keys != b.keys).any()
    assert a.payload(5, 1) != b.payload(5, 1)
    assert a.payload(5, 1) != a.payload(5, 2)


def test_new_files_are_found_without_editing_old_ones(tmp_path, bench):
    root = tmp_path / "benchmark"
    for sub in ("configs", "traffic", "metrics"):
        shutil.copytree(HERE / sub, root / sub)
    before = {p: p.read_bytes() for p in root.rglob("*.json")}
    conf = json.loads((root / "configs" / "rados_ec83_4m.json").read_text())
    conf["pool"]["profile"] = {"plugin": "tpu", "k": "6", "m": "3"}
    (root / "configs" / "new_conf.json").write_text(json.dumps(conf))
    mix = json.loads((root / "traffic" / "write_4m.json").read_text())
    mix["mix"] = {"read": 0.3, "write": 0.7}
    (root / "traffic" / "new_mix.json").write_text(json.dumps(mix))
    (root / "metrics" / "new_metric.json").write_text(json.dumps({
        "name": "new_metric", "layer": "staging", "unit": "B/op",
        "better": "lower", "source": "program_counter",
        "moves": "client_MBps", "workloads": ["new_cell"],
        "reader": "counter_ratio",
        "args": {"num": ["ec_kernels.ec_stage_h2d_bytes"],
                 "den": ["client.ops"]}}))
    grown = json.loads(json.dumps(bench))
    grown["configs"].append({"name": "new_conf", "source": conf["source"],
                             "file": "benchmark/configs/new_conf.json",
                             "reduced": ["object_name_ring"], "why": "x"})
    grown["workloads"].append({"name": "new_cell", "config": "new_conf",
                               "traffic": "new_mix", "chips": 1,
                               "why": "x"})
    grown["per_layer"].append({"name": "new_metric", "unit": "B/op",
                               "better": "lower",
                               "source": "program_counter",
                               "layer": "staging", "moves": "client_MBps",
                               "workloads": ["new_cell"]})
    for m in grown["end_to_end"]:
        if m["name"] == "client_MBps":
            m["workloads"].append("new_cell")
    cell = cells.load_cell("new_cell", grown, root=root)
    assert cell["config"]["pool"]["profile"]["k"] == "6"
    assert cell["traffic"]["mix"] == {"read": 0.3, "write": 0.7}
    assert [m["name"] for m in cell["per_layer"]] == [
        m["name"] for m in grown["per_layer"]
        if "new_cell" in m.get("workloads", ["new_cell"])]
    assert "new_metric" in [m["name"] for m in cell["per_layer"]]
    assert {m["name"] for m in cell["end_to_end"]} == {
        "client_MBps", "op_p90_ms", "setup_s"}
    assert all(p.read_bytes() == b for p, b in before.items())
