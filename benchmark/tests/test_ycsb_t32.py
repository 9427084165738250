"""``ycsb_a_1k_t32``: YCSB-A with 32 callers, rehearsed on the CPU at 48
records with all 32 callers kept (``tiny()`` would cut them to four, and
the callers are what the cell is about).  ``correct`` has to come out
true for the program and false for the control, and the two per-layer
metrics the cell brings have something to read.  No number here is a
measurement.
"""

import copy

from benchmark import cells, run, verify
from benchmark.cluster import Deployment

SEED = 3_000_000_019          # the driver's seeds pass 2**31
CELL = "ycsb_a_1k_t32"
NEW = ["obj_lock_waits_per_kop", "pg_requeries_per_kop"]


def small(cell: dict) -> dict:
    """48 records of 1 KiB, eight read back, 32 callers as the cell has
    them."""
    cell = copy.deepcopy(cell)
    t = cell["traffic"]
    t["objects"] = 48
    t["payload"]["pool"] = 53
    t["verify"]["objects"] = 8
    return cell


def test_the_cell_is_the_one_caller_cell_with_32_callers(bench):
    cell = cells.load_cell(CELL, bench)
    one = cells.load_cell("ycsb_a_1k", bench)
    assert cell["traffic"]["inflight"] == 32
    assert cell["chips"] == 1
    assert {k: v for k, v in cell["traffic"].items() if k != "inflight"} \
        == {k: v for k, v in one["traffic"].items() if k != "inflight"}
    changed = {k for k in cell["config"]
               if cell["config"][k] != one["config"].get(k)}
    assert changed == {"source", "concurrent_ops", "guarantees", "assumed",
                       "settings", "pool"}
    # the pool is the one-caller cell's but for the hash that places its
    # records (every byte of a name, so that they lie on all its PGs) and
    # twice the PGs, so that no OSD leads a quarter of the records
    assert cell["config"]["pool"] == dict(
        one["config"]["pool"], pg_num=64, profile=dict(
            one["config"]["pool"]["profile"], object_hash="full"))
    # defaults but for the back-end and the op workers (upstream's 16
    # for flash); ``require_features`` changes no behaviour: a program
    # without the order refuses the file
    assert cell["config"]["settings"] == dict(
        one["config"]["settings"], require_features="object_rw_order",
        osd_op_num_shards=16)
    assert {"pg_num", "object_hash", "settings", "clients"} \
        <= set(cell["config"]["assumed"])
    assert "order" in cell["config"]["guarantees"]
    assert {k: v for k, v in cell["config"]["guarantees"].items()
            if k != "order"} == one["config"]["guarantees"]
    assert cell["config"]["reduced"] == {}
    assert {m["name"] for m in cell["end_to_end"]} == {
        "client_ops_per_s", "op_p90_ms", "setup_s"}
    names = [m["name"] for m in cell["per_layer"]]
    assert names[-2:] == NEW
    # every per-layer metric of the one-caller cell, and the two new ones
    assert names[:-2] == [m["name"] for m in one["per_layer"]
                          if m["name"] not in NEW]
    for m in bench["per_layer"][-2:]:
        assert sorted(m["workloads"]) == sorted(
            w["name"] for w in bench["workloads"])


def test_program_is_correct_and_control_is_not(bench):
    cell = small(cells.load_cell(CELL, bench))
    assert cell["traffic"]["inflight"] == 32
    r = run.execute(cell, SEED, 2.0, False, require_chips=False,
                    control=True)
    assert r["failed"] == 0 and r["attempted"] > 32
    assert r["correct"] is True, r["compared"]
    assert all(c["value"] == 0 for c in r["compared"].values()
               if c["limit"] == 0)
    assert r["compared"]["answers_compared"]["value"] > 0
    assert r["compared"]["shards_compared"]["value"] > 0
    assert not verify.is_correct(r["control"]), r["control"]
    assert set(r["metrics"]) == {"client_ops_per_s", "op_p90_ms",
                                 "setup_s"}


def test_both_new_metrics_read_the_program(bench):
    cell = small(cells.load_cell(CELL, bench))
    specs = {m["name"]: m for m in cell["per_layer"]}
    dep = Deployment(cell["config"])
    try:
        payload = bytes(range(256)) * 4             # 1 KiB
        names = [f"obj{i:02d}" for i in range(2)]
        dep.write_many(((n, payload) for n in names), 2)     # warm
        before = dep.counters()
        # sixteen writers and sixteen readers on two records
        dep.write_many(((names[i % 2], payload) for i in range(64)), 16)
        assert dep.read_many([names[i % 2] for i in range(64)], 16) \
            == [payload] * 64
        after = dep.counters()
    finally:
        dep.close()
    ctx = {"counters": {n: after[n] - before.get(n, 0.0) for n in after}}
    got = {n: run.read_metric(specs[n], ctx) for n in NEW}
    assert got["obj_lock_waits_per_kop"] > 0, got
    # no OSD went away and no map changed: no inventory round
    assert got["pg_requeries_per_kop"] == 0, got
    assert ctx["counters"]["osd.ec_read_torn"] == 0
    # every write reaches its primary (a read may be served under a lease)
    assert ctx["counters"]["osd.op_timeline.count"] >= 64
    # a parent that has no such counter reports nothing and does not raise
    bare = {"counters": {"osd.op_timeline.count": 5.0}}
    assert all(run.read_metric(specs[n], bare) is None for n in NEW)
