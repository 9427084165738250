"""``rbd_randwrite_4k``: ``rbd bench`` random 4 KiB overwrites of an
image on an EC data pool, rehearsed on the CPU at 48 objects of 64 KiB
with all 16 callers kept.  ``correct`` has to come out true for the
program, false for the control (a stale version: caught by the
read-backs and by the stores) and false with the parity fold broken
underneath (caught by the stores alone); the three per-layer metrics
the cell brings have something to read.  No number here is a
measurement.
"""

import copy
from collections import Counter

import pytest

from benchmark import cells, run, verify
from benchmark.generators import rbd_bench
from benchmark.readers import trace_roofline_user

SEED = 3_000_000_019          # the driver's seeds pass 2**31
CELL = "rbd_randwrite_4k"
NEW = ["ec_roofline.ow", "ow_delta_plans_per_kop", "ow_shard_msgs_per_op"]


def small(cell: dict) -> dict:
    """48 objects of 64 KiB (four stripe rows each), eight read back,
    16 callers and 4 KiB writes as the cell has them."""
    cell = copy.deepcopy(cell)
    t = cell["traffic"]
    t["object_bytes"] = 65536
    t["image_bytes"] = 48 * 65536
    t["payload"] = {"pool": 7, "patches": 53}
    t["verify"]["objects"] = 8
    return cell


def _execute(bench, seconds=2.0, control=False):
    return run.execute(small(cells.load_cell(CELL, bench)), SEED, seconds,
                       False, require_chips=False, control=control)


def test_the_cells_files_load(bench):
    cell = cells.load_cell(CELL, bench)
    c, t = cell["config"], cell["traffic"]
    assert cell["chips"] == c["chips"] == 1
    assert t["generator"] == "rbd_bench"
    assert (t["io_bytes"], t["object_bytes"], t["image_bytes"],
            t["inflight"]) == (4096, 4 << 20, 1 << 30, 16)
    assert (t["io_type"], t["io_pattern"]) == ("write", "rand")
    assert c["pool"]["profile"] == {"plugin": "tpu", "k": "4", "m": "2",
                                    "object_hash": "full"}
    assert (c["osds"], c["stripe_unit"], c["pool"]["pg_num"]) == (
        12, 4096, 64)
    assert c["settings"] == {
        "ec_backend": "jax",
        "require_features": "ec_overwrite_on_device"}
    assert set(c["guarantees"]) == {"acknowledged_write", "read",
                                    "redundancy", "atomicity"}
    assert set(c["reduced"]) == {"io_total"}
    entry = next(e for e in bench["configs"] if e["name"] == "rbd_ec42_4k")
    assert entry["reduced"] == ["io_total"]
    assert {m["name"] for m in cell["end_to_end"]} == {
        "client_ops_per_s", "op_p90_ms", "setup_s"}
    names = [m["name"] for m in cell["per_layer"]]
    # the entries are there, wherever later PRs append theirs
    assert set(NEW) <= set(names)
    assert {"op_ms.subread_wait", "op_ms.subwrite_wait",
            "compiles_in_window", "ops_per_launch.kv",
            "staged_bytes_per_user_byte.kv", "device_idle_share.kv",
            "ec_ops_per_client_op.kv"} <= set(names)
    # work.write_bytes reckons a whole-object write's bytes: the cell
    # has a roofline of its own
    assert "ec_roofline.kv" not in names
    by_name = {m["name"]: m for m in bench["per_layer"]}
    for n in NEW:
        assert by_name[n]["workloads"] == [CELL]
        assert by_name[n]["moves"] == "client_ops_per_s"


def test_every_seed_sends_the_same_blocks_in_another_order(bench):
    t = small(cells.load_cell(CELL, bench))["traffic"]
    a, b = rbd_bench.make_plan(t, 1), rbd_bench.make_plan(t, SEED)
    n = t["image_bytes"] // t["io_bytes"]
    assert sorted(a.blocks) == sorted(b.blocks) == list(range(n))
    assert list(a.blocks) != list(b.blocks)
    assert list(a.blocks) == list(rbd_bench.make_plan(t, 1).blocks)
    assert (a.object_bytes, a.stored_bytes, a.objects) == (4096, 65536, 48)
    assert [a.name(0), a.name(47)] == ["obj0000000", "obj0000047"]
    # the model: version v is set-up's bytes with the object's first v
    # overwrites laid over them
    base = a.payload(3, 0)
    assert len(base) == 65536 and a.payload(3, 0) != b.payload(3, 0)
    a.sent[3] = [3 * 16 + 5, 3 * 16 + 0]
    v1, v2 = a.payload(3, 1), a.payload(3, 2)
    assert v1 == base[:5 * 4096] + a.patch(53) + base[6 * 4096:]
    assert v2 == a.patch(48) + v1[4096:]
    assert len({base, v1, v2}) == 3
    with pytest.raises(ValueError):
        rbd_bench.make_plan(dict(t, io_pattern="seq"), 1)


def test_program_is_correct_and_control_is_not(bench):
    r = _execute(bench, control=True)
    assert r["failed"] == 0 and r["attempted"] > 16
    assert r["correct"] is True, r["compared"]
    assert all(c["value"] == 0 for c in r["compared"].values()
               if c["limit"] == 0)
    assert r["compared"]["readbacks_compared"]["value"] >= 8
    assert r["compared"]["shards_compared"]["value"] >= 48
    # a write acknowledged and not applied: the read-back lacks it, and
    # so do the data shard and both parity shards of its row
    ctl = r["control"]
    assert not verify.is_correct(ctl), ctl
    # (where an object's last two writes were in flight together
    # either may be the last, so the older one is no fault there)
    assert ctl["readbacks_wrong"]["value"] > \
        ctl["readbacks_compared"]["value"] // 2
    assert ctl["shards_wrong"]["value"] >= \
        3 * ctl["readbacks_wrong"]["value"]
    assert set(r["metrics"]) == {"client_ops_per_s", "op_p90_ms",
                                 "setup_s"}


def test_fault_delta_dropped_on_one_parity_shard(monkeypatch, bench):
    """The parity fold broken underneath: the second parity shard
    acknowledges its deltas and applies none.  Read-backs never touch
    parity; only the comparison with the stores shows it."""
    from ceph_tpu.osd.daemon import OSDDaemon
    real = OSDDaemon._apply_partial

    def dropped(self, pgid, oid, shard, extents, version, **kw):
        if kw.get("xor") and shard == 5 and oid.startswith("obj"):
            extents = [(off, bytes(len(d))) for off, d in extents]
        return real(self, pgid, oid, shard, extents, version, **kw)
    monkeypatch.setattr(OSDDaemon, "_apply_partial", dropped)
    r = _execute(bench)
    assert r["failed"] == 0
    assert r["correct"] is False
    wrong = {n: c["value"] for n, c in r["compared"].items()
             if c["limit"] == 0 and c["value"]}
    assert set(wrong) == {"shards_wrong"}, wrong
    assert wrong["shards_wrong"] == \
        r["compared"]["readbacks_compared"]["value"]


def test_the_new_metrics_read_the_program(bench):
    from benchmark.cluster import Deployment
    cell = small(cells.load_cell(CELL, bench))
    specs = {m["name"]: m for m in cell["per_layer"]}
    plan = rbd_bench.make_plan(cell["traffic"], SEED)
    dep = Deployment(cell["config"])
    try:
        dep.write_many(((plan.name(k), plan.payload(k, 0))
                        for k in range(plan.objects)), 8)
        before = dep.counters()
        ops, _t0, _t1 = rbd_bench.run(plan, dep, 1.0)
        after = dep.counters()
        health = dep.health()
    finally:
        dep.close()
    assert ops and all(op.ok for op in ops)
    # an object's versions are in the order of its sends
    by_key = Counter()
    for op in ops:
        by_key[op.key] += 1
        assert op.version == by_key[op.key]
    ctx = {"counters": {n: after[n] - before.get(n, 0.0) for n in after}}
    ctx["counters"]["client.ops"] = float(len(ops))
    assert run.read_metric(specs["ow_delta_plans_per_kop"], ctx) == 1000.0
    # a sub-write to each of the five other shards of 4+2 (every shard
    # of the up set takes the new version); this small image lies in
    # the extent caches whole, so no overwrite sends a sub-read (at
    # the cell's size nearly every one does: at most 6 a write)
    assert run.read_metric(specs["ow_shard_msgs_per_op"], ctx) == 5.0
    assert ctx["counters"]["osd.ec_ow_old_cached"] == len(ops)
    assert ctx["counters"]["osd.ec_plan_rmw"] == 0
    assert ctx["counters"]["osd.op_phase_subwrite_wait.sum_seconds"] > 0
    assert all(v == 0 for v in health.values()), health
    # the roofline: 3 B needed a user byte (the delta in, two parity
    # deltas out), over the peak, over the trace's device seconds
    ctx.update(user_bytes=1000 * 4096, peaks={"hbm_bytes_per_s": 819e9},
               trace={"device_s": 1e-3, "busy_s": 1e-3, "window_s": 1.0})
    assert specs["ec_roofline.ow"]["reader"] == "trace_roofline_user"
    assert run.read_metric(specs["ec_roofline.ow"], ctx) == pytest.approx(
        100.0 * (3 * 1000 * 4096 / 819e9) / 1e-3)
    assert trace_roofline_user.read(dict(ctx, trace=None), 3.0) is None
    # a parent that has no such counter reports nothing and does not raise
    bare = {"counters": {"client.ops": 5.0}}
    assert run.read_metric(specs["ow_delta_plans_per_kop"], bare) is None
    assert run.read_metric(specs["ow_shard_msgs_per_op"], bare) is None
