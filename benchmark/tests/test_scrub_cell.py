"""``rados_write_4m_scrub``: ``rados bench`` writes on RS 8+3 with an
operator deep-scrubbing the pool round and round, rehearsed on the CPU
at 48 objects of 64 KiB and four callers.  ``correct`` has to come out
true for the program and false for the control; the generator has to
refuse a run whose stores hold a planted fault; the five per-layer
metrics the cell brings have something to read where the program
counts it and nothing on a program that does not.  No number here is a
measurement.
"""

import copy

import pytest

from benchmark import cells, reference_scrub, run, verify
from benchmark.generators import closed_loop, closed_loop_scrub
from benchmark.readers import trace_roofline_scrub
from benchmark.tests.conftest import tiny

SEED = 3_000_000_019          # the driver's seeds pass 2**31
CELL = "rados_write_4m_scrub"
NEW = ["scrub_bytes_per_user_byte", "scrub_findings_per_kop",
       "scrub_ms.chunk", "op_ms.scrub_wait", "device_roofline.scrub"]


def small(bench, device_fold: bool = False) -> dict:
    cell = tiny(cells.load_cell(CELL, bench))
    if device_fold:   # the CPU's auto is the host sweep
        cell["config"]["settings"]["osd_scrub_fold"] = "device"
    return cell


def test_the_cells_files_load(bench):
    cell = cells.load_cell(CELL, bench)
    c, t = cell["config"], cell["traffic"]
    twin = cells.load_cell("rados_write_4m", bench)
    assert cell["chips"] == c["chips"] == 1
    assert t["generator"] == "closed_loop_scrub"
    # the foreground is rados_write_4m's, key for key
    for key in twin["traffic"]:
        if key != "generator":
            assert t[key] == twin["traffic"][key], key
    for key in ("osds", "store", "failure_domain", "stripe_unit",
                "object_bytes", "concurrent_ops", "object_name_ring",
                "reduced"):
        assert c[key] == twin["config"][key], key
    # the pool is the twin's but for where a name lies (``assumed``):
    # the ring on all 32 PGs, a PG's names scattered over the ring
    pool = copy.deepcopy(twin["config"]["pool"])
    pool["profile"]["object_hash"] = "full"
    assert c["pool"] == pool
    # the chunk at the program's default, which is upstream's
    from ceph_tpu.utils.config import default_config
    assert default_config()["osd_scrub_chunk_max"] == 25
    assert c["settings"] == {"ec_backend": "jax",
                             "require_features": "scrub_under_writes",
                             "osd_scrub_chunk_max": 25}
    assert t["scrub"] == c["scrub"] == {
        "deep": True, "pool": "bench", "order": "pg_seed",
        "pgs_in_flight": 1}
    assert {"scrub_schedule", "scrub_in_flight", "scrub_chunk",
            "object_hash", "require_features"} <= set(c["assumed"])
    assert set(c["guarantees"]) == {"acknowledged_write", "read",
                                    "redundancy", "scrub"}
    for key, text in twin["config"]["guarantees"].items():
        assert c["guarantees"][key] == text
    entry = next(e for e in bench["configs"]
                 if e["name"] == "rados_ec83_4m_scrub")
    assert entry["reduced"] == ["object_name_ring"]
    assert len(entry["source"]) <= 200 and entry["source"] == c["source"]
    assert {m["name"] for m in cell["end_to_end"]} == {
        "client_MBps", "op_p90_ms", "setup_s"}
    names = {m["name"] for m in cell["per_layer"]}
    assert set(NEW) <= names
    assert {"pg_requeries_per_kop", "compiles_in_window",
            "lossy_drops_per_kop", "op_timeline_coverage",
            "device_idle_share.bw",
            "staged_bytes_per_user_byte.bw"} <= names
    # their counters would hold the verify launches beside the encodes
    assert not {"ec_roofline.bw", "ops_per_launch.bw",
                "ec_ops_per_client_op.bw"} & names
    by_name = {m["name"]: m for m in bench["per_layer"]}
    for n in NEW:
        assert by_name[n]["workloads"] == [CELL]
    plan = closed_loop_scrub.make_plan(
        dict(t, object_bytes=64, payload=dict(t["payload"], pool=3)), 1)
    assert plan.scrub == c["scrub"] and plan.objects == 256
    with pytest.raises(ValueError):
        closed_loop_scrub.make_plan(
            dict(t, scrub=dict(t["scrub"], pgs_in_flight=2)), 1)


def test_a_program_without_the_feature_refuses_the_file(bench):
    """What the parent does with this PR's files laid over it: a name
    under ``require_features`` that the program does not know refuses
    the configuration before anything boots."""
    from benchmark.cluster import Deployment
    from ceph_tpu.utils.config import FEATURES, ConfigError
    config = cells.load_cell(CELL, bench)["config"]
    assert config["settings"]["require_features"] in FEATURES
    config["settings"]["require_features"] += ",scrub_of_a_later_pr"
    with pytest.raises(ConfigError):
        Deployment(config)


def test_program_is_correct_and_control_is_not(bench, capfd):
    r = run.execute(small(bench), SEED, 3.0, False, require_chips=False,
                    control=True)
    assert r["failed"] == 0 and r["attempted"] > 8
    assert r["correct"] is True, r["compared"]
    assert all(c["value"] == 0 for c in r["compared"].values()
               if c["limit"] == 0)
    assert not verify.is_correct(r["control"]), r["control"]
    assert set(r["metrics"]) == {"client_MBps", "op_p90_ms", "setup_s"}
    said = capfd.readouterr().err
    assert "PG scrubs ended in the window" in said


def test_the_generator_refuses_a_planted_fault(bench):
    """A flipped byte in one stored shard: the window's scrubs find it
    and the run is no result.  The digests are the device program's
    (the chip's own rehearsal at 512 KiB streams is chip_smoke.py's
    cluster phase)."""
    from benchmark.cluster import Deployment
    cell = small(bench, device_fold=True)
    plan = closed_loop_scrub.make_plan(cell["traffic"], SEED)
    dep = Deployment(cell["config"])
    try:
        dep.write_many(((plan.name(k), plan.payload(k, 0))
                        for k in range(plan.objects)), 4)
        # nobody writes in this window: the fault stays where it is
        idle = copy.copy(plan)
        idle.inflight = 1
        idle.kinds = plan.kinds & False
        name = plan.name(5)
        seed, up = dep._pg(name)
        from ceph_tpu.msg.messages import PgId
        osd = dep.cluster.osds[up[9]]
        assert osd.inject.corrupt_object(
            osd.store, PgId(dep.pool_id, seed), name, shard=9, offset=77)
        with pytest.raises(closed_loop_scrub.ScrubFailed) as e:
            # the CPU compiles the verify program in its first scrub
            closed_loop_scrub.run(idle, dep, 6.0, grace=20.0)
        assert "digest_mismatch" in str(e.value) and name in str(e.value)
        # the reference agrees on what was planted, and where
        from ceph_tpu.osd.objectstore import CollectionId, ObjectId
        shards = {}
        for shard, o in enumerate(up):
            st = dep.cluster.osds[o].store
            oid = ObjectId(name, shard=shard)
            attrs = st.getattrs(CollectionId(dep.pool_id, seed), oid)
            shards[(name, shard)] = (
                st.read(CollectionId(dep.pool_id, seed), oid).to_bytes(),
                int(attrs["d"]), int(attrs["v"]))
        assert reference_scrub.expected_findings(
            shards, [(name, 9, "digest_mismatch")]) == [
                (name, 9, "digest_mismatch")]
    finally:
        dep.close()


def test_the_generator_refuses_a_quiet_pass_that_is_not_whole(
        bench, monkeypatch):
    """A pass that leaves a PG out, or whose digests no device program
    made, reports nothing too: the quiet pass is held to one scrub a PG
    and to the bytes the stores hold."""
    from benchmark.cluster import Deployment
    cell = small(bench, device_fold=True)
    plan = closed_loop_scrub.make_plan(cell["traffic"], SEED)
    dep = Deployment(cell["config"])
    try:
        dep.write_many(((plan.name(k), plan.payload(k, 0))
                        for k in range(plan.objects)), 4)
        pgs = int(cell["config"]["pool"]["pg_num"])
        streams, held = closed_loop_scrub.stored(dep, pgs)
        assert streams == plan.objects * 11
        assert held == plan.objects * plan.object_bytes * 11 // 8
        assert closed_loop_scrub.on_device(dep)
        idle = copy.copy(plan)
        idle.inflight = 1
        idle.kinds = plan.kinds & False
        closed_loop_scrub.run(idle, dep, 0.5, grace=20.0)   # whole: fine
        real = closed_loop_scrub.scrub_pg
        quiet = {"on": False}

        def lazy(d, seed):
            # the window's scrubs are real; the quiet pass skips PG 1
            if quiet["on"] and seed == 1:
                return []
            return real(d, seed)

        def counters():
            quiet["on"] = True    # the first reading is the quiet pass's
            return read()

        read = dep.counters
        monkeypatch.setattr(closed_loop_scrub, "scrub_pg", lazy)
        monkeypatch.setattr(dep, "counters", counters)
        with pytest.raises(closed_loop_scrub.ScrubFailed) as e:
            closed_loop_scrub.run(idle, dep, 0.5, grace=20.0)
        assert "not one whole pass" in str(e.value)
    finally:
        dep.close()


def test_the_new_metrics_read_the_program(bench):
    from benchmark.cluster import Deployment
    cell = small(bench, device_fold=True)
    specs = {m["name"]: m for m in cell["per_layer"]}
    plan = closed_loop_scrub.make_plan(cell["traffic"], SEED)
    dep = Deployment(cell["config"])
    try:
        dep.write_many(((plan.name(k), plan.payload(k, 0))
                        for k in range(plan.objects)), 4)
        before = dep.counters()
        ops, _t0, _t1 = closed_loop_scrub.run(plan, dep, 2.0, grace=20.0)
        after = dep.counters()
        health = dep.health()
    finally:
        dep.close()
    assert ops and all(op.ok for op in ops)
    assert all(v == 0 for v in health.values()), health
    c = {n: after[n] - before.get(n, 0.0) for n in after}
    c["client.ops"] = float(len(ops))
    c["client.user_bytes"] = float(len(ops) * plan.object_bytes)
    ctx = {"counters": c}
    assert run.read_metric(specs["scrub_findings_per_kop"], ctx) == 0.0
    assert run.read_metric(specs["pg_requeries_per_kop"], ctx) == 0.0
    pgs = int(cell["config"]["pool"]["pg_num"])
    stored = plan.objects * plan.object_bytes * 11 // 8
    # the quiet pass is one whole pass, kept under names of its own
    # and taken off the window's two scrub metrics
    assert c["bench_scrub.quiet_verified_bytes"] == stored
    assert c["bench_scrub.quiet_chunks"] >= pgs
    assert 0 < c["bench_scrub.quiet_chunk_seconds"] < \
        c["osd.scrub_chunk.sum_seconds"]
    for n in ("scrub_bytes_per_user_byte", "scrub_ms.chunk"):
        assert specs[n]["reader"] == "counter_ratio_net"
    assert run.read_metric(specs["scrub_bytes_per_user_byte"], ctx) == \
        pytest.approx((c["osd.scrub_verified_bytes"] - stored)
                      / c["client.user_bytes"])
    assert run.read_metric(specs["scrub_ms.chunk"], ctx) == pytest.approx(
        1000.0 * (c["osd.scrub_chunk.sum_seconds"]
                  - c["bench_scrub.quiet_chunk_seconds"])
        / (c["osd.scrub_chunk.count"] - c["bench_scrub.quiet_chunks"]))
    assert c["osd.scrub_chunk.count"] > c["bench_scrub.quiet_chunks"]
    assert c["osd.scrub_chunk_lock_wait.count"] == c["osd.scrub_chunk.count"]
    # every stored shard byte of a pass went through the device program
    assert c["osd.scrub_verified_bytes"] > stored
    assert c["osd.scrub_verified_bytes"] % (plan.object_bytes // 8) == 0
    wait = run.read_metric(specs["op_ms.scrub_wait"], ctx)
    assert wait is not None and wait >= 0.0
    # the phases stay a partition: a write's wait behind a chunk is
    # inside its obj_lock phase, and counted beside it
    assert c["osd.op_scrub_wait.sum_seconds"] <= \
        c["osd.op_phase_obj_lock.sum_seconds"] + 1e-6
    # the roofline: the writes' bytes and the scrubbed bytes over the peak
    ctx.update(needed_bytes=1e6, peaks={"hbm_bytes_per_s": 819e9},
               trace={"device_s": 1e-3, "busy_s": 1e-3, "window_s": 1.0})
    assert specs["device_roofline.scrub"]["reader"] == \
        "trace_roofline_scrub"
    assert run.read_metric(specs["device_roofline.scrub"], ctx) == \
        pytest.approx(100.0 * ((1e6 + c["osd.scrub_verified_bytes"])
                               / 819e9) / 1e-3)
    assert trace_roofline_scrub.read(dict(ctx, trace=None)) is None
    # a parent that has no such counter reports nothing and does not raise
    bare = {"counters": {"client.ops": 5.0, "client.user_bytes": 5.0,
                         "osd.op_timeline.count": 5.0},
            "needed_bytes": 1.0, "peaks": ctx["peaks"],
            "trace": ctx["trace"]}
    for n in NEW:
        assert run.read_metric(specs[n], bare) is None, n
