"""Every client operation has one timeline of marks (utils/tracked_op.py)
that closes where its reply is handed to the messenger, and whose
intervals are booked as op_phase_* / subop_phase_* TIME counters on the
OSD's registry.  These tests hold the instrument to its identities on a
MiniCluster EC pool; nothing here is a measurement."""

import time

import pytest

from ceph_tpu.msg.messages import MOSDOp, MSubWrite, PgId
from ceph_tpu.tools.vstart import MiniCluster
from ceph_tpu.utils.perf import global_perf
from ceph_tpu.utils.tracked_op import (MARKS, OP_PHASES, SUBOP_PHASES,
                                       OpTracker, phase_counters)
from tests.test_cluster import make_cfg

EAGAIN, EIO = -11, -5
PAYLOAD = bytes(range(256)) * 96          # 24 KiB


def _cluster(n_osds=4, **cfg):
    # plain MSubRead per sub-read: one message, one sub-op timeline
    return MiniCluster(n_osds=n_osds,
                       cfg=make_cfg(ec_read_window_us=0, **cfg)).start()


def _pool(client, backend="native", **profile):
    client.create_pool("p", kind="ec", pg_num=4,
                       ec_profile={"plugin": "tpu", "k": "2", "m": "1",
                                   "backend": backend, **profile})


def _time(c, name):
    """(sum_seconds, count) of a TIME counter over the live OSDs."""
    dumps = [o.perf.dump()[name] for o in c.osds.values()]
    return (sum(d["sum_seconds"] for d in dumps),
            sum(d["count"] for d in dumps))


def _count(c, name):
    return sum(o.perf.get(name) for o in c.osds.values())


def _objecter(name):
    d = global_perf().dump()["objecter"][name]
    return d["sum_seconds"], d["count"]


def _inflight(c):
    return [d for o in c.osds.values()
            for d in o.op_tracker.dump_ops_in_flight()]


def _assert_partition(c, kind, phases):
    total, n = _time(c, f"{kind}_timeline")
    parts = [_time(c, f"{kind}_phase_{p}") for p in phases]
    assert all(cnt == n for _s, cnt in parts), (kind, n, parts)
    assert sum(s for s, _c in parts) == pytest.approx(total, rel=1e-9)
    assert all(s >= 0 for s, _c in parts)
    return total, n


def _primary(c, client, oid):
    pool_id = client._pool_id("p")
    seed = client.osdmap.object_to_pg(pool_id, oid)
    up = client.osdmap.pg_to_up_osds(pool_id, seed)
    return c.osds[next(u for u in up if u is not None)], \
        PgId(pool_id, seed), up


def test_vocabulary_is_closed():
    assert set(MARKS.values()) == set(OP_PHASES)
    assert phase_counters("subop") == (
        "subop_phase_queue", "subop_phase_apply", "subop_timeline")
    assert set(SUBOP_PHASES) == {"queue", "apply"}


def test_phases_partition_the_timeline_for_ops_and_subops():
    """(a) writes, reads and a degraded read: the phase sums equal the
    timeline's sum, their counts the operations served — for client
    ops and for shard sub-ops — and nothing is left in flight."""
    c = _cluster(n_osds=4)
    try:
        client = c.client()
        _pool(client)
        lat0, n0 = _objecter("op_lat")
        names = [f"o{i}" for i in range(8)]
        for n in names:
            client.write_full("p", n, PAYLOAD)
        for n in names:
            assert client.read("p", n) == PAYLOAD
        served = _count(c, "op_w") + _count(c, "op_r")
        assert served == 2 * len(names)
        total, n_ops = _assert_partition(c, "op", OP_PHASES)
        # one timeline an attempt: an attempt bounced at the peering
        # gate of the fresh pool is an op served too
        assert n_ops >= served
        sub_total, n_sub = _assert_partition(c, "subop", SUBOP_PHASES)
        assert n_sub == _count(c, "subop_w") + _count(c, "subop_r") > 0
        # (e) the client's latency holds the OSD's timeline, op for op
        lat1, n1 = _objecter("op_lat")
        assert n1 - n0 == n_ops
        assert lat1 - lat0 >= total
        send, _n = _objecter("op_send")
        reply, _n = _objecter("op_reply")
        assert send > 0 and reply > 0
        # the handler's return no longer ends an op: op_lat_us times
        # the same interval as op_timeline
        hist = [o.perf.dump()["op_lat_us"] for o in c.osds.values()]
        assert sum(h["count"] for h in hist) == n_ops
        assert sum(h["sum"] for h in hist) / 1e6 == pytest.approx(
            total, rel=1e-6)
        assert "op_lat" not in c.osds[0].perf.dump()   # never fed: gone
        # a degraded read decodes: still one closed timeline each
        victim = _primary(c, client, names[0])[2][1]
        c.kill_osd(victim)
        c.wait_for_epoch(c.mon.osdmap.epoch, timeout=10)
        for n in names:
            assert client.read("p", n) == PAYLOAD
        _assert_partition(c, "op", OP_PHASES)
        _assert_partition(c, "subop", SUBOP_PHASES)
        assert _time(c, "op_phase_flush")[0] > 0       # the decodes
        assert _time(c, "op_phase_subread_wait")[0] > 0
        assert _inflight(c) == []
    finally:
        c.stop()


def test_failed_ops_close_their_timeline_once():
    """(b) EAGAIN at the peering gate and EIO from the sweep of a write
    whose acks never came: one closed timeline each."""
    c = _cluster(n_osds=4, osd_op_timeout=0.4)
    try:
        client = c.client()
        _pool(client)
        client.write_full("p", "obj", PAYLOAD)
        prim, pgid, up = _primary(c, client, "obj")
        _t, n0 = _time(c, "op_timeline")
        prim._peering[pgid] = {-1}
        try:
            tid = next(client._tids)
            reply = client._rpc(prim.name, MOSDOp(
                tid, client.name, pgid.pool, "obj", "read", 0, 0, b"",
                client.osdmap.epoch), tid)
        finally:
            prim._peering.pop(pgid, None)
        assert reply.result == EAGAIN
        _t, n1 = _time(c, "op_timeline")
        assert n1 == n0 + 1
        assert _inflight(c) == []
        last = prim.op_tracker.dump_historic_ops()[-1]
        assert [e["event"] for e in last["events"]][-2:] == [
            "commit_sent", "done"]
        # one shard swallows its sub-write: the sweep fails the op
        shard = c.osds[next(u for u in up if u != prim.osd_id)]
        shard._handlers[MSubWrite] = lambda conn, m: None
        tid = next(client._tids)
        reply = client._rpc(prim.name, MOSDOp(
            tid, client.name, pgid.pool, "obj", "write_full", 0, 0,
            PAYLOAD, client.osdmap.epoch), tid, timeout=10)
        assert reply.result == EIO
        _t, n2 = _time(c, "op_timeline")
        assert n2 == n1 + 1
        _assert_partition(c, "op", OP_PHASES)
        deadline = time.time() + 5
        while _inflight(c) and time.time() < deadline:
            time.sleep(0.05)
        # the swallowed sub-op never acknowledged: it is the one op
        # still in flight, on the shard that swallowed it
        left = _inflight(c)
        assert [d.get("kind") for d in left] == ["subop"], left
        assert not prim.op_tracker.dump_ops_in_flight()
    finally:
        c.stop()


def test_op_lat_covers_the_wait_for_acks():
    """(c) one shard's ack is late: the delay shows in op_lat_us and in
    op_phase_subwrite_wait, not in op_phase_prepare."""
    c = _cluster(n_osds=4)
    try:
        client = c.client()
        _pool(client)
        client.write_full("p", "obj", PAYLOAD)
        prim, _pgid, up = _primary(c, client, "obj")
        shard = c.osds[next(u for u in up if u != prim.osd_id)]
        orig = shard._do_sub_write

        def late(conn, m):
            time.sleep(0.25)
            orig(conn, m)

        shard._do_sub_write = late
        wait0 = prim.perf.dump()["op_phase_subwrite_wait"]["sum_seconds"]
        prep0 = prim.perf.dump()["op_phase_prepare"]["sum_seconds"]
        lat0 = prim.perf.dump()["op_lat_us"]["sum"]
        client.write_full("p", "obj", PAYLOAD[::-1])
        d = prim.perf.dump()
        assert d["op_phase_subwrite_wait"]["sum_seconds"] - wait0 >= 0.2
        assert d["op_phase_prepare"]["sum_seconds"] - prep0 < 0.15
        assert d["op_lat_us"]["sum"] - lat0 >= 0.2e6
        # the late shard's own timeline books the delay as apply
        assert shard.perf.dump()["subop_phase_apply"]["sum_seconds"] >= 0.2
    finally:
        c.stop()


def test_batch_wait_span_and_interval_share_their_readings():
    """(d) with trace_sample_rate=1 the ec-batch-wait span's duration
    equals the op's batch_wait interval to the nanosecond, and the
    ec-flush span starts where the wait ends."""
    c = _cluster(n_osds=4, trace_sample_rate=1.0)
    try:
        client = c.client()
        _pool(client, backend="jax", batch="on")
        prim, _pgid, _up = _primary(c, client, "obj")
        finished = []
        orig = prim.op_tracker._finish
        prim.op_tracker._finish = \
            lambda op, at=None: (finished.append(op), orig(op, at))[1]
        client.write_full("p", "obj", PAYLOAD)
        op = next(o for o in reversed(finished)
                  if o.desc == "write_full obj")
        marks = {name: at for at, name in op.events}
        spent = op.intervals()
        assert spent["batch_wait"] == marks["ec_taken"] - marks["ec_queued"]
        spans = [s for s in prim.tracer.dump(op.span.trace_id)]
        wait = next(s for s in spans if s["name"] == "ec-batch-wait")
        assert wait["dur_ns"] == spent["batch_wait"]
        flush = next(s for s in spans if s["name"] == "ec-flush")
        assert flush["start"] == pytest.approx(marks["ec_taken"] / 1e9,
                                               abs=1e-6)
        # the sub-writes' spans open on their sub-op's handler start
        assert spent["flush"] == marks["ec_done"] - marks["ec_taken"]
        assert sum(spent.values()) == marks["done"] - marks["initiated"]
    finally:
        c.stop()


def test_dump_historic_ops_shows_the_marks():
    """(f) the operator's view: the marks of a write and of a read, in
    time order, from one vocabulary."""
    c = _cluster(n_osds=4)
    try:
        client = c.client()
        _pool(client)
        client.write_full("p", "obj", PAYLOAD)
        prim, pgid, _up = _primary(c, client, "obj")
        prim._ec_cache.invalidate(pgid, "obj")   # the read fans out
        assert client.read("p", "obj") == PAYLOAD
        hist = prim.admin_command("dump_historic_ops")
        # the newest of each: the fresh pool may have bounced a first
        # attempt at its peering gate
        write = next(d for d in reversed(hist)
                     if d["description"] == "write_full obj")
        read = next(d for d in reversed(hist)
                    if d["description"] == "read obj")
        assert [e["event"] for e in write["events"]] == [
            "initiated", "queued_for_pg", "reached_pg",
            "waiting_for_obj_lock", "started", "ec_taken", "ec_done",
            "waiting_for_subops", "sub_op_commit_rec", "commit_sent",
            "done"]
        # (the first k replies may hold a parity shard: then it decodes)
        assert [e["event"] for e in read["events"]
                if e["event"] not in ("ec_taken", "ec_done")] == [
            "initiated", "queued_for_pg", "reached_pg", "started",
            "waiting_for_subreads", "sub_reads_rec", "commit_sent",
            "done"]
        for d in (write, read):
            ats = [e["at"] for e in d["events"]]
            assert ats == sorted(ats)
            assert all(e["event"] in MARKS for e in d["events"])
            assert d["age_seconds"] == pytest.approx(ats[-1] - ats[0],
                                                     abs=1e-5)
        sub = next(o for o in c.osds.values() if o is not prim
                   and o.op_tracker.dump_historic_ops())
        kinds = {d.get("kind") for d in sub.op_tracker.dump_historic_ops()}
        assert "subop" in kinds
    finally:
        c.stop()


def test_tracker_books_unknown_marks_as_prepare():
    from ceph_tpu.utils.perf import CounterType, PerfCounters
    pc = PerfCounters("t")
    pc.add("op_lat_us", CounterType.HISTOGRAM)
    tr = OpTracker(perf=pc)
    op = tr.create("x", start_ns=1_000)
    op.mark("queued_for_pg", 2_000)
    op.mark("somebody's mark", 5_000)
    op.mark("ec_taken", 4_000)            # appended late, earlier stamp
    op.finish()
    spent = op.intervals()
    assert spent["queue"] == 3_000        # 1000->2000, 2000->4000
    assert spent["flush"] == 1_000        # 4000->5000
    assert sum(spent.values()) == op.events[-1][0] - 1_000
    d = pc.dump()
    assert d["op_timeline"]["count"] == 1
    assert d["op_phase_obj_lock"] == {"sum_seconds": 0.0, "count": 1}
    op.finish()                           # once
    assert pc.dump()["op_timeline"]["count"] == 1
