"""Every client operation has one timeline of marks (utils/tracked_op.py)
that closes where its reply is handed to the messenger, and whose
intervals are booked as op_phase_* / subop_phase_* TIME counters on the
OSD's registry.  These tests hold the instrument to its identities on a
MiniCluster EC pool; nothing here is a measurement."""

import threading
import time

import pytest

from ceph_tpu.msg.messages import MOSDOp, MSubWrite, PgId
from ceph_tpu.osd.objectstore import MemStore
from ceph_tpu.tools.vstart import MiniCluster
from ceph_tpu.utils.perf import global_perf
from ceph_tpu.utils.tracked_op import (MARKS, OP_PHASES, SUBOP_PHASES,
                                       OpTracker, phase_counters)
from tests.test_cluster import make_cfg

EAGAIN, EIO = -11, -5
PAYLOAD = bytes(range(256)) * 96          # 24 KiB


class DurableMemStore(MemStore):
    """A MemStore that declares a durable commit: an OSD engages the
    group-commit pipeline on it, as on FileStore or BlueStore."""

    durable_commit = True


def _cluster(n_osds=4, durable=False, **cfg):
    """``durable``: every OSD on a DurableMemStore, so its acks ride the
    store's kv-sync and finisher threads; else plain memstore, whose
    acks leave inside their handler."""
    # plain MSubRead per sub-read: one message, one sub-op timeline
    c = MiniCluster(n_osds=0 if durable else n_osds,
                    cfg=make_cfg(ec_read_window_us=0, **cfg)).start()
    if durable:
        for i in range(n_osds):
            c.add_osd(i, store=DurableMemStore())
        c.wait_for_up(n_osds)
    return c


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


# ------------------------------------------------ where a sub-op's apply goes
APPLY_PARTS = ("subop_apply_handler", "subop_apply_commit",
               "subop_apply_finish")


def _subops(c, prefix):
    """The closed sub-op timelines whose description starts ``prefix``."""
    return [op for o in c.osds.values()
            for op in list(o.op_tracker._history)
            if op.kind == "subop" and op.desc.startswith(prefix)]


def _reading(op, mark):
    return next(t for t, e in op.events if e == mark)


def _assert_parts_add_up(c):
    apply_s, n = _time(c, "subop_phase_apply")
    parts = [_time(c, name) for name in APPLY_PARTS]
    assert all(cnt == n for _s, cnt in parts), (n, parts)
    assert sum(s for s, _c in parts) == pytest.approx(apply_s, rel=1e-9)
    assert all(s >= 0 for s, _c in parts)
    return [s for s, _c in parts]


def test_async_subwrite_apply_splits_at_return_and_durability():
    """A sub-write on the async store: its apply cut where the handler
    returns, where the kv-sync thread finds its batch durable and where
    the finisher hands the ack to the messenger; the three parts equal
    the apply to the nanosecond, sub-op for sub-op, and the counters'
    sums the phase's."""
    c = _cluster(n_osds=4, durable=True)
    try:
        client = c.client()
        _pool(client)
        for i in range(6):
            client.write_full("p", f"o{i}", PAYLOAD)
        writes = _subops(c, "MSubWrite")
        assert len(writes) >= 12          # two remote shards a write
        for op in writes:
            apply_ns = op.intervals()["apply"]
            parts = op.apply_parts(apply_ns)
            assert sum(parts) == apply_ns
            assert all(p >= 0 for p in parts), parts
            reached = _reading(op, "reached_pg")
            applied = _reading(op, "sub_op_applied")
            committed = _reading(op, "sub_op_committed")
            assert reached <= applied <= op.end_ns
            assert committed <= _reading(op, "commit_sent") == op.end_ns
            if committed >= applied:
                assert parts == (applied - reached, committed - applied,
                                 op.end_ns - committed)
        handler, commit, finish = _assert_parts_add_up(c)
        assert handler > 0 and commit + finish > 0
    finally:
        c.stop()


def test_reply_inside_the_handler_books_all_apply_to_it():
    """Sync store mode, and every sub-read: the ack leaves inside the
    handler, so the handler part is the whole apply."""
    c = _cluster(n_osds=4, store_sync_commit="on")
    try:
        client = c.client()
        _pool(client)
        for i in range(4):
            client.write_full("p", f"o{i}", PAYLOAD)
        for i in range(4):
            prim, pgid, _up = _primary(c, client, f"o{i}")
            prim._ec_cache.invalidate(pgid, f"o{i}")
            assert client.read("p", f"o{i}") == PAYLOAD
        assert _subops(c, "MSubWrite") and _subops(c, "MSubRead")
        for op in _subops(c, "MSub"):
            assert "sub_op_applied" not in dict(
                (e, t) for t, e in op.events)
            apply_ns = op.intervals()["apply"]
            assert op.apply_parts(apply_ns) == (apply_ns, 0, 0)
        handler, commit, finish = _assert_parts_add_up(c)
        assert commit == finish == 0
        assert handler == pytest.approx(_time(c, "subop_phase_apply")[0],
                                        rel=1e-9)
    finally:
        c.stop()
    c = _cluster(n_osds=4, durable=True)  # the async store's sub-reads
    try:
        client = c.client()
        _pool(client)
        client.write_full("p", "obj", PAYLOAD)
        prim, pgid, _up = _primary(c, client, "obj")
        prim._ec_cache.invalidate(pgid, "obj")
        assert client.read("p", "obj") == PAYLOAD
        reads = _subops(c, "MSubRead")
        assert reads
        for op in reads:
            apply_ns = op.intervals()["apply"]
            assert op.apply_parts(apply_ns) == (apply_ns, 0, 0)
    finally:
        c.stop()


def _reply_queue(prim):
    d = prim.perf.dump()["op_reply_queue"]
    return d["sum_seconds"], d["count"]


def _newest(prim, desc):
    return next(op for op in reversed(list(prim.op_tracker._history))
                if op.desc == desc)


def test_completing_reply_queue_is_one_sample_inside_its_wait():
    """op_reply_queue: one sample for each write and each EC read that
    fans out, booked by the reply that ends the wait, never more than
    the op's subwrite_wait / subread_wait."""
    c = _cluster(n_osds=4)
    try:
        client = c.client()
        _pool(client)
        client.write_full("p", "warm", PAYLOAD)
        for i in range(5):
            oid = f"o{i}"
            prim, pgid, _up = _primary(c, client, oid)
            s0, n0 = _reply_queue(prim)
            client.write_full("p", oid, PAYLOAD)
            s1, n1 = _reply_queue(prim)
            assert n1 == n0 + 1
            wait = _newest(prim, f"write_full {oid}").intervals()
            assert 0 <= (s1 - s0) * 1e9 <= wait["subwrite_wait"] + 1
            prim._ec_cache.invalidate(pgid, oid)
            assert client.read("p", oid) == PAYLOAD
            s2, n2 = _reply_queue(prim)
            assert n2 == n1 + 1
            wait = _newest(prim, f"read {oid}").intervals()
            assert 0 <= (s2 - s1) * 1e9 <= wait["subread_wait"] + 1
        # over the cluster: as many samples as ops whose wait ended
        total = sum(_reply_queue(o)[1] for o in c.osds.values())
        fanned = sum(1 for o in c.osds.values()
                     for op in list(o.op_tracker._history)
                     if op.kind == "op" and any(
                         e in ("sub_op_commit_rec", "sub_reads_rec")
                         for _t, e in op.events))
        assert total == fanned >= 11
    finally:
        c.stop()


def test_primary_own_commit_ack_books_its_handoff_queue(monkeypatch):
    """Shard -2, the primary's own commit ack, completes the wait when
    its barrier comes last: its "receive" stamp is the finisher's
    hand-off to the scheduler, and it books the one sample (a
    replicated write counts its own commit as an ack)."""
    from ceph_tpu.osd import daemon as daemon_mod
    c = _cluster(n_osds=4, durable=True)
    try:
        client = c.client()
        client.create_pool("p", size=3, pg_num=4)
        client.write_full("p", "obj", PAYLOAD)
        prim, _pgid, _up = _primary(c, client, "obj")
        orig_ack = prim._local_commit_ack
        late = []

        def late_ack(tid, pgid):
            # the local barrier registers after the remote acks are in
            t = threading.Timer(0.3, orig_ack, (tid, pgid))
            late.append(t)
            t.start()

        seen = []
        orig_reply = prim._handle_sub_write_reply

        def reply(conn, m):
            seen.append((m.shard, conn))
            orig_reply(conn, m)

        booked = []
        orig_book = daemon_mod._reply_queued

        def book(pending, wait, rq):
            booked.append(rq)
            orig_book(pending, wait, rq)

        prim._local_commit_ack = late_ack
        prim._handlers[daemon_mod.MSubWriteReply] = reply
        prim._handle_sub_write_reply = reply
        monkeypatch.setattr(daemon_mod, "_reply_queued", book)
        s0, n0 = _reply_queue(prim)
        client.write_full("p", "obj", PAYLOAD[::-1])
        for t in late:
            t.join()
        shard, conn = seen[-1]
        assert shard == -2 and isinstance(conn, daemon_mod._Handoff)
        assert len(booked) == 1 and booked[0][0] == conn.recv_stamp > 0
        s1, n1 = _reply_queue(prim)
        assert n1 == n0 + 1
        wait = _newest(prim, "write_full obj").intervals()
        assert 0 <= (s1 - s0) * 1e9 <= wait["subwrite_wait"] + 1
        assert wait["subwrite_wait"] >= 0.25e9
    finally:
        c.stop()


def test_read_coalesce_wait_counts_each_fetch_sent():
    """ec_read_coalesce_wait: one sample for each fetch the aggregator
    hands to the messenger, from its queueing to the send."""
    c = MiniCluster(n_osds=4, cfg=make_cfg(ec_read_window_us=2000)).start()
    try:
        client = c.client()
        _pool(client)
        names = [f"o{i}" for i in range(4)]
        for n in names:
            client.write_full("p", n, PAYLOAD)
        f0 = _count(c, "ec_read_fetches")
        w0, n0 = _time(c, "ec_read_coalesce_wait")
        for n in names:
            prim, pgid, _up = _primary(c, client, n)
            prim._ec_cache.invalidate(pgid, n)
            assert client.read("p", n) == PAYLOAD
        fetches = _count(c, "ec_read_fetches") - f0
        w1, n1 = _time(c, "ec_read_coalesce_wait")
        assert fetches >= len(names)
        assert n1 - n0 == fetches
        assert w1 - w0 > 0
    finally:
        c.stop()


def test_phase_counters_read_as_before_on_a_scripted_subop():
    """The new marks move nothing the phases book: one scripted sub-op
    with them and one without read the same queue, apply and timeline;
    the parts cut the apply at the two readings; a mark read after the
    close lies outside the timeline."""
    from ceph_tpu.utils.perf import CounterType, PerfCounters
    dumps = []
    for cuts in ((), (("sub_op_applied", 4_000),
                      ("sub_op_committed", 6_000))):
        pc = PerfCounters("t")
        pc.add("op_lat_us", CounterType.HISTOGRAM)
        tr = OpTracker(perf=pc)
        op = tr.create("MSubWrite x", start_ns=1_000, kind="subop")
        op.mark("queued_for_pg", 2_000)
        op.mark("reached_pg", 3_000)
        for name, at in cuts:
            op.mark(name, at)
        op.finish(op.mark("commit_sent", 9_000))
        op.mark("sub_op_applied", 9_500)      # read after the close
        assert op.intervals() == {"queue": 2_000, "apply": 6_000}
        assert op.age() == 8_000 / 1e9
        dumps.append(pc.dump())
    plain, cut = dumps
    for name in phase_counters("subop"):
        assert plain[name] == cut[name]
    assert cut["subop_phase_apply"]["sum_seconds"] == 6_000 / 1e9
    assert [cut[n]["sum_seconds"] for n in APPLY_PARTS] == [
        1_000 / 1e9, 2_000 / 1e9, 3_000 / 1e9]
    assert [plain[n]["sum_seconds"] for n in APPLY_PARTS] == [
        6_000 / 1e9, 0.0, 0.0]
    assert all(cut[n]["count"] == 1 for n in APPLY_PARTS)
    # a client op books no parts
    assert plain["op_timeline"]["count"] == 0


def test_reply_queue_is_held_inside_its_wait():
    """A scripted op: the completing reply's queue starts no earlier
    than the wait it ends, a shard the primary read itself books 0, and
    the close books one sample; an op without one books none."""
    from ceph_tpu.utils.perf import CounterType, PerfCounters
    pc = PerfCounters("t")
    pc.add("op_lat_us", CounterType.HISTOGRAM)
    tr = OpTracker(perf=pc)
    op = tr.create("write_full x", start_ns=1_000)
    op.reply_queued("waiting_for_subops", (2_000, 3_000))   # no wait yet
    assert op.reply_queue_ns is None
    op.mark("waiting_for_subops", 5_000)
    op.reply_queued("waiting_for_subops", None)
    assert op.reply_queue_ns == 0
    op.reply_queued("waiting_for_subops", (4_000, 9_000))
    assert op.reply_queue_ns == 4_000         # from 5_000, not 4_000
    op.mark("sub_op_commit_rec", 9_500)
    op.finish(op.mark("commit_sent", 10_000))
    assert op.intervals()["subwrite_wait"] == 4_500
    tr.create("read y", start_ns=1_000).finish(2_000)
    assert pc.dump()["op_reply_queue"] == {"sum_seconds": 4_000 / 1e9,
                                           "count": 1}


def test_store_commit_span_covers_the_commit_part():
    """A traced sub-write's store-commit span opens on its
    sub_op_applied reading and closes on its sub_op_committed one: its
    duration is the sub-op's commit part, to the nanosecond."""
    c = _cluster(n_osds=4, durable=True, trace_sample_rate=1.0)
    try:
        client = c.client()
        client.tracing = True
        _pool(client)
        client.write_full("p", "obj", PAYLOAD)
        root = next(s for s in client.tracer.dump()
                    if s["name"] == "client-op write_full")
        matched = 0
        for osd in c.osds.values():
            subs = {_reading(op, "reached_pg"): op
                    for op in list(osd.op_tracker._history)
                    if op.kind == "subop"}
            spans = osd.tracer.dump(root["trace_id"])
            for sw in spans:
                # the primary's own apply is no sub-op: it has none
                if not sw["name"].startswith("sub-write") or not subs:
                    continue
                op = next(o for at, o in subs.items()
                          if at == pytest.approx(sw["start"] * 1e9,
                                                 abs=1e3))
                sc = next(s for s in spans if s["name"] == "store-commit"
                          and s["parent_id"] == sw["span_id"])
                applied, committed = op.commit_cuts()
                parts = op.apply_parts(op.intervals()["apply"])
                assert sc["dur_ns"] == committed - applied == parts[1]
                assert sc["start"] == pytest.approx(applied / 1e9,
                                                    abs=1e-6)
                matched += 1
        assert matched >= 2
    finally:
        c.stop()


# ------------------------------------- the benchmark's readers of the split
SPLIT_METRICS = ("subop_ms.handler", "subop_ms.commit", "subop_ms.finish",
                 "op_ms.reply_queue", "subread_ms.coalesce")


@pytest.fixture(scope="module")
def booted_osd_counters():
    """A booted OSD's ``osd.N`` registry, nothing served yet."""
    c = _cluster(n_osds=3)
    try:
        yield c.osds[0].name, c.osds[0].perf.dump()
    finally:
        c.stop()


@pytest.mark.parametrize("metric", SPLIT_METRICS)
def test_split_metric_loads_and_its_counters_exist(metric,
                                                   booted_osd_counters):
    """Each metric loads through ``load_cell`` in every cell its entry
    lists and in no other, and every counter it names is registered,
    zeroed, on a booted OSD's registry: a renamed counter fails here."""
    from benchmark import cells
    bench = cells.manifest()
    entry = next(m for m in bench["per_layer"] if m["name"] == metric)
    assert entry["moves"] == "op_p90_ms"
    for w in bench["workloads"]:
        names = [m["name"] for m in cells.load_cell(w["name"])["per_layer"]]
        assert (metric in names) == (w["name"] in entry["workloads"])
    spec = next(m for m in cells.load_cell(entry["workloads"][0])
                ["per_layer"] if m["name"] == metric)
    assert spec["reader"] == "counter_ratio"
    registry, dump = booted_osd_counters
    assert registry.startswith("osd.")
    for key in spec["args"]["num"] + spec["args"]["den"]:
        prefix, counter, part = key.split(".")
        assert prefix == "osd"
        assert dump[counter] == {"sum_seconds": 0.0, "count": 0}, key
        assert part in dump[counter]
