"""EC read pipeline: per-peer MSubReadN coalescing + batched decode.

Three layers, mirroring how the write-path batcher is tested:

- pure-function units (extent union / coverage / carve) — the math the
  duplicate-collapse and union-merge guarantees rest on;
- SubReadAggregator units against a fake daemon/messenger (window and
  size flushes, duplicate collapse queued AND in-flight, union-range
  merge with per-waiter carving, reply fan-out);
- MiniCluster end-to-end byte-identity: coalesced vs per-op reads must
  return identical bytes healthy, ranged, degraded, under duplicate
  hammering of one hot object, and across a mid-burst OSD kill —
  plus the ranged-read minimal-attr contract, the batcher-level
  folded-decode sharing, the mesh-sharded fused encode+CRC, and the
  byte-weighted recovery progress events.
"""

import threading
import time

import numpy as np
import pytest

from ceph_tpu.msg.messages import MSubReadN, PgId
from ceph_tpu.osd.daemon import (SubReadAggregator, _carve_extents,
                                 _extents_cover, _merge_extents)
from ceph_tpu.tools.vstart import MiniCluster
from ceph_tpu.utils.config import default_config

RNG = np.random.default_rng(41)


# ------------------------------------------------------------ pure units
def test_merge_extents_unions_overlaps_and_touching():
    assert _merge_extents(((0, 10),), ((5, 10),)) == ((0, 15),)
    assert _merge_extents(((0, 10),), ((10, 5),)) == ((0, 15),)
    assert _merge_extents(((0, 4),), ((8, 4),)) == ((0, 4), (8, 4))
    assert _merge_extents(((8, 4), (0, 4)), ((2, 8),)) == ((0, 12),)


def test_extents_cover():
    assert _extents_cover(None, None)
    assert _extents_cover(None, ((3, 5),))      # whole serves any range
    assert not _extents_cover(((0, 10),), None)  # range can't serve whole
    assert _extents_cover(((0, 10), (20, 4)), ((2, 5), (21, 2)))
    assert not _extents_cover(((0, 10),), ((8, 4),))


def test_carve_extents_byte_identical_to_direct_slices():
    blob = bytes(RNG.integers(0, 256, 64, dtype=np.uint8))

    def direct(extents):
        """What the peer would return for a direct ranged read of the
        blob, each slice zero-padded to its requested length."""
        out = []
        for off, ln in extents:
            seg = blob[off:off + ln]
            out.append(seg + b"\0" * (ln - len(seg)))
        return b"".join(out)

    union = ((4, 20), (40, 40))  # second interval runs past the blob
    union_data = direct(union)
    for want in (((4, 20),), ((10, 6),), ((4, 4), (44, 8)),
                 ((50, 30),)):  # zero-padded tail carve
        assert _carve_extents(union, union_data, want) == direct(want)
    # whole-shard buffer carve
    assert _carve_extents(None, blob, ((8, 16),)) == direct(((8, 16),))
    assert _carve_extents(None, blob, ((60, 10),)) == direct(((60, 10),))
    # want == union passes through untouched
    assert _carve_extents(union, union_data, union) is union_data


# ----------------------------------------------------- aggregator units
class _FakeDaemon:
    def __init__(self):
        self.name = "osd.fake"
        self.sent = []         # (peer, MSubReadN)
        self.completions = []  # (tid, shard, result, data, attrs)
        self.messenger = self
        self.wseq = 0
        self.written = {}      # (pgid, oid) -> last acked-write seq

    def send_message(self, peer, msg):
        self.sent.append((peer, msg))
        return True

    def _on_shard_read(self, tid, shard, result, data, attrs):
        self.completions.append((tid, shard, result, bytes(data),
                                 dict(attrs)))

    # read-barrier surface the aggregator consults (OSDDaemon's
    # _note_obj_write bumps these on every acked write)
    def _obj_write_marker(self):
        return self.wseq

    def _obj_written_since(self, key, marker):
        return self.written.get(key, 0) > marker

    def note_write(self, pgid, oid):
        self.wseq += 1
        self.written[(pgid, oid)] = self.wseq


def _wait(pred, timeout=5.0):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if pred():
            return True
        time.sleep(0.005)
    return pred()


def test_aggregator_window_flush_coalesces_one_message():
    d = _FakeDaemon()
    agg = SubReadAggregator(d, window_us=20_000, max_items=64)
    pg = PgId(1, 0)
    agg.submit("osd.1", 11, pg, "a", 0, None)
    agg.submit("osd.1", 12, pg, "b", 2, [(0, 100)])
    assert _wait(lambda: d.sent), "window flush never fired"
    assert len(d.sent) == 1
    peer, msg = d.sent[0]
    assert peer == "osd.1" and isinstance(msg, MSubReadN)
    assert len(msg.items) == 2
    # reply routes both waiters through _on_shard_read
    items = [(fid, shard, 0, b"x" * 8, {"v": 1})
             for fid, _oid, shard, _ext in msg.items]
    agg.on_reply("osd.1", items)
    assert _wait(lambda: len(d.completions) == 2)
    assert sorted(c[0] for c in d.completions) == [11, 12]
    assert agg.pending() == 0
    agg.stop()


def test_aggregator_size_flush_and_per_peer_queues():
    d = _FakeDaemon()
    agg = SubReadAggregator(d, window_us=10_000_000, max_items=2)
    pg = PgId(1, 0)
    agg.submit("osd.1", 1, pg, "a", 0, None)
    agg.submit("osd.2", 2, pg, "a", 1, None)  # different peer queue
    agg.submit("osd.1", 3, pg, "b", 0, None)  # hits max_items -> flush
    assert _wait(lambda: d.sent)
    assert [p for p, _ in d.sent] == ["osd.1"]
    assert len(d.sent[0][1].items) == 2
    agg.stop()


def test_aggregator_duplicate_collapse_queued_and_inflight():
    d = _FakeDaemon()
    agg = SubReadAggregator(d, window_us=10_000_000, max_items=2)
    pg = PgId(2, 1)
    ext = [(0, 512)]
    agg.submit("osd.3", 21, pg, "hot", 1, ext)
    agg.submit("osd.3", 22, pg, "hot", 1, ext)   # queued dup: no new item
    agg.submit("osd.3", 23, pg, "other", 1, None)  # fills to max_items
    assert _wait(lambda: d.sent)
    assert len(d.sent) == 1
    msg = d.sent[0][1]
    assert len(msg.items) == 2  # hot fetch + other fetch, NOT 3
    # in-flight dup: attaches to the sent fetch, still no new message
    agg.submit("osd.3", 24, pg, "hot", 1, ext)
    hot_fid = next(fid for fid, oid, _s, _e in msg.items
                   if oid == "hot")
    other_fid = next(fid for fid, oid, _s, _e in msg.items
                     if oid == "other")
    agg.on_reply("osd.3", [(hot_fid, 1, 0, b"h" * 512, {"v": 7}),
                           (other_fid, 1, 0, b"o" * 9, {})])
    assert _wait(lambda: len(d.completions) == 4)
    hot = [c for c in d.completions if c[3] == b"h" * 512]
    assert sorted(c[0] for c in hot) == [21, 22, 24]
    assert len(d.sent) == 1  # the dup never produced wire traffic
    agg.stop()


def test_aggregator_union_merge_carves_per_waiter():
    d = _FakeDaemon()
    agg = SubReadAggregator(d, window_us=20_000, max_items=64)
    pg = PgId(2, 2)
    blob = bytes(RNG.integers(0, 256, 4096, dtype=np.uint8))
    agg.submit("osd.1", 31, pg, "o", 0, [(0, 1024)])
    agg.submit("osd.1", 32, pg, "o", 0, [(512, 1024)])  # overlaps
    assert _wait(lambda: d.sent)
    msg = d.sent[0][1]
    assert len(msg.items) == 1
    fid, _oid, _s, union = msg.items[0]
    assert union == [(0, 1536)]  # merged into ONE store read
    agg.on_reply("osd.1", [(fid, 0, 0, blob[0:1536], {"v": 1})])
    assert _wait(lambda: len(d.completions) == 2)
    by_tid = {c[0]: c[3] for c in d.completions}
    assert by_tid[31] == blob[0:1024]
    assert by_tid[32] == blob[512:1536]
    agg.stop()


def test_aggregator_recovery_lane_coalesces_per_helper():
    """ISSUE 14 satellite (ROADMAP wide-codes follow-on (c)): repair-
    plane sub-chunk fetches ride the aggregator in a RECOVERY-class
    lane — a storm rebuilding many objects sends ONE MSubReadN per
    helper per window (msgs/helper drops N -> 1), the message carries
    klass="recovery" for the serving peer's mclock queue, and client
    fetches to the same helper never share the wire message."""
    d = _FakeDaemon()
    agg = SubReadAggregator(d, window_us=20_000, max_items=64)
    pg = PgId(3, 0)
    # 6 repair-plane fetches of 6 objects to ONE helper + an
    # interleaved client read to the same helper
    for i in range(6):
        agg.submit("osd.1", 100 + i, pg, f"obj{i}", 2,
                   [(0, 512), (2048, 512)], klass="recovery")
    agg.submit("osd.1", 99, pg, "client-obj", 2, [(0, 100)])
    assert _wait(lambda: len(d.sent) >= 2)
    by_klass = {m.klass: m for _p, m in d.sent}
    assert set(by_klass) == {"recovery", "client"}
    rec = by_klass["recovery"]
    assert len(rec.items) == 6          # 6 fetches, ONE wire message
    assert len(by_klass["client"].items) == 1
    # replies route exactly like client-lane ones
    agg.on_reply("osd.1", [(fid, shard, 0, b"z" * 1024, {"v": 3})
                           for fid, _o, shard, _e in rec.items])
    assert _wait(lambda: len(d.completions) == 6)
    assert sorted(c[0] for c in d.completions) == list(range(100, 106))
    agg.stop()


def test_aggregator_ranged_rides_whole_shard_fetch():
    """A ranged read of a shard object with a queued OR in-flight
    whole-shard fetch attaches as a waiter (the whole stream covers any
    slice) instead of issuing a second wire fetch."""
    d = _FakeDaemon()
    agg = SubReadAggregator(d, window_us=10_000_000, max_items=2)
    pg = PgId(3, 0)
    blob = bytes(RNG.integers(0, 256, 2048, dtype=np.uint8))
    agg.submit("osd.1", 41, pg, "o", 0, None)          # whole-shard
    agg.submit("osd.1", 42, pg, "o", 0, [(256, 512)])  # queued ride
    agg.submit("osd.1", 43, pg, "x", 0, None)          # fills to flush
    assert _wait(lambda: d.sent)
    msg = d.sent[0][1]
    assert len(msg.items) == 2  # ranged read produced NO extra item
    whole_fid = next(fid for fid, oid, _s, ext in msg.items
                     if oid == "o")
    assert next(ext for _f, oid, _s, ext in msg.items
                if oid == "o") is None  # fetch stayed whole-shard
    # in-flight ride: another ranged read of the same shard object
    agg.submit("osd.1", 44, pg, "o", 0, [(0, 100)])
    assert len(d.sent) == 1  # still no extra wire traffic
    agg.on_reply("osd.1", [(whole_fid, 0, 0, blob, {"v": 1})])
    assert _wait(lambda: len(d.completions) == 3)
    by_tid = {c[0]: c[3] for c in d.completions}
    assert by_tid[41] == blob
    assert by_tid[42] == blob[256:768]
    assert by_tid[44] == blob[0:100]
    assert agg.pending() == 1  # only the unanswered "x" fetch remains
    agg.stop()


def test_aggregator_inflight_ride_fenced_by_write_barrier():
    """A read issued AFTER an acked write must not ride an in-flight
    fetch created BEFORE it (the fetch's reply can carry pre-write
    bytes): the barrier forces a fresh wire fetch, read-after-write
    stays intact."""
    d = _FakeDaemon()
    agg = SubReadAggregator(d, window_us=10_000_000, max_items=1)
    pg = PgId(4, 0)
    agg.submit("osd.1", 51, pg, "o", 0, None)  # size flush -> in flight
    assert _wait(lambda: d.sent) and len(d.sent) == 1
    # no intervening write: the dup ride works
    agg.submit("osd.1", 52, pg, "o", 0, None)
    assert len(d.sent) == 1
    # acked write lands; a NEW read must not see pre-write bytes
    d.note_write(pg, "o")
    agg.submit("osd.1", 53, pg, "o", 0, None)
    assert _wait(lambda: len(d.sent) == 2), \
        "post-write read rode the stale in-flight fetch"
    fid_old = d.sent[0][1].items[0][0]
    fid_new = d.sent[1][1].items[0][0]
    agg.on_reply("osd.1", [(fid_old, 0, 0, b"old", {"v": 1})])
    agg.on_reply("osd.1", [(fid_new, 0, 0, b"new", {"v": 2})])
    assert _wait(lambda: len(d.completions) == 3)
    by_tid = {c[0]: c[3] for c in d.completions}
    assert by_tid[51] == b"old" and by_tid[52] == b"old"
    assert by_tid[53] == b"new"  # the fenced read got fresh bytes
    # a fetch created AFTER the write serves post-write dups again
    agg.submit("osd.1", 54, pg, "o", 0, None)
    assert _wait(lambda: len(d.sent) == 3)
    agg.submit("osd.1", 55, pg, "o", 0, None)
    assert len(d.sent) == 3  # rode fetch #3: barrier clears
    agg.stop()


# --------------------------------------------------- batcher decode unit
def test_batcher_folded_decode_sharing_one_launch():
    """Same-signature decodes submitted concurrently (the shape the
    read pipeline's multi-delivery completions produce) share ONE
    folded inverse-matrix launch, byte-exact per op."""
    from ceph_tpu import ec
    from ceph_tpu.ec.batcher import ECBatcher
    from ceph_tpu.ops import gf256

    codec = ec.factory("tpu", {"k": 4, "m": 2, "backend": "jax"})
    L, n = 2048, 4
    cases = []
    for _ in range(n):
        data = RNG.integers(0, 256, (4, L), dtype=np.uint8)
        parity = gf256.encode_region(codec.matrix, data)
        chunks = {i: data[i] for i in range(4) if i != 1}
        chunks.update({4 + j: parity[j] for j in range(2)})
        cases.append((data, chunks))
    b = ECBatcher(window_us=200_000, max_bytes=64 << 20)
    results = [None] * n
    barrier = threading.Barrier(n)

    def reader(i):
        barrier.wait()
        results[i] = b.decode(codec, [0, 1, 2, 3], dict(cases[i][1]))

    threads = [threading.Thread(target=reader, args=(i,))
               for i in range(n)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert b.stats["launches"] == 1  # the whole group shared one fold
    for (data, _), out in zip(cases, results):
        for i in range(4):
            assert np.array_equal(np.asarray(out[i]), data[i])


# ----------------------------------------------------------- end to end
def _cfg(**over):
    cfg = default_config()
    cfg.apply_dict({"osd_heartbeat_interval": 0.05,
                    "osd_heartbeat_grace": 0.5,
                    "ec_backend": "native",
                    "osd_op_num_shards": 2,
                    "ms_dispatch_workers": 2,
                    "ec_read_coalesce": "on",
                    # these tests exercise the sub-read aggregator: the
                    # extent-cache serve would shortcut the wire fan-out
                    "ec_read_cache_serve": "off",
                    "ec_read_window_us": 500.0, **over})
    return cfg


@pytest.fixture
def read_cluster():
    """6-OSD cluster with k=4+m=2 (NO spares: a killed OSD's shards
    cannot rebuild, so degraded reads STAY degraded) and the read
    pipeline forced on."""
    c = MiniCluster(n_osds=6, cfg=_cfg()).start()
    cl = c.client()
    cl.create_pool("ecr", kind="ec", pg_num=4,
                   ec_profile={"plugin": "jerasure", "k": "4", "m": "2",
                               "backend": "numpy"})
    yield c, cl
    c.stop()


def _write_set(cl, n=8, size=24_000):
    payloads = {}
    for i in range(n):
        data = bytes(RNG.integers(0, 256, size, dtype=np.uint8))
        payloads[f"o{i}"] = data
        cl.write_full("ecr", f"o{i}", data)
    return payloads


def _burst(c, payloads, readers=6, rounds=1, names=None):
    clients = [c.client() for _ in range(readers)]
    errors = []

    def reader(r):
        try:
            for _ in range(rounds):
                for name in (names or sorted(payloads)):
                    got = clients[r].read("ecr", name)
                    assert got == payloads[name], name
        except Exception as e:  # noqa: BLE001 - surfaced by the test
            errors.append(e)

    threads = [threading.Thread(target=reader, args=(r,))
               for r in range(readers)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    return errors


def _read_counters(c):
    tot = {}
    for osd in c.osds.values():
        for k, v in osd.perf.dump().items():
            if k.startswith("ec_read") and isinstance(v, (int, float)):
                tot[k] = tot.get(k, 0) + v
    return tot


def test_e2e_healthy_burst_byte_identity_and_coalescing(read_cluster):
    c, cl = read_cluster
    payloads = _write_set(cl)
    errors = _burst(c, payloads, rounds=2)
    assert not errors, errors[:3]
    tot = _read_counters(c)
    # the burst actually coalesced: fewer wire messages than sub-reads
    assert tot["ec_read_msgs"] > 0
    assert tot["ec_read_coalesced_subreads"] + tot["ec_read_dup_hits"] \
        > tot["ec_read_msgs"]


def test_e2e_coalesced_equals_per_op_reads(read_cluster):
    """The same object set read with coalescing ON must equal a
    per-op (window 0) read of the same bytes — the pass-through
    baseline contract."""
    c, cl = read_cluster
    payloads = _write_set(cl, n=4)
    for osd in c.osds.values():
        assert osd._ec_read_coalesce_on(cl._pool_id("ecr"))
    coalesced = {n: cl.read("ecr", n) for n in payloads}
    for osd in c.osds.values():  # flip to pass-through live
        osd._read_agg.window_us = 0.0
    perop = {n: cl.read("ecr", n) for n in payloads}
    for n, data in payloads.items():
        assert coalesced[n] == data and perop[n] == data


def test_e2e_ranged_reads_byte_identity(read_cluster):
    c, cl = read_cluster
    payloads = _write_set(cl, n=4, size=50_000)
    cases = [(0, 100), (500, 4096), (16_000, 9000), (49_000, 5000),
             (25_000, 0)]  # tail read past EOF + offset-only
    for name, data in payloads.items():
        for off, ln in cases:
            if ln:
                assert cl.read("ecr", name, offset=off, length=ln) == \
                    data[off:off + ln]
            else:
                assert cl.read("ecr", name, offset=off) == data[off:]
    # concurrent overlapping ranged reads of ONE hot object: the union
    # merge / dup collapse must not corrupt any slice
    errors = []

    def ranged_reader(off, ln):
        try:
            got = cl2.read("ecr", "o0", offset=off, length=ln)
            assert got == payloads["o0"][off:off + ln]
        except Exception as e:  # noqa: BLE001
            errors.append(e)

    cl2 = c.client()
    threads = [threading.Thread(target=ranged_reader,
                                args=(256 * i, 8192))
               for i in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert not errors, errors[:3]


def test_e2e_hot_object_duplicate_collapse(read_cluster):
    c, cl = read_cluster
    payloads = _write_set(cl, n=1, size=30_000)
    before = _read_counters(c)["ec_read_dup_hits"]
    errors = _burst(c, payloads, readers=6, rounds=4, names=["o0"])
    assert not errors, errors[:3]
    assert _read_counters(c)["ec_read_dup_hits"] > before


def test_e2e_degraded_read_byte_identity(read_cluster):
    c, cl = read_cluster
    payloads = _write_set(cl)
    c.kill_osd(5)  # no spares: every PG it held a shard for decodes
    c.settle(0.8)
    errors = _burst(c, payloads, readers=4)
    assert not errors, errors[:3]


def test_e2e_mid_burst_osd_kill(read_cluster):
    """An OSD dying mid-burst must never corrupt a read: every read
    either returns the exact written bytes (possibly after client
    retries) or fails cleanly — and once the map settles, everything
    reads back byte-identical."""
    c, cl = read_cluster
    payloads = _write_set(cl)
    stop = threading.Event()
    corrupt = []

    def reader(r, cl_r):
        while not stop.is_set():
            for name in sorted(payloads):
                try:
                    got = cl_r.read("ecr", name)
                except Exception:  # noqa: BLE001 - clean failure ok
                    continue
                if got != payloads[name]:
                    corrupt.append(name)

    clients = [c.client() for _ in range(4)]
    threads = [threading.Thread(target=reader, args=(r, clients[r]))
               for r in range(4)]
    for t in threads:
        t.start()
    time.sleep(0.3)
    c.kill_osd(4)  # mid-burst
    time.sleep(1.0)
    stop.set()
    for t in threads:
        t.join()
    assert not corrupt, corrupt[:5]
    c.settle(0.5)
    for name, data in payloads.items():
        assert cl.read("ecr", name) == data, name


def test_ranged_subread_ships_minimal_attrs(read_cluster):
    """Ranged client sub-reads carry only the verification attrs
    (v/len/d/dcsum/wh); whole-shard recovery reads keep the full attr
    dict + omap."""
    c, cl = read_cluster
    _write_set(cl, n=1)
    cl.setxattr("ecr", "o0", "user.color", b"blue")
    pool_id = cl._pool_id("ecr")
    seed = c.mon.osdmap.object_to_pg(pool_id, "o0")
    up = c.mon.osdmap.pg_to_up_osds(pool_id, seed)
    osd = c.osds[up[0]]
    pg = PgId(pool_id, seed)
    res, _data, attrs = osd._read_one_sub(pg, "o0", 0, [(0, 512)])
    assert res == 0
    assert set(attrs) <= {"v", "len", "d", "dcsum", "wh"}
    assert "v" in attrs and "len" in attrs
    res, _data, attrs = osd._read_one_sub(pg, "o0", 0, None)
    assert res == 0
    assert "u:user.color" in attrs  # whole-shard reads keep user attrs


def test_e2e_traced_read_spans(read_cluster):
    """A traced read produces the fan-out decomposition: one
    ec-subread-fanout under the osd-op, ec-read-wait spans carrying
    flush_span cross-tags, and the shared ec-read-flush span."""
    c, cl = read_cluster
    payloads = _write_set(cl, n=2)
    cl.tracing = True
    assert cl.read("ecr", "o0") == payloads["o0"]
    root = next(s for s in cl.tracer.dump() if s["parent_id"] == 0)
    spans = c.collect_trace(root["trace_id"]) + \
        cl.tracer.spans_for(root["trace_id"])
    names = {s["name"] for s in spans}
    assert "ec-subread-fanout" in names
    waits = [s for s in spans if s["name"] == "ec-read-wait"]
    flushes = [s for s in spans if s["name"] == "ec-read-flush"]
    assert waits and flushes
    flush_ids = {s["span_id"] for s in flushes}
    assert all(s["tags"].get("flush_span") in flush_ids for s in waits)


def test_exporter_exposes_read_counters(read_cluster):
    """The ec_read_* schema is stable: every counter/histogram appears
    in a scrape even before (and after) any read traffic."""
    from ceph_tpu.mon.exporter import render_metrics
    c, cl = read_cluster
    body = render_metrics(c.mon)
    for name in ("ec_read_msgs", "ec_read_fetches",
                 "ec_read_dup_hits", "ec_read_union_merges",
                 "ec_read_stale_rejects", "ec_read_flush_window"):
        assert f"ceph_tpu_daemon_{name}" in body, name
    assert "ceph_tpu_daemon_ec_read_fetches_per_msg_bucket" in body


def test_recovery_progress_byte_weighted():
    """Recovery events weight done/total by object bytes (op counts
    ride alongside as done_ops/total_ops): with skewed object sizes
    the weighted total must exceed the op count."""
    cfg = _cfg(osd_recovery_progress_interval=0.0)
    c = MiniCluster(n_osds=3, cfg=cfg).start()
    try:
        cl = c.client()
        cl.create_pool("p", kind="ec", pg_num=2,
                       ec_profile={"plugin": "jerasure", "k": "2",
                                   "m": "1", "backend": "numpy"})
        for i in range(6):
            size = 4096 if i % 2 else 64 * 1024  # skewed sizes
            cl.write_full("p", f"o{i}", b"r" * size)
        c.kill_osd(2)
        c.settle(0.3)
        c.revive_osd(2)  # fresh store: every shard rebuilds
        deadline = time.time() + 30
        seen = []
        while time.time() < deadline and not seen:
            for osd in c.osds.values():
                for e in osd.events.recent(channel="recovery"):
                    f = e.get("fields") or {}
                    if f.get("event") in ("recovery_progress",
                                          "recovery_done"):
                        seen.append(f)
            time.sleep(0.05)
        assert seen, "no recovery progress events observed"
        weighted = [f for f in seen if "total_ops" in f]
        assert weighted, seen[:3]
        for f in weighted:
            assert f["total"] >= f["total_ops"]  # bytes >= op count
            assert f["done"] <= f["total"]
        # the skew shows: at least one event's byte total dwarfs its
        # op count (a 64KiB object outweighs a 4KiB one 16x)
        assert any(f["total"] > 4 * f["total_ops"] for f in weighted)
    finally:
        c.stop()
