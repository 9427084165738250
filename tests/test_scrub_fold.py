"""Folded deep scrub (ISSUE 20 tentpole; one pass since PR 40): the
schedule's cursor machinery, the folded verify of a chunk through the
ECBatcher seam, and its byte-identity with the per-object python loop.
A pass is started the operator's way (``scrub_pool``) or by the
schedule at a short interval, never by reaching into an OSD.

The tier-1 smoke pins ``osd_scrub_fold="device"`` so the folded CRC
sweep runs through the jax graph even on CPU (the fold path CI always
exercises); the full-store leg is ``slow``.
"""

import time

import numpy as np
import pytest

from ceph_tpu.ec.batcher import ECBatcher
from ceph_tpu.ec.verify import verifier
from ceph_tpu.msg.messages import PgId
from ceph_tpu.ops.checksum import crc32c_extend_zeros, crc32c_ref
from ceph_tpu.tools.vstart import MiniCluster
from tests.test_cluster import make_cfg

RNG = np.random.default_rng(202)


def scrub_cfg(**over):
    # fifo queue: no scheduler threads, so a pass's chunks run on the
    # threads that bring its messages (the mclock leg is
    # tests/test_scrub_pass.py and the benchmark's cell)
    return make_cfg(osd_op_queue="fifo", osd_scrub_fold="device",
                    osd_scrub_chunk_max=8, **over)


def total(cluster, counter: str) -> int:
    return sum(o.perf.get(counter) for o in cluster.osds.values())


def scrub_by_verb(cluster, client, pool: str) -> list:
    """One deep pass of every PG, the operator's way."""
    return client.scrub_pool(pool, deep=True)


def scrub_by_schedule(cluster, client, pool: str) -> list:
    """Wait until the schedule (``SCHEDULE``: every PG due a second
    after its last pass) has run two more passes of every PG, so that
    one of them began after this call; returns what the journal says
    they found."""
    pgs = client.osdmap.pools[client._pool_id(pool)].pg_num
    want = total(cluster, "scrubs") + 2 * pgs
    deadline = time.time() + 30.0
    while total(cluster, "scrubs") < want:
        assert time.time() < deadline, "the schedule ran no pass"
        time.sleep(0.05)
    return [e for o in cluster.osds.values()
            for e in o.events.recent(channel="scrub")
            if e["fields"].get("kind") == "digest_mismatch"]


SCHEDULE = dict(osd_scrub_min_interval=1.0, osd_scrub_max_interval=1.0,
                osd_heartbeat_interval=0.05)
STARTS = {"verb": ({}, scrub_by_verb), "schedule": (SCHEDULE,
                                                    scrub_by_schedule)}


@pytest.fixture
def cluster():
    c = MiniCluster(n_osds=4, cfg=scrub_cfg()).start()
    yield c
    c.stop()


@pytest.fixture(params=sorted(STARTS))
def started(request):
    """(cluster, how a pass of a pool is started there)."""
    over, run = STARTS[request.param]
    c = MiniCluster(n_osds=4, cfg=scrub_cfg(**over)).start()
    yield c, run
    c.stop()


# ---------------------------------------------------- folded-verify smoke
def test_folded_verify_smoke_small_pg(started):
    """Tier-1 CPU-jax smoke: ragged objects fold into pow2-bucket
    device launches; a clean store scrubs clean with real byte/launch
    telemetry, whoever started the pass."""
    cluster, run = started
    client = cluster.client()
    client.create_pool("p", size=3, pg_num=2)
    sizes = [1, 5, 100, 1000, 4096, 5000, 9000]
    for i, n in enumerate(sizes):
        data = RNG.integers(0, 256, n, dtype=np.uint8).tobytes()
        client.write_full("p", f"o{i}", data)
    cluster.settle(0.3)
    assert run(cluster, client, "p") == []
    scrubbed = [o for o in cluster.osds.values()
                if o.perf.get("scrubs") > 0]
    assert scrubbed, "no primary completed a deep-scrub pass"
    assert total(cluster, "scrub_mismatches") == 0
    assert total(cluster, "scrub_errors") == 0
    for osd in scrubbed:    # a primary holds a copy: it verified it
        assert osd.perf.get("scrub_verify_launches") > 0
        assert osd.perf.get("scrub_verified_bytes") > 0
        evs = osd.events.recent(channel="scrub")
        kinds = {e["fields"].get("event") for e in evs}
        assert "scrub_done" in kinds
        if run is scrub_by_schedule:
            assert "scrub_start" in kinds
    assert total(cluster, "scrub_verified_bytes") >= 3 * sum(sizes)


def test_folded_verify_ec_pool(cluster):
    """EC shards (including parity) carry stored digests and fold
    through the same verify seam."""
    client = cluster.client()
    client.create_pool("ec", kind="ec", pg_num=1,
                       ec_profile={"plugin": "jerasure", "k": "2",
                                   "m": "1", "backend": "native"})
    payload = RNG.integers(0, 256, 9000, dtype=np.uint8).tobytes()
    client.write_full("ec", "obj", payload)
    cluster.settle(0.3)
    pool_id = client._pool_id("ec")
    seed = cluster.mon.osdmap.object_to_pg(pool_id, "obj")
    up = cluster.mon.osdmap.pg_to_up_osds(pool_id, seed)
    assert scrub_by_verb(cluster, client, "ec") == []
    for osd_id in up:
        assert cluster.osds[osd_id].perf.get("scrub_mismatches") == 0
        assert cluster.osds[osd_id].perf.get("scrub_verified_bytes") > 0


# -------------------------------------------- byte-identity with the loop
def test_folded_matches_python_loop_on_bitflip():
    """A corruption-injected bit flip is caught by the folded verify
    byte-identically to the per-object python loop — same victim set,
    zero false positives on 40 ragged objects."""
    objs = [RNG.integers(0, 256, int(n), dtype=np.uint8).tobytes()
            for n in RNG.integers(1, 6000, 40)]
    digests = [crc32c_ref(o) for o in objs]
    victim = 17
    bad = bytearray(objs[victim])
    bad[len(bad) // 2] ^= 0x10
    objs[victim] = bytes(bad)

    loop_bad = [i for i, (o, d) in enumerate(zip(objs, digests))
                if crc32c_ref(o) != d]

    ver = verifier("device")
    batcher = ECBatcher(window_us=0.0)
    buckets: dict[int, list] = {}
    for i, o in enumerate(objs):
        n = len(o)
        b = 4 if n <= 4 else 1 << (n - 1).bit_length()
        buckets.setdefault(b, []).append(i)
    folded_bad = []
    for blen, idxs in sorted(buckets.items()):
        rows = np.zeros((len(idxs), blen), dtype=np.uint8)
        expected = np.empty(len(idxs), dtype=np.uint32)
        for r, i in enumerate(idxs):
            rows[r, :len(objs[i])] = np.frombuffer(objs[i],
                                                   dtype=np.uint8)
            expected[r] = crc32c_extend_zeros(digests[i],
                                              blen - len(objs[i]))
        digs = batcher.verify(ver, rows)
        for r in np.nonzero(digs != expected)[0]:
            i = idxs[int(r)]
            # candidate -> host confirm, exactly like the scrub engine
            if crc32c_ref(objs[i]) != digests[i]:
                folded_bad.append(i)
    assert loop_bad == [victim]
    assert sorted(folded_bad) == loop_bad


def test_background_scrub_detects_and_repairs():
    """A silently corrupted replica is caught by the scheduled deep
    scrub (counted once) and repaired via the per-object pull path."""
    cluster = MiniCluster(n_osds=4, cfg=scrub_cfg(**SCHEDULE)).start()
    try:
        client = cluster.client()
        client.create_pool("r", size=3, pg_num=1)
        payload = RNG.integers(0, 256, 5000, dtype=np.uint8).tobytes()
        client.write_full("r", "victim", payload)
        pool_id = client._pool_id("r")
        seed = cluster.mon.osdmap.object_to_pg(pool_id, "victim")
        up = cluster.mon.osdmap.pg_to_up_osds(pool_id, seed)
        target = cluster.osds[up[1]]
        assert target.inject.corrupt_object(
            target.store, PgId(pool_id, seed), "victim", shard=-1,
            offset=100)
        found = scrub_by_schedule(cluster, client, "r")
        assert len(found) == 1
        assert found[0]["fields"]["object"] == "victim"
        assert total(cluster, "scrub_mismatches") == 1
        cluster.settle(0.5)
        # pull repair landed: later passes and the operator's deep
        # scrub both read clean
        scrub_by_schedule(cluster, client, "r")
        assert total(cluster, "scrub_mismatches") == 1  # not re-counted
        assert client.scrub_pg("r", seed, deep=True).inconsistencies == []
        assert client.read("r", "victim") == payload
    finally:
        cluster.stop()


# ------------------------------------------------- cursor kill / revive
def test_scrub_cursor_resumes_after_osd_kill(cluster):
    """A primary killed mid-pass resumes from the persisted omap cursor
    on revival: the pass completes over the REMAINING objects only,
    and a mismatch before the cursor (reported before the crash) is
    not reported again."""
    client = cluster.client()
    client.create_pool("k", size=3, pg_num=1)
    names = sorted(f"o{i:02d}" for i in range(12))
    for n in names:
        client.write_full("k", n, RNG.integers(
            0, 256, 2000, dtype=np.uint8).tobytes())
    cluster.settle(0.3)
    pool_id = client._pool_id("k")
    seed = cluster.mon.osdmap.object_to_pg(pool_id, names[0])
    up = cluster.mon.osdmap.pg_to_up_osds(pool_id, seed)
    osd_id = up[0]
    osd = cluster.osds[osd_id]
    pgid = PgId(pool_id, seed)
    from ceph_tpu.osd.objectstore import CollectionId
    cid = CollectionId(pool_id, seed)
    # corrupt an object of the FIRST chunk (chunk_max=8, sorted order),
    # and leave what a scheduled pass leaves after that chunk: its
    # cursor in the PG's meta object; then crash
    assert osd.inject.corrupt_object(osd.store, pgid, names[0],
                                     shard=-1, offset=10)
    osd._scrub_cursor_store(cid, names[7])
    store = cluster.kill_osd(osd_id, mark_down=True)
    cluster.settle(0.3)
    revived = cluster.revive_osd(osd_id, store=store)
    cluster.settle(0.5)
    # the persisted cursor marks a pass that died mid-flight, so the
    # revived primary resumes PROMPTLY instead of waiting an interval
    # (a day, in this cluster)
    deadline = time.time() + 10.0
    while revived.perf.get("scrubs") < 1:
        assert time.time() < deadline, "the pass was not resumed"
        time.sleep(0.05)
        revived._scrub_tick(time.time())
    done = [e for e in revived.events.recent(channel="scrub")
            if e["fields"].get("event") == "scrub_done"]
    # resumed past the cursor: only the remaining objects were walked
    assert 0 < done[-1]["fields"]["done"] <= len(names) - 8
    # the mismatch before the cursor is NOT reported by this pass
    assert total(cluster, "scrub_mismatches") == 0
    # cursor cleared once the pass ended
    assert revived._scrub_cursor_load(cid) is None


# ------------------------------------------------------- full-store leg
@pytest.mark.slow
def test_full_store_scrub_all_pgs(cluster):
    """Full-store background scrub across pools and PGs: every hosted
    PG cycles, totals add up, zero mismatches on a clean store."""
    client = cluster.client()
    client.create_pool("fa", size=3, pg_num=4)
    client.create_pool("fb", kind="ec", pg_num=2,
                       ec_profile={"plugin": "jerasure", "k": "2",
                                   "m": "1", "backend": "native"})
    written = 0
    for i in range(40):
        data = RNG.integers(0, 256, int(RNG.integers(100, 20000)),
                            dtype=np.uint8).tobytes()
        client.write_full("fa" if i % 2 else "fb", f"obj{i}", data)
        written += len(data)
    cluster.settle(0.5)
    assert scrub_by_verb(cluster, client, "fa") == []
    assert scrub_by_verb(cluster, client, "fb") == []
    total_bytes = total(cluster, "scrub_verified_bytes")
    total_cycles = total(cluster, "scrubs")
    assert total_cycles > 0
    # replicated x3 + EC shards store more than the logical bytes
    assert total_bytes > written
    assert all(o.perf.get("scrub_mismatches") == 0
               for o in cluster.osds.values())
