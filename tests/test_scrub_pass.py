"""One deep-scrub pass (ISSUE 40): the operator's verb and the schedule
run the same chunked pass; its digests are the device program's where
``osd_scrub_fold`` says so; it holds a chunk's objects against writes,
so a healthy pool under overwrites yields no finding; a planted fault
is found by both; a map that does not come is asked for again or fails
the pass.  ``osd_op_queue`` is the default here (mclock): a pass's
chunks take their turns in the ``scrub`` class.
"""

import collections
import threading
import time

import numpy as np
import pytest

from benchmark import reference_scrub
from ceph_tpu.client.rados import RadosError
from ceph_tpu.ec import batcher as batcher_mod
from ceph_tpu.ec import verify as verify_mod
from ceph_tpu.ec.batcher import ECBatcher
from ceph_tpu.ec.verify import (ROWS, CrcVerifier, unpad_digests,
                                verify_bucket)
from ceph_tpu.msg.messages import MScrubMap, PgId
from ceph_tpu.ops.checksum import crc32c_rows_pallas
from ceph_tpu.osd import scrub as scrub_mod
from ceph_tpu.osd.objectstore import CollectionId, ObjectId
from ceph_tpu.tools.vstart import MiniCluster
from ceph_tpu.utils.config import FEATURES
from tests.test_cluster import make_cfg

RNG = np.random.default_rng(40)
EC42 = {"plugin": "tpu", "k": "4", "m": "2"}
SCHEDULE = dict(osd_scrub_min_interval=0.5, osd_scrub_max_interval=0.5,
                osd_heartbeat_interval=0.05)


def total(cluster, counter: str) -> int:
    return sum(o.perf.get(counter) for o in cluster.osds.values())


# ------------------------------------------------------ the CRC program
@pytest.mark.parametrize("rows", [1, 7, 8, 9, 17])
@pytest.mark.parametrize("bucket", [4096, 8192, 65536])
def test_device_program_against_the_reference(bucket, rows):
    """Every row count a chunk can hold at a bucket's one shape
    (``ROWS`` a launch, the last group padded), ragged lengths in front
    of zeros, through the batcher's flush on the ``device`` mode."""
    ver = CrcVerifier("device")
    assert ver.on_device
    lengths = [int(n) for n in RNG.integers(bucket // 2 + 1, bucket + 1,
                                            rows)]
    lengths[0] = bucket            # a whole row
    if rows > 1:
        lengths[1] = bucket // 2 + 1   # the shortest the bucket takes
    assert {verify_bucket(n) for n in lengths} == {bucket}
    datas = [RNG.integers(0, 256, n, dtype=np.uint8).tobytes()
             for n in lengths]
    buf = np.zeros((rows, bucket), dtype=np.uint8)
    for i, d in enumerate(datas):
        buf[i, bucket - len(d):] = np.frombuffer(d, dtype=np.uint8)
    b = ECBatcher(window_us=200.0)
    got = unpad_digests(b.verify(ver, buf), bucket, lengths)
    assert [int(g) for g in got] == [reference_scrub.crc32c(d)
                                     for d in datas]
    assert b.stats["launches"] == 1
    # the host sweep is the same function's other branch
    host = unpad_digests(ECBatcher(window_us=0.0).verify(
        CrcVerifier("native"), buf), bucket, lengths)
    assert list(host) == list(got)


def test_buckets_are_powers_of_two_from_4_kib():
    assert [verify_bucket(n) for n in (0, 1, 4096, 4097, 524288,
                                       524289)] == [
        4096, 4096, 4096, 8192, 524288, 1048576]


@pytest.mark.parametrize("nbytes", [4096, 32768])
def test_the_tpu_kernel_interpreted(nbytes):
    """The Pallas kernel a TPU runs, interpreted here: byte-exact."""
    import jax
    data = RNG.integers(0, 2 ** 32, (ROWS, nbytes // 4), dtype=np.uint32)
    got = np.asarray(jax.jit(crc32c_rows_pallas(
        ROWS, nbytes, interpret=True))(data.reshape(-1, 128)))
    assert [int(g) for g in got] == [reference_scrub.crc32c(r.tobytes())
                                     for r in data]
    with pytest.raises(ValueError):
        crc32c_rows_pallas(ROWS, 2048)


def test_a_first_store_claims_the_verify_programs_warm_up(monkeypatch):
    """The first shard an OSD stores into a length bucket tells its
    batcher to expect that bucket's verify program, once; on an
    accelerator the batcher compiles it off the IO path, once a
    process."""
    asked = collections.Counter()
    real = ECBatcher.expect_verify

    def spy(self, ver, bucket):
        asked[bucket] += 1
        return real(self, ver, bucket)
    monkeypatch.setattr(ECBatcher, "expect_verify", spy)
    cluster = MiniCluster(n_osds=6, cfg=make_cfg(
        ec_backend="numpy", osd_scrub_fold="device")).start()
    try:
        client = cluster.client()
        client.create_pool("w", kind="ec", pg_num=2, ec_profile=EC42)
        client.write_full("w", "a", bytes(4 * 40000))   # 40000 B a shard
        client.write_full("w", "b", bytes(4 * 50000))   # the same bucket
        client.write_full("w", "c", bytes(4 * 100000))
        # every OSD that stored a shard asked once a bucket
        assert set(asked) == {65536, 131072}
        assert all(1 <= n <= 6 for n in asked.values())
        assert not batcher_mod._WARM_THREADS     # the CPU: no warm-up
    finally:
        cluster.stop()
    # the accelerator's side of it, without one: the claim
    warmed = []
    monkeypatch.setattr(ECBatcher, "_stages_on_ingest",
                        staticmethod(lambda codec: True))
    monkeypatch.setattr(ECBatcher, "_warm_verify",
                        lambda self, ver, bucket: warmed.append(bucket))
    monkeypatch.setattr(batcher_mod, "_WARM_CLAIMED", set())
    monkeypatch.setattr(batcher_mod, "_WARM_THREADS", [])
    ver = CrcVerifier("device")
    for b in (ECBatcher(), ECBatcher()):      # two OSDs of one process
        real(b, ver, 65536)
        real(b, ver, 65536)
    real(ECBatcher(), ver, 131072)
    assert ECBatcher.warm_wait(10) and warmed == [65536, 131072]


def test_host_digests_on_an_accelerator_are_a_counted_fall_through(
        monkeypatch):
    """(Counted on a stand-in: the real counter is the process's, and
    ``Deployment.health()`` / the smoke refuse a run that moved it.)"""
    from ceph_tpu.utils import staging
    assert "ec_scrub_host_digest" in staging.FALLTHROUGHS
    counted = collections.Counter()
    monkeypatch.setattr(staging, "stage_perf", lambda: type(
        "Perf", (), {"inc": staticmethod(
            lambda name, by=1: counted.update({name: by}))}))
    rows = np.zeros((2, 4096), dtype=np.uint8)
    verify_mod.host_digests(rows)
    assert not counted               # a CPU host: the sweep is the way
    monkeypatch.setattr(staging, "backend_is_cpu", lambda: False)
    verify_mod.host_digests(rows)
    assert counted == {"ec_scrub_host_digest": 1}


def test_feature_is_named_for_deployment_files():
    assert "scrub_under_writes" in FEATURES


# ------------------------------------------------- scrub under overwrites
@pytest.mark.parametrize("backend,fold", [("numpy", "auto"),
                                          ("native", "native"),
                                          ("jax", "device")])
def test_no_finding_on_a_healthy_pool_under_overwrites(backend, fold):
    """Several callers overwrite the pool's objects while the operator
    deep-scrubs it round and round: nothing found, no inventory round,
    nothing repaired, every write acknowledged and read back right."""
    cluster = MiniCluster(n_osds=6, cfg=make_cfg(
        ec_backend=backend, osd_scrub_fold=fold)).start()
    try:
        client = cluster.client()
        client.create_pool("p", kind="ec", pg_num=4, ec_profile=EC42)
        n, size = 16, 64 << 10
        pay = [RNG.integers(0, 256, size, dtype=np.uint8).tobytes()
               for _ in range(5)]
        last = {}
        for i in range(n):
            client.write_full("p", f"o{i:02d}", pay[i % 5])
            last[i] = i % 5
        stop = threading.Event()
        errors, writes = [], [0]

        def writer(w: int) -> None:
            i = w
            while not stop.is_set():
                k, v = i % n, (i * 3 + w) % 5
                try:
                    client.write_full("p", f"o{k:02d}", pay[v])
                    last[k] = v      # one writer an object: k = w mod 4
                    writes[0] += 1
                except Exception as e:  # noqa: BLE001
                    errors.append(repr(e))
                i += 4
        threads = [threading.Thread(target=writer, args=(w,), daemon=True)
                   for w in range(4)]
        before = {c: total(cluster, c)
                  for c in ("pg_requery", "scrub_errors")}
        for t in threads:
            t.start()
        found, passes = [], 0
        t_end = time.time() + 2.5
        while time.time() < t_end:
            found += client.scrub_pool("p", deep=True)
            passes += 1
        stop.set()
        for t in threads:
            t.join(30)
        found += client.scrub_pool("p", deep=True)   # the quiet pass
        assert found == [] and errors == []
        assert passes >= 1 and writes[0] > 8
        assert {c: total(cluster, c) for c in before} == before
        for k, v in last.items():
            assert client.read("p", f"o{k:02d}") == pay[v]
        waited = sum(o.perf.dump()["op_scrub_wait"]["count"]
                     for o in cluster.osds.values())
        chunks = sum(o.perf.dump()["scrub_chunk"]["count"]
                     for o in cluster.osds.values())
        assert chunks >= 4 * (passes + 1) and waited >= 0
        verified = total(cluster, "scrub_verified_bytes")
        # only a device program's digests count as verified bytes
        assert (verified > 0) == (fold == "device")
    finally:
        cluster.stop()


# ---------------------------------------------------------- planted faults
def _stored(cluster, pool_id, seed, name, up):
    out = {}
    cid = CollectionId(pool_id, seed)
    for shard, o in enumerate(up):
        st = cluster.osds[o].store
        oid = ObjectId(name, shard=shard)
        a = st.getattrs(cid, oid)
        out[(name, shard)] = (st.read(cid, oid).to_bytes(), int(a["d"]),
                              int(a["v"]))
    return out


@pytest.mark.parametrize("start", ["verb", "schedule"])
@pytest.mark.parametrize("fault", ["digest_mismatch", "stale_version"])
def test_a_planted_fault_is_found_under_writes_to_other_objects(
        fault, start):
    """A flipped byte in one stored shard, or one shard left at the
    version before: the verb and the scheduled pass report the same
    finding, the reference's, while other objects are overwritten."""
    over = SCHEDULE if start == "schedule" else {}
    cluster = MiniCluster(n_osds=6, cfg=make_cfg(
        ec_backend="numpy", osd_scrub_fold="device", **over)).start()
    try:
        client = cluster.client()
        client.create_pool("p", kind="ec", pg_num=1, ec_profile=EC42)
        pay = [RNG.integers(0, 256, 32 << 10, dtype=np.uint8).tobytes()
               for _ in range(3)]
        for i in range(6):
            client.write_full("p", f"o{i}", pay[i % 3])
        pool_id = client._pool_id("p")
        up = cluster.mon.osdmap.pg_to_up_osds(pool_id, 0)
        pgid, victim, shard = PgId(pool_id, 0), "o2", 4
        target = cluster.osds[up[shard]]
        if fault == "digest_mismatch":
            assert target.inject.corrupt_object(
                target.store, pgid, victim, shard=shard, offset=99)
        else:
            # the shard missed the object's last write: it holds the
            # bytes, digest and version of the one before
            old = _stored(cluster, pool_id, 0, victim, up)[(victim, shard)]
            client.write_full("p", victim, pay[0])
            target._apply_write(pgid, victim, shard, old[0],
                                {"v": old[2], "d": old[1]})
        want = reference_scrub.expected_findings(
            _stored(cluster, pool_id, 0, victim, up),
            [(victim, shard, fault)])
        stop = threading.Event()

        def writer() -> None:
            i = 0
            while not stop.is_set():
                client.write_full("p", f"o{(0, 1, 3, 4, 5)[i % 5]}",
                                  pay[i % 3])
                i += 1
        t = threading.Thread(target=writer, daemon=True)
        t.start()
        try:
            if start == "verb":
                found = client.scrub_pg("p", 0, deep=True).inconsistencies
                got = [(f["object"], f["shard"], f["kind"]) for f in found]
            else:
                primary = cluster.osds[up[0]]
                deadline = time.time() + 20
                name = f"scrub_finding_{fault}"
                while not primary.perf.get(name):
                    assert time.time() < deadline, "no scheduled pass"
                    time.sleep(0.05)
                got = [(victim, shard, k) for k in scrub_mod.FINDING_KINDS
                       if primary.perf.get(f"scrub_finding_{k}")]
        finally:
            stop.set()
            t.join(30)
        assert got == want
    finally:
        cluster.stop()


# ------------------------------------------------------- a pass always ends
@pytest.mark.parametrize("drops", [1, 10 ** 6])
def test_a_dropped_map_is_asked_for_again_or_fails_the_pass(
        monkeypatch, drops):
    monkeypatch.setattr(scrub_mod, "SCRUB_RESEND_S", 0.2)
    monkeypatch.setattr(scrub_mod, "SCRUB_SENDS", 3)
    cluster = MiniCluster(n_osds=6, cfg=make_cfg(
        ec_backend="numpy", osd_heartbeat_interval=0.05)).start()
    try:
        client = cluster.client()
        client.create_pool("p", kind="ec", pg_num=1, ec_profile=EC42)
        client.write_full("p", "o", bytes(20000))
        pool_id = client._pool_id("p")
        up = cluster.mon.osdmap.pg_to_up_osds(pool_id, 0)
        primary = cluster.osds[up[0]]
        dropped = collections.Counter()
        real = primary._handlers[MScrubMap]

        def lossy(conn, m):
            if m.from_osd == up[3] and dropped["n"] < drops:
                dropped["n"] += 1
                return
            real(conn, m)
        primary._handlers[MScrubMap] = lossy
        if drops == 1:
            assert client.scrub_pg("p", 0, deep=True).inconsistencies == []
            assert dropped["n"] == 1
        else:
            with pytest.raises(RadosError):
                client.scrub_pg("p", 0, deep=True)
            assert dropped["n"] == 3
        assert primary._pending_scrubs == {}
        assert primary._scrub_passes == {}
        # the object is given up: a write goes through
        client.write_full("p", "o", bytes(30000))
    finally:
        cluster.stop()


# ------------------------------------------- one function, whoever started it
def test_the_schedule_and_the_verb_run_one_function(monkeypatch):
    """Both passes take their maps from ``_scrub_shard_map`` and get
    the same digests, the reference's."""
    from ceph_tpu.osd.daemon import OSDDaemon
    calls = []
    real = OSDDaemon._scrub_shard_map

    def spy(self, pgid, deep, after=None, upto=None):
        out = real(self, pgid, deep, after, upto)
        calls.append((self.osd_id, {k: dict(v) for k, v in out.items()}))
        return out
    monkeypatch.setattr(OSDDaemon, "_scrub_shard_map", spy)
    cluster = MiniCluster(n_osds=6, cfg=make_cfg(
        ec_backend="jax", osd_scrub_fold="device", **SCHEDULE)).start()
    try:
        client = cluster.client()
        client.create_pool("p", kind="ec", pg_num=1, ec_profile=EC42)
        for i in range(3):
            client.write_full("p", f"o{i}", RNG.integers(
                0, 256, 10000 + i, dtype=np.uint8).tobytes())
        cluster.settle(0.3)
        calls.clear()
        first = total(cluster, "scrubs")
        deadline = time.time() + 20
        while total(cluster, "scrubs") < first + 2:   # the schedule's
            assert time.time() < deadline
            time.sleep(0.05)
        n_scheduled = len(calls)
        assert n_scheduled >= 6
        res = client.scrub_pg("p", 0, deep=True)      # the operator's
        assert res.inconsistencies == []
        assert len(calls) >= n_scheduled + 6
        verb: dict = {}
        for osd, m in calls:      # whoever asked, an OSD's map is one
            assert verb.setdefault(osd, m) == m
        assert len(verb) == 6
        pool_id = client._pool_id("p")
        for osd, m in verb.items():
            for (name, shard), entry in m.items():
                data = cluster.osds[osd].store.read(
                    CollectionId(pool_id, 0),
                    ObjectId(name, shard=shard)).to_bytes()
                assert entry["digest"] == entry["stored_digest"] \
                    == reference_scrub.crc32c(data)
    finally:
        cluster.stop()
