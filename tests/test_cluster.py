"""End-to-end cluster tests: the test-erasure-code.sh / ceph-helpers tier
(SURVEY.md §4 tier 3) in one process: boot mon+osds, create pools, write,
kill shard OSDs, verify reconstruction and recovery."""

import time

import numpy as np
import pytest

from ceph_tpu.client.rados import RadosError
from ceph_tpu.osd.objectstore import CollectionId, ObjectId
from ceph_tpu.tools.vstart import MiniCluster
from ceph_tpu.utils.config import default_config

RNG = np.random.default_rng(77)


def make_cfg(**over):
    cfg = default_config()
    cfg.apply_dict({"osd_heartbeat_interval": 0.05,
                    "osd_heartbeat_grace": 0.5,
                    "ec_backend": "native",
                    # sharded dispatch stays exercised (2 shards per
                    # OSD) without the full default-4 thread pressure —
                    # an 8-daemon test cluster already runs ~50 threads
                    # and CI-box contention was flaking timing-tight
                    # tests at 4
                    "osd_op_num_shards": 2,
                    "ms_dispatch_workers": 2, **over})
    return cfg


@pytest.fixture(params=["local", "tcp"])
def cluster(request):
    """Every core cluster test runs over BOTH transports: in-proc queues
    and real TCP sockets with the codec-framed wire format."""
    c = MiniCluster(n_osds=6, cfg=make_cfg(),
                    transport=request.param).start()
    yield c
    c.stop()


@pytest.fixture
def big_cluster():
    c = MiniCluster(n_osds=12, cfg=make_cfg()).start()
    yield c
    c.stop()


def test_boot_and_status(cluster):
    client = cluster.client()
    st = client.status()
    assert st["num_up"] == 6
    assert st["health"] == "HEALTH_OK"


def test_replicated_write_read_remove(cluster):
    client = cluster.client()
    client.create_pool("rbd", size=3)
    payload = RNG.integers(0, 256, 100_000, dtype=np.uint8).tobytes()
    v = client.write_full("rbd", "obj1", payload)
    assert v >= 1
    assert client.read("rbd", "obj1") == payload
    assert client.read("rbd", "obj1", offset=500, length=100) == \
        payload[500:600]
    assert client.stat("rbd", "obj1") == len(payload)
    client.remove("rbd", "obj1")
    with pytest.raises(RadosError):
        client.read("rbd", "obj1")


def test_replicated_copies_land_on_replicas(cluster):
    client = cluster.client()
    client.create_pool("rbd", size=3, pg_num=4)
    client.write_full("rbd", "obj", b"hello replicas")
    # count osds holding the object
    holders = 0
    for osd in cluster.osds.values():
        for cid in osd.store.list_collections():
            if ObjectId("obj") in dict.fromkeys(osd.store.list_objects(cid)):
                holders += 1
    assert holders == 3


def test_ec_pool_write_read(big_cluster):
    client = big_cluster.client()
    client.create_pool("ecpool", kind="ec", pg_num=4,
                       ec_profile={"plugin": "jerasure", "k": "4", "m": "2",
                                   "backend": "native"})
    payload = RNG.integers(0, 256, 1 << 20, dtype=np.uint8).tobytes()
    client.write_full("ecpool", "bigobj", payload)
    assert client.read("ecpool", "bigobj") == payload
    assert client.stat("ecpool", "bigobj") == len(payload)


@pytest.mark.parametrize("who", ["primary", "holder"])
def test_first_write_before_the_pg_collection_is_made(big_cluster, who):
    """The first write of a new pool can reach an OSD before it has
    made the PG's collection (a peer took the map first): the apply
    makes the collection and the write is acknowledged; it used to
    raise NoSuchCollection on the shard, no ack left, and the client
    got EIO at ``osd_op_timeout``."""
    from ceph_tpu.osd.objectstore import Transaction
    client = big_cluster.client()
    pool_id = client.create_pool(
        "ecnew", kind="ec", pg_num=4,
        ec_profile={"plugin": "jerasure", "k": "4", "m": "2",
                    "backend": "native"})
    seed = client.osdmap.object_to_pg(pool_id, "first")
    up = list(client.osdmap.pg_to_up_osds(pool_id, seed))
    cid = CollectionId(pool_id, seed)
    shard = 0 if who == "primary" else 3
    store = big_cluster.osds[up[shard]].store
    # as if this OSD had not run _ensure_collections for the pool yet
    # (wait until it has, so that it does not make it again behind us)
    deadline = time.monotonic() + 10
    while cid not in store.list_collections():
        assert time.monotonic() < deadline
        time.sleep(0.01)
    store.queue_transaction(Transaction().remove_collection(cid))
    assert cid not in store.list_collections()
    payload = RNG.integers(0, 256, 64 << 10, dtype=np.uint8).tobytes()
    t0 = time.monotonic()
    client.write_full("ecnew", "first", payload)
    assert time.monotonic() - t0 < 4.0       # no wait for a time-out
    assert cid in store.list_collections()
    assert len(store.read(cid, ObjectId("first", shard=shard)).to_bytes())
    assert client.read("ecnew", "first") == payload


def test_ec_degraded_read_after_osd_loss(big_cluster):
    """The test-erasure-code.sh scenario: write, kill shard OSDs, read back
    with reconstruction (qa/standalone/erasure-code/test-erasure-code.sh)."""
    client = big_cluster.client()
    client.create_pool("ecpool", kind="ec", pg_num=2,
                       ec_profile={"plugin": "jerasure", "k": "4", "m": "2",
                                   "backend": "native"})
    objs = {f"obj{i}": RNG.integers(0, 256, 50_000, dtype=np.uint8).tobytes()
            for i in range(6)}
    for name, data in objs.items():
        client.write_full("ecpool", name, data)
    # kill two OSDs (any shards they held must reconstruct)
    victims = sorted(big_cluster.osds)[:2]
    epoch = big_cluster.mon.osdmap.epoch
    for v in victims:
        big_cluster.kill_osd(v)
    big_cluster.wait_for_epoch(epoch + 2)
    big_cluster.settle(0.5)  # let spares recover shards
    for name, data in objs.items():
        assert client.read("ecpool", name) == data, name


def test_ec_loss_beyond_m_fails(big_cluster):
    client = big_cluster.client()
    client.create_pool("ec31", kind="ec", pg_num=1,
                       ec_profile={"plugin": "jerasure", "k": "3", "m": "1",
                                   "backend": "native"})
    payload = b"x" * 10_000
    client.write_full("ec31", "obj", payload)
    # kill 2 of the 4 shard holders (> m=1 simultaneous losses)
    up = big_cluster.mon.osdmap.pg_to_up_osds(
        client._pool_id("ec31"), big_cluster.mon.osdmap.object_to_pg(
            client._pool_id("ec31"), "obj"))
    epoch = big_cluster.mon.osdmap.epoch
    for v in [u for u in up if u is not None][:2]:
        big_cluster.kill_osd(v)
    big_cluster.wait_for_epoch(epoch + 2)
    big_cluster.settle(0.5)
    # with 12 osds, spares refill the up set and recovery may rebuild from
    # survivors -- but killing 2 of 4 shards before recovery can complete
    # can still succeed if recovery wins the race; accept either full
    # recovery or EIO, never wrong data
    try:
        got = client.read("ec31", "obj")
        assert got == payload
    except RadosError as e:
        # EIO (unrecoverable) or EAGAIN/timeout (stuck peering/degraded);
        # never wrong data
        assert e.code in (-5, -11, -110)


def test_recovery_rebuilds_shards_on_spare(big_cluster):
    client = big_cluster.client()
    client.create_pool("ecpool", kind="ec", pg_num=1,
                       ec_profile={"plugin": "jerasure", "k": "4", "m": "2",
                                   "backend": "native"})
    pool_id = client._pool_id("ecpool")
    payload = RNG.integers(0, 256, 30_000, dtype=np.uint8).tobytes()
    client.write_full("ecpool", "obj", payload)
    m = big_cluster.mon.osdmap
    seed = m.object_to_pg(pool_id, "obj")
    up_before = m.pg_to_up_osds(pool_id, seed)
    victim = up_before[1]
    epoch = m.epoch
    big_cluster.kill_osd(victim)
    big_cluster.wait_for_epoch(epoch + 1)
    big_cluster.settle(0.8)
    up_after = big_cluster.mon.osdmap.pg_to_up_osds(pool_id, seed)
    spare = up_after[1]
    if spare is not None and spare != victim:
        # the spare must now hold shard 1, rebuilt from survivors
        osd = big_cluster.osds[spare]
        cid = CollectionId(pool_id, seed)
        assert osd.store.exists(cid, ObjectId("obj", shard=1))
    assert client.read("ecpool", "obj") == payload


def test_tpu_plugin_pool_in_cluster(big_cluster):
    """The flagship `tpu` plugin (JAX kernels) serving a live EC pool."""
    client = big_cluster.client()
    client.create_pool("tpupool", kind="ec", pg_num=2,
                       ec_profile={"plugin": "tpu", "k": "4", "m": "2",
                                   "backend": "jax"})
    payload = RNG.integers(0, 256, 200_000, dtype=np.uint8).tobytes()
    client.write_full("tpupool", "obj", payload)
    assert client.read("tpupool", "obj") == payload
    # degraded read through the JAX decode path
    pool_id = client._pool_id("tpupool")
    seed = big_cluster.mon.osdmap.object_to_pg(pool_id, "obj")
    up = big_cluster.mon.osdmap.pg_to_up_osds(pool_id, seed)
    epoch = big_cluster.mon.osdmap.epoch
    big_cluster.kill_osd(up[0])
    big_cluster.wait_for_epoch(epoch + 1)
    big_cluster.settle(0.5)
    assert client.read("tpupool", "obj") == payload


def test_mon_stats_aggregation():
    """OSD stats reports feed `status` usage (MMgrReport/PGStats role)."""
    cfg = make_cfg(osd_heartbeat_interval=0.05)
    c = MiniCluster(n_osds=3, cfg=cfg).start()
    try:
        client = c.client()
        client.create_pool("rbd", size=3, pg_num=2)
        client.write_full("rbd", "obj", b"z" * 10_000)
        deadline = time.time() + 10
        usage = {}
        while time.time() < deadline:
            usage = client.status().get("usage", {})
            # wait for EVERY asserted aggregate, not just the object
            # count: a replica's report can land with the object
            # applied but its byte stats one report cycle behind —
            # breaking on objects alone flakes the bytes assert
            if usage.get("objects", 0) >= 3 \
                    and usage.get("bytes", 0) >= 30_000 \
                    and usage.get("op_w", 0) >= 1:
                break
            time.sleep(0.05)
        assert usage.get("objects", 0) >= 3
        assert usage.get("bytes", 0) >= 30_000
        assert usage.get("op_w", 0) >= 1
        per_osd = client.mon_command({"prefix": "osd stats"})
        assert len(per_osd) == 3
    finally:
        c.stop()


def test_heartbeat_failure_detection():
    """Kill an OSD without telling the mon; heartbeats must notice
    (OSD::handle_osd_ping -> MOSDFailure -> prepare_failure path)."""
    cfg = make_cfg(osd_heartbeat_interval=0.05, osd_heartbeat_grace=0.3)
    c = MiniCluster(n_osds=4, cfg=cfg).start()
    try:
        client = c.client()
        c.settle(0.3)  # let heartbeats establish
        epoch = c.mon.osdmap.epoch
        c.kill_osd(2, mark_down=False)
        deadline = time.time() + 10
        while time.time() < deadline:
            if not c.mon.osdmap.osds[2].up:
                break
            time.sleep(0.05)
        assert not c.mon.osdmap.osds[2].up, "heartbeats failed to detect"
        assert c.mon.osdmap.epoch > epoch
    finally:
        c.stop()


def test_replicated_recovery_after_revive(cluster):
    client = cluster.client()
    client.create_pool("rbd", size=3, pg_num=2)
    client.write_full("rbd", "before", b"written before kill")
    victim = 1
    epoch = cluster.mon.osdmap.epoch
    cluster.kill_osd(victim)
    cluster.wait_for_epoch(epoch + 1)
    client.write_full("rbd", "during", b"written while osd down")
    # revive: it boots empty (memstore) and must be backfilled by primaries
    cluster.revive_osd(victim)
    cluster.wait_for_epoch(epoch + 2)
    cluster.settle(0.8)
    assert client.read("rbd", "before") == b"written before kill"
    assert client.read("rbd", "during") == b"written while osd down"
    # revived osd holds whatever maps to it now
    osd = cluster.osds[victim]
    for cid in osd.store.list_collections():
        for oid in osd.store.list_objects(cid):
            if oid.shard <= -2:
                continue  # PG metadata (pglog), not user data
            assert osd.store.read(cid, oid).to_bytes() in (
                b"written before kill", b"written while osd down")


def test_ec_ranged_read(big_cluster):
    client = big_cluster.client()
    client.create_pool("ecr", kind="ec", pg_num=2,
                       ec_profile={"plugin": "jerasure", "k": "4", "m": "2",
                                   "backend": "native"})
    payload = RNG.integers(0, 256, 25_600, dtype=np.uint8).tobytes()
    client.write_full("ecr", "obj", payload)
    assert client.read("ecr", "obj", offset=500, length=100) == \
        payload[500:600]
    assert client.read("ecr", "obj", offset=25_000) == payload[25_000:]


def test_unknown_op_rejected(cluster):
    client = cluster.client()
    client.create_pool("rbd", size=2)
    client.write_full("rbd", "obj", b"x")
    with pytest.raises(RadosError) as ei:
        client._op("rbd", "obj", "append", b"y")
    assert ei.value.code == -22


def test_bad_ec_profile_does_not_wedge_monitor(cluster):
    client = cluster.client()
    # int-valued profile (coerced) and bogus k both must leave mon healthy
    client.create_pool("ok1", kind="ec",
                       ec_profile={"plugin": "jerasure", "k": 2, "m": 1})
    with pytest.raises(RadosError):
        client.create_pool("bad", kind="ec",
                           ec_profile={"plugin": "jerasure", "k": "zzz"})
    client.create_pool("ok2", size=2)  # further commits still work
    client.write_full("ok2", "obj", b"alive")
    assert client.read("ok2", "obj") == b"alive"


def test_remove_not_resurrected_by_recovery(cluster):
    """Tombstones: a replica that missed a remove must not feed the object
    back during recovery (the PGLog delete-entry role)."""
    client = cluster.client()
    client.create_pool("rbd", size=3, pg_num=2)
    client.write_full("rbd", "zombie", b"braaains")
    pool_id = client._pool_id("rbd")
    seed = cluster.mon.osdmap.object_to_pg(pool_id, "zombie")
    up = cluster.mon.osdmap.pg_to_up_osds(pool_id, seed)
    # partition one replica so it misses the remove
    lagger = up[-1]
    for other in up[:-1]:
        cluster.network.partition(f"osd.{lagger}", f"osd.{other}")
    cluster.network.partition(f"osd.{lagger}", "client.0")
    try:
        client.remove("rbd", "zombie")
    except RadosError:
        pass  # the sub-op to the partitioned replica may fail the 2PC
    cluster.network.heal()
    # force a map change so primaries re-peer
    cluster.mon._commit_map("nudge")
    cluster.settle(0.8)
    with pytest.raises(RadosError):
        client.read("rbd", "zombie")
    # and the lagging replica purged its copy
    from ceph_tpu.osd.objectstore import CollectionId as _C, ObjectId as _O
    if lagger in cluster.osds:
        assert not cluster.osds[lagger].store.exists(
            _C(pool_id, seed), _O("zombie"))


def test_client_retries_when_primary_dies(cluster):
    client = cluster.client()
    client.create_pool("rbd", size=3, pg_num=2)
    client.write_full("rbd", "obj", b"v1")
    pool_id = client._pool_id("rbd")
    seed = cluster.mon.osdmap.object_to_pg(pool_id, "obj")
    primary = cluster.mon.osdmap.pg_to_up_osds(pool_id, seed)[0]
    epoch = cluster.mon.osdmap.epoch
    cluster.kill_osd(primary)
    cluster.wait_for_epoch(epoch + 1)
    cluster.settle(0.3)
    assert client.read("rbd", "obj") == b"v1"  # re-targets new primary
