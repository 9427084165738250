"""Map machinery: pg_upmap overrides, primary affinity, the balancer,
and the durable KV store (ref OSDMap.cc:2779/3143 upmap + affinity,
mgr balancer module, src/kv/)."""

import numpy as np
import pytest

from ceph_tpu.osd.kvstore import KVTransaction, WalKV, create_kv
from ceph_tpu.tools.vstart import MiniCluster
from tests.test_cluster import make_cfg

RNG = np.random.default_rng(21)


# ------------------------------------------------------------------- kv
def test_walkv_durability_and_compaction(tmp_path):
    kv = WalKV(str(tmp_path))
    kv.submit(KVTransaction().put("meta", "a", b"1").put("meta", "b",
                                                        b"2"))
    kv.put("data", "x", b"payload")
    kv.rm("meta", "a")
    kv.close()
    kv2 = WalKV(str(tmp_path))
    assert kv2.get("meta", "a") is None
    assert kv2.get("meta", "b") == b"2"
    assert list(kv2.iterate("data")) == [("x", b"payload")]
    # churn forces snapshot compaction; state survives reopen
    for i in range(500):
        kv2.put("hot", "k", b"v%d" % i)
    import os
    size = os.path.getsize(str(tmp_path) + "/kv.wal")
    assert size < 100_000, size
    kv2.close()
    kv3 = WalKV(str(tmp_path))
    assert kv3.get("hot", "k") == b"v499"
    kv3.close()
    with pytest.raises(ValueError):
        create_kv("rocksdb")


def test_walkv_discards_torn_tail(tmp_path):
    kv = WalKV(str(tmp_path))
    kv.put("p", "k", b"good")
    kv.close()
    with open(str(tmp_path) + "/kv.wal", "ab") as f:
        f.write(b"\x50\x00\x00\x00\xba\xad" + b"torn")
    kv2 = WalKV(str(tmp_path))
    assert kv2.get("p", "k") == b"good"
    kv2.put("p", "k2", b"after")
    kv2.close()


# --------------------------------------------------------------- cluster
@pytest.fixture
def cluster():
    c = MiniCluster(n_osds=6, cfg=make_cfg()).start()
    yield c
    c.stop()


def _poll_read(client, pool, oid, want, timeout=30.0):
    """Read until the answer is ``want``: a PG that has just moved
    answers ENOENT or EAGAIN until its new members are backfilled."""
    import time

    from ceph_tpu.client.rados import RadosError
    got, deadline = None, time.time() + timeout
    while got != want and time.time() < deadline:
        try:
            got = client.read(pool, oid)
        except RadosError as e:
            got = e
        if got != want:
            time.sleep(0.1)
    assert got == want


def test_pg_upmap_moves_data(cluster):
    c = cluster
    client = c.client()
    client.create_pool("p", size=2, pg_num=1)
    data = RNG.integers(0, 256, 100_000, dtype=np.uint8).tobytes()
    client.write_full("p", "obj", data)
    c.settle(0.3)
    pool_id = client._pool_id("p")
    up = c.mon.osdmap.pg_to_up_osds(pool_id, 0)
    # move the PG to two osds NOT currently serving it
    others = [o for o in sorted(c.osds) if o not in up][:2]
    client.mon_command({"prefix": "osd pg-upmap", "pool": pool_id,
                        "seed": 0, "osds": others})
    assert c.mon.osdmap.pg_to_up_osds(pool_id, 0) == others
    # peering + backfill to the new members end on their own schedule
    _poll_read(client, "p", "obj", data)
    from ceph_tpu.osd.objectstore import CollectionId, ObjectId
    assert c.osds[others[0]].store.read(
        CollectionId(pool_id, 0), ObjectId("obj")).to_bytes() == data
    # rm-pg-upmap returns to computed placement
    client.mon_command({"prefix": "osd rm-pg-upmap", "pool": pool_id,
                        "seed": 0})
    assert c.mon.osdmap.pg_to_up_osds(pool_id, 0) == up
    _poll_read(client, "p", "obj", data)


def test_primary_affinity_shifts_primary(cluster):
    c = cluster
    client = c.client()
    client.create_pool("p", size=3, pg_num=1)
    client.write_full("p", "obj", b"affinity")
    pool_id = client._pool_id("p")
    up = c.mon.osdmap.pg_to_up_osds(pool_id, 0)
    old_primary = up[0]
    client.mon_command({"prefix": "osd primary-affinity",
                        "id": old_primary, "weight": 0.0})
    c.settle(0.5)
    up2 = c.mon.osdmap.pg_to_up_osds(pool_id, 0)
    assert up2[0] != old_primary
    assert sorted(up2) == sorted(up)  # same members, new leader
    assert client.read("p", "obj") == b"affinity"
    with pytest.raises(Exception):
        client.mon_command({"prefix": "osd primary-affinity",
                            "id": old_primary, "weight": 2.0})


def test_balancer_flattens_membership(cluster):
    c = cluster
    client = c.client()
    client.create_pool("p", size=2, pg_num=8)
    for i in range(8):
        client.write_full("p", f"o{i}", bytes([i]) * 5000)
    c.settle(0.3)
    pool_id = client._pool_id("p")

    def spread():
        counts = dict.fromkeys(sorted(c.osds), 0)
        for seed in range(8):
            for d in c.mon.osdmap.pg_to_up_osds(pool_id, seed):
                counts[d] += 1
        return max(counts.values()) - min(counts.values())

    before = spread()
    out = client.mon_command({"prefix": "balancer optimize",
                              "max_moves": 16})
    if before > 1:
        assert out["moves"], "imbalance existed but no moves proposed"
    assert spread() <= max(1, before)
    c.settle(1.5)
    for i in range(8):
        assert client.read("p", f"o{i}") == bytes([i]) * 5000


def test_upmap_redraws_dead_members(cluster):
    """A dead OSD pinned by an upmap must not leave the PG degraded:
    healthy replacements are drawn like normal placement."""
    c = cluster
    client = c.client()
    client.create_pool("p", size=2, pg_num=1)
    client.write_full("p", "o", b"upmap-death")
    pool_id = client._pool_id("p")
    others = [o for o in sorted(c.osds)][:2]
    client.mon_command({"prefix": "osd pg-upmap", "pool": pool_id,
                        "seed": 0, "osds": others})
    c.settle(1.0)
    epoch = c.mon.osdmap.epoch
    c.kill_osd(others[0])
    c.wait_for_epoch(epoch + 1)
    c.settle(1.0)
    up = c.mon.osdmap.pg_to_up_osds(pool_id, 0)
    assert len(up) == 2 and others[0] not in up
    assert client.read("p", "o") == b"upmap-death"
