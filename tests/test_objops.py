"""PrimaryLogPG op breadth: omap client ops, watch/notify, object
classes (ref do_osd_ops op-switch :6163, Watch.cc, ClassHandler/cls).
"""

import time

import pytest

from ceph_tpu.client.rados import RadosError
from ceph_tpu.tools.vstart import MiniCluster
from tests.test_cluster import make_cfg


@pytest.fixture
def cluster():
    c = MiniCluster(n_osds=6, cfg=make_cfg()).start()
    yield c
    c.stop()


def test_omap_ops_replicate(cluster):
    client = cluster.client()
    client.create_pool("p", size=3, pg_num=2)
    client.write_full("p", "o", b"body")
    client.omap_set("p", "o", {"a": b"1", "b": b"2"})
    client.omap_set("p", "o", {"b": b"22", "c": b"3"})
    client.omap_rm("p", "o", ["a"])
    assert client.omap_get("p", "o") == {"b": b"22", "c": b"3"}
    # omap on an object that only exists through omap
    client.omap_set("p", "fresh", {"k": b"v"})
    assert client.omap_get("p", "fresh") == {"k": b"v"}
    # replicas carry the omap: kill the primary, read from the new one
    pool_id = client._pool_id("p")
    seed = cluster.mon.osdmap.object_to_pg(pool_id, "o")
    up = cluster.mon.osdmap.pg_to_up_osds(pool_id, seed)
    cluster.settle(0.3)
    epoch = cluster.mon.osdmap.epoch
    cluster.kill_osd(up[0])
    cluster.wait_for_epoch(epoch + 1)
    cluster.settle(0.5)
    assert client.omap_get("p", "o") == {"b": b"22", "c": b"3"}


def test_omap_supported_on_ec_pool(cluster):
    """EC pools journal omap via ECOmapJournal (reference: optimized EC
    path, src/osd/ECOmapJournal.cc) — the old rejection contract is gone.
    Deep coverage lives in tests/test_ec_omap.py; this asserts the
    general-objops surface agrees."""
    client = cluster.client()
    client.create_pool("ec", kind="ec", pg_num=1,
                       ec_profile={"plugin": "jerasure", "k": "3",
                                   "m": "2", "backend": "native"})
    client.write_full("ec", "o", b"x")
    client.omap_set("ec", "o", {"k": b"v"})
    assert client.omap_get("ec", "o") == {"k": b"v"}


def test_watch_notify_roundtrip(cluster):
    client_a = cluster.client()
    client_b = cluster.client()
    notifier = cluster.client()
    client_a.create_pool("p", size=2, pg_num=1)
    client_a.write_full("p", "obj", b"watched")
    got_a, got_b = [], []
    client_a.watch("p", "obj", lambda o, n, p: got_a.append((o, n, p)))
    client_b.watch("p", "obj", lambda o, n, p: got_b.append((o, n, p)))
    acked = notifier.notify("p", "obj", b"hello-watchers")
    assert sorted(acked) == sorted([client_a.name, client_b.name])
    assert got_a == [("obj", notifier.name, b"hello-watchers")]
    assert got_b == [("obj", notifier.name, b"hello-watchers")]
    # a watcher notifying does not notify itself
    acked = client_a.notify("p", "obj", b"again")
    assert acked == [client_b.name]
    assert len(got_a) == 1 and len(got_b) == 2
    # unwatch stops delivery
    client_b.unwatch("p", "obj")
    acked = notifier.notify("p", "obj", b"final")
    assert acked == [client_a.name]
    assert len(got_b) == 2


def test_watch_survives_primary_failover(cluster):
    """Watches are primary-local soft state; the client re-registers on
    map change (the linger-op semantic)."""
    watcher = cluster.client()
    notifier = cluster.client()
    watcher.create_pool("p", size=3, pg_num=1)
    watcher.write_full("p", "obj", b"x")
    got = []
    watcher.watch("p", "obj", lambda o, n, p: got.append(p))
    pool_id = watcher._pool_id("p")
    up = cluster.mon.osdmap.pg_to_up_osds(pool_id, 0)
    epoch = cluster.mon.osdmap.epoch
    cluster.kill_osd(up[0])
    cluster.wait_for_epoch(epoch + 1)
    cluster.settle(0.8)
    deadline = time.time() + 10
    while time.time() < deadline:
        acked = notifier.notify("p", "obj", b"post-failover")
        if watcher.name in acked:
            break
        time.sleep(0.2)
    assert got and got[-1] == b"post-failover"


def test_cls_lock_and_version(cluster):
    client = cluster.client()
    other = cluster.client()
    client.create_pool("p", size=2, pg_num=1)
    client.write_full("p", "obj", b"locked-thing")
    # exclusive lock: second owner bounces with EBUSY
    out = client.cls_call("p", "obj", "lock", "lock",
                          {"name": "l1", "owner": "alice"})
    assert out["owners"] == ["alice"]
    with pytest.raises(RadosError) as ei:
        other.cls_call("p", "obj", "lock", "lock",
                       {"name": "l1", "owner": "bob"})
    assert ei.value.code == -16
    info = client.cls_call("p", "obj", "lock", "info", {"name": "l1"})
    assert info["owners"] == ["alice"]
    client.cls_call("p", "obj", "lock", "unlock",
                    {"name": "l1", "owner": "alice"})
    out = other.cls_call("p", "obj", "lock", "lock",
                         {"name": "l1", "owner": "bob",
                          "exclusive": False})
    assert out["owners"] == ["bob"]
    # shared lock admits more owners
    out = client.cls_call("p", "obj", "lock", "lock",
                          {"name": "l1", "owner": "carol",
                           "exclusive": False})
    assert sorted(out["owners"]) == ["bob", "carol"]
    other.cls_call("p", "obj", "lock", "break_lock", {"name": "l1"})
    # cls_version: cas-guarded counter
    assert client.cls_call("p", "obj", "version", "read")["ver"] == 0
    assert client.cls_call("p", "obj", "version", "inc")["ver"] == 1
    with pytest.raises(RadosError) as ei:
        client.cls_call("p", "obj", "version", "inc", {"expect": 0})
    assert ei.value.code == -125
    assert client.cls_call("p", "obj", "version", "inc",
                           {"expect": 1})["ver"] == 2
    # unknown class/method is a clean error
    with pytest.raises(RadosError):
        client.cls_call("p", "obj", "nope", "zip")


def test_cls_effects_replicate(cluster):
    """Class-method mutations ride the replicated write path: a lock
    taken before the primary dies is still held after failover."""
    client = cluster.client()
    client.create_pool("p", size=3, pg_num=1)
    client.write_full("p", "obj", b"x")
    client.cls_call("p", "obj", "lock", "lock",
                    {"name": "ha", "owner": "alice"})
    cluster.settle(0.3)
    pool_id = client._pool_id("p")
    up = cluster.mon.osdmap.pg_to_up_osds(pool_id, 0)
    epoch = cluster.mon.osdmap.epoch
    cluster.kill_osd(up[0])
    cluster.wait_for_epoch(epoch + 1)
    cluster.settle(0.5)
    info = client.cls_call("p", "obj", "lock", "info", {"name": "ha"})
    assert info["owners"] == ["alice"]
    with pytest.raises(RadosError):
        client.cls_call("p", "obj", "lock", "lock",
                        {"name": "ha", "owner": "bob"})


def test_omap_survives_backfill(cluster):
    """A revived-empty replica gets the omap back with the object
    (recovery pushes carry omap, not just data)."""
    client = cluster.client()
    client.create_pool("p", size=3, pg_num=1)
    client.write_full("p", "o", b"body")
    client.omap_set("p", "o", {"k1": b"v1", "k2": b"v2"})
    cluster.settle(0.3)
    pool_id = client._pool_id("p")
    up = cluster.mon.osdmap.pg_to_up_osds(pool_id, 0)
    victim = up[-1]
    epoch = cluster.mon.osdmap.epoch
    cluster.kill_osd(victim)
    cluster.wait_for_epoch(epoch + 1)
    # ... and until the map has reached the OSDs: a primary that still
    # counts the victim in sends it a sub-op nobody answers
    deadline = time.time() + 10
    while time.time() < deadline and any(
            o.osdmap.epoch <= epoch for o in cluster.osds.values()):
        time.sleep(0.01)
    client.omap_set("p", "o", {"k3": b"v3"})  # moves on while down
    cluster.revive_osd(victim)
    cluster.wait_for_epoch(epoch + 2)
    from ceph_tpu.osd.objectstore import CollectionId, ObjectId
    want = {"k1": b"v1", "k2": b"v2", "k3": b"v3"}
    # the push arrives on recovery's schedule, not a settle's
    got, deadline = None, time.time() + 30
    while got != want and time.time() < deadline:
        try:
            got = cluster.osds[victim].store.omap_get(
                CollectionId(pool_id, 0), ObjectId("o"))
        except Exception as e:  # noqa: BLE001 - not pushed yet
            got = e
        if got != want:
            time.sleep(0.1)
    assert got == want
