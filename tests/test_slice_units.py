"""Unit tests for the cluster-slice components: objectstore transactions,
placement determinism/balance, map encode/decode, messenger faults."""

import collections
import threading

import numpy as np
import pytest

from ceph_tpu.mon.maps import OSDMap, PoolSpec
from ceph_tpu.msg.messenger import Dispatcher, LocalNetwork, Messenger, Policy
from ceph_tpu.osd.objectstore import (CollectionId, NoSuchCollection,
                                      NoSuchObject, ObjectId, ObjectStore,
                                      Transaction)
from ceph_tpu.parallel.placement import (PlacementMap, pg_of_object,
                                         stable_mod)

CID = CollectionId(1, 0)
OID = ObjectId("foo")


# ------------------------------------------------------------- objectstore
def make_store():
    s = ObjectStore.create("memstore")
    s.mount()
    s.queue_transaction(Transaction().create_collection(CID))
    return s


def test_store_write_read_roundtrip():
    s = make_store()
    s.queue_transaction(Transaction().write(CID, OID, 0, b"hello"))
    assert s.read(CID, OID).to_bytes() == b"hello"
    s.queue_transaction(Transaction().write(CID, OID, 3, b"XY"))
    assert s.read(CID, OID).to_bytes() == b"helXY"
    s.queue_transaction(Transaction().zero(CID, OID, 1, 2))
    assert s.read(CID, OID).to_bytes() == b"h\0\0XY"
    assert s.read(CID, OID, 1, 3).to_bytes() == b"\0\0X"


def test_store_tx_atomicity():
    """A failing op mid-transaction must leave no partial effects."""
    s = make_store()
    tx = (Transaction().write(CID, OID, 0, b"data")
          .clone(CID, ObjectId("missing"), ObjectId("dst")))
    with pytest.raises(NoSuchObject):
        s.queue_transaction(tx)
    assert not s.exists(CID, OID)  # the write did not apply


def test_store_tx_intra_dependencies():
    """touch -> truncate -> write -> clone inside ONE tx must validate."""
    s = make_store()
    tx = (Transaction().touch(CID, OID).truncate(CID, OID, 0)
          .write(CID, OID, 0, b"abc").clone(CID, OID, ObjectId("copy"))
          .setattrs(CID, OID, {"v": 1}))
    s.queue_transaction(tx)
    assert s.read(CID, ObjectId("copy")).to_bytes() == b"abc"
    assert s.getattrs(CID, OID) == {"v": 1}


def test_store_omap_and_attrs():
    s = make_store()
    s.queue_transaction(
        Transaction().touch(CID, OID)
        .omap_setkeys(CID, OID, {"k1": b"v1", "k2": b"v2"})
        .setattrs(CID, OID, {"a": b"b"}))
    assert s.omap_get(CID, OID) == {"k1": b"v1", "k2": b"v2"}
    s.queue_transaction(Transaction().omap_rmkeys(CID, OID, ["k1"]))
    assert s.omap_get(CID, OID) == {"k2": b"v2"}


def test_store_collections():
    s = make_store()
    with pytest.raises(NoSuchCollection):
        s.read(CollectionId(9, 9), OID)
    s.queue_transaction(Transaction().remove_collection(CID))
    assert s.list_collections() == []


def test_store_commit_callback():
    s = make_store()
    fired = []
    s.queue_transaction(Transaction().touch(CID, OID),
                        on_commit=lambda: fired.append(1))
    assert fired == [1]


# --------------------------------------------------------------- placement
def test_stable_mod_matches_semantics():
    # b=6: bmask=7; values with (x&7) >= 6 fall back to x&3
    for x in range(64):
        got = stable_mod(x, 6, 7)
        want = (x & 7) if (x & 7) < 6 else (x & 3)
        assert got == want


def test_pg_of_object_range_and_determinism():
    for pg_num in (1, 3, 8, 15, 32):
        seen = set()
        for i in range(500):
            pg = pg_of_object(f"obj{i}", pg_num)
            assert 0 <= pg < pg_num
            seen.add(pg)
        assert len(seen) == pg_num  # all pgs hit
    assert pg_of_object("x", 8) == pg_of_object("x", 8)


@pytest.mark.parametrize("fmt, count", [
    ("obj%07d", 1000),            # the benchmark's records
    ("obj%07d", 256),             # its write ring
    ("rbd_data.1234abcd.%016x", 512),
    ("benchmark_data_host_12345_object%d", 512),
])
def test_object_hash_full_reads_the_whole_name(fmt, count):
    """A pool's ``object_hash=full`` spreads names that differ only
    past their eighth byte; the default, ``first8``, hashes them as it
    always did (1000 names ``obj%07d`` on a few PGs of 32)."""
    names = [fmt % i for i in range(count)]
    full = collections.Counter(pg_of_object(n, 32, "full") for n in names)
    assert len(full) == 32
    assert max(full.values()) <= 3 * count / 32
    first8 = collections.Counter(pg_of_object(n, 32) for n in names)
    assert len(first8) <= 16
    assert [pg_of_object(n, 32, "first8") for n in names] \
        == [pg_of_object(n, 32) for n in names]
    # every byte and the length count
    assert len({pg_of_object("abcdefgh" + "x" * n, 1 << 16, "full")
                for n in range(24)}) >= 23
    assert pg_of_object("obj1", 1 << 16) == 0x61df      # as ever


#: name -> its PG of 32, of 65536 and of 12, as ``pg_of_object`` gave
#: them before a pool had an ``object_hash`` (computed on that tree)
FIRST8_PINS = {
    "obj0000000": (20, 34196, 4), "obj0000001": (20, 34196, 4),
    "obj0000999": (9, 15177, 9), "obj1": (31, 25055, 7),
    "a": (20, 11252, 4), "": (7, 15815, 7),
    "rbd_data.1234abcd.0000000000000000": (7, 16743, 7),
    "rbd_data.1234abcd.00000000000000ff": (7, 16743, 7),
    "benchmark_data_host_12345_object7": (22, 28342, 6),
    "x" * 7: (7, 58887, 7), "x" * 8: (17, 31345, 1),
    "x" * 9: (8, 49768, 8), "h\u00e9llo-w\u00f6rld": (18, 11506, 2),
    "bucket/key/with/slashes.bin": (27, 27771, 11),
}


@pytest.mark.parametrize("object_hash", [None, "first8"])
def test_object_hash_first8_places_every_name_where_it_lay(object_hash):
    """The default moves no object of any pool that exists."""
    args = () if object_hash is None else (object_hash,)
    for name, pins in FIRST8_PINS.items():
        assert tuple(pg_of_object(name, n, *args)
                     for n in (32, 1 << 16, 12)) == pins, name
    # the benchmark's names: records, write ring, degraded read
    assert [len({pg_of_object("obj%07d" % i, 32, *args)
                 for i in range(n)}) for n in (1000, 256, 512)] == [7, 2, 4]


def test_placement_distinct_hosts_and_determinism():
    pm = PlacementMap()
    for i in range(12):
        pm.add_device(i, 1.0, host=f"host{i % 6}")
    sel = pm.select(12345, 3)
    assert len(sel) == 3 == len(set(sel))
    hosts = {pm.devices[d].host for d in sel}
    assert len(hosts) == 3  # failure-domain separation
    assert sel == pm.select(12345, 3)  # pure function


def test_placement_balance_and_weights():
    pm = PlacementMap()
    for i in range(8):
        pm.add_device(i, 2.0 if i == 0 else 1.0, host=f"host{i}")
    counts = collections.Counter()
    for key in range(2000):
        for d in pm.select(key, 3):
            counts[d] += 1
    # the double-weight device gets roughly double a normal one's share
    normal = sum(counts[i] for i in range(1, 8)) / 7
    assert counts[0] / normal > 1.4
    # every device participates meaningfully
    assert min(counts.values()) > 0.3 * normal


def test_placement_stability_under_rejection():
    """Down devices are re-drawn; surviving members keep positions."""
    pm = PlacementMap()
    for i in range(10):
        pm.add_device(i, 1.0, host=f"host{i}")
    base = pm.select(999, 4)
    down = {base[1]}
    degraded = pm.select(999, 4, reject=lambda d: d in down)
    assert base[0] in degraded
    assert base[2] in degraded and base[3] in degraded
    assert down.isdisjoint(degraded)


# -------------------------------------------------------------------- maps
def test_osdmap_encode_decode_roundtrip():
    m = OSDMap()
    for i in range(4):
        m.add_osd(i, f"host{i}", f"osd.{i}")
        m.mark_up(i)
    m.mark_down(3)
    m.add_pool(PoolSpec(1, "rbd", "replicated", 3, 2, 8))
    m.add_pool(PoolSpec(2, "ec", "ec", 6, 4, 4,
                        {"plugin": "jerasure", "k": "4", "m": "2"}))
    m.epoch = 17
    m2 = OSDMap.decode_bytes(m.encode_bytes())
    assert m2.epoch == 17
    assert m2.osds[3].up is False and m2.osds[0].up is True
    assert m2.pools[2].ec_profile["k"] == "4"
    assert m2.pg_to_osds(1, 3) == m.pg_to_osds(1, 3)


def test_osdmap_ec_holes_keep_positions():
    m = OSDMap()
    for i in range(6):
        m.add_osd(i, f"host{i}")
        m.mark_up(i)
    m.add_pool(PoolSpec(1, "ec", "ec", 5, 4, 1))
    up = m.pg_to_up_osds(1, 0)
    assert len(up) == 5
    victim_pos = 2
    m.mark_down(up[victim_pos])
    up2 = m.pg_to_up_osds(1, 0)
    for pos in range(5):
        if pos != victim_pos:
            assert up2[pos] == up[pos]  # shard positions stable
    assert up2[victim_pos] != up[victim_pos]  # hole filled by spare or None


# --------------------------------------------------------------- messenger
class Echo(Dispatcher):
    def __init__(self):
        self.got = []
        self.event = threading.Event()

    def ms_dispatch(self, conn, msg):
        self.got.append(msg)
        if msg == "ping":
            conn.send("pong")
        self.event.set()
        return True


def test_messenger_roundtrip():
    net = LocalNetwork()
    a, b = Echo(), Echo()
    ma = Messenger(net, "a")
    mb = Messenger(net, "b")
    ma.add_dispatcher(a)
    mb.add_dispatcher(b)
    ma.start()
    mb.start()
    ma.send_message("b", "ping")
    assert b.event.wait(2) and a.event.wait(2)
    assert b.got == ["ping"] and a.got == ["pong"]
    ma.shutdown()
    mb.shutdown()
    assert net.lookup("a") is None


def test_messenger_partition_and_drops():
    net = LocalNetwork(seed=1)
    recv = Echo()
    m1 = Messenger(net, "one")
    m2 = Messenger(net, "two")
    m2.add_dispatcher(recv)
    m2.start()
    net.partition("one", "two")
    m1.send_message("two", "lost")
    net.heal()
    m1.send_message("two", "found")
    assert recv.event.wait(2)
    assert recv.got == ["found"]
    # probabilistic drops count
    net.drop_rate = 1.0
    m1.send_message("two", "gone")
    assert net.dropped >= 2
    m1.shutdown()
    m2.shutdown()


def test_messenger_duplicate_entity_rejected():
    net = LocalNetwork()
    m1 = Messenger(net, "dup")
    with pytest.raises(ValueError):
        Messenger(net, "dup")
    m1.shutdown()
