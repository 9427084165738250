"""BlueStore-lite tests: allocator reuse and COW clones, deferred-write
crash replay, large-write ordering, checksum verification on every read,
remount fidelity, fsck invariants, and a cluster run on bluestore OSDs."""

import os

import numpy as np
import pytest

from ceph_tpu.osd.bluestore import HOLE, PAGE, BlueStore
from ceph_tpu.osd.objectstore import (CollectionId, NoSuchObject, ObjectId,
                                      ObjectStore, StoreError, Transaction)

CID = CollectionId(1, 0)
OID = ObjectId("obj", shard=2)
RNG = np.random.default_rng(77)


def fresh(tmp_path, name="bs", **kw) -> BlueStore:
    s = BlueStore(str(tmp_path / name), **kw)
    s.mount()
    return s


def test_basic_write_read_remount(tmp_path):
    s = fresh(tmp_path)
    data = RNG.integers(0, 256, 3 * PAGE + 123, dtype=np.uint8).tobytes()
    s.queue_transaction(
        Transaction().create_collection(CID).touch(CID, OID)
        .write(CID, OID, 0, data).setattrs(CID, OID, {"v": 3})
        .omap_setkeys(CID, OID, {"k1": b"v1", "k2": b"v2"}))
    assert s.read(CID, OID).to_bytes() == data
    assert s.read(CID, OID, PAGE - 10, 20).to_bytes() == data[PAGE - 10:PAGE + 10]
    s.umount()
    s2 = BlueStore(s.path)
    s2.mount()
    assert s2.read(CID, OID).to_bytes() == data
    assert s2.getattrs(CID, OID)["v"] == 3
    assert s2.omap_get(CID, OID) == {"k1": b"v1", "k2": b"v2"}
    assert s2.list_objects(CID) == [OID]
    assert s2.stat(CID, OID)["size"] == len(data)
    s2.umount()


def test_small_overwrite_is_deferred_and_replayed(tmp_path):
    """A committed deferred write whose device write never happened must
    replay from the KV 'D' records at mount."""
    s = fresh(tmp_path, defer_limit=PAGE)  # base write takes the large path
    base = b"A" * (2 * PAGE)
    s.queue_transaction(Transaction().create_collection(CID)
                        .write(CID, OID, 0, base))
    assert not s._deferred
    s.queue_transaction(Transaction().write(CID, OID, 100, b"deferred!"))
    assert s._deferred, "small overwrite should sit in the deferred set"
    # simulate the crash: clobber the device page the deferred write
    # targeted (as if the write never reached the platter), keep the KV
    [(phys, content)] = list(s._deferred.items())
    s._dev_write(phys, b"\0" * PAGE)
    s._dev.flush()
    os.fsync(s._dev.fileno())
    s._dev.close()  # bypass umount: umount would flush properly
    s._kv.close()
    s._mounted = False
    s2 = BlueStore(s.path)
    s2.mount()
    want = bytearray(base)
    want[100:109] = b"deferred!"
    assert s2.read(CID, OID).to_bytes() == bytes(want)
    s2.umount()


def test_large_write_allocates_fresh_pages(tmp_path):
    """Large writes are COW: the page map must point at different pages
    after a full overwrite, and the old pages return to the allocator."""
    s = fresh(tmp_path, defer_limit=PAGE - 1)
    data1 = b"x" * (4 * PAGE)
    data2 = b"y" * (4 * PAGE)
    s.queue_transaction(Transaction().create_collection(CID)
                        .write(CID, OID, 0, data1))
    pages1 = [p for p, _ in s._colls[CID][OID].pages]
    s.queue_transaction(Transaction().write(CID, OID, 0, data2))
    pages2 = [p for p, _ in s._colls[CID][OID].pages]
    assert set(pages1).isdisjoint(set(pages2))
    assert s.read(CID, OID).to_bytes() == data2
    # old pages are reusable
    free = set(s._free)
    assert set(pages1) <= free
    s.umount()


def test_clone_shares_pages_and_cows(tmp_path):
    s = fresh(tmp_path)
    a, b = ObjectId("a"), ObjectId("b")
    data = RNG.integers(0, 256, 2 * PAGE, dtype=np.uint8).tobytes()
    s.queue_transaction(Transaction().create_collection(CID)
                        .write(CID, a, 0, data)
                        .omap_setkeys(CID, a, {"k": b"v"}))
    s.queue_transaction(Transaction().clone(CID, a, b))
    pa = [p for p, _ in s._colls[CID][a].pages]
    pb = [p for p, _ in s._colls[CID][b].pages]
    assert pa == pb, "clone must share pages"
    assert all(s._refs[p] == 2 for p in pa)
    # write to the clone: COW, original untouched
    s.queue_transaction(Transaction().write(CID, b, 0, b"Z" * 10))
    assert s.read(CID, a).to_bytes() == data
    got = s.read(CID, b).to_bytes()
    assert got[:10] == b"Z" * 10 and got[10:] == data[10:]
    assert s.omap_get(CID, b) == {"k": b"v"}
    # remove the original: shared pages must survive for the clone
    s.queue_transaction(Transaction().remove(CID, a))
    assert s.read(CID, b).to_bytes() == got
    s.umount()
    s2 = BlueStore(s.path)
    s2.mount()
    assert s2.read(CID, b).to_bytes() == got
    with pytest.raises(NoSuchObject):
        s2.read(CID, a)
    s2.umount()


def test_checksum_detects_bitrot(tmp_path):
    s = fresh(tmp_path)
    data = b"Q" * (3 * PAGE)
    s.queue_transaction(Transaction().create_collection(CID)
                        .write(CID, OID, 0, data))
    s.umount()
    s2 = BlueStore(s.path)
    s2.mount()
    phys = s2._colls[CID][OID].pages[1][0]
    with open(os.path.join(s2.path, "block.img"), "r+b") as f:
        f.seek(phys * PAGE + 17)
        f.write(b"\xff")
    assert not s2.deep_verify(CID, OID)
    with pytest.raises(StoreError, match="checksum"):
        s2.read(CID, OID)
    # unaffected pages still read fine
    assert s2.read(CID, OID, 0, PAGE).to_bytes() == data[:PAGE]
    s2.umount()


def test_read_joins_back_to_back_pages_and_keeps_every_check(tmp_path):
    """A read takes the device pages that lie back to back in one call
    and still verifies each page's crc; pages elsewhere on the device,
    holes and deferred pages still read as they were written."""
    s = fresh(tmp_path, defer_limit=PAGE)
    want = bytearray(RNG.integers(0, 256, 8 * PAGE, dtype=np.uint8))
    s.queue_transaction(Transaction().create_collection(CID)
                        .write(CID, OID, 0, bytes(want)))
    # pages 2-3 move to fresh pages, page 5 takes the deferred path,
    # and a write past the end leaves pages 8-9 holes
    moved = bytes(RNG.integers(0, 256, 2 * PAGE, dtype=np.uint8))
    s.queue_transaction(Transaction().write(CID, OID, 2 * PAGE, moved))
    s.queue_transaction(Transaction().write(CID, OID, 5 * PAGE + 9,
                                            b"deferred"))
    s.queue_transaction(Transaction().write(CID, OID, 10 * PAGE, b"end"))
    want[2 * PAGE:4 * PAGE] = moved
    want[5 * PAGE + 9:5 * PAGE + 17] = b"deferred"
    want += b"\0" * (2 * PAGE) + b"end"
    assert s._deferred
    reads = []
    real = s._dev.read
    s._dev.read = lambda n: reads.append(n) or real(n)
    assert s.read(CID, OID).to_bytes() == bytes(want)
    # 0-1, 2-3 (moved), 4, 6-7: page 5 comes from the overlay
    assert reads == [2 * PAGE, 2 * PAGE, PAGE, 2 * PAGE]
    assert s.read(CID, OID, 3 * PAGE + 5, 5 * PAGE).to_bytes() \
        == bytes(want[3 * PAGE + 5:8 * PAGE + 5])
    s._dev.read = real
    s.umount()
    # a flipped byte inside a joined run fails the read
    s2 = BlueStore(s.path)
    s2.mount()
    phys = s2._colls[CID][OID].pages[7][0]
    with open(os.path.join(s2.path, "block.img"), "r+b") as f:
        f.seek(phys * PAGE + 100)
        f.write(bytes([want[7 * PAGE + 100] ^ 1]))
    assert s2.read(CID, OID, 0, 7 * PAGE).to_bytes() == bytes(
        want[:7 * PAGE])
    with pytest.raises(StoreError, match="checksum"):
        s2.read(CID, OID, 5 * PAGE, 3 * PAGE)
    s2.umount()


def test_zero_truncate_semantics(tmp_path):
    s = fresh(tmp_path)
    s.queue_transaction(Transaction().create_collection(CID)
                        .write(CID, OID, 0, b"ab" * PAGE))
    # full-page zero punches a hole
    s.queue_transaction(Transaction().zero(CID, OID, 0, PAGE))
    assert s._colls[CID][OID].pages[0][0] == HOLE
    assert s.read(CID, OID, 0, PAGE).to_bytes() == b"\0" * PAGE
    # truncate down into a page, then grow: the tail must read zeros
    s.queue_transaction(Transaction().truncate(CID, OID, PAGE + 10))
    s.queue_transaction(Transaction().truncate(CID, OID, 2 * PAGE))
    got = s.read(CID, OID).to_bytes()
    assert len(got) == 2 * PAGE
    assert got[PAGE + 10:] == b"\0" * (PAGE - 10)
    assert got[PAGE:PAGE + 10] == b"ab" * 5
    s.umount()


def test_rejected_tx_rolls_back_allocations(tmp_path):
    s = fresh(tmp_path)
    s.queue_transaction(Transaction().create_collection(CID))
    refs_before = dict(s._refs)
    # write stages allocations, then the clone of a missing src fails
    with pytest.raises(NoSuchObject):
        s.queue_transaction(
            Transaction().write(CID, OID, 0, b"W" * (2 * PAGE))
            .clone(CID, ObjectId("missing"), ObjectId("dst")))
    assert not s.exists(CID, OID)
    assert s._refs == refs_before
    # every device page is back on the freelist (the tx may have grown
    # the device; growth itself is not a leak)
    assert len(s._free) == s._npages
    s.umount()


def test_fsck_clean_and_allocator_rebuild(tmp_path):
    s = fresh(tmp_path)
    for i in range(5):
        s.queue_transaction(
            Transaction().create_collection(CollectionId(1, i))
            .write(CollectionId(1, i), ObjectId(f"o{i}"), 0,
                   bytes([i]) * (PAGE + i)))
    s.queue_transaction(Transaction().remove(CollectionId(1, 2),
                                             ObjectId("o2")))
    rep = s.fsck()
    assert not rep["leaked"] and not rep["double_booked"] \
        and not rep["bad_refcounts"]
    used = dict(s._refs)
    s.umount()
    s2 = BlueStore(s.path)
    s2.mount()
    assert s2._refs == used, "mount must rebuild identical refcounts"
    rep2 = s2.fsck()
    assert not rep2["leaked"] and not rep2["bad_refcounts"]
    s2.umount()


def test_crash_between_data_write_and_kv_commit_leaks_nothing(tmp_path):
    """Large-path ordering: data hits fresh pages before the KV commit.
    If the KV commit never happens, mount reclaims those pages."""
    s = fresh(tmp_path, defer_limit=0)
    s.queue_transaction(Transaction().create_collection(CID)
                        .write(CID, OID, 0, b"1" * PAGE))
    # simulate: write pages directly without any KV commit (the crash
    # window), by writing garbage to a freshly popped free page
    import heapq
    phys = heapq.heappop(s._free)
    s._dev_write(phys, b"g" * PAGE)
    s._dev.flush()
    s._dev.close()
    s._kv.close()
    s._mounted = False
    s2 = BlueStore(s.path)
    s2.mount()
    assert phys in set(s2._free), "leaked page must be reclaimed"
    assert s2.read(CID, OID).to_bytes() == b"1" * PAGE
    s2.umount()


def test_remove_collection_frees_everything(tmp_path):
    s = fresh(tmp_path)
    s.queue_transaction(Transaction().create_collection(CID))
    for i in range(3):
        s.queue_transaction(Transaction().touch(CID, ObjectId(f"o{i}")))
    s.queue_transaction(
        Transaction().write(CID, ObjectId("big"), 0, b"B" * (8 * PAGE)))
    s.queue_transaction(Transaction().remove_collection(CID))
    assert s.list_collections() == []
    assert not s._refs
    s.umount()
    s2 = BlueStore(s.path)
    s2.mount()
    assert s2.list_collections() == []
    s2.umount()


def test_remove_collection_atomic_with_same_tx_create(tmp_path):
    """An object created earlier in the SAME transaction must die with
    the collection: nothing may leak or resurrect on remount."""
    s = fresh(tmp_path)
    s.queue_transaction(
        Transaction().create_collection(CID).write(CID, OID, 0, b"x" * 5000)
        .omap_setkeys(CID, OID, {"k": b"v"}).remove_collection(CID))
    assert s.list_collections() == []
    rep = s.fsck()
    assert not rep["leaked"] and not s._refs
    s.umount()
    s2 = BlueStore(s.path)
    s2.mount()
    assert s2.list_collections() == []
    assert not s2.exists(CID, OID)
    s2.umount()


def test_scrub_repairs_bluestore_bitrot(tmp_path):
    """Deep scrub detects device-level rot on a bluestore replica (the
    read fails its checksum) and repair rewrites it from a good copy."""
    from ceph_tpu.msg.messages import PgId
    from ceph_tpu.osd.daemon import OSDDaemon
    from ceph_tpu.tools.vstart import MiniCluster
    from tests.test_cluster import make_cfg

    cfg = make_cfg()
    c = MiniCluster(n_osds=0, cfg=cfg)
    c.mon.start()
    for i in range(3):
        st = ObjectStore.create("bluestore", path=str(tmp_path / f"osd{i}"))
        osd = OSDDaemon(i, c.network, cfg=cfg, store=st, host=f"host{i}")
        c.osds[i] = osd
        osd.start()
    c.wait_for_up(3)
    client = c.client()
    client.create_pool("rbd", size=3, pg_num=1)
    payload = RNG.integers(0, 256, 9000, dtype=np.uint8).tobytes()
    client.write_full("rbd", "victim", payload)
    c.settle(0.3)
    pool_id = client._pool_id("rbd")
    seed = c.mon.osdmap.object_to_pg(pool_id, "victim")
    up = c.mon.osdmap.pg_to_up_osds(pool_id, seed)
    target = c.osds[up[1]]
    assert target.inject.corrupt_object(target.store, PgId(pool_id, seed),
                                        "victim", shard=-1, offset=4200)
    res = client.scrub_pg("rbd", seed, deep=True)
    assert res.inconsistencies, "rot must be detected"
    res = client.scrub_pg("rbd", seed, deep=True, repair=True)
    assert res.repaired >= 1
    c.settle(0.3)
    assert client.scrub_pg("rbd", seed, deep=True).inconsistencies == []
    assert client.read("rbd", "victim") == payload
    c.stop()


@pytest.mark.slow
def test_cluster_on_bluestore(tmp_path):
    """EC pool over bluestore OSDs: write, kill two shard holders, read
    back reconstructed — then full cluster restart on the same stores."""
    from ceph_tpu.osd.daemon import OSDDaemon
    from ceph_tpu.tools.vstart import MiniCluster
    from tests.test_cluster import make_cfg

    stores = {i: str(tmp_path / f"osd{i}") for i in range(6)}
    cfg = make_cfg()
    c = MiniCluster(n_osds=0, cfg=cfg)
    c.mon.start()
    for i in range(6):
        st = ObjectStore.create("bluestore", path=stores[i])
        osd = OSDDaemon(i, c.network, cfg=cfg, store=st, host=f"host{i}")
        c.osds[i] = osd
        osd.start()
    c.wait_for_up(6)
    client = c.client()
    client.create_pool("ec", kind="ec",
                       ec_profile={"plugin": "jerasure", "k": "4", "m": "2",
                                   "backend": "numpy"})
    payload = RNG.integers(0, 256, 200_000, dtype=np.uint8).tobytes()
    client.write_full("ec", "obj", payload)
    c.kill_osd(0)
    c.kill_osd(1)
    assert client.read("ec", "obj") == payload
    c.stop()

    c2 = MiniCluster(n_osds=0, cfg=cfg)
    c2.mon.start()
    for i in range(6):
        st = ObjectStore.create("bluestore", path=stores[i])
        osd = OSDDaemon(i, c2.network, cfg=cfg, store=st, host=f"host{i}")
        c2.osds[i] = osd
        osd.start()
    c2.wait_for_up(6)
    client2 = c2.client()
    client2.create_pool("ec", kind="ec",
                        ec_profile={"plugin": "jerasure", "k": "4", "m": "2",
                                    "backend": "numpy"})
    assert client2.read("ec", "obj") == payload
    c2.stop()


def test_inline_compression_blob_roundtrip(tmp_path):
    """Compressible large writes store as blobs in fewer device pages
    (Compression.cc role); reads are byte-exact; overwrites
    materialise; clones share blob pages; fsck stays clean."""
    from ceph_tpu.osd.bluestore import PAGE, BlueStore
    from ceph_tpu.osd.objectstore import (CollectionId, ObjectId,
                                          Transaction)
    st = BlueStore(str(tmp_path / "bs"), compression="zlib")
    st.mount()
    cid, oid = CollectionId(1, 0), ObjectId("o")
    st.queue_transaction(Transaction().create_collection(cid))
    data = (b"compress-me!" * 6000)[: 16 * PAGE]  # highly compressible
    st.queue_transaction(Transaction().touch(cid, oid)
                         .write(cid, oid, 0, data))
    o = st._onode(cid, oid)
    assert o.blobs, "large compressible write did not form a blob"
    used = sum(len(b["pages"]) for b in o.blobs.values())
    assert used < 16, f"blob saved nothing ({used} pages)"
    assert st.read(cid, oid).to_bytes() == data
    assert st.fsck()["leaked"] == []
    # clone shares the blob
    clone = ObjectId("o", generation=3)
    st.queue_transaction(Transaction().clone(cid, oid, clone))
    assert st.read(cid, clone).to_bytes() == data
    # partial overwrite materialises the blob; clone keeps old bytes
    st.queue_transaction(Transaction().write(cid, oid, PAGE, b"X" * 10))
    got = st.read(cid, oid).to_bytes()
    assert got[PAGE:PAGE + 10] == b"X" * 10
    assert got[:PAGE] == data[:PAGE]
    assert not st._onode(cid, oid).blobs
    assert st.read(cid, clone).to_bytes() == data
    assert st.fsck()["leaked"] == [] and not st.fsck()["bad_refcounts"]
    # durability: remount decodes the blob map and still reads
    st.umount()
    st2 = BlueStore(str(tmp_path / "bs"), compression="zlib")
    st2.mount()
    assert st2.read(cid, clone).to_bytes() == data
    assert st2.fsck()["leaked"] == []
    # deep verify covers blob pages
    assert st2.deep_verify(cid, clone)
    st2.umount()


def test_incompressible_data_stays_plain(tmp_path):
    import numpy as np

    from ceph_tpu.osd.bluestore import PAGE, BlueStore
    from ceph_tpu.osd.objectstore import (CollectionId, ObjectId,
                                          Transaction)
    st = BlueStore(str(tmp_path / "bs"), compression="zlib")
    st.mount()
    cid, oid = CollectionId(1, 0), ObjectId("r")
    st.queue_transaction(Transaction().create_collection(cid))
    rng = np.random.default_rng(5)
    data = rng.integers(0, 256, 16 * PAGE, dtype=np.uint8).tobytes()
    st.queue_transaction(Transaction().touch(cid, oid)
                         .write(cid, oid, 0, data))
    assert not st._onode(cid, oid).blobs, \
        "random data must not be stored compressed"
    assert st.read(cid, oid).to_bytes() == data
    st.umount()
