"""OSDs that build their store from the options: BlueStore over the
``sst`` KV under an EC 4+2 pool, as ``rbd_ec42_4k_bluestore`` deploys it.

Held to plain references that import nothing of the program: every
stored shard against ``benchmark/reference.py``'s numpy GF(2^8) encode
of the image (the objects as plain bytes), and a store's durable state
after a crash against a dict of objects that applies the same
transactions.  Small sizes on the CPU platform, seeded data; nothing
here is a measurement.
"""

import os
import subprocess
import sys

import numpy as np

from benchmark import reference
from ceph_tpu.osd.bluestore import PAGE, BlueStore
from ceph_tpu.osd.objectstore import (CollectionId, MemStore, ObjectId,
                                      ObjectStore, Transaction)
from ceph_tpu.utils.config import default_config

K, M, UNIT = 4, 2, 4096
ROW = K * UNIT
SIZE = 8 * ROW          # 32 KiB shards: a whole write takes the large path
REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ---------------------------------------------------------------- a cluster
class Bed:
    """A cluster of BlueStore OSDs, one EC pool on it, and the image
    beside it: the objects as plain bytes, every acknowledged overwrite
    laid over them in the order it was sent."""

    def __init__(self, seed: int, image: dict | None = None, **over):
        from ceph_tpu.tools.vstart import MiniCluster
        from tests.test_cluster import make_cfg
        self.cluster = MiniCluster(n_osds=K + M, cfg=make_cfg(
            ec_backend="jax", osd_objectstore="bluestore",
            kv_backend="sst", **over)).start()
        self.client = self.cluster.client()
        self.pool_id = self.client.create_pool(
            "p", kind="ec", pg_num=4,
            ec_profile={"plugin": "tpu", "k": str(K), "m": str(M),
                        "backend": "jax"})
        self.rng = np.random.default_rng([46, seed])
        self.image = {} if image is None else image

    def stop(self) -> None:
        self.cluster.stop()

    def create(self, oid: str) -> None:
        self.image[oid] = bytearray(self.rng.bytes(SIZE))
        self.client.write_full("p", oid, bytes(self.image[oid]))

    def overwrite(self, oid: str, block: int) -> None:
        data = self.rng.bytes(UNIT)
        self.client.write("p", oid, data, offset=block * UNIT)
        self.image[oid][block * UNIT:(block + 1) * UNIT] = data

    def stored(self, oid: str) -> dict[int, bytes]:
        seed = self.client.osdmap.object_to_pg(self.pool_id, oid)
        up = self.client.osdmap.pg_to_up_osds(self.pool_id, seed)
        cid = CollectionId(self.pool_id, seed)
        return {shard: self.cluster.osds[osd].store.read(
                    cid, ObjectId(oid, shard=shard)).to_bytes()
                for shard, osd in enumerate(up)}

    def check(self, oid: str) -> dict[int, bytes]:
        """The read-back is the image and every stored shard, parity
        included, the reference's encoding of it."""
        want = bytes(self.image[oid])
        assert self.client.read("p", oid) == want
        shards = reference.encode(want, K, M, UNIT)
        have = self.stored(oid)
        assert sorted(have) == list(range(K + M))
        for shard, data in have.items():
            assert data == shards[shard], (oid, shard)
        return have

    def counts(self) -> dict[str, float]:
        """The OSDs' registries summed, a TIME counter as .count and
        .sum_seconds."""
        out: dict[str, float] = {}
        for o in self.cluster.osds.values():
            for name, v in o.perf.dump().items():
                if isinstance(v, dict) and "sum_seconds" in v:
                    for part in ("count", "sum_seconds"):
                        key = f"{name}.{part}"
                        out[key] = out.get(key, 0) + v[part]
                elif isinstance(v, (int, float)):
                    out[name] = out.get(name, 0) + v
        return out


def _grew(before: dict, after: dict) -> dict:
    return {n: after[n] - before.get(n, 0) for n in after}


def _lose_power(cluster) -> int:
    """Stop every OSD, then lose its store as a power cut would: the
    device pages of the deferred writes never reach the disk, device
    and KV are closed where they stand, nothing is unmounted.  Returns
    how many deferred pages were undone."""
    undone = 0
    for o in cluster.osds.values():
        o.stop()           # every acknowledged transaction is durable
        st = o.store
        for phys in st._deferred:
            st._dev_write(phys, bytes(PAGE))
            undone += 1
        st._dev.close()
        st._kv.close()
        st._mounted = False
    cluster.osds.clear()
    cluster.stop()
    return undone


def test_overwrites_on_bluestore_osds_match_the_reference():
    bed = Bed(seed=1)
    try:
        for o in bed.cluster.osds.values():
            assert isinstance(o.store, BlueStore)
            assert o.store.kv_backend == "sst"
            assert o.store.compression is None
            assert o._store_async
        names = [f"img{i}" for i in range(3)]
        for n in names:
            bed.create(n)
        per = SIZE // UNIT
        blocks = bed.rng.permutation(len(names) * per)[:12]
        before = bed.counts()
        for b in blocks:
            bed.overwrite(names[b // per], int(b % per))
        grew = _grew(before, bed.counts())
        for n in names:
            bed.check(n)
        assert grew["ec_plan_parity_delta"] == len(blocks)
        assert grew["store_fsync.count"] > 0
        assert grew["store_fsync.sum_seconds"] > 0
        # the data shard's 4 KiB and two parity deltas an overwrite; a
        # version stamp carries no payload
        assert grew["store_deferred_write"] >= 3 * len(blocks)
        # every sub-write's ack waited for its store's commit: what
        # left inside a handler were sub-reads
        assert grew["subop_w"] > 0
        assert grew["subop_timeline.count"] \
            - grew["subop_ack_in_handler"] == grew["subop_w"]
        assert grew["subop_apply_commit.sum_seconds"] > 0
        assert "kv_stall.count" in grew
    finally:
        bed.stop()


def test_acknowledged_overwrites_survive_a_crash_of_every_store(tmp_path):
    osd_data = str(tmp_path / "osd.$id")
    bed = Bed(seed=2, osd_data=osd_data)
    try:
        names = [f"img{i}" for i in range(2)]
        for n in names:
            bed.create(n)
        per = SIZE // UNIT
        for b in bed.rng.permutation(len(names) * per)[:10]:
            bed.overwrite(names[b // per], int(b % per))
        stored = {n: bed.check(n) for n in names}
    except BaseException:
        bed.stop()
        raise
    undone = _lose_power(bed.cluster)
    assert undone > 0       # the remount has deferred pages to replay
    again = Bed(seed=2, image=bed.image, osd_data=osd_data)
    try:
        assert again.pool_id == bed.pool_id
        for n in names:
            assert again.check(n) == stored[n]
    finally:
        again.stop()


# ------------------------------------------------ one store, crashed
CID = CollectionId(1, 3)
PGLOG = ObjectId("__pglog__", shard=-9)
STREAM = 16 * PAGE


def _ops(seed: int, n: int) -> list[tuple]:
    """The cell's transaction shapes on one PG's collection, seeded: a
    whole write of a shard stream, an extent XORed in (a parity
    delta), a data extent, a version stamp (the log entry alone) and
    the attributes that follow each apply."""
    rng = np.random.default_rng([46, seed])
    objs = [ObjectId(f"obj{i}", shard=i % (K + M)) for i in range(3)]
    ops = [("whole", o, rng.bytes(STREAM)) for o in objs]
    kinds = ("whole", "xor", "data", "stamp", "attrs")
    for _ in range(n - len(ops)):
        kind = kinds[rng.choice(5, p=[0.05, 0.3, 0.3, 0.15, 0.2])]
        o = objs[int(rng.integers(len(objs)))]
        off = int(rng.integers(STREAM // PAGE)) * PAGE
        if kind == "whole":
            ops.append((kind, o, rng.bytes(STREAM)))
        elif kind in ("xor", "data"):
            ops.append((kind, o, off, rng.bytes(PAGE)))
        elif kind == "stamp":
            ops.append((kind, o))
        else:
            ops.append((kind, o, {"v": len(ops), "d": int(
                rng.integers(1 << 32)), "wh": 0}))
    return ops


def _tx(i: int, op: tuple, old: bytes | None) -> Transaction:
    """Op ``i`` as the OSD would queue it; ``old``: the extent's bytes
    before it (what the log entry keeps for a rollback)."""
    kind, o = op[0], op[1]
    tx = Transaction()
    key = f"{i:08d}"
    if kind == "whole":
        tx.touch(CID, o).truncate(CID, o, 0).write(CID, o, 0, op[2])
        tx.setattrs(CID, o, {"v": i, "len": len(op[2])})
        tx.omap_setkeys(CID, PGLOG, {key: b"write"})
    elif kind in ("xor", "data"):
        off, data = op[2], op[3]
        if kind == "xor":
            data = (np.frombuffer(old, np.uint8)
                    ^ np.frombuffer(data, np.uint8)).tobytes()
        tx.write(CID, o, off, data)
        tx.omap_setkeys(CID, PGLOG, {key: old})
    elif kind == "stamp":
        tx.omap_setkeys(CID, PGLOG, {key: b""})
    else:
        tx.setattrs(CID, o, op[2])
    return tx


class _Dict:
    """The reference: objects as a dict of bytes, attributes and omap,
    the same transactions applied in order."""

    def __init__(self):
        self.objs: dict[ObjectId, list] = {PGLOG: [bytearray(), {}, {}]}

    def extent(self, op: tuple) -> bytes | None:
        if op[0] not in ("xor", "data"):
            return None
        return bytes(self.objs[op[1]][0][op[2]:op[2] + PAGE])

    def apply(self, i: int, op: tuple) -> None:
        kind, o = op[0], op[1]
        key = f"{i:08d}"
        if kind == "whole":
            obj = self.objs.setdefault(o, [bytearray(), {}, {}])
            obj[0] = bytearray(op[2])
            obj[1].update({"v": i, "len": len(op[2])})
            self.objs[PGLOG][2][key] = b"write"
        elif kind in ("xor", "data"):
            data, old = self.objs[o][0], self.extent(op)
            new = op[3] if kind == "data" else bytes(
                a ^ b for a, b in zip(old, op[3]))
            data[op[2]:op[2] + PAGE] = new
            self.objs[PGLOG][2][key] = old
        elif kind == "stamp":
            self.objs[PGLOG][2][key] = b""
        else:
            self.objs[o][1].update(op[2])

    def state(self) -> dict:
        return {o: (bytes(d), dict(a), dict(m))
                for o, (d, a, m) in self.objs.items()}


def _state(store) -> dict:
    return {o: (store.read(CID, o).to_bytes(), store.getattrs(CID, o),
                store.omap_get(CID, o))
            for o in store.list_objects(CID)}


def _crash_child(path: str, ackfile: str, seed: str, n: str,
                 kill_at: str) -> None:
    """Queue the ops on a BlueStore over ``sst`` through the commit
    pipeline, record each acknowledgement durably, and die inside the
    ``kill_at``-th one while later transactions are queued."""
    s = BlueStore(path, kv_backend="sst", compression="none")
    s.mount()
    s.enable_async(name="crash-child")
    s.queue_transaction(Transaction().create_collection(CID).touch(
        CID, PGLOG))
    s.flush()
    ack = os.open(ackfile, os.O_WRONLY | os.O_CREAT | os.O_APPEND)

    def on_commit(i: int) -> None:
        os.write(ack, f"{i}\n".encode())
        os.fsync(ack)
        if i == int(kill_at):
            os._exit(1)

    for i, op in enumerate(_ops(int(seed), int(n))):
        old = None
        if op[0] in ("xor", "data"):
            old = s.read(CID, op[1], op[2], PAGE).to_bytes()
        s.queue_transaction(_tx(i, op, old),
                            on_commit=lambda i=i: on_commit(i))
    s.flush()
    os._exit(0)


def test_remount_after_a_crash_holds_every_acknowledged_transaction(
        tmp_path):
    seed, n = 4601, 160
    kill_at = int(np.random.default_rng(seed).integers(n // 3,
                                                        2 * n // 3))
    path, ackfile = str(tmp_path / "bs"), str(tmp_path / "acks")
    child = ("import sys; sys.path.insert(0, {!r}); "
             "from tests.test_bluestore_osds import _crash_child; "
             "_crash_child(*sys.argv[1:])").format(REPO)
    proc = subprocess.run(
        [sys.executable, "-c", child, path, ackfile, str(seed), str(n),
         str(kill_at)], capture_output=True, text=True, timeout=120,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert proc.returncode == 1, (proc.returncode, proc.stderr[-2000:])
    acked = [int(x) for x in open(ackfile).read().split()]
    assert acked == list(range(kill_at + 1))

    s = BlueStore(path, kv_backend="sst", compression="none")
    s.mount()
    try:
        have = _state(s)
        ref, ops = _Dict(), _ops(seed, n)
        for i in range(len(acked)):
            ref.apply(i, ops[i])
        # every acknowledged transaction, and after them a prefix of
        # the ones queued: the state of the reference after some j
        matched = None
        for j in range(len(acked), n + 1):
            if ref.state() == have:
                matched = j
                break
            if j < n:
                ref.apply(j, ops[j])
        assert matched is not None
        fs = s.fsck()
        assert not fs["leaked"] and not fs["double_booked"], fs
    finally:
        s.umount()


def test_fsyncs_and_lsm_maintenance_are_annotated(tmp_path, monkeypatch):
    """``ceph:store-fsync`` (device or KV) around every fsync the store
    makes, and ``ceph:kv-flush`` / ``ceph:kv-compact`` on the LSM's
    background threads, with a memtable small enough to seal often."""
    import threading

    from ceph_tpu.utils import tracer
    seen: list[tuple[str, dict]] = []
    lock = threading.Lock()

    class Recorder:
        def __init__(self, name, **meta):
            with lock:
                seen.append((name, meta))

        def __enter__(self):
            return self

        def __exit__(self, *exc):
            return False
    monkeypatch.setattr(tracer, "_trace_annotation", Recorder)
    s = BlueStore(str(tmp_path / "bs"), kv_backend="sst",
                  compression="none", kv_memtable_bytes=4096)
    s.mount()
    try:
        rng = np.random.default_rng(4602)
        s.queue_transaction(Transaction().create_collection(CID))
        for i in range(48):
            o = ObjectId(f"obj{i % 4}")
            s.queue_transaction(Transaction().touch(CID, o).write(
                CID, o, (i // 4) * PAGE, rng.bytes(PAGE)))
        s.queue_transaction(Transaction().write(
            CID, ObjectId("big"), 0, rng.bytes(STREAM)))
        assert s._kv.wait_maintenance_idle()
    finally:
        s.umount()
    names = {n for n, _ in seen}
    assert {"ceph:store-fsync", "ceph:kv-flush",
            "ceph:kv-compact"} <= names
    assert {m["what"] for n, m in seen
            if n == "ceph:store-fsync"} == {"device", "kv"}


# ------------------------------------------------------- the directories
def test_stop_removes_only_the_directories_the_cluster_made(tmp_path):
    from ceph_tpu.tools.vstart import MiniCluster
    from tests.test_cluster import make_cfg
    cfg = make_cfg(osd_objectstore="bluestore", kv_backend="sst")
    c = MiniCluster(n_osds=3, cfg=cfg).start()
    root = c._data_root
    try:
        assert cfg["osd_data"] == os.path.join(root, "osd.$id")
        assert sorted(os.listdir(root)) == ["osd.0", "osd.1", "osd.2"]
        stores = [o.store for o in c.osds.values()]
    finally:
        c.stop()
    assert not os.path.exists(root)
    assert cfg["osd_data"] == ""
    assert not any(st._mounted for st in stores)

    given = str(tmp_path / "osd.$id")
    c = MiniCluster(n_osds=2, cfg=make_cfg(
        osd_objectstore="bluestore", osd_data=given)).start()
    assert c._data_root is None
    stores = [o.store for o in c.osds.values()]
    c.stop()
    for i, st in enumerate(stores):
        assert not st._mounted
        assert os.path.exists(os.path.join(str(tmp_path), f"osd.{i}",
                                           "block.img"))


def test_kill_then_revive_on_the_old_store_or_a_fresh_one():
    """A kill leaves the store as it stands; a revive on it is a
    restart, a revive on none a device swap; ``stop`` unmounts every
    store the OSDs built, the killed ones too, before it removes their
    directory."""
    from ceph_tpu.tools.vstart import MiniCluster
    from tests.test_cluster import make_cfg
    c = MiniCluster(n_osds=3, cfg=make_cfg(
        osd_objectstore="bluestore", kv_backend="sst")).start()
    root = c._data_root
    try:
        client = c.client()
        client.create_pool("r", size=3, pg_num=1)
        client.write_full("r", "obj", b"kept")
        c.settle(0.3)
        dead = c.kill_osd(0, mark_down=False)
        assert dead._mounted            # a hard kill: nothing unmounted
        held = dead.list_collections()
        assert held
        assert c.revive_osd(0, store=dead).store is dead
        assert dead._mounted and dead.list_collections() == held
        gone = c.kill_osd(1, mark_down=False)
        mark = os.path.join(gone.path, "old-device")
        open(mark, "w").close()
        fresh = c.revive_osd(1).store
        assert fresh is not gone and fresh.path == gone.path
        assert not gone._mounted and fresh._mounted
        assert not os.path.exists(mark)
        lost = c.kill_osd(2, mark_down=False)   # never revived
        assert lost._mounted
        stores = [dead, fresh, lost]
    finally:
        c.stop()
    assert not any(st._mounted or st._kv is not None for st in stores)
    assert not os.path.exists(root)


def test_without_the_option_an_osd_keeps_a_memstore():
    from ceph_tpu.tools.vstart import MiniCluster
    from tests.test_cluster import make_cfg
    assert type(ObjectStore.from_config(default_config(), 0)) is MemStore
    c = MiniCluster(n_osds=3, cfg=make_cfg()).start()
    try:
        assert c._data_root is None
        for o in c.osds.values():
            assert type(o.store) is MemStore
            assert not o._store_async
    finally:
        c.stop()
