"""Which acknowledgements ride the store's group-commit pipeline.

An OSD engages the pipeline (kv-sync and finisher threads) only on a
store whose commit makes something durable (``ObjectStore.
durable_commit``).  On memstore a sub-write's ack leaves inside its
handler and is counted as ``subop_ack_in_handler``; on FileStore it
waits for the batch's ``_commit_batch``.  Under either, every shard
holds the plain reference's encoding of what was written."""

import threading

import numpy as np
import pytest

from benchmark import reference
from ceph_tpu.msg.messages import PgId
from ceph_tpu.osd.bluestore import BlueStore
from ceph_tpu.osd.filestore import FileStore
from ceph_tpu.osd.objectstore import (CollectionId, MemStore, ObjectId,
                                      ObjectStore)
from ceph_tpu.tools.vstart import MiniCluster
from tests.test_cluster import make_cfg

K, M, UNIT = 4, 2, 4096
APPLY_PARTS = ("subop_apply_handler", "subop_apply_commit",
               "subop_apply_finish")


def _payload(seed: int, size: int = 3 * K * UNIT + 1000) -> bytes:
    return np.random.default_rng([45, seed]).bytes(size)


def _cluster(stores=None, **cfg) -> MiniCluster:
    """k + m OSDs, one sub-op timeline a sub-read; ``stores(i)`` makes
    OSD i's store (None: the default memstore)."""
    n = K + M
    c = MiniCluster(n_osds=0 if stores else n,
                    cfg=make_cfg(ec_read_window_us=0, **cfg)).start()
    if stores:
        for i in range(n):
            c.add_osd(i, store=stores(i))
        c.wait_for_up(n)
    return c


def _filestores(tmp_path):
    return lambda i: FileStore(str(tmp_path / f"osd{i}"))


def _pool(client) -> int:
    return client.create_pool(
        "p", kind="ec", pg_num=4,
        ec_profile={"plugin": "tpu", "k": str(K), "m": str(M),
                    "backend": "native"})


def _place(c, client, pool_id, oid):
    seed = client.osdmap.object_to_pg(pool_id, oid)
    up = list(client.osdmap.pg_to_up_osds(pool_id, seed))
    return c.osds[up[0]], PgId(pool_id, seed), up


def _check(c, client, pool_id, oid, want: bytes) -> None:
    """The read-back is ``want``, and every shard, parity included, is
    the reference's GF(2^8) encoding of it."""
    assert client.read("p", oid) == want
    _prim, pgid, up = _place(c, client, pool_id, oid)
    cid = CollectionId(pgid.pool, pgid.seed)
    shards = reference.encode(want, K, M, UNIT)
    for shard, osd in enumerate(up):
        have = c.osds[osd].store.read(
            cid, ObjectId(oid, shard=shard)).to_bytes()
        assert have == shards[shard], (oid, shard)


def _time(c, name):
    dumps = [o.perf.dump()[name] for o in c.osds.values()]
    return (sum(d["sum_seconds"] for d in dumps),
            sum(d["count"] for d in dumps))


def _count(c, name):
    return sum(o.perf.get(name) for o in c.osds.values())


def _subops(c, prefix):
    return [op for o in c.osds.values()
            for op in list(o.op_tracker._history)
            if op.kind == "subop" and op.desc.startswith(prefix)]


def _assert_acks_in_handler(c) -> int:
    """Every sub-op's ack left inside its handler: the counter is the
    number of sub-op timelines, and the handler part is all the apply."""
    _s, n_sub = _time(c, "subop_timeline")
    assert n_sub == _count(c, "subop_w") + _count(c, "subop_r") > 0
    assert _count(c, "subop_ack_in_handler") == n_sub
    handler, commit, finish = (_time(c, name)[0] for name in APPLY_PARTS)
    assert commit == finish == 0
    assert handler == pytest.approx(_time(c, "subop_phase_apply")[0],
                                    rel=1e-9)
    for op in _subops(c, "MSub"):
        assert all(e != "sub_op_applied" for _t, e in op.events)
    return n_sub


def test_stores_declare_whether_their_commit_is_durable():
    assert MemStore.durable_commit is False
    assert FileStore.durable_commit is BlueStore.durable_commit is True
    assert ObjectStore.durable_commit is True
    # a caller that asks for the pipeline on memstore still gets one
    s = MemStore()
    s.mount()
    s.enable_async(name="t-inline-ack")
    try:
        assert s._pipeline is not None
    finally:
        s.disable_async()


def test_memstore_osd_acks_sub_writes_inside_the_handler():
    """(a) Default config, memstore: no OSD engages a pipeline, and a
    4+2 write_full's sub-writes book all their apply to the handler."""
    c = _cluster()
    try:
        assert all(o.store._pipeline is None and not o._store_async
                   for o in c.osds.values())
        client = c.client()
        pool_id = _pool(client)
        payloads = {f"o{i}": _payload(i) for i in range(4)}
        for oid, data in payloads.items():
            client.write_full("p", oid, data)
        writes = _subops(c, "MSubWrite")
        assert len(writes) >= K + M - 1          # remote shards, a write
        assert _count(c, "subop_r") == 0
        assert _assert_acks_in_handler(c) == _count(c, "subop_w")
        for oid, data in payloads.items():
            _check(c, client, pool_id, oid, data)
        _assert_acks_in_handler(c)
    finally:
        c.stop()


def test_filestore_osd_ack_waits_for_its_commit(tmp_path, monkeypatch):
    """(b) FileStore: every OSD engages the pipeline, and a shard's
    sub-write ack does not leave while its ``_commit_batch`` is held;
    the held time lands in the sub-op's commit part."""
    c = _cluster(_filestores(tmp_path))
    try:
        assert all(o.store._pipeline is not None and o._store_async
                   for o in c.osds.values())
        client = c.client()
        pool_id = _pool(client)
        client.write_full("p", "warm", _payload(0))
        oid, data = "obj", _payload(1)
        _prim, _pgid, up = _place(c, client, pool_id, oid)
        shard = c.osds[up[1]]
        gate, entered = threading.Event(), threading.Event()
        orig = shard.store._commit_batch

        def held(items):
            entered.set()
            assert gate.wait(10)
            return orig(items)

        monkeypatch.setattr(shard.store, "_commit_batch", held)
        in_handler = shard.perf.get("subop_ack_in_handler")
        done, result = threading.Event(), []

        def write():
            result.append(client.write_full("p", oid, data))
            done.set()

        t = threading.Thread(target=write)
        t.start()
        try:
            assert entered.wait(10)
            assert not done.wait(0.3)       # the shard's ack is held
            assert [d.get("kind") for d in
                    shard.op_tracker.dump_ops_in_flight()] == ["subop"]
        finally:
            gate.set()
            t.join(10)
        assert done.is_set()
        assert shard.perf.get("subop_ack_in_handler") == in_handler
        op = _subops(c, f"MSubWrite {oid}")
        op = next(o for o in op if o.tracker is shard.op_tracker)
        _handler, commit, _finish = op.apply_parts(op.intervals()["apply"])
        assert commit >= 0.25e9
        _check(c, client, pool_id, oid, data)
    finally:
        c.stop()


@pytest.mark.parametrize("store", ["memstore", "filestore"])
def test_shards_hold_the_reference_encoding(store, tmp_path):
    """(c) Whole writes, an overwrite and a read back: every shard is
    the plain GF(2^8) encoding of the object, pipeline or none."""
    c = _cluster(_filestores(tmp_path) if store == "filestore" else None)
    try:
        client = c.client()
        pool_id = _pool(client)
        objects = {f"o{i}": bytearray(_payload(10 + i)) for i in range(3)}
        for oid, data in objects.items():
            client.write_full("p", oid, bytes(data))
        patch = _payload(20, 2 * UNIT + 100)
        client.write("p", "o1", patch, offset=UNIT + 7)
        objects["o1"][UNIT + 7:UNIT + 7 + len(patch)] = patch
        for oid, data in objects.items():
            _check(c, client, pool_id, oid, bytes(data))
        assert _count(c, "subop_w") > 0
    finally:
        c.stop()


@pytest.mark.parametrize("cfg", [{"store_sync_commit": "on"},
                                 {"osd_op_queue": "fifo"}],
                         ids=["sync_commit", "fifo"])
def test_inline_modes_hold_on_a_durable_store(cfg, tmp_path):
    """(d) store_sync_commit=on and the fifo op queue keep the inline
    path they always ran, on a store whose commit is durable too."""
    c = _cluster(_filestores(tmp_path), **cfg)
    try:
        assert all(o.store._pipeline is None and not o._store_async
                   for o in c.osds.values())
        client = c.client()
        pool_id = _pool(client)
        data = _payload(30)
        client.write_full("p", "obj", data)
        _check(c, client, pool_id, "obj", data)
        _assert_acks_in_handler(c)
    finally:
        c.stop()


def test_acks_in_handler_metric_loads_and_its_counters_exist():
    """``subop_acks_in_handler_pct`` loads in the cells its entry lists
    and in no other, and reads counters a booted OSD registers."""
    from benchmark import cells
    bench = cells.manifest()
    entry = next(m for m in bench["per_layer"]
                 if m["name"] == "subop_acks_in_handler_pct")
    assert entry["moves"] == "op_p90_ms" and entry["layer"] == "store"
    commit = next(m for m in bench["per_layer"]
                  if m["name"] == "subop_ms.commit")
    assert entry["workloads"] == commit["workloads"]
    for w in bench["workloads"]:
        names = [m["name"] for m in cells.load_cell(w["name"])["per_layer"]]
        assert ("subop_acks_in_handler_pct" in names) == (
            w["name"] in entry["workloads"])
    spec = next(m for m in cells.load_cell(entry["workloads"][0])
                ["per_layer"] if m["name"] == "subop_acks_in_handler_pct")
    assert spec["reader"] == "counter_ratio"
    assert spec["args"] == {"num": ["osd.subop_ack_in_handler"],
                            "den": ["osd.subop_timeline.count"],
                            "scale": 100.0}
    c = MiniCluster(n_osds=1, cfg=make_cfg()).start()
    try:
        dump = c.osds[0].perf.dump()
        assert dump["subop_ack_in_handler"] == 0
        assert dump["subop_timeline"]["count"] == 0
    finally:
        c.stop()
