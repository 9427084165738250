"""Inline store compression (ISSUE 20): per-pool compression_* options
with BlueStore none|passive|aggressive semantics, byte-identity across
every object store and codec, required_ratio fall-through, mixed
compressed/raw extents, and scrub over compressed blobs (the stored
digest covers STORED bytes — deep scrub never inflates).
"""

import numpy as np
import pytest

from ceph_tpu.client.rados import RadosError
from ceph_tpu.msg.messages import PgId
from ceph_tpu.osd.daemon import OSDDaemon
from ceph_tpu.osd.objectstore import CollectionId, ObjectId, ObjectStore
from ceph_tpu.tools.vstart import MiniCluster
from tests.test_cluster import make_cfg

RNG = np.random.default_rng(303)

#: compresses extremely well (repeating phrase), far past any
#: required_ratio worth configuring
COMPRESSIBLE = (b"the quick brown fox jumps over the lazy dog / " * 2000)
#: random bytes: no codec beats required_ratio on these
INCOMPRESSIBLE = RNG.integers(0, 256, 50_000, dtype=np.uint8).tobytes()

AGGRESSIVE = {"compression_mode": "aggressive",
              "compression_algorithm": "czlib",
              "compression_required_ratio": "0.875",
              "compression_min_blob_size": "1024"}


def store_cluster(tmp_path, kind, n=3):
    c = MiniCluster(n_osds=0, cfg=make_cfg())
    c.mon.start()
    for i in range(n):
        kw = {} if kind == "memstore" else {
            "path": str(tmp_path / f"{kind}{i}")}
        st = ObjectStore.create(kind, **kw)
        osd = OSDDaemon(i, c.network, cfg=c.cfg, store=st,
                        host=f"host{i}")
        c.osds[i] = osd
        osd.start()
    c.wait_for_up(n)
    return c


def stored_attrs(cluster, client, pool, name, shard=-1):
    """The attr dicts every holder stored for one object."""
    pool_id = client._pool_id(pool)
    seed = cluster.mon.osdmap.object_to_pg(pool_id, name)
    up = cluster.mon.osdmap.pg_to_up_osds(pool_id, seed)
    cid = CollectionId(pool_id, seed)
    out = []
    for i, osd_id in enumerate(up):
        osd = cluster.osds[osd_id]
        oid = ObjectId(name, shard=(i if shard == "ec" else shard))
        out.append(dict(osd.store.getattrs(cid, oid)))
    return out


# ------------------------------------------------- store / codec matrix
@pytest.mark.parametrize("kind", ["memstore", "filestore", "bluestore"])
def test_roundtrip_every_store(tmp_path, kind):
    """Aggressive compression round-trips byte-identically on every
    object store; incompressible data falls through via required_ratio
    and stays raw."""
    c = store_cluster(tmp_path, kind)
    try:
        client = c.client()
        client.create_pool("cz", size=3, pg_num=1,
                           ec_profile=dict(AGGRESSIVE))
        client.write_full("cz", "text", COMPRESSIBLE)
        client.write_full("cz", "noise", INCOMPRESSIBLE)
        assert client.read("cz", "text") == COMPRESSIBLE
        assert client.read("cz", "noise") == INCOMPRESSIBLE
        assert client.stat("cz", "text") == len(COMPRESSIBLE)
        for attrs in stored_attrs(c, client, "cz", "text"):
            assert attrs["cz"] == "czlib"
            assert int(attrs["crl"]) == len(COMPRESSIBLE)
        for attrs in stored_attrs(c, client, "cz", "noise"):
            assert "cz" not in attrs and "crl" not in attrs
        blobs = sum(o.perf.get("compress_blobs") for o in c.osds.values())
        rej = sum(o.perf.get("compress_rejected")
                  for o in c.osds.values())
        orig = sum(o.perf.get("bluestore_compressed_original")
                   for o in c.osds.values())
        alloc = sum(o.perf.get("bluestore_compressed_allocated")
                    for o in c.osds.values())
        assert blobs >= 3 and rej >= 3
        assert 0 < alloc < orig * 0.6  # ISSUE gate: ratio <= 0.6
    finally:
        c.stop()


@pytest.mark.parametrize("codec", ["czlib", "zlib", "bz2"])
def test_roundtrip_every_codec(codec):
    c = MiniCluster(n_osds=3, cfg=make_cfg()).start()
    try:
        client = c.client()
        prof = dict(AGGRESSIVE, compression_algorithm=codec)
        client.create_pool("p", size=3, pg_num=1, ec_profile=prof)
        client.write_full("p", "obj", COMPRESSIBLE)
        assert client.read("p", "obj") == COMPRESSIBLE
        for attrs in stored_attrs(c, client, "p", "obj"):
            assert attrs["cz"] == codec
    finally:
        c.stop()


def test_pool_modes():
    """none and passive never compress (no hinted ingest path exists
    here); only aggressive does."""
    c = MiniCluster(n_osds=3, cfg=make_cfg()).start()
    try:
        client = c.client()
        for mode in ("none", "passive"):
            prof = dict(AGGRESSIVE, compression_mode=mode)
            client.create_pool(mode, size=3, pg_num=1, ec_profile=prof)
            client.write_full(mode, "obj", COMPRESSIBLE)
            assert client.read(mode, "obj") == COMPRESSIBLE
            for attrs in stored_attrs(c, client, mode, "obj"):
                assert "cz" not in attrs
        assert sum(o.perf.get("compress_blobs")
                   for o in c.osds.values()) == 0
    finally:
        c.stop()


# ------------------------------------------------ mixed extents / partial
def test_partial_write_inflates_and_rewrite_recompresses():
    c = MiniCluster(n_osds=3, cfg=make_cfg()).start()
    try:
        client = c.client()
        client.create_pool("m", size=3, pg_num=1,
                           ec_profile=dict(AGGRESSIVE))
        client.write_full("m", "obj", COMPRESSIBLE)
        for attrs in stored_attrs(c, client, "m", "obj"):
            assert attrs["cz"] == "czlib"
        # partial overwrite: extent math happens in RAW space — the
        # blob inflates in place and stays raw
        patch = b"X" * 5000
        client.write("m", "obj", patch, offset=1234)
        want = (COMPRESSIBLE[:1234] + patch
                + COMPRESSIBLE[1234 + len(patch):])
        assert client.read("m", "obj") == want
        for attrs in stored_attrs(c, client, "m", "obj"):
            assert "cz" not in attrs and "crl" not in attrs
        # next whole-object rewrite re-compresses
        client.write_full("m", "obj", COMPRESSIBLE)
        assert client.read("m", "obj") == COMPRESSIBLE
        for attrs in stored_attrs(c, client, "m", "obj"):
            assert attrs["cz"] == "czlib"
        # mixed neighbours in one PG read fine side by side
        client.write_full("m", "raw_neighbour", INCOMPRESSIBLE)
        assert client.read("m", "raw_neighbour") == INCOMPRESSIBLE
        assert client.read("m", "obj") == COMPRESSIBLE
    finally:
        c.stop()


def test_ec_pool_compression_roundtrip():
    """EC shards compress per-holder (deterministic codec: replicas of
    a shard land byte-identical); reads reconstruct the raw object."""
    c = MiniCluster(n_osds=4, cfg=make_cfg()).start()
    try:
        client = c.client()
        prof = {"plugin": "jerasure", "k": "2", "m": "1",
                "backend": "native", **AGGRESSIVE}
        client.create_pool("ec", kind="ec", pg_num=1, ec_profile=prof)
        client.write_full("ec", "obj", COMPRESSIBLE)
        c.settle(0.3)
        assert client.read("ec", "obj") == COMPRESSIBLE
        pool_id = client._pool_id("ec")
        seed = c.mon.osdmap.object_to_pg(pool_id, "obj")
        up = c.mon.osdmap.pg_to_up_osds(pool_id, seed)
        cid = CollectionId(pool_id, seed)
        for shard, osd_id in enumerate(up):
            attrs = dict(c.osds[osd_id].store.getattrs(
                cid, ObjectId("obj", shard=shard)))
            assert attrs["cz"] == "czlib"
            assert int(attrs["len"]) == len(COMPRESSIBLE)
    finally:
        c.stop()


# --------------------------------------------------- scrub over compressed
def test_scrub_clean_over_compressed_extents():
    """The stored digest covers STORED bytes, so both the python-loop
    deep scrub and the folded background scrub verify compressed
    extents without inflating them."""
    c = MiniCluster(n_osds=3, cfg=make_cfg(
        osd_op_queue="fifo", osd_scrub_fold="device")).start()
    try:
        client = c.client()
        client.create_pool("s", size=3, pg_num=1,
                           ec_profile=dict(AGGRESSIVE))
        client.write_full("s", "ctext", COMPRESSIBLE)
        client.write_full("s", "noise", INCOMPRESSIBLE)
        c.settle(0.3)
        assert client.scrub_pool("s", deep=True) == []
        import time as _t
        for osd in c.osds.values():
            osd._scrub_tick(_t.time())
            for st in osd._scrub_auto.values():
                st["due"] = 0.0
            osd._scrub_tick(_t.time())
        # the scheduled passes deep-scrub WITH repair: let them end, or
        # one still in flight mends the corruption below before the
        # verb queued behind it looks
        deadline = _t.time() + 20
        while any(o._scrub_passes for o in c.osds.values()) \
                and _t.time() < deadline:
            _t.sleep(0.05)
        assert not any(o._scrub_passes for o in c.osds.values())
        assert all(o.perf.get("scrub_mismatches") == 0
                   for o in c.osds.values())
        decomp_before = sum(o.perf.get("compress_decompress")
                            for o in c.osds.values())
        assert client.read("s", "ctext") == COMPRESSIBLE
        assert sum(o.perf.get("compress_decompress")
                   for o in c.osds.values()) > decomp_before
        # a corrupted compressed blob is still caught
        pool_id = client._pool_id("s")
        seed = c.mon.osdmap.object_to_pg(pool_id, "ctext")
        up = c.mon.osdmap.pg_to_up_osds(pool_id, seed)
        target = c.osds[up[1]]
        assert target.inject.corrupt_object(
            target.store, PgId(pool_id, seed), "ctext", shard=-1,
            offset=3)
        res = client.scrub_pg("s", seed, deep=True)
        assert any(i["kind"] in ("digest_mismatch",
                                 "replica_digest_mismatch")
                   for i in res.inconsistencies)
        client.scrub_pg("s", seed, deep=True, repair=True)
        c.settle(0.5)
        assert client.read("s", "ctext") == COMPRESSIBLE
    finally:
        c.stop()


# -------------------------------------------------- mon command / validate
def test_set_compression_command_and_validation():
    c = MiniCluster(n_osds=3, cfg=make_cfg()).start()
    try:
        client = c.client()
        client.create_pool("live", size=3, pg_num=1)
        client.write_full("live", "pre", COMPRESSIBLE)
        for attrs in stored_attrs(c, client, "live", "pre"):
            assert "cz" not in attrs
        out = client.mon_command({
            "prefix": "osd pool set-compression", "pool": "live",
            **AGGRESSIVE})
        assert out["compression_mode"] == "aggressive"
        client._wait_epoch_past(client.osdmap.epoch, client.timeout)
        c.settle(0.3)
        # existing objects keep their on-disk form; new writes compress
        client.write_full("live", "post", COMPRESSIBLE)
        for attrs in stored_attrs(c, client, "live", "post"):
            assert attrs.get("cz") == "czlib"
        assert client.read("live", "pre") == COMPRESSIBLE
        assert client.read("live", "post") == COMPRESSIBLE
        # a bad algorithm fails the COMMAND, not the write path
        with pytest.raises(RadosError):
            client.mon_command({
                "prefix": "osd pool set-compression", "pool": "live",
                "compression_mode": "aggressive",
                "compression_algorithm": "nope"})
        with pytest.raises(RadosError):
            client.mon_command({
                "prefix": "osd pool set-compression", "pool": "live",
                "compression_mode": "sometimes"})
        # pool CREATE validates too (both kinds)
        with pytest.raises(RadosError):
            client.create_pool("bad", size=3, pg_num=1, ec_profile={
                "compression_mode": "aggressive",
                "compression_algorithm": "nope"})
    finally:
        c.stop()


def test_required_ratio_fall_through_is_tunable():
    """required_ratio=0 rejects everything (nothing compresses to zero
    bytes); the default accepts highly-compressible text."""
    c = MiniCluster(n_osds=3, cfg=make_cfg()).start()
    try:
        client = c.client()
        prof = dict(AGGRESSIVE, compression_required_ratio="0.0")
        client.create_pool("strict", size=3, pg_num=1, ec_profile=prof)
        client.write_full("strict", "obj", COMPRESSIBLE)
        assert client.read("strict", "obj") == COMPRESSIBLE
        for attrs in stored_attrs(c, client, "strict", "obj"):
            assert "cz" not in attrs
        assert sum(o.perf.get("compress_rejected")
                   for o in c.osds.values()) >= 3
    finally:
        c.stop()
