"""PG split: live pg_num growth + the pg_autoscaler mgr module.

The reference scales placement granularity by splitting PGs in place
(OSD::split_pgs, src/osd/OSD.h:1999; stable-mod child mapping in
src/osd/OSDMap.cc; src/pybind/mgr/pg_autoscaler/ proposing growth):
objects re-hash from parent seed s to a child seed in {s + k*old_n},
holders split locally, and recovery moves shards to their CRUSH homes.
"""

import numpy as np
import pytest

from ceph_tpu.client.rados import RadosError
from ceph_tpu.osd.objectstore import CollectionId
from ceph_tpu.parallel.placement import pg_of_object
from ceph_tpu.tools.vstart import MiniCluster
from tests.test_cluster import make_cfg

RNG = np.random.default_rng(55)


@pytest.fixture
def cluster():
    c = MiniCluster(n_osds=6, cfg=make_cfg()).start()
    yield c
    c.stop()


def _poll_scrub_clean(client, pool, timeout=20.0):
    """Replica fill continues after reads converge (pushes are async
    behind the primary's catch-up): poll deep scrub to clean."""
    import time as _time
    deadline = _time.time() + timeout
    issues = ["never ran"]
    while _time.time() < deadline:
        issues = client.scrub_pool(pool, deep=True)
        if not issues:
            return
        _time.sleep(0.3)
    assert not issues, issues


def _poll_reads(client, pool, objs, timeout=25.0):
    """Recovery after a pg_num change converges on its own schedule:
    poll every object instead of guessing a settle time."""
    import time as _time
    deadline = _time.time() + timeout
    remaining = dict(objs)
    while remaining and _time.time() < deadline:
        for name in list(remaining):
            try:
                if client.read(pool, name) == remaining[name]:
                    del remaining[name]
            except RadosError:
                pass
        if remaining:
            _time.sleep(0.25)
    assert not remaining, sorted(remaining)


def test_split_preserves_every_object(cluster):
    """THE acceptance test: write through a pg_num doubling under load,
    no lost object, scrub clean."""
    client = cluster.client()
    client.create_pool("grow", size=2, pg_num=2)
    objs = {f"obj{i}": RNG.integers(0, 256, 20_000,
                                    dtype=np.uint8).tobytes()
            for i in range(40)}
    for name, data in objs.items():
        client.write_full("grow", name, data)
    # double pg_num: 2 -> 4
    out = client.mon_command({"prefix": "osd pool set-pg-num",
                              "pool": "grow", "pg_num": 4})
    assert out["pg_num"] == 4
    # keep writing THROUGH the split (new objects land on child seeds)
    for i in range(40, 60):
        data = RNG.integers(0, 256, 10_000, dtype=np.uint8).tobytes()
        objs[f"obj{i}"] = data
        client.write_full("grow", f"obj{i}", data)
    _poll_reads(client, "grow", objs)
    # overwrite a pre-split object after the split (routes to its child)
    client.write_full("grow", "obj0", b"post-split rewrite")
    assert client.read("grow", "obj0") == b"post-split rewrite"
    # scrub every PG of the grown pool: clean
    _poll_scrub_clean(client, "grow")


def test_split_moves_objects_to_child_seeds(cluster):
    client = cluster.client()
    client.create_pool("grow", size=2, pg_num=2)
    names = [f"o{i}" for i in range(32)]
    for n in names:
        client.write_full("grow", n, n.encode() * 50)
    client.mon_command({"prefix": "osd pool set-pg-num",
                        "pool": "grow", "pg_num": 8})
    _poll_reads(client, "grow", {n: n.encode() * 50 for n in names},
                timeout=45)
    pool_id = client._pool_id("grow")
    moves = {n: pg_of_object(n, 2) for n in names
             if pg_of_object(n, 8) != pg_of_object(n, 2)}
    assert moves  # the split actually redistributed something

    def stragglers():
        """(object, parent seed, osd) still held in a PARENT collection:
        reads converge once the primaries have split, the other holders
        split when the map reaches them."""
        left = []
        for osd in cluster.osds.values():
            colls = set(osd.store.list_collections())
            for seed in set(moves.values()) & {c.pg_seed for c in colls
                                               if c.pool == pool_id}:
                held = {o.name for o in osd.store.list_objects(
                    CollectionId(pool_id, seed)) if o.shard > -2}
                left += [(n, seed, osd.osd_id) for n, s in moves.items()
                         if s == seed and n in held]
        return left

    # every object now lives (only) in the collection of its NEW seed
    import time as _time
    deadline = _time.time() + 30
    while (left := stragglers()) and _time.time() < deadline:
        _time.sleep(0.25)
    assert not left, f"still in their parent pg: {left}"


def test_split_ec_pool(cluster):
    client = cluster.client()
    client.create_pool("ecgrow", kind="ec", pg_num=2,
                       ec_profile={"plugin": "jerasure", "k": "3",
                                   "m": "2", "backend": "native"})
    objs = {f"e{i}": RNG.integers(0, 256, 50_000,
                                  dtype=np.uint8).tobytes()
            for i in range(12)}
    for name, data in objs.items():
        client.write_full("ecgrow", name, data)
    client.mon_command({"prefix": "osd pool set-pg-num",
                        "pool": "ecgrow", "pg_num": 4})
    _poll_reads(client, "ecgrow", objs)
    _poll_scrub_clean(client, "ecgrow")


def test_split_pool_with_full_object_hash(cluster):
    """``object_hash=full`` rides the pool's profile: the client, the
    OSDs and the split all place by it, and names that share their
    first eight bytes spread over the PGs.  (The pool and the split
    are ``test_split_ec_pool``'s.)"""
    client = cluster.client()
    client.create_pool("wide", kind="ec", pg_num=2,
                       ec_profile={"plugin": "jerasure", "k": "3",
                                   "m": "2", "backend": "native",
                                   "object_hash": "full"})
    pool_id = client._pool_id("wide")
    objs = {f"obj{i:07d}": RNG.integers(0, 256, 9_000,
                                        dtype=np.uint8).tobytes()
            for i in range(12)}
    for name, data in objs.items():
        client.write_full("wide", name, data)
    om = client.osdmap
    assert {om.object_to_pg(pool_id, n) for n in objs} == {0, 1}
    assert len({pg_of_object(n, 2) for n in objs}) == 1    # first8: one PG
    assert all(om.object_to_pg(pool_id, n) == pg_of_object(n, 2, "full")
               for n in objs)
    client.mon_command({"prefix": "osd pool set-pg-num",
                        "pool": "wide", "pg_num": 4})
    _poll_reads(client, "wide", objs, timeout=45)
    # every shard now lives in the collection of the object's new seed
    seeds = set()
    for n in objs:
        seed = pg_of_object(n, 4, "full")
        seeds.add(seed)
        assert client.osdmap.object_to_pg(pool_id, n) == seed
        held = [cid.pg_seed for osd in cluster.osds.values()
                for cid in osd.store.list_collections()
                if cid.pool == pool_id and any(
                    o.name == n and o.shard > -2
                    for o in osd.store.list_objects(cid))]
        assert held and set(held) == {seed}, (n, seed, held)
    assert len(seeds) > 2       # the children got their objects
    _poll_scrub_clean(client, "wide")


def test_object_hash_is_validated_at_create(cluster):
    client = cluster.client()
    with pytest.raises(RadosError, match="object_hash"):
        client.create_pool("bad", size=2, pg_num=2,
                           ec_profile={"object_hash": "crc"})
    client.create_pool("ok", size=2, pg_num=2,
                       ec_profile={"object_hash": "first8"})
    client.write_full("ok", "obj0000001", b"x")
    assert client.read("ok", "obj0000001") == b"x"


def test_split_validation(cluster):
    client = cluster.client()
    client.create_pool("p", size=2, pg_num=4)
    with pytest.raises(RadosError):  # non-divisor shrink refused
        client.mon_command({"prefix": "osd pool set-pg-num",
                            "pool": "p", "pg_num": 3})
    with pytest.raises(RadosError):  # non-multiple refused
        client.mon_command({"prefix": "osd pool set-pg-num",
                            "pool": "p", "pg_num": 6})
    with pytest.raises(RadosError):  # unknown pool
        client.mon_command({"prefix": "osd pool set-pg-num",
                            "pool": "nope", "pg_num": 8})
    # no-op growth to the same value succeeds
    out = client.mon_command({"prefix": "osd pool set-pg-num",
                              "pool": "p", "pg_num": 4})
    assert out["pg_num"] == 4


def test_split_survives_osd_restart(cluster):
    """Durability: the split state (child logs, les, intervals) is in
    the store — a crash-restart right after the split must converge."""
    client = cluster.client()
    client.create_pool("grow", size=2, pg_num=2)
    objs = {f"r{i}": RNG.integers(0, 256, 15_000,
                                  dtype=np.uint8).tobytes()
            for i in range(20)}
    for name, data in objs.items():
        client.write_full("grow", name, data)
    client.mon_command({"prefix": "osd pool set-pg-num",
                        "pool": "grow", "pg_num": 4})
    cluster.settle(0.3)
    victim = sorted(cluster.osds)[0]
    store = cluster.kill_osd(victim)
    cluster.settle(0.2)
    cluster.revive_osd(victim, store=store)  # crash-RESTART, same store
    _poll_reads(client, "grow", objs, timeout=45)


def test_autoscaler_proposes_and_applies(cluster):
    client = cluster.client()
    client.create_pool("busy", size=2, pg_num=2)
    for i in range(30):
        client.write_full("busy", f"b{i}", b"x" * 100)
    # stats must reach the mon before the module can see them
    for osd in cluster.osds.values():
        osd._report_stats(budget=5.0)
    from ceph_tpu.mon.mgr import MgrDaemon
    cfg = cluster.mon.cfg
    cfg.apply_dict({"mgr_autoscaler_objects_per_pg": 5})
    mgr = MgrDaemon(cluster.mon, modules=("pg_autoscaler",), tick=0.1)
    try:
        # the stats reports travel the messenger asynchronously: poll
        # until the mon has absorbed them and the proposal appears
        import time as _time
        deadline = _time.time() + 10
        props = {}
        while _time.time() < deadline:
            st = mgr.command("pg_autoscaler", "status")
            props = {p["pool"]: p for p in st["proposals"]}
            if "busy" in props:
                break
            for osd in cluster.osds.values():
                osd._report_stats(budget=5.0)
            _time.sleep(0.1)
        assert "busy" in props, (
            props, mgr.module("pg_autoscaler").target,
            {i: s.get("pool_objects")
             for i, s in cluster.mon._osd_stats.items()})
        assert props["busy"]["proposed"] > props["busy"]["pg_num"]
        # turn it on: the next tick applies the split
        mgr.command("pg_autoscaler", "on")
        mgr.module("pg_autoscaler").tick()
        assert cluster.mon.osdmap.pools[
            client._pool_id("busy")].pg_num == props["busy"]["proposed"]
        cluster.settle(0.5)
        for i in range(30):
            assert client.read("busy", f"b{i}") == b"x" * 100
    finally:
        mgr.stop() if hasattr(mgr, "stop") else None


def test_merge_preserves_every_object(cluster):
    """pg merge (the reverse scaling verb): fold pg_num back down with
    no lost object and a clean deep scrub; writes continue after."""
    client = cluster.client()
    client.create_pool("shrink", size=2, pg_num=8)
    objs = {f"m{i}": RNG.integers(0, 256, 12_000,
                                  dtype=np.uint8).tobytes()
            for i in range(40)}
    for name, data in objs.items():
        client.write_full("shrink", name, data)
    out = client.mon_command({"prefix": "osd pool set-pg-num",
                              "pool": "shrink", "pg_num": 2})
    assert out["pg_num"] == 2
    _poll_reads(client, "shrink", objs)
    # the merged PGs serve writes (fresh version floor holds: a new
    # write must supersede, not collide with, pre-merge versions)
    client.write_full("shrink", "m0", b"post-merge rewrite")
    assert client.read("shrink", "m0") == b"post-merge rewrite"
    for i in range(40, 50):
        client.write_full("shrink", f"m{i}", bytes([i]) * 500)
        assert client.read("shrink", f"m{i}") == bytes([i]) * 500
    _poll_scrub_clean(client, "shrink")
    # source collections are gone everywhere
    pool_id = client._pool_id("shrink")
    for osd in cluster.osds.values():
        for cid in osd.store.list_collections():
            if cid.pool == pool_id:
                assert cid.pg_seed < 2, (osd.osd_id, cid)


def test_merge_validation(cluster):
    client = cluster.client()
    client.create_pool("mv", size=2, pg_num=4)
    with pytest.raises(RadosError):  # non-divisor shrink refused
        client.mon_command({"prefix": "osd pool set-pg-num",
                            "pool": "mv", "pg_num": 3})
    out = client.mon_command({"prefix": "osd pool set-pg-num",
                              "pool": "mv", "pg_num": 2})
    assert out["pg_num"] == 2


def test_split_then_merge_roundtrip(cluster):
    client = cluster.client()
    client.create_pool("rt", size=2, pg_num=2)
    objs = {f"r{i}": bytes([i]) * 3000 for i in range(24)}
    for name, data in objs.items():
        client.write_full("rt", name, data)
    client.mon_command({"prefix": "osd pool set-pg-num",
                        "pool": "rt", "pg_num": 8})
    cluster.settle(0.5)
    client.mon_command({"prefix": "osd pool set-pg-num",
                        "pool": "rt", "pg_num": 2})
    _poll_reads(client, "rt", objs)
    _poll_scrub_clean(client, "rt")


def test_merge_ec_pool(cluster):
    """EC pools merge through the same fold path: shards relocate via
    the inventory-sourced rebuilds, stripes stay decodable."""
    client = cluster.client()
    client.create_pool("ecshrink", kind="ec", pg_num=4,
                       ec_profile={"plugin": "jerasure", "k": "3",
                                   "m": "2", "backend": "native"})
    objs = {f"em{i}": RNG.integers(0, 256, 40_000,
                                   dtype=np.uint8).tobytes()
            for i in range(10)}
    for name, data in objs.items():
        client.write_full("ecshrink", name, data)
    out = client.mon_command({"prefix": "osd pool set-pg-num",
                              "pool": "ecshrink", "pg_num": 2})
    assert out["pg_num"] == 2
    _poll_reads(client, "ecshrink", objs)
    # post-merge writes and a clean deep scrub
    client.write_full("ecshrink", "em0", b"post-merge ec rewrite")
    assert client.read("ecshrink", "em0") == b"post-merge ec rewrite"
    _poll_scrub_clean(client, "ecshrink")
