"""Scrub/repair + striper tests (the scrub and striping tiers of the
reference's coverage: scrub_backend compare, ec consistency check,
Striper file_to_extents)."""

import numpy as np
import pytest

from ceph_tpu.client.striper import FileLayout, StripedObject
from ceph_tpu.msg.messages import PgId
from ceph_tpu.tools.vstart import MiniCluster
from tests.test_cluster import make_cfg

RNG = np.random.default_rng(55)


@pytest.fixture
def cluster():
    c = MiniCluster(n_osds=6, cfg=make_cfg()).start()
    yield c
    c.stop()


# ------------------------------------------------------------------ scrub
def test_scrub_clean_pool(cluster):
    client = cluster.client()
    client.create_pool("rbd", size=3, pg_num=2)
    for i in range(4):
        client.write_full("rbd", f"o{i}", bytes([i]) * 1000)
    assert client.scrub_pool("rbd", deep=True) == []


def test_deep_scrub_detects_and_repairs_corruption(cluster):
    client = cluster.client()
    client.create_pool("rbd", size=3, pg_num=1)
    payload = RNG.integers(0, 256, 5000, dtype=np.uint8).tobytes()
    client.write_full("rbd", "victim", payload)
    cluster.settle(0.3)  # drain boot-time recovery before injecting faults
    pool_id = client._pool_id("rbd")
    seed = cluster.mon.osdmap.object_to_pg(pool_id, "victim")
    up = cluster.mon.osdmap.pg_to_up_osds(pool_id, seed)
    # silently corrupt one replica (ECInject-style)
    target = cluster.osds[up[1]]
    assert target.inject.corrupt_object(target.store, PgId(pool_id, seed),
                                        "victim", shard=-1, offset=100)
    # shallow scrub sees nothing (metadata matches)
    res = client.scrub_pg("rbd", seed, deep=False)
    assert res.inconsistencies == []
    # deep scrub finds the digest mismatch
    res = client.scrub_pg("rbd", seed, deep=True)
    kinds = {i["kind"] for i in res.inconsistencies}
    assert "digest_mismatch" in kinds or "replica_digest_mismatch" in kinds
    # repair rewrites the bad copy; next deep scrub is clean
    res = client.scrub_pg("rbd", seed, deep=True, repair=True)
    assert res.repaired >= 1
    cluster.settle(0.3)
    res = client.scrub_pg("rbd", seed, deep=True)
    assert res.inconsistencies == []
    assert client.read("rbd", "victim") == payload


def test_ec_deep_scrub_repairs_shard(cluster):
    client = cluster.client()
    client.create_pool("ec", kind="ec", pg_num=1,
                       ec_profile={"plugin": "jerasure", "k": "3", "m": "2",
                                   "backend": "native"})
    payload = RNG.integers(0, 256, 9000, dtype=np.uint8).tobytes()
    client.write_full("ec", "obj", payload)
    cluster.settle(0.3)  # drain boot-time recovery before injecting faults
    pool_id = client._pool_id("ec")
    seed = cluster.mon.osdmap.object_to_pg(pool_id, "obj")
    up = cluster.mon.osdmap.pg_to_up_osds(pool_id, seed)
    shard = 2
    target = cluster.osds[up[shard]]
    assert target.inject.corrupt_object(target.store, PgId(pool_id, seed),
                                        "obj", shard=shard)
    res = client.scrub_pg("ec", seed, deep=True)
    assert any(i["kind"] == "digest_mismatch" and i["shard"] == shard
               for i in res.inconsistencies)
    res = client.scrub_pg("ec", seed, deep=True, repair=True)
    assert res.repaired >= 1
    cluster.settle(0.5)
    res = client.scrub_pg("ec", seed, deep=True)
    assert res.inconsistencies == []
    assert client.read("ec", "obj") == payload


def test_ec_scrub_detects_missing_shard(cluster):
    """A dropped shard write (ECInject write-error role) must surface as a
    missing_shard finding and be repairable."""
    client = cluster.client()
    client.create_pool("ec2", kind="ec", pg_num=1,
                       ec_profile={"plugin": "jerasure", "k": "3", "m": "2",
                                   "backend": "native"})
    pool_id = client._pool_id("ec2")
    seed = 0
    cluster.settle(0.4)  # drain boot-time recovery: it would self-heal
    up = cluster.mon.osdmap.pg_to_up_osds(pool_id, seed)
    # arm a write drop on the shard-3 holder before writing
    dropper = cluster.osds[up[3]]
    dropper.inject.drop_shard_writes.add(3)
    # find an object mapping to pg 0
    name = next(f"o{i}" for i in range(50)
                if cluster.mon.osdmap.object_to_pg(pool_id, f"o{i}") == seed)
    client.write_full("ec2", name, b"Q" * 6000)
    dropper.inject.drop_shard_writes.clear()
    res = client.scrub_pg("ec2", seed, deep=False)
    assert any(i["kind"] == "missing_shard" and i["shard"] == 3
               for i in res.inconsistencies)
    res = client.scrub_pg("ec2", seed, deep=False, repair=True)
    # the scrub that saw the hole re-armed recovery (osd/scrub.py,
    # RECOVERABLE): the repair mends it or finds it mended already
    assert res.repaired >= 1 or not res.inconsistencies
    cluster.settle(0.5)
    assert client.scrub_pg("ec2", seed, deep=True).inconsistencies == []


def test_scrub_repairs_corrupt_primary(cluster):
    """A corrupt PRIMARY copy must be repaired by pulling from a good
    replica, never by pushing its own bad bytes."""
    client = cluster.client()
    client.create_pool("rbd2", size=3, pg_num=1)
    payload = RNG.integers(0, 256, 4000, dtype=np.uint8).tobytes()
    client.write_full("rbd2", "obj", payload)
    cluster.settle(0.3)
    pool_id = client._pool_id("rbd2")
    seed = cluster.mon.osdmap.object_to_pg(pool_id, "obj")
    up = cluster.mon.osdmap.pg_to_up_osds(pool_id, seed)
    primary = cluster.osds[up[0]]
    assert primary.inject.corrupt_object(primary.store, PgId(pool_id, seed),
                                         "obj", shard=-1, offset=10)
    res = client.scrub_pg("rbd2", seed, deep=True, repair=True)
    assert any(i["kind"] == "digest_mismatch" for i in res.inconsistencies)
    cluster.settle(0.5)
    assert client.scrub_pg("rbd2", seed, deep=True).inconsistencies == []
    assert client.read("rbd2", "obj") == payload


def test_admin_commands(cluster):
    client = cluster.client()
    client.create_pool("rbd", size=2)
    client.write_full("rbd", "x", b"data")
    osd = next(iter(cluster.osds.values()))
    perf = osd.admin_command("perf dump")
    assert "subop_w" in perf or "op_w" in perf
    assert isinstance(osd.admin_command("dump_historic_ops"), list)
    st = osd.admin_command("status")
    assert st["osd"] == osd.osd_id and st["epoch"] >= 1
    assert "ec_plugin" in osd.admin_command("config show")
    with pytest.raises(ValueError):
        osd.admin_command("reboot")


# ----------------------------------------------------------------- striper
def test_file_to_extents_roundtrip():
    lo = FileLayout(stripe_unit=4096, stripe_count=3, object_size=16384)
    covered = 0
    for objno, obj_off, ln in lo.file_to_extents(1000, 100_000):
        start = lo.extent_to_file(objno, obj_off)
        assert 1000 <= start < 101_000
        covered += ln
    assert covered == 100_000


def test_striped_object_io(cluster):
    client = cluster.client()
    client.create_pool("data", size=2, pg_num=4)
    lo = FileLayout(stripe_unit=8192, stripe_count=3, object_size=32768)
    f = StripedObject(client, "data", "bigfile", lo)
    payload = RNG.integers(0, 256, 300_000, dtype=np.uint8).tobytes()
    f.write(0, payload)
    assert f.size() == len(payload)
    assert f.read() == payload
    assert f.read(100_000, 5000) == payload[100_000:105_000]
    # overwrite in the middle, spanning pieces
    patch = b"P" * 50_000
    f.write(123_456, patch)
    want = payload[:123_456] + patch + payload[123_456 + 50_000:]
    assert f.read() == want
    # pieces actually spread across objects
    pieces = {objno for objno, _, _ in lo.file_to_extents(0, len(payload))}
    assert len(pieces) > 3
    f.remove()
    assert f.size() == 0


def test_ec_consistency_checker_cli():
    """The standalone online audit (ceph_ec_consistency_checker role):
    connects to a LIVE cluster over TCP, re-encode-verifies a pool,
    reports inconsistencies, exit-code semantics."""
    import subprocess
    import sys

    from ceph_tpu.msg.messages import PgId
    from ceph_tpu.tools.ec_consistency import run as audit
    from ceph_tpu.tools.vstart import MiniCluster
    from tests.test_cluster import make_cfg

    c = MiniCluster(n_osds=5, cfg=make_cfg(), transport="tcp").start()
    try:
        client = c.client()
        client.create_pool("ec", kind="ec", pg_num=1,
                           ec_profile={"plugin": "jerasure", "k": "3",
                                       "m": "2", "backend": "numpy"})
        client.write_full("ec", "obj", b"audit-me" * 5000)
        c.settle(0.5)
        assert audit(client, "ec") == []
        # the standalone process path (TCP bootstrap + exit codes)
        mon_addr = c.network.addr_of(c.mon.name)
        out = subprocess.run(
            [sys.executable, "-m", "ceph_tpu.tools.ec_consistency",
             "--pool", "ec", "--mon-addr", mon_addr, "--json"],
            capture_output=True, text=True, timeout=120,
            cwd="/root/repo")
        assert out.returncode == 0, out.stderr[-500:]
        import json as _json
        rep = _json.loads(out.stdout.strip().splitlines()[-1])
        assert rep["issues"] == []
        # corrupt one shard: the audit must catch it
        pool_id = client._pool_id("ec")
        seed = c.mon.osdmap.object_to_pg(pool_id, "obj")
        up = c.mon.osdmap.pg_to_up_osds(pool_id, seed)
        victim = c.osds[up[1]]
        assert victim.inject.corrupt_object(
            victim.store, PgId(pool_id, seed), "obj", shard=1)
        issues = audit(client, "ec")
        assert issues, "corruption went undetected"
    finally:
        c.stop()
