"""Round-2 runtime fill-ins: HeartbeatMap, mempool, xxhash checksummer
dispatch, the offline EC tool, and the EC extent cache.
"""

import subprocess
import sys

import numpy as np
import pytest

from ceph_tpu.msg.messages import PgId
from ceph_tpu.ops import native
from ceph_tpu.osd.extent_cache import ECExtentCache
from ceph_tpu.utils.heartbeat_map import HeartbeatMap
from ceph_tpu.utils.mempool import global_mempools

RNG = np.random.default_rng(3)


# ------------------------------------------------------------ heartbeat map
def test_heartbeat_map_detects_stalls_and_suicides():
    clock = [100.0]
    doomed = []
    hb = HeartbeatMap(on_suicide=doomed.append, clock=lambda: clock[0])
    hb.add_worker("dispatch", grace=2.0, suicide_grace=10.0)
    hb.add_worker("flush", grace=5.0)
    assert hb.is_healthy()
    clock[0] += 3.0
    assert not hb.is_healthy("dispatch")
    assert hb.is_healthy("flush")
    bad = hb.check()
    assert [b["name"] for b in bad] == ["dispatch"] and not doomed
    hb.touch("dispatch")
    assert hb.is_healthy()
    clock[0] += 11.0
    hb.check()
    assert doomed == ["dispatch"]
    hb.remove_worker("dispatch")
    hb.touch("dispatch")  # no-op after removal


def test_mempool_accounting():
    pools = global_mempools()
    p = pools.pool("pglog")
    before = p.stats()["bytes"]
    p.add(4096, items=2)
    p.sub(96, items=1)
    st = pools.dump()["pglog"]
    assert st["bytes"] == before + 4000


# ----------------------------------------------------------------- xxhash
def test_xxhash_known_vectors():
    # canonical XXH32/XXH64 test vectors (public xxHash spec)
    assert native.xxhash32(b"") == 0x02CC5D05
    assert native.xxhash64(b"") == 0xEF46DB3751D8E999
    assert native.xxhash32(b"abc") == 0x32D153FF
    assert native.xxhash64(b"abc") == 0x44BC2CF5AD770999
    # seeds matter; long inputs cover the lane loops
    data = bytes(range(256)) * 33
    assert native.xxhash32(data) != native.xxhash32(data, seed=1)
    assert native.xxhash64(data) != native.xxhash64(data, seed=1)
    # checksummer dispatch (Checksummer.h role)
    assert native.checksummer("xxhash64")(b"x") == native.xxhash64(b"x")
    assert native.checksummer("crc32c")(b"x") == native.crc32c(b"x")
    with pytest.raises(ValueError):
        native.checksummer("md5")


# ------------------------------------------------------------ offline tool
def test_ec_tool_roundtrip(tmp_path):
    data = RNG.integers(0, 256, 100_000, dtype=np.uint8).tobytes()
    src = tmp_path / "payload.bin"
    src.write_bytes(data)
    outdir = tmp_path / "chunks"
    prof = "plugin=jerasure,technique=reed_sol_van,k=4,m=2"
    run = [sys.executable, "-m", "ceph_tpu.tools.ec_tool"]
    r = subprocess.run(run + ["encode", prof, str(src), str(outdir)],
                       capture_output=True, text=True, timeout=120)
    assert r.returncode == 0, r.stderr
    assert sorted(p.name for p in outdir.iterdir()) == \
        [f"chunk.{i}" for i in range(6)] + ["size"]
    # lose two chunks, reassemble byte-exact
    (outdir / "chunk.1").unlink()
    (outdir / "chunk.4").unlink()
    out = tmp_path / "restored.bin"
    r = subprocess.run(run + ["decode", prof, str(outdir), str(out)],
                       capture_output=True, text=True, timeout=120)
    assert r.returncode == 0, r.stderr
    assert out.read_bytes() == data
    r = subprocess.run(run + ["info", prof], capture_output=True,
                       text=True, timeout=120)
    assert r.returncode == 0 and "k=4 m=2" in r.stdout


# ------------------------------------------------------------ extent cache
def test_extent_cache_semantics():
    c = ECExtentCache(max_bytes=1 << 20)
    pg = PgId(1, 0)
    assert c.read(pg, "o", 0, 0, 10) is None
    c.write(pg, "o", 0, 100, b"A" * 50, version=3)
    c.write(pg, "o", 0, 150, b"B" * 50, version=4)  # adjacent: merges
    assert c.version(pg, "o") == 4
    assert c.read(pg, "o", 0, 120, 60) == b"A" * 30 + b"B" * 30
    assert c.read(pg, "o", 0, 90, 20) is None  # not fully covered
    c.write(pg, "o", 0, 120, b"C" * 10)  # overwrite inside a run
    assert c.read(pg, "o", 0, 100, 100) == \
        b"A" * 20 + b"C" * 10 + b"A" * 20 + b"B" * 50
    c.invalidate(pg, "o")
    assert c.read(pg, "o", 0, 100, 10) is None
    assert c.version(pg, "o") is None
    # LRU eviction stays within the byte budget
    small = ECExtentCache(max_bytes=1000)
    for i in range(10):
        small.write(pg, f"obj{i}", 0, 0, b"x" * 300, version=1)
    assert small._bytes <= 1000


def test_extent_cache_serves_overlapping_partial_writes():
    """Cluster-level: the second overlapping delta write hits the cache
    (no old-byte read fan-out) and parity stays consistent."""
    from ceph_tpu.tools.vstart import MiniCluster
    from tests.test_cluster import make_cfg
    c = MiniCluster(n_osds=6, cfg=make_cfg()).start()
    try:
        client = c.client()
        client.create_pool("ec", kind="ec", pg_num=1,
                           ec_profile={"plugin": "jerasure", "k": "4",
                                       "m": "2", "backend": "native"})
        base = RNG.integers(0, 256, 64_000, dtype=np.uint8).tobytes()
        client.write_full("ec", "hot", base)
        c.settle(0.3)
        shadow = bytearray(base)
        for i in range(6):
            patch = bytes([0x40 + i]) * 3000
            client.write("ec", "hot", patch, offset=8192)
            shadow[8192:11192] = patch
        assert client.read("ec", "hot") == bytes(shadow)
        pool_id = client._pool_id("ec")
        seed = c.mon.osdmap.object_to_pg(pool_id, "hot")
        up = c.mon.osdmap.pg_to_up_osds(pool_id, seed)
        prim = c.osds[up[0]]
        assert prim.perf.get("ec_cache_hit") >= 4, \
            (prim.perf.get("ec_cache_hit"), prim.perf.get("ec_cache_miss"))
        c.settle(0.3)
        assert client.scrub_pg("ec", seed,
                               deep=True).inconsistencies == []
        # degraded read after cached writes still decodes
        epoch = c.mon.osdmap.epoch
        c.kill_osd(up[1])
        c.wait_for_epoch(epoch + 1)
        c.settle(0.6)
        assert client.read("ec", "hot") == bytes(shadow)
    finally:
        c.stop()


def test_heartbeat_map_grace_accounting_details():
    """Timeout/grace arithmetic the watchdog health report is built on:
    stalled_for measures from the LAST touch, the boundary (== grace)
    is still healthy, an unregistered worker is NOT healthy, and a
    remove during a stall silences its report without firing suicide."""
    clock = [50.0]
    doomed = []
    hb = HeartbeatMap(on_suicide=doomed.append, clock=lambda: clock[0])
    hb.add_worker("a", grace=2.0, suicide_grace=8.0)
    hb.add_worker("b", grace=4.0)
    clock[0] += 1.5
    hb.touch("b")                       # b's window restarts at 51.5
    clock[0] += 2.0                     # a stalled 3.5s, b 2.0s
    bad = hb.unhealthy_workers()
    assert [w["name"] for w in bad] == ["a"]
    assert bad[0]["stalled_for"] == 3.5 and bad[0]["grace"] == 2.0
    # exactly AT the grace boundary is still healthy (<=, not <)
    hb.touch("a")
    clock[0] += 2.0
    assert hb.is_healthy("a")
    assert hb.unhealthy_workers() == []
    # unknown/unregistered worker is unhealthy, never healthy-by-absence
    assert not hb.is_healthy("ghost")
    # removing a stalled worker silences it before the suicide sweep
    clock[0] += 100.0
    hb.remove_worker("a")
    assert hb.check() == [] or all(w["name"] != "a"
                                   for w in hb.check())
    assert doomed == []                 # "a" left before the sweep
    assert not hb.is_healthy()          # "b" stalled through the jump...
    hb.touch("b")
    assert hb.is_healthy()              # ...and a touch clears the map
