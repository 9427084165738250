"""Partial-write paths: replicated in-place, EC parity-delta, EC rmw.

The io_exerciser/EcIoSequence tier of the reference (SURVEY.md §4:
src/common/io_exerciser drives EC-specific read/write sequences), plus a
deep-scrub gate proving parity stays consistent after delta writes.
"""

import numpy as np
import pytest

from ceph_tpu.tools.vstart import MiniCluster
from tests.test_cluster import make_cfg

RNG = np.random.default_rng(99)


@pytest.fixture
def cluster():
    c = MiniCluster(n_osds=8, cfg=make_cfg()).start()
    yield c
    c.stop()


@pytest.fixture(params=["native", "jax"])
def profile(request):
    """The EC cases run on both region back-ends: the host's native
    multiply, and the jax programs (through the batcher) that an
    accelerator pool runs."""
    return {"plugin": "jerasure", "k": "4", "m": "2",
            "backend": request.param}


def test_replicated_partial_write(cluster):
    client = cluster.client()
    client.create_pool("rbd", size=3, pg_num=2)
    base = RNG.integers(0, 256, 50_000, dtype=np.uint8).tobytes()
    client.write_full("rbd", "obj", base)
    client.write("rbd", "obj", b"MID", offset=20_000)
    want = base[:20_000] + b"MID" + base[20_003:]
    assert client.read("rbd", "obj") == want
    # all replicas byte-identical (deep scrub clean)
    seed = cluster.mon.osdmap.object_to_pg(client._pool_id("rbd"), "obj")
    cluster.settle(0.2)
    assert client.scrub_pg("rbd", seed, deep=True).inconsistencies == []


def test_ec_parity_delta_overwrite(cluster, profile):
    """Sub-object overwrite within the object takes the parity-delta path
    and leaves parity consistent (verified by reconstruction AND scrub)."""
    client = cluster.client()
    client.create_pool("ec", kind="ec", pg_num=1, ec_profile=profile)
    base = RNG.integers(0, 256, 64_000, dtype=np.uint8).tobytes()
    client.write_full("ec", "obj", base)
    cluster.settle(0.3)
    patch = RNG.integers(0, 256, 5_000, dtype=np.uint8).tobytes()
    client.write("ec", "obj", patch, offset=10_000)  # within one chunk
    want = base[:10_000] + patch + base[15_000:]
    assert client.read("ec", "obj") == want
    # cross-chunk patch
    patch2 = b"~" * 20_000
    client.write("ec", "obj", patch2, offset=12_000)
    want = want[:12_000] + patch2 + want[32_000:]
    assert client.read("ec", "obj") == want
    cluster.settle(0.3)
    seed = cluster.mon.osdmap.object_to_pg(client._pool_id("ec"), "obj")
    assert client.scrub_pg("ec", seed, deep=True).inconsistencies == []
    # and parity is REALLY consistent: kill enough shards to force decode
    pool_id = client._pool_id("ec")
    up = cluster.mon.osdmap.pg_to_up_osds(pool_id, seed)
    epoch = cluster.mon.osdmap.epoch
    cluster.kill_osd(up[0])
    cluster.kill_osd(up[2])
    cluster.wait_for_epoch(epoch + 2)
    cluster.settle(0.5)
    assert client.read("ec", "obj") == want


def test_ec_rmw_growing_write(cluster, profile):
    """A write extending the object falls back to read-modify-write
    re-encode and stays readable."""
    client = cluster.client()
    client.create_pool("ec", kind="ec", pg_num=1, ec_profile=profile)
    base = b"A" * 10_000
    client.write_full("ec", "obj", base)
    cluster.settle(0.2)
    client.write("ec", "obj", b"B" * 4_000, offset=8_000)  # grows to 12000
    assert client.read("ec", "obj") == b"A" * 8_000 + b"B" * 4_000
    assert client.stat("ec", "obj") == 12_000


def test_ec_offset_write_creates_object(cluster, profile):
    """rados write semantics: an offset write to a missing object creates
    it zero-filled up to the offset."""
    client = cluster.client()
    client.create_pool("ec", kind="ec", pg_num=1, ec_profile=profile)
    client.write("ec", "fresh", b"tail", offset=100)
    assert client.read("ec", "fresh") == b"\0" * 100 + b"tail"


def test_replicated_partial_extend_updates_stat(cluster):
    client = cluster.client()
    client.create_pool("rbd", size=2, pg_num=1)
    client.write_full("rbd", "o", b"abc")
    client.write("rbd", "o", b"XYZWW", offset=2)
    assert client.read("rbd", "o") == b"abXYZWW"
    assert client.stat("rbd", "o") == 7


def test_ec_concurrent_overlapping_writes_keep_parity_consistent(
        cluster, profile):
    """Two clients hammering the same object with partial writes: parity
    must stay consistent (per-object serialization on the primary)."""
    import threading as _t
    c1 = cluster.client()
    c2 = cluster.client()
    c1.create_pool("ec", kind="ec", pg_num=1, ec_profile=profile)
    base = RNG.integers(0, 256, 32_000, dtype=np.uint8).tobytes()
    c1.write_full("ec", "hot", base)
    cluster.settle(0.3)

    def hammer(client, marker):
        for i in range(8):
            client.write("ec", "hot", bytes([marker]) * 3000,
                         offset=4_000 + (i % 3) * 1000)

    t1 = _t.Thread(target=hammer, args=(c1, 0x11))
    t2 = _t.Thread(target=hammer, args=(c2, 0x22))
    t1.start(); t2.start(); t1.join(); t2.join()
    cluster.settle(0.3)
    seed = cluster.mon.osdmap.object_to_pg(c1._pool_id("ec"), "hot")
    # parity consistent: deep scrub clean AND degraded read agrees
    assert c1.scrub_pg("ec", seed, deep=True).inconsistencies == []
    healthy = c1.read("ec", "hot")
    pool_id = c1._pool_id("ec")
    up = cluster.mon.osdmap.pg_to_up_osds(pool_id, seed)
    epoch = cluster.mon.osdmap.epoch
    cluster.kill_osd(up[1])
    cluster.wait_for_epoch(epoch + 1)
    cluster.settle(0.5)
    assert c1.read("ec", "hot") == healthy


def test_ec_partial_write_sequence(cluster, profile):
    """io-sequence style: a burst of random partial writes against a
    shadow buffer, then full verification + deep scrub."""
    client = cluster.client()
    client.create_pool("ec", kind="ec", pg_num=1, ec_profile=profile)
    size = 40_000
    shadow = bytearray(RNG.integers(0, 256, size, dtype=np.uint8).tobytes())
    client.write_full("ec", "obj", bytes(shadow))
    cluster.settle(0.3)
    for _ in range(12):
        off = int(RNG.integers(0, size - 1))
        ln = int(RNG.integers(1, min(8_000, size - off)))
        patch = RNG.integers(0, 256, ln, dtype=np.uint8).tobytes()
        client.write("ec", "obj", patch, offset=off)
        shadow[off:off + ln] = patch
    assert client.read("ec", "obj") == bytes(shadow)
    seed = cluster.mon.osdmap.object_to_pg(client._pool_id("ec"), "obj")
    cluster.settle(0.3)
    assert client.scrub_pg("ec", seed, deep=True).inconsistencies == []
