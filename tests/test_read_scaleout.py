"""Read scale-out: balanced reads, client read leases, HBM hot tier.

Three layers, matching the feature's structure:

- balanced reads (pool ``read_policy=balance``): clients hash reads
  across the acting set's shard holders; every leg must stay
  byte-identical to the primary-path oracle — healthy, degraded on a
  NO-SPARE cluster, and under concurrent writes (the mid-write ESTALE
  bounce back to the primary);
- client read leases: hot objects grant TTL leases, repeat reads are
  served from the client's byte-budgeted cache with ZERO RADOS ops
  (counter-enforced), writes revoke via the "_lease" notify, and a
  LOST revoke is bounded by one lease window of (untorn) staleness;
- the primary-side hot-read tier: second-hit admission into the
  extent cache, with hit/admit/evict telemetry.
"""

import threading
import time

import numpy as np
import pytest

from ceph_tpu.tools.vstart import MiniCluster
from ceph_tpu.utils.config import default_config

RNG = np.random.default_rng(47)

OBJ_SIZE = 12_000


def _cfg(**over):
    cfg = default_config()
    cfg.apply_dict({"osd_heartbeat_interval": 0.05,
                    "osd_heartbeat_grace": 0.5,
                    "ec_backend": "native",
                    "osd_op_num_shards": 2,
                    "ms_dispatch_workers": 2, **over})
    return cfg


def _make_cluster(**over):
    """3-OSD no-spare cluster (k=2+m=1: a killed OSD's shards cannot
    rebuild, so degraded reads STAY degraded) with a balance-policy
    EC pool."""
    c = MiniCluster(n_osds=3, cfg=_cfg(**over)).start()
    cl = c.client()
    cl.create_pool("ecs", kind="ec", pg_num=2,
                   ec_profile={"plugin": "jerasure", "k": "2", "m": "1",
                               "backend": "numpy",
                               "read_policy": "balance"})
    return c, cl


@pytest.fixture
def balance_cluster():
    """Leases OFF (ttl=0): pure balanced-read + hot-tier semantics."""
    c, cl = _make_cluster(**{"osd_read_lease_ttl": 0.0})
    yield c, cl
    c.stop()


@pytest.fixture
def lease_cluster():
    """Leases ON with a LONG ttl (any fresh-bytes observation within
    the test window is attributable to the revoke path, never expiry)
    and a low grant threshold (~5 rapid reads cross it)."""
    c, cl = _make_cluster(**{"osd_read_lease_ttl": 30.0,
                             "osd_read_lease_rate": 5.0})
    yield c, cl
    c.stop()


def _payloads(cl, n=6, size=OBJ_SIZE, pool="ecs"):
    out = {}
    for i in range(n):
        data = bytes(RNG.integers(0, 256, size, dtype=np.uint8))
        out[f"o{i}"] = data
        cl.write_full(pool, f"o{i}", data)
    return out


def _counter_sum(c, name):
    return sum(osd.perf.dump().get(name, 0) for osd in c.osds.values())


def _count_ops(client):
    """Wrap client._op to count every op that actually reaches RADOS
    (the zero-RADOS-ops lease gate is enforced against this)."""
    calls = [0]
    orig = client._op

    def counting_op(*a, **kw):
        calls[0] += 1
        return orig(*a, **kw)

    client._op = counting_op
    return calls


# ------------------------------------------------------- balanced reads
def test_balanced_reads_byte_identity_and_spread(balance_cluster):
    c, cl = balance_cluster
    payloads = _payloads(cl)
    # many clients = many nonces: the (oid, nonce) hash must fan the
    # same hot objects across different shard holders
    clients = [c.client() for _ in range(6)]
    for rdr in clients:
        for name, want in payloads.items():
            assert rdr.read("ecs", name) == want, name
    served = _counter_sum(c, "balanced_read_serve")
    assert served > 0, "no read was ever served by a non-primary holder"
    # spread: with 6 nonces over 3 holders, well over half the reads
    # land off-primary in expectation (~2/3) — require at least 1/4
    total = len(clients) * len(payloads)
    assert served >= total // 4, (served, total)


def test_balanced_reads_degraded_byte_identity(balance_cluster):
    c, cl = balance_cluster
    payloads = _payloads(cl)
    c.kill_osd(2)          # no spares: reads stay degraded (any-k)
    c.settle(0.5)
    clients = [c.client() for _ in range(4)]
    for rdr in clients:
        for name, want in payloads.items():
            assert rdr.read("ecs", name) == want, name


def test_balanced_reads_mid_write_never_torn(balance_cluster):
    """Concurrent write_full generations vs balanced readers: every
    read must observe exactly ONE generation (the ESTALE bounce sends
    in-flight-write reads to the primary's ordered path; a torn or
    stale-mix result here is the bug this leg exists to catch)."""
    c, cl = balance_cluster
    gens = [bytes([g]) * OBJ_SIZE for g in range(1, 16)]
    cl.write_full("ecs", "hot", gens[0])
    stop = threading.Event()
    errors = []

    def writer():
        try:
            for g in gens[1:]:
                cl.write_full("ecs", "hot", g)
                time.sleep(0.01)
        except Exception as e:  # noqa: BLE001 - surfaced by the test
            errors.append(e)
        finally:
            stop.set()

    def reader(rdr):
        try:
            while not stop.is_set():
                got = rdr.read("ecs", "hot")
                assert len(got) == OBJ_SIZE, len(got)
                # exactly one generation, no byte mixing
                assert got == bytes([got[0]]) * OBJ_SIZE, \
                    f"torn read: {got[0]} vs {set(got[:64])}"
                assert bytes([got[0]]) * OBJ_SIZE in gens, got[0]
        except Exception as e:  # noqa: BLE001
            errors.append(e)

    readers = [threading.Thread(target=reader, args=(c.client(),))
               for _ in range(3)]
    wt = threading.Thread(target=writer)
    for t in readers:
        t.start()
    wt.start()
    wt.join()
    for t in readers:
        t.join()
    assert not errors, errors[:3]
    assert cl.read("ecs", "hot") == gens[-1]


# ------------------------------------------------------- hot-read tier
def test_hot_tier_second_hit_admission_and_hits(balance_cluster):
    c, cl = balance_cluster
    data = bytes(RNG.integers(0, 256, OBJ_SIZE, dtype=np.uint8))
    cl.write_full("ecs", "hotobj", data)
    # several clients = several sticky holders; on each NON-primary
    # holder (the primary already holds write-through bytes) read 1
    # records in the seen-window, read 2 admits, read 3 serves from
    # the tier
    clients = [c.client() for _ in range(4)]
    for _ in range(3):
        for rdr in clients:
            assert rdr.read("ecs", "hotobj") == data
    assert _counter_sum(c, "ec_read_tier_admit") >= 1
    assert _counter_sum(c, "ec_read_tier_hit") >= 1
    # one-pass scans never admit: fresh objects read ONCE each
    admits_before = _counter_sum(c, "ec_read_tier_admit")
    for i in range(4):
        blob = bytes(RNG.integers(0, 256, 4096, dtype=np.uint8))
        cl.write_full("ecs", f"cold{i}", blob)
        assert clients[i].read("ecs", f"cold{i}") == blob
    assert _counter_sum(c, "ec_read_tier_admit") == admits_before


def test_hot_tier_write_invalidates_before_next_read(balance_cluster):
    c, cl = balance_cluster
    old = bytes([7]) * OBJ_SIZE
    new = bytes([9]) * OBJ_SIZE
    cl.write_full("ecs", "wobj", old)
    rdr = c.client()
    for _ in range(4):
        assert rdr.read("ecs", "wobj") == old
    cl.write_full("ecs", "wobj", new)
    # the sub-write fence invalidated every holder's cached copy
    for _ in range(4):
        assert rdr.read("ecs", "wobj") == new


def test_extent_cache_eviction_telemetry():
    """Unit: capacity-pressure evictions fire the telemetry hook;
    invalidations do not."""
    from ceph_tpu.msg.messages import PgId
    from ceph_tpu.osd.extent_cache import ECExtentCache
    evicted = [0]
    cache = ECExtentCache(
        max_bytes=4096,
        on_evict=lambda: evicted.__setitem__(0, evicted[0] + 1))
    pg = PgId(1, 0)
    cache.write(pg, "a", 0, 0, b"x" * 3000, version=1, length=3000)
    assert evicted[0] == 0
    cache.write(pg, "b", 0, 0, b"y" * 3000, version=1, length=3000)
    assert evicted[0] == 1          # "a" evicted under pressure
    cache.invalidate(pg, "b")
    assert evicted[0] == 1          # invalidation is not an eviction


# ----------------------------------------------------------- read leases
def test_lease_repeat_reads_zero_rados_ops(lease_cluster):
    c, cl = lease_cluster
    data = bytes(RNG.integers(0, 256, OBJ_SIZE, dtype=np.uint8))
    cl.write_full("ecs", "leased", data)
    rdr = c.client()
    # warm: rapid reads push the EWMA over the grant threshold, the
    # reply's lease tail populates the client cache
    deadline = time.time() + 10
    while not rdr._lease_cache and time.time() < deadline:
        assert rdr.read("ecs", "leased") == data
    assert rdr._lease_cache, "no lease was ever granted"
    assert _counter_sum(c, "read_lease_grant") >= 1
    # gate: repeat reads under the lease are ZERO RADOS ops
    calls = _count_ops(rdr)
    hits0 = rdr.lease_hits
    for _ in range(20):
        assert rdr.read("ecs", "leased") == data
    assert calls[0] == 0, f"{calls[0]} ops escaped to RADOS"
    assert rdr.lease_hits == hits0 + 20
    # ranged repeat reads are trimmed from the cached whole object
    assert rdr.read("ecs", "leased", offset=100, length=256) == \
        data[100:356]
    assert calls[0] == 0


def test_lease_write_revokes_and_next_read_is_fresh(lease_cluster):
    c, cl = lease_cluster
    old = bytes([3]) * OBJ_SIZE
    new = bytes([4]) * OBJ_SIZE
    cl.write_full("ecs", "rev", old)
    rdr = c.client()
    deadline = time.time() + 10
    while not rdr._lease_cache and time.time() < deadline:
        assert rdr.read("ecs", "rev") == old
    assert rdr._lease_cache
    cl.write_full("ecs", "rev", new)
    # ttl is 30s — only the "_lease" revoke notify can deliver fresh
    # bytes inside this window
    deadline = time.time() + 5
    got = rdr.read("ecs", "rev")
    while got != new and time.time() < deadline:
        time.sleep(0.02)
        got = rdr.read("ecs", "rev")
    assert got == new, "revoke never reached the lease holder"
    assert _counter_sum(c, "read_lease_revoke") >= 1
    # byte-identity throughout: nothing but the two generations
    assert rdr.read("ecs", "rev") == new


def test_lost_revoke_staleness_bounded_by_lease_window():
    """Fault-injection leg: the client drops the revoke notify.  It
    may serve stale bytes — UNTORN, exactly the pre-write object —
    for at most one lease window; after expiry the next read is
    fresh."""
    ttl = 1.5
    c, cl = _make_cluster(**{"osd_read_lease_ttl": ttl,
                             "osd_read_lease_rate": 1.0})
    try:
        old = bytes([5]) * OBJ_SIZE
        new = bytes([6]) * OBJ_SIZE
        cl.write_full("ecs", "st", old)
        rdr = c.client()
        deadline = time.time() + 5
        while not rdr._lease_cache and time.time() < deadline:
            assert rdr.read("ecs", "st") == old
        assert rdr._lease_cache, "no lease granted"
        rdr.drop_lease_revokes = True      # the lost-revoke injection
        granted_at = time.time()
        cl.write_full("ecs", "st", new)
        got = rdr.read("ecs", "st")
        # inside the window: stale is allowed but must be the EXACT
        # pre-write object (never torn, never garbage)
        assert got in (old, new), "torn/garbage read under lost revoke"
        if time.time() - granted_at < ttl * 0.5:
            # fast path: we are certainly inside the window, so the
            # read MUST have been the (stale) cached serve
            assert got == old
        # hard bound: one lease window later the cache has expired
        time.sleep(ttl + 0.3)
        assert rdr.read("ecs", "st") == new
        assert rdr.read("ecs", "st") == new
    finally:
        c.stop()


def test_replicated_pool_balanced_reads_byte_identity():
    """read_policy rides ec_profile on replicated pools too: replica
    serves locally, ENOENT/behind bounces to the primary."""
    c = MiniCluster(n_osds=3,
                    cfg=_cfg(**{"osd_read_lease_ttl": 0.0})).start()
    try:
        cl = c.client()
        cl.create_pool("repb", kind="replicated", size=3, pg_num=2,
                       ec_profile={"read_policy": "balance"})
        payloads = {}
        for i in range(6):
            data = bytes(RNG.integers(0, 256, 8192, dtype=np.uint8))
            payloads[f"r{i}"] = data
            cl.write_full("repb", f"r{i}", data)
        clients = [c.client() for _ in range(5)]
        for rdr in clients:
            for name, want in payloads.items():
                assert rdr.read("repb", name) == want, name
        assert _counter_sum(c, "balanced_read_serve") > 0
    finally:
        c.stop()


def test_ranged_read_rides_existing_lease(lease_cluster):
    """A RANGED read never starts a lease, but on an object already
    lease-covered it RIDES the standing grant: the reply carries the
    remaining window, the client caches the exact range (zero RADOS
    ops on repeats), and a write revokes the ranged entry through the
    same grant map."""
    c, cl = lease_cluster
    data = bytes(RNG.integers(0, 256, OBJ_SIZE, dtype=np.uint8))
    cl.write_full("ecs", "ride", data)
    rdr = c.client()
    # warm whole-object reads until the grant lands client-side
    deadline = time.time() + 10
    while not rdr._lease_cache and time.time() < deadline:
        assert rdr.read("ecs", "ride") == data
    assert rdr._lease_cache, "no lease was ever granted"
    # drop only the CLIENT cache entry — the server-side grant stays
    # live (ttl 30s) — so the next ranged read goes back to the wire
    rdr._lease_drop(rdr._pool_id("ecs"), "ride")
    assert rdr.read("ecs", "ride", offset=64, length=512) == \
        data[64:576]
    assert any(len(k) == 4 for k in rdr._lease_cache), \
        "ranged reply did not ride the standing grant"
    assert _counter_sum(c, "read_lease_ride") >= 1
    # repeats of the exact range are served locally: zero RADOS ops
    calls = _count_ops(rdr)
    for _ in range(10):
        assert rdr.read("ecs", "ride", offset=64, length=512) == \
            data[64:576]
    assert calls[0] == 0, f"{calls[0]} ranged ops escaped to RADOS"
    # a write revokes the rider too (it joined the grant map): fresh
    # range bytes arrive inside the 30 s window only via the notify
    new = bytes(reversed(data))
    cl.write_full("ecs", "ride", new)
    deadline = time.time() + 5
    got = rdr.read("ecs", "ride", offset=64, length=512)
    while got != new[64:576] and time.time() < deadline:
        time.sleep(0.02)
        got = rdr.read("ecs", "ride", offset=64, length=512)
    assert got == new[64:576], "revoke never reached the rider"
