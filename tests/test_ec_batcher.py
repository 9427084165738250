"""ECBatcher tests: batched vs per-op byte-exactness against the numpy
gf256 oracle, every flush path (window / size / idle), mixed lengths and
mixed (k, m) signatures in flight, degraded-read decode coalescing, and
the pass-through (window=0) identity + no-leak smoke.

Runs on the CPU jax backend (conftest forces JAX_PLATFORMS=cpu); the
math is identical on TPU — kernels are covered by test_ec_kernels.
"""

import threading
import time

import numpy as np
import pytest

from ceph_tpu import ec
from ceph_tpu.msg.messages import PgId
from ceph_tpu.ec.batcher import (ECBatcher, FLUSH_IDLE, FLUSH_SIZE,
                                 FLUSH_WINDOW, bucket_len)
from ceph_tpu.ops import gf256, native

RNG = np.random.default_rng(11)


def _codec(k=4, m=2):
    return ec.factory("tpu", {"k": k, "m": m, "backend": "jax"})


def _oracle_parity(codec, data):
    return gf256.encode_region(codec.matrix, data)


def _oracle_csums(data, parity):
    stack = np.concatenate([data, np.asarray(parity)], axis=0)
    return np.array([native.crc32c(row.tobytes()) for row in stack],
                    dtype=np.uint32)


def _burst(batcher, codec, payloads, *, with_csums=False, stagger=0.02):
    """Submit each payload from its own thread; first thread leads."""
    results = [None] * len(payloads)
    errors = []

    def writer(i):
        try:
            results[i] = batcher.encode(codec, payloads[i],
                                        with_csums=with_csums)
        except Exception as e:  # noqa: BLE001 - surfaced by the test
            errors.append(e)

    threads = [threading.Thread(target=writer, args=(i,))
               for i in range(len(payloads))]
    threads[0].start()
    time.sleep(stagger)  # let the leader enter its window first
    for t in threads[1:]:
        t.start()
    for t in threads:
        t.join()
    assert not errors, errors
    return results


def test_bucket_len_bounded():
    # pow2 buckets plus 1.5x half-steps: 512, 768, 1024, 1536, 2048, ...
    assert bucket_len(1) == 512
    assert bucket_len(512) == 512
    assert bucket_len(513) == 768
    assert bucket_len(768) == 768
    assert bucket_len(769) == 1024
    assert bucket_len(4096) == 4096
    assert bucket_len(4097) == 6144
    assert bucket_len(5000) == 6144
    assert bucket_len(6145) == 8192


def test_bucket_len_pad_waste_bounded():
    """The half-step buckets cap pad waste at 50% of the chunk length
    above the 512-byte tiling floor — a just-over-pow2 chunk (the
    4 KiB + header case) must never pad almost 2x."""
    for L in range(512, 20_000, 7):
        b = bucket_len(L)
        assert b >= L and b % 4 == 0
        assert b - L <= L * 0.5, (L, b)
    # bucket set stays bounded: two shapes per octave (step 13 < the
    # narrowest bucket interval, so every bucket is still visited)
    buckets = {bucket_len(L) for L in range(1, 1 << 20, 13)}
    assert buckets == {512, 768, 1024, 1536, 2048, 3072, 4096, 6144,
                       8192, 12_288, 16_384, 24_576, 32_768, 49_152,
                       65_536, 98_304, 131_072, 196_608, 262_144,
                       393_216, 524_288, 786_432, 1 << 20}


def test_passthrough_window0_bit_identical_no_leaks():
    """window=0 pass-through: bit-identical to the per-op codec entry
    points, every callback fired synchronously, nothing pending."""
    codec = _codec()
    b = ECBatcher(window_us=0)
    fired = []
    for L in (512, 1000, 4096, 53_248):
        data = RNG.integers(0, 256, (4, L), dtype=np.uint8)
        parity, csums = b.encode(codec, data, with_csums=True,
                                 callback=lambda p, c: fired.append(1))
        want_p, want_c = codec.encode_chunks_with_csums(data)
        assert np.array_equal(np.asarray(parity), want_p)
        assert np.array_equal(np.asarray(csums), want_c)
        # plain encode too
        p2, c2 = b.encode(codec, data, with_csums=False,
                          callback=lambda p, c: fired.append(1))
        assert np.array_equal(np.asarray(p2), codec.encode_chunks(data))
        assert c2 is None
    # decode pass-through
    full = codec.encode(b"q" * 8192)
    avail = {i: c for i, c in full.items() if i != 2}
    out = b.decode(codec, [0, 1, 2, 3], dict(avail),
                   callback=lambda o: fired.append(1))
    ref = codec.decode([0, 1, 2, 3], dict(avail))
    for i in ref:
        assert np.array_equal(np.asarray(out[i]), np.asarray(ref[i]))
    assert len(fired) == 9  # 4 lengths x 2 encodes + 1 decode
    assert b.pending_ops() == 0
    assert b.stats["launches"] == 9
    assert b.stats[FLUSH_IDLE] == 9 and b.stats[FLUSH_WINDOW] == 0


def test_size_flush_coalesces_two_ops_one_launch():
    """Second arrival crosses max_bytes -> ONE folded launch, reason
    'size', both results byte-exact vs the oracle."""
    codec = _codec()
    L = 4096
    b = ECBatcher(window_us=10_000_000, max_bytes=2 * 4 * L)
    pays = [RNG.integers(0, 256, (4, L), dtype=np.uint8)
            for _ in range(2)]
    results = _burst(b, codec, pays, with_csums=True)
    for data, (parity, csums) in zip(pays, results):
        assert np.array_equal(np.asarray(parity), _oracle_parity(codec,
                                                                 data))
        assert np.array_equal(np.asarray(csums),
                              _oracle_csums(data, parity))
    assert b.stats["launches"] == 1
    assert b.stats["ops"] == 2
    assert b.stats[FLUSH_SIZE] == 1
    assert b.pending_ops() == 0


def test_mixed_lengths_coalesce_byte_exact():
    """Ops of different lengths share a bucket, pad, and slice back
    byte-exact (each op's csums are the sweep of its own length)."""
    codec = _codec()
    lens = [1000, 900, 1024]  # one shared 1024 bucket (769..1024)
    b = ECBatcher(window_us=10_000_000,
                  max_bytes=4 * sum(lens))  # third arrival size-flushes
    pays = [RNG.integers(0, 256, (4, L), dtype=np.uint8) for L in lens]
    results = _burst(b, codec, pays, with_csums=True)
    for data, (parity, csums) in zip(pays, results):
        assert np.array_equal(np.asarray(parity),
                              _oracle_parity(codec, data))
        assert np.array_equal(np.asarray(csums),
                              _oracle_csums(data, parity))
    assert b.stats["launches"] == 1 and b.stats["ops"] == 3


def test_window_flush_coalesces():
    """Leader waits out the window; a follower arriving inside it rides
    the same launch (reason 'window')."""
    codec = _codec()
    L = 2048
    b = ECBatcher(window_us=1_500_000)  # 1.5s: CI-safe margin
    pays = [RNG.integers(0, 256, (4, L), dtype=np.uint8)
            for _ in range(2)]
    results = _burst(b, codec, pays, stagger=0.1)
    for data, (parity, _c) in zip(pays, results):
        assert np.array_equal(np.asarray(parity),
                              _oracle_parity(codec, data))
    assert b.stats["launches"] == 1
    assert b.stats[FLUSH_WINDOW] == 1
    assert b.stats["ops"] == 2


def test_mixed_signatures_in_flight():
    """Two (k, m) signatures in flight at once form two independent
    groups — one launch each, results exact for both codecs."""
    c42, c83 = _codec(4, 2), _codec(8, 3)
    b = ECBatcher(window_us=1_500_000)
    L = 1024
    p42 = [RNG.integers(0, 256, (4, L), dtype=np.uint8)
           for _ in range(2)]
    p83 = [RNG.integers(0, 256, (8, L), dtype=np.uint8)
           for _ in range(2)]
    results = {}
    errors = []

    def writer(key, codec, data):
        try:
            results[key] = b.encode(codec, data)
        except Exception as e:  # noqa: BLE001
            errors.append(e)

    threads = [threading.Thread(target=writer, args=(("a", i), c42,
                                                     p42[i]))
               for i in range(2)]
    threads += [threading.Thread(target=writer, args=(("b", i), c83,
                                                      p83[i]))
                for i in range(2)]
    threads[0].start()
    threads[2].start()
    time.sleep(0.1)
    threads[1].start()
    threads[3].start()
    for t in threads:
        t.join()
    assert not errors, errors
    for i in range(2):
        assert np.array_equal(np.asarray(results[("a", i)][0]),
                              _oracle_parity(c42, p42[i]))
        assert np.array_equal(np.asarray(results[("b", i)][0]),
                              _oracle_parity(c83, p83[i]))
    assert b.stats["launches"] == 2
    assert b.stats["ops"] == 4
    assert b.pending_ops() == 0


def test_degraded_decode_coalesce():
    """Two degraded-read decodes with the same erasure signature ride
    one decode_chunks flush, byte-exact vs the per-op decode."""
    codec = _codec()
    L = 4096
    stripes = [RNG.integers(0, 256, (4, L), dtype=np.uint8)
               for _ in range(2)]
    cases = []
    for data in stripes:
        parity = _oracle_parity(codec, data)
        chunks = {0: data[0], 2: data[2], 3: data[3],
                  4: parity[0], 5: parity[1]}  # shard 1 erased
        cases.append((data, chunks))
    b = ECBatcher(window_us=1_500_000)
    out = [None, None]
    errors = []

    def reader(i):
        try:
            out[i] = b.decode(codec, [0, 1, 2, 3], dict(cases[i][1]))
        except Exception as e:  # noqa: BLE001
            errors.append(e)

    t0 = threading.Thread(target=reader, args=(0,))
    t1 = threading.Thread(target=reader, args=(1,))
    t0.start()
    time.sleep(0.1)
    t1.start()
    t0.join()
    t1.join()
    assert not errors, errors
    for i, (data, chunks) in enumerate(cases):
        ref = codec.decode([0, 1, 2, 3], dict(chunks))
        for s in ref:
            assert np.array_equal(np.asarray(out[i][s]),
                                  np.asarray(ref[s])), (i, s)
            assert np.array_equal(np.asarray(out[i][s]), data[s]), (i, s)
    assert b.stats["launches"] == 1
    assert b.stats["ops"] == 2
    assert b.pending_ops() == 0


def test_decode_all_present_no_launch():
    """Wanted shards all present: pure pass-through dict, no launch."""
    codec = _codec()
    full = codec.encode(b"y" * 8192)
    b = ECBatcher(window_us=1000)
    out = b.decode(codec, [0, 1], {i: full[i] for i in range(4)})
    assert np.array_equal(out[0], full[0])
    assert b.stats["launches"] == 0


def test_batched_encode_matches_oracle_many_lengths():
    """Sequential (idle-flush) batched encodes across many lengths stay
    byte-exact — covers the bucket/pad/slice path without threads."""
    codec = _codec()
    b = ECBatcher(window_us=50)  # tiny window: each op idle-flushes
    # 12_288 = 3 stripe rows of 4096: NOT a power of two but % 4 == 0,
    # so the fused encode+CRC device path must still engage
    for L in (512, 513, 1000, 2048, 4096, 10_000, 12_288, 53_248):
        data = RNG.integers(0, 256, (4, L), dtype=np.uint8)
        parity, csums = b.encode(codec, data, with_csums=True)
        assert np.array_equal(np.asarray(parity),
                              _oracle_parity(codec, data)), L
        assert np.array_equal(np.asarray(csums),
                              _oracle_csums(data, parity)), L
    assert b.pending_ops() == 0


@pytest.mark.parametrize("shard", ["off", "2"])
def test_checksummed_flush_one_launch_one_fetch(shard):
    """A write flush of checksummed ops of one length: ONE folded
    launch, ONE metered fetch, and each op's csums are the native sweep
    over its data and parity rows — on one device and fanned over a
    two-device mesh."""
    from ceph_tpu.utils import staging
    codec = ec.factory("tpu", {"k": 4, "m": 2, "backend": "jax",
                               "shard": shard})
    n_ops, L = 4, 4096
    b = ECBatcher(window_us=5_000_000, max_bytes=n_ops * 4 * L)
    pays = [RNG.integers(0, 256, (4, L), dtype=np.uint8)
            for _ in range(n_ops)]
    pc = staging.stage_perf()
    copies0 = pc.get("ec_stage_d2h_copies")
    results = _burst(b, codec, pays, with_csums=True)
    assert b.stats["launches"] == 1 and b.stats[FLUSH_SIZE] == 1
    assert b.stats["sharded_launches"] == (1 if shard == "2" else 0)
    assert pc.get("ec_stage_d2h_copies") == copies0 + 1
    for data, (parity, csums) in zip(pays, results):
        assert np.array_equal(np.asarray(parity),
                              _oracle_parity(codec, data))
        assert np.array_equal(np.asarray(csums),
                              _oracle_csums(data, parity))


def test_bad_shape_fails_alone_not_the_batch():
    """An op with the wrong k must raise the codec's own error via the
    per-op path — never fold and poison coalesced neighbors."""
    import pytest

    from ceph_tpu.ec import ErasureCodeError
    codec = _codec(4, 2)
    b = ECBatcher(window_us=10_000)
    bad = RNG.integers(0, 256, (3, 1024), dtype=np.uint8)  # k-1 rows
    with pytest.raises(ErasureCodeError):
        b.encode(codec, bad)
    good = RNG.integers(0, 256, (4, 1024), dtype=np.uint8)
    parity, _ = b.encode(codec, good)
    assert np.array_equal(np.asarray(parity), _oracle_parity(codec, good))
    assert b.pending_ops() == 0


def test_non_matrix_codec_passes_through():
    """A codec whose encode isn't a plain region matmul (CLAY's coupled
    layers) must never fold — pass-through with exact results."""
    clay = ec.factory("clay", {"k": "4", "m": "2"})
    data = RNG.integers(0, 256, (4, 4096), dtype=np.uint8)
    b = ECBatcher(window_us=10_000)
    parity, _ = b.encode(clay, data)
    assert np.array_equal(np.asarray(parity), clay.encode_chunks(data))
    assert b.stats[FLUSH_IDLE] == 1


# -------------------------------------- device-resident stripe plane e2e
def test_device_cache_serves_and_invalidation_forces_reread():
    """E2E leg for the extent cache's client-read serve (ISSUE 6; on
    the host since ISSUE 35): on a jax pool the primary's write-through
    populates the host cache, a hot-object client read serves straight
    from it (ec_read_cache_hit, byte-identical to the store path,
    nothing staged), and the invalidation contract holds end to end —
    an overwrite serves the NEW bytes, an osdmap change evicts the
    remapped PGs' entries, and a remove leaves no cached version
    behind."""
    from ceph_tpu.tools.vstart import MiniCluster
    from ceph_tpu.utils import staging
    from tests.test_cluster import make_cfg

    c = MiniCluster(n_osds=6, cfg=make_cfg()).start()
    try:
        client = c.client()
        client.create_pool("plane", kind="ec", pg_num=1,
                           ec_profile={"plugin": "tpu", "k": "4",
                                       "m": "2", "backend": "jax"})
        payload = RNG.integers(0, 256, 120_000, dtype=np.uint8).tobytes()
        client.write_full("plane", "hot", payload)
        pool_id = client._pool_id("plane")
        seed = c.mon.osdmap.object_to_pg(pool_id, "hot")
        up = c.mon.osdmap.pg_to_up_osds(pool_id, seed)
        prim = c.osds[up[0]]
        pc = staging.stage_perf()
        hits0 = prim.perf.get("ec_read_cache_hit")
        staged0 = (pc.get("ec_stage_h2d_bytes"),
                   pc.get("ec_stage_d2h_bytes"))
        assert client.read("plane", "hot") == payload
        assert prim.perf.get("ec_read_cache_hit") == hits0 + 1
        # served from the cache's host runs: nothing staged in either
        # direction
        assert (pc.get("ec_stage_h2d_bytes"),
                pc.get("ec_stage_d2h_bytes")) == staged0
        # ranged read off the cached rows stays byte-identical too
        assert client.read("plane", "hot", offset=4096,
                           length=10_000) == payload[4096:14096]
        # overwrite: write-through replaces the cached rows at the new
        # version — the cached serve must produce the NEW bytes
        payload2 = RNG.integers(0, 256, 120_000,
                                dtype=np.uint8).tobytes()
        client.write_full("plane", "hot", payload2)
        assert client.read("plane", "hot") == payload2
        # osdmap change remapping the PG: the primary's cache entries
        # for that PG evict; the next read re-fans to
        # the stores (degraded) and still returns the right bytes
        epoch = c.mon.osdmap.epoch
        victim = next(o for o in up[1:] if o is not None)
        c.kill_osd(victim)
        c.wait_for_epoch(epoch + 1)
        c.settle(0.6)
        pgid = PgId(pool_id, seed)
        deadline = time.time() + 10
        while time.time() < deadline and \
                prim._ec_cache.version(pgid, "hot") is not None:
            time.sleep(0.05)  # primary still draining the new map
        assert prim._ec_cache.version(pgid, "hot") is None
        assert client.read("plane", "hot") == payload2
        # remove: the cached version must not survive the object
        client.remove("plane", "hot")
        c.settle(0.3)
        pg = next(iter(prim._ec_cache.pgids()), None)
        if pg is not None:
            assert prim._ec_cache.version(pg, "hot") is None
    finally:
        c.stop()
