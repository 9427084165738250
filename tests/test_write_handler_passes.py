"""The primary's write handler passes over an object's bytes once
(ISSUE 37): ``StripeInfo.ro_scatter`` in one strided pass, the rows'
checksums taken where the rows lie (``row_csums``), one copy of each
row into the extent cache's write-through.  Every return value is what
it was, byte for byte; the constructions they are held to are written
out here.
"""

import numpy as np
import pytest

from ceph_tpu import ec
from ceph_tpu.ec.batcher import ECBatcher
from ceph_tpu.ec.matrix_code import row_csums
from ceph_tpu.ec.stripe import StripeInfo
from ceph_tpu.msg.messages import PgId
from ceph_tpu.ops.checksum import crc32c_ref
from ceph_tpu.osd.extent_cache import ECExtentCache
from ceph_tpu.osd.objectstore import CollectionId, ObjectId

CHUNK = 4096


# ------------------------------------------------------------ ro_scatter
def _three_pass_scatter(k: int, chunk: int, data: bytes) -> np.ndarray:
    """What ``ro_scatter`` returned before ISSUE 37: a zero-filled pad
    buffer, the object copied into it, the transpose-reshape copy."""
    width = k * chunk
    rows = -(-len(data) // width)
    padded = np.zeros(rows * width, dtype=np.uint8)
    padded[: len(data)] = np.frombuffer(data, dtype=np.uint8)
    return padded.reshape(rows, k, chunk).transpose(1, 0, 2) \
        .reshape(k, rows * chunk)


#: object lengths as functions of the stripe row's width
LENGTHS = {
    "1": lambda w: 1,
    "4095": lambda w: 4095,
    "4096": lambda w: 4096,
    "row-1": lambda w: w - 1,
    "row": lambda w: w,
    "row+1": lambda w: w + 1,
    "4MiB": lambda w: 4 << 20,
    "4MiB+1": lambda w: (4 << 20) + 1,
}

#: the input types a caller hands over, each built from the same bytes
INPUTS = {
    "bytes": lambda b: b,
    "bytearray": bytearray,
    "memoryview": memoryview,
    "ndarray": lambda b: np.frombuffer(b, dtype=np.uint8).copy(),
}


@pytest.mark.parametrize("kind", list(INPUTS))
@pytest.mark.parametrize("k", (8, 4))
@pytest.mark.parametrize("length", list(LENGTHS))
def test_ro_scatter_is_the_three_pass_construction(length, k, kind):
    si = StripeInfo(k, 2, CHUNK)
    n = LENGTHS[length](si.stripe_width)
    payload = np.random.default_rng(n + k).integers(
        0, 256, n, dtype=np.uint8).tobytes()
    handed = INPUTS[kind](payload)
    got = si.ro_scatter(handed)
    want = _three_pass_scatter(k, CHUNK, payload)
    assert got.dtype == np.uint8 and got.shape == want.shape
    assert np.array_equal(got, want)
    # one buffer, the array's own, laid out for the encode as it is
    assert got.flags.c_contiguous and got.flags.owndata
    assert got.base is None and got.flags.writeable
    assert bytes(handed) == payload  # the input is left as it was
    # and it is no view of the input: changing one leaves the other
    if kind in ("bytearray", "ndarray"):
        handed[0] ^= 0xFF
        assert np.array_equal(got, want)


def test_ro_scatter_of_nothing_is_k_empty_streams():
    got = StripeInfo(4, 2, CHUNK).ro_scatter(b"")
    assert got.shape == (4, 0) and got.dtype == np.uint8


# ------------------------------------------------------------- row_csums
def _ref_csums(streams, parity) -> list[int]:
    return [crc32c_ref(np.asarray(row).tobytes())
            for row in list(streams) + list(parity)]


def _contiguous(rng, k: int, m: int, L: int):
    return (rng.integers(0, 256, (k, L), dtype=np.uint8),
            rng.integers(0, 256, (m, L), dtype=np.uint8))


def _views_of_wider(rng, k: int, m: int, L: int):
    """Rows as the carve meets them: column slices of wider arrays (an
    op's slot of a folded launch buffer)."""
    wide_s = rng.integers(0, 256, (k, 3 * L + 17), dtype=np.uint8)
    wide_p = rng.integers(0, 256, (m, 2 * L + 5), dtype=np.uint8)
    return wide_s[:, L + 3: 2 * L + 3], wide_p[:, 5: L + 5]


LAYOUTS = {"contiguous": _contiguous, "views_of_wider": _views_of_wider}


@pytest.mark.parametrize("layout", list(LAYOUTS))
@pytest.mark.parametrize("k,m,L", [(8, 3, 1536), (4, 2, 1000), (2, 1, 1)])
def test_row_csums_is_the_reference_crc_row_by_row(k, m, L, layout):
    streams, parity = LAYOUTS[layout](np.random.default_rng(L), k, m, L)
    before = streams.copy(), parity.copy()
    got = row_csums(streams, parity)
    assert got.dtype == np.uint32 and got.shape == (k + m,)
    assert got.tolist() == _ref_csums(streams, parity)
    assert np.array_equal(streams, before[0])
    assert np.array_equal(parity, before[1])


def _encode_unbatched(codec, data, calls):
    return codec.encode_chunks_with_csums(data)


def _encode_flushed(codec, data, calls):
    b = ECBatcher(window_us=1000)
    for name in ("_flush_encode", "_flush_encode_subchunk"):
        inner = getattr(b, name)

        def spy(*a, _inner=inner, _name=name, **kw):
            calls.append(_name)
            return _inner(*a, **kw)
        setattr(b, name, spy)
    return b.encode(codec, data, with_csums=True)


#: the three callers of the helper: (codec, how it is driven, the flush
#: that has to run it; None for the unbatched entry point)
CALLERS = {
    "plain_flush": (("tpu", {"k": "4", "m": "2", "backend": "jax"}),
                    _encode_flushed, "_flush_encode"),
    "subchunk_flush": (("clay", {"k": "4", "m": "2"}),
                       _encode_flushed, "_flush_encode_subchunk"),
    "encode_chunks_with_csums": (
        ("tpu", {"k": "4", "m": "2", "backend": "jax"}),
        _encode_unbatched, None),
}


@pytest.mark.parametrize("layout", list(LAYOUTS))
@pytest.mark.parametrize("caller", list(CALLERS))
def test_a_callers_digests_are_the_crc_of_the_rows_it_returns(caller,
                                                              layout):
    """The digest a shard stores (``dcsum``) is the CRC-32C of the
    bytes it stores: data rows as handed in, parity rows as returned."""
    (plugin, profile), drive, flush = CALLERS[caller]
    codec = ec.factory(plugin, dict(profile))
    L = 64 * codec.get_sub_chunk_count() * 3
    data, _ = LAYOUTS[layout](np.random.default_rng(7), codec.k,
                              codec.m, L)
    calls: list = []
    parity, csums = drive(codec, data, calls)
    assert calls == ([flush] if flush else [])
    parity = np.asarray(parity)
    assert np.array_equal(parity, codec.encode_chunks(
        np.ascontiguousarray(data)))
    assert np.asarray(csums).dtype == np.uint32
    assert np.asarray(csums).tolist() == _ref_csums(data, parity)


# --------------------------------------------------------- write-through
PG = PgId(1, 0)


def _written_through(k: int, m: int, payload: bytes, hand):
    """A cache that took an object's k+m rows as ``_ec_write`` hands
    them over, and the arrays the rows were handed from."""
    si = StripeInfo(k, m, CHUNK)
    streams = si.ro_scatter(payload)
    parity = np.random.default_rng(len(payload)).integers(
        0, 256, (m, streams.shape[1]), dtype=np.uint8)
    cache = ECExtentCache(max_bytes=64 << 20)
    for shard in range(k + m):
        row = streams[shard] if shard < k else parity[shard - k]
        cache.write(PG, "o", shard, 0, hand(row), version=3,
                    length=len(payload))
    return cache, streams, parity


#: what a caller may hand ``ECExtentCache.write`` for a row
HANDS = {
    "row_buffer": lambda row: row.data,   # _ec_write since ISSUE 37
    "bytes": lambda row: row.tobytes(),   # every other caller
}


@pytest.mark.parametrize("hand", list(HANDS))
@pytest.mark.parametrize("size", (1, 4096, 5 * 4 * CHUNK + 77, 1 << 20))
def test_write_through_then_read_rows_returns_the_object(size, hand):
    k, m = 4, 2
    payload = np.random.default_rng(size).integers(
        0, 256, size, dtype=np.uint8).tobytes()
    cache, streams, parity = _written_through(k, m, payload, HANDS[hand])
    L = streams.shape[1]
    assert cache.version(PG, "o") == 3
    assert cache.object_len(PG, "o") == size
    assert bytes(cache.read_rows(PG, "o", k, CHUNK, 0, L))[:size] == payload
    want_parity = parity.copy()
    # what was handed in belongs to the handler again: changing it must
    # not change what the cache serves (the run is the cache's own copy)
    streams ^= 0xFF
    parity ^= 0xFF
    assert bytes(cache.read_rows(PG, "o", k, CHUNK, 0, L))[:size] == payload
    for shard in range(m):
        assert cache.read(PG, "o", k + shard, 0, L) == \
            want_parity[shard].tobytes()


def test_write_through_of_an_empty_row_is_dropped():
    cache = ECExtentCache()
    cache.write(PG, "o", 0, 0, np.empty(0, np.uint8).data, version=1)
    cache.write(PG, "o", 0, 0, b"", version=1)
    assert cache.version(PG, "o") is None


def test_write_through_evicts_by_the_bytes_it_holds():
    """Two objects of 11 rows in a cache with room for one: the older
    goes, whole, as it did when rows arrived as ``bytes``."""
    evicted = []
    cache = ECExtentCache(max_bytes=11 * CHUNK,
                          on_evict=lambda: evicted.append(1))
    row = np.arange(CHUNK, dtype=np.uint8)
    for oid in ("a", "b"):
        for shard in range(11):
            cache.write(PG, oid, shard, 0, row.data, version=1,
                        length=8 * CHUNK)
    assert len(evicted) == 1
    assert cache.version(PG, "a") is None
    assert cache.read(PG, "b", 10, 0, CHUNK) == row.tobytes()


# ------------------------------------------------------------- a cluster
K, M = 4, 2
SIZES = (1, 4096, K * CHUNK, 3 * K * CHUNK + 1234)


@pytest.fixture(scope="module", params=("jax", "numpy"))
def cluster(request):
    from ceph_tpu.tools.vstart import MiniCluster
    from tests.test_cluster import make_cfg

    c = MiniCluster(n_osds=6, cfg=make_cfg(osd_read_lease_ttl=0.0)).start()
    try:
        client = c.client()
        client.create_pool("passes", kind="ec", pg_num=4,
                           ec_profile={"plugin": "tpu", "k": str(K),
                                       "m": str(M),
                                       "backend": request.param})
        yield c, client
    finally:
        c.stop()


@pytest.mark.parametrize("size", SIZES)
def test_stored_digest_is_the_crc_of_the_stored_bytes(cluster, size):
    """A ``write_full`` through the whole handler: every shard's store
    holds its stream of the three-pass scatter (parity: the codec's
    over those streams) with the digest it was sent (``dcsum``, kept
    as ``d`` without a second sweep) its CRC-32C, and the read that
    the write-through serves returns the object."""
    c, client = cluster
    payload = np.random.default_rng(size).integers(
        0, 256, size, dtype=np.uint8).tobytes()
    oid = f"obj{size}"
    client.write_full("passes", oid, payload)
    pool_id = client._pool_id("passes")
    seed = client.osdmap.object_to_pg(pool_id, oid)
    up = list(client.osdmap.pg_to_up_osds(pool_id, seed))
    cid = CollectionId(pool_id, seed)
    streams = _three_pass_scatter(K, CHUNK, payload)
    codec = ec.factory("tpu", {"k": str(K), "m": str(M),
                               "backend": "numpy"})
    rows = list(streams) + list(codec.encode_chunks(streams))
    for shard, osd in enumerate(up):
        store = c.osds[osd].store
        sid = ObjectId(oid, shard=shard)
        stored = store.read(cid, sid).to_bytes()
        assert stored == rows[shard].tobytes(), shard
        # the encode's ``dcsum`` is what the shard keeps as ``d``
        assert int(store.getattrs(cid, sid)["d"]) == \
            crc32c_ref(stored), shard
    hits = sum(o.perf.get("ec_read_cache_hit") for o in c.osds.values())
    assert client.read("passes", oid) == payload
    assert sum(o.perf.get("ec_read_cache_hit")
               for o in c.osds.values()) == hits + 1
