"""An encode flush's carve with digests is ONE native call
(``native.crc32c_rows``): each op's parity copied out of the launch
buffer and its k+m CRC-32C taken, data rows first.  The digests are the
CRC-32C a call a row gave (``native.crc32c``) and the pure-Python
reference, bit for bit; the parity is the launch's bytes in an array of
the op's own.  ``row_csums`` and ``native.crc32c_blocks`` are callers of
the same entry.
"""

import threading

import numpy as np
import pytest

from ceph_tpu import ec
from ceph_tpu.ec.batcher import ECBatcher
from ceph_tpu.ec.matrix_code import carve_with_csums, row_csums
from ceph_tpu.ops import native
from ceph_tpu.ops.checksum import crc32c_ref
from ceph_tpu.osd.objectstore import CollectionId, ObjectId
from ceph_tpu.utils.perf import PerfCounters


def _per_row(rows) -> list[int]:
    """What a digest list was before the one call: a call a row."""
    return [native.crc32c(np.ascontiguousarray(r)) for r in rows]


def _fold(rng, k: int, m: int, lengths, stride: int, lead: int = 0):
    """Ops as a flush meets them: each op's (k, L) source rows a column
    slice of a wider array, and the (m, lead + n*stride + 7) launch
    buffer whose op i's parity starts at ``lead + i * stride``."""
    streams = []
    for L in lengths:
        wide = rng.integers(0, 256, (k, 2 * L + 13), dtype=np.uint8)
        streams.append(wide[:, 13: 13 + L])
    launch = rng.integers(0, 256, (m, lead + len(lengths) * stride + 7),
                          dtype=np.uint8)
    cols = [lead + i * stride for i in range(len(lengths))]
    return streams, launch, cols


#: op lengths of a fold of 1, 2 and 3 ops, none a multiple of 8
FOLDS = {1: (1531,), 2: (1531, 1001), 3: (1531, 1, 999)}


@pytest.mark.parametrize("k,m", [(4, 2), (8, 3)])
@pytest.mark.parametrize("n_ops", list(FOLDS))
def test_carve_digests_are_the_crc_of_every_row(n_ops, k, m):
    rng = np.random.default_rng(100 * n_ops + k)
    streams, launch, cols = _fold(rng, k, m, FOLDS[n_ops], stride=1536,
                                  lead=3)
    parities, sums = carve_with_csums(streams, launch, cols)
    assert sums.dtype == np.uint32 and sums.shape == (n_ops, k + m)
    for s, c, p, got in zip(streams, cols, parities, sums):
        assert s.strides[0] != s.shape[1]  # rows of a wider array
        want = launch[:, c: c + s.shape[1]]
        assert np.array_equal(p, want)
        rows = list(s) + list(want)
        assert got.tolist() == _per_row(rows)
        assert got.tolist() == [crc32c_ref(r.tobytes()) for r in rows]


@pytest.mark.parametrize("n_ops", list(FOLDS))
def test_carved_parity_is_the_ops_own(n_ops):
    rng = np.random.default_rng(n_ops)
    streams, launch, cols = _fold(rng, 8, 3, FOLDS[n_ops], stride=1536)
    parities, sums = carve_with_csums(streams, launch, cols)
    kept = [p.copy() for p in parities]
    for p in parities:
        assert p.flags.c_contiguous and p.flags.owndata
        assert not np.shares_memory(p, launch)
    launch ^= 0xFF  # the flush's buffer, reused or freed after the carve
    for p, want in zip(parities, kept):
        assert np.array_equal(p, want)
    assert sums.tolist() == carve_with_csums(
        streams, launch ^ 0xFF, cols)[1].tolist()


def test_carve_refuses_rows_outside_the_launch():
    rng = np.random.default_rng(5)
    streams, launch, cols = _fold(rng, 4, 2, (100, 100), stride=100)
    with pytest.raises(ValueError):
        carve_with_csums(streams, launch[:, :150], cols)


@pytest.mark.parametrize("k,m", [(4, 2), (8, 3)])
@pytest.mark.parametrize("L", [1, 1000, 4093])
def test_row_csums_is_one_call_of_the_carve(k, m, L, monkeypatch):
    rng = np.random.default_rng(L + k)
    (streams,), launch, _ = _fold(rng, k, m, (L,), stride=L, lead=5)
    parity = launch[:, 5: 5 + L]
    calls = []
    inner = native.crc32c_rows

    def spy(*a, **kw):
        calls.append(a[2])
        return inner(*a, **kw)
    monkeypatch.setattr(native, "crc32c_rows", spy)
    got = row_csums(streams, parity)
    assert got.tolist() == _per_row(list(streams) + list(parity))
    assert calls == [None]  # one call, and the parity is not copied


@pytest.mark.parametrize("size,block", [(0, 4096), (1, 4096),
                                        (4096 * 3, 4096),
                                        (4096 * 3 + 1001, 4096),
                                        (10_007, 512)])
@pytest.mark.parametrize("kind", ["bytes", "ndarray"])
def test_crc32c_blocks_is_one_call_and_the_per_block_crc(size, block,
                                                          kind,
                                                          monkeypatch):
    payload = np.random.default_rng(size).integers(
        0, 256, size, dtype=np.uint8)
    data = payload.tobytes() if kind == "bytes" else payload
    want = [native.crc32c(payload[o: o + block])
            for o in range(0, size, block)]
    calls = []
    inner = native.crc32c_rows

    def spy(*a, **kw):
        calls.append(1)
        return inner(*a, **kw)
    monkeypatch.setattr(native, "crc32c_rows", spy)
    got = native.crc32c_blocks(data, block)
    assert got == want
    assert got == [crc32c_ref(payload[o: o + block].tobytes())
                   for o in range(0, size, block)]
    assert calls == [1]


# ------------------------------------------------------------ the flush
def _burst(b, codec, payloads, with_csums):
    results = [None] * len(payloads)
    errors = []

    def writer(i):
        try:
            results[i] = b.encode(codec, payloads[i],
                                  with_csums=with_csums)
        except Exception as e:  # noqa: BLE001 - surfaced below
            errors.append(e)

    threads = [threading.Thread(target=writer, args=(i,))
               for i in range(len(payloads))]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert not errors, errors
    return results


CODECS = {
    "plain": ("tpu", {"k": "4", "m": "2", "backend": "jax"}),
    "subchunk": ("clay", {"k": "4", "m": "2"}),
}


@pytest.mark.parametrize("with_csums", [True, False])
@pytest.mark.parametrize("kind", list(CODECS))
def test_a_flush_carves_in_one_native_call(kind, with_csums, monkeypatch):
    """A flush whose ops asked for digests calls the native carve once,
    whatever its fold; one without digests calls it never.  Either way
    every op's parity is the codec's and its digests the per-row CRC."""
    plugin, profile = CODECS[kind]
    codec = ec.factory(plugin, dict(profile))
    unit = 64 * codec.get_sub_chunk_count()
    # the sub-chunk fold takes one length; the plain one mixes them
    lengths = ((unit * 3,) * 3 if kind == "subchunk"
               else (unit * 3, unit * 3 - 5, unit * 2 + 1))
    rng = np.random.default_rng(len(lengths))
    payloads = [rng.integers(0, 256, (codec.k, L), dtype=np.uint8)
                for L in lengths]
    calls, row_calls = [], []
    inner = native.crc32c_rows

    def spy(*a, **kw):
        calls.append(len(a[3]))
        return inner(*a, **kw)
    monkeypatch.setattr(native, "crc32c_rows", spy)
    monkeypatch.setattr(native, "crc32c",
                        lambda *a, **kw: row_calls.append(1))
    perf = PerfCounters("osd.test")
    b = ECBatcher(window_us=200_000, perf=perf)
    results = _burst(b, codec, payloads, with_csums)
    monkeypatch.undo()
    launches = b.stats["launches"]
    assert b.stats["ops"] == len(payloads)
    if kind == "plain":
        assert launches < len(payloads)  # something folded
    for data, (parity, csums) in zip(payloads, results):
        assert np.array_equal(np.asarray(parity),
                              codec.encode_chunks(data))
        if with_csums:
            assert np.asarray(csums).tolist() == _per_row(
                list(data) + list(np.asarray(parity)))
        else:
            assert csums is None
    assert row_calls == []
    rows = len(payloads) * (codec.k + codec.m)
    if with_csums:
        assert len(calls) == launches  # one call a flush
        assert sum(calls) == len(payloads)
        assert perf.get("ec_carve_native_calls") == launches
        assert perf.get("ec_carve_rows") == rows
    else:
        assert calls == []
        assert perf.get("ec_carve_native_calls") == 0
        assert perf.get("ec_carve_rows") == 0


def test_unbatched_encode_with_csums_is_one_call(monkeypatch):
    codec = ec.factory("tpu", {"k": "8", "m": "3", "backend": "jax"})
    data = np.random.default_rng(3).integers(0, 256, (8, 4099),
                                             dtype=np.uint8)
    calls = []
    inner = native.crc32c_rows

    def spy(*a, **kw):
        calls.append(1)
        return inner(*a, **kw)
    monkeypatch.setattr(native, "crc32c_rows", spy)
    for b in (None, ECBatcher(window_us=0)):
        calls.clear()
        parity, csums = (codec.encode_chunks_with_csums(data) if b is None
                         else b.encode(codec, data, with_csums=True))
        assert calls == [1]
        assert csums.tolist() == _per_row(list(data) + list(parity))


# ------------------------------------------------------------- a cluster
K, M, CHUNK = 4, 2, 4096


def test_stored_digests_after_cluster_writes_are_the_per_row_crc():
    """``write_full`` from four writers at once through the whole
    handler: every shard keeps as ``d`` what a call a row over the
    stored stream gives (the formula before the one call)."""
    from ceph_tpu.tools.vstart import MiniCluster
    from tests.test_cluster import make_cfg

    c = MiniCluster(n_osds=6, cfg=make_cfg(osd_read_lease_ttl=0.0)).start()
    try:
        client = c.client()
        client.create_pool("carve", kind="ec", pg_num=4,
                           ec_profile={"plugin": "tpu", "k": str(K),
                                       "m": str(M), "backend": "jax"})
        sizes = (1, 4096, K * CHUNK, 3 * K * CHUNK + 1234)
        payloads = {f"obj{s}": np.random.default_rng(s).integers(
            0, 256, s, dtype=np.uint8).tobytes() for s in sizes}
        threads = [threading.Thread(target=client.write_full,
                                    args=("carve", oid, data))
                   for oid, data in payloads.items()]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        pool_id = client._pool_id("carve")
        for oid, data in payloads.items():
            seed = client.osdmap.object_to_pg(pool_id, oid)
            up = list(client.osdmap.pg_to_up_osds(pool_id, seed))
            cid = CollectionId(pool_id, seed)
            for shard, osd in enumerate(up):
                store = c.osds[osd].store
                sid = ObjectId(oid, shard=shard)
                stored = store.read(cid, sid).to_bytes()
                assert int(store.getattrs(cid, sid)["d"]) == \
                    native.crc32c(stored), (oid, shard)
            assert client.read("carve", oid) == data
        assert sum(o.perf.get("ec_carve_native_calls")
                   for o in c.osds.values()) >= 1
    finally:
        c.stop()
