"""A shard's stored digest after an extent apply.

``_apply_partial`` derives the digest ``d`` from the one the stream had
and the extents (``ops/checksum.crc32c_overwrite``: CRC-32C is affine
over GF(2)), and reads the stream back and sweeps it only where it has
no digest to start from or the extents overlap.  Whichever it does, the
stored ``d`` is the CRC-32C of the stream as stored: every case here
holds it to ``native_crc32c`` of the bytes read back, and to
``crc32c_ref`` for the helper alone.  Small sizes on the CPU platform,
seeded data; nothing here is a measurement.
"""

import numpy as np
import pytest

from ceph_tpu.msg.messages import PgId
from ceph_tpu.ops import checksum
from ceph_tpu.ops.checksum import crc32c_overwrite, crc32c_ref
from ceph_tpu.ops.native import crc32c as native_crc32c
from ceph_tpu.osd import daemon as osd_daemon
from ceph_tpu.osd.objectstore import CollectionId, ObjectId, Transaction
from ceph_tpu.tools.vstart import MiniCluster
from tests.test_cluster import make_cfg
from tests.test_compression import store_cluster

K, M, UNIT = 4, 2, 4096
ROW = K * UNIT
EAGAIN, ENOENT = -11, -2
FOLD, SWEEP = "partial_digest_fold", "partial_digest_sweep"
COMPRESSIBLE = b"the quick brown fox jumps over the lazy dog / " * 2000


# ------------------------------------------------------- the helper alone
def _xor(a: bytes, b: bytes) -> np.ndarray:
    return np.bitwise_xor(np.frombuffer(a, np.uint8),
                          np.frombuffer(b, np.uint8))


def _overwrite(stream: bytearray, extents: list) -> list:
    """Lay ``extents`` over ``stream`` as a store does (zero fill past
    the end) and return the deltas ``crc32c_overwrite`` is handed."""
    deltas = []
    for off, new in extents:
        end = off + len(new)
        if len(stream) < end:
            stream.extend(bytes(end - len(stream)))
        deltas.append((off, _xor(bytes(stream[off:end]), new)))
        stream[off:end] = new
    return deltas


HELPER_CASES = {
    # name: (stream length, [(offset, length), ...])
    "inside": (50_000, [(12_345, 4096)]),
    "one-byte": (50_000, [(777, 1)]),
    "unaligned-64k": (300_000, [(100_001, 65_536)]),
    "at-the-start": (50_000, [(0, 999)]),
    "at-the-end": (50_000, [(50_000 - 4097, 4097)]),
    "whole-stream": (4096, [(0, 4096)]),
    "grows": (50_000, [(49_000, 4096)]),
    "appends": (50_000, [(50_000, 313)]),
    "hole": (50_000, [(70_001, 2048)]),
    "onto-nothing": (0, [(0, 5000)]),
    "hole-onto-nothing": (0, [(4096, 4096)]),
    "two-disjoint": (50_000, [(30_000, 4096), (1000, 500)]),
    "two-adjacent": (50_000, [(8192, 4096), (4096, 4096)]),
    "three-with-growth": (50_000, [(60_000, 100), (0, 1), (49_999, 2)]),
    "one-mib": (1 << 20, [(1_000_003, 40_000)]),
}


@pytest.mark.parametrize("name", sorted(HELPER_CASES))
def test_helper_follows_an_overwrite(name):
    length, spans = HELPER_CASES[name]
    rng = np.random.default_rng([length, len(spans), 0x64])
    stream = bytearray(rng.bytes(length))
    crc = crc32c_ref(stream)
    deltas = _overwrite(stream, [(off, rng.bytes(n)) for off, n in spans])
    assert crc32c_overwrite(crc, length, deltas) == \
        (crc32c_ref(stream), len(stream))


def test_helper_random_extents():
    """Random offsets and lengths, 1 B to 64 KiB, on a stream that each
    overwrite may grow; the digest is carried from write to write."""
    rng = np.random.default_rng(0x6F76)
    stream = bytearray(rng.bytes(10_000))
    crc = crc32c_ref(stream)
    for _ in range(40):
        length = len(stream)
        off = int(rng.integers(0, length + 3000))
        n = int(rng.integers(1, 65_537))
        deltas = _overwrite(stream, [(off, rng.bytes(n))])
        crc, new_len = crc32c_overwrite(crc, length, deltas)
        assert new_len == len(stream)
        assert crc == native_crc32c(bytes(stream))
    assert crc == crc32c_ref(stream)


def test_helper_zero_delta_and_no_extents():
    stream = np.random.default_rng(5).bytes(9000)
    crc = crc32c_ref(stream)
    assert crc32c_overwrite(crc, 9000, []) == (crc, 9000)
    same = [(100, _xor(stream[100:600], stream[100:600]))]
    assert crc32c_overwrite(crc, 9000, same) == (crc, 9000)
    # zeros written past the end still grow the stream
    assert crc32c_overwrite(crc, 9000, [(9000, np.zeros(50, np.uint8))]) \
        == (crc32c_ref(stream + bytes(50)), 9050)


@pytest.mark.parametrize("deltas", [
    [(0, b"\1" * 100), (50, b"\1" * 100)],       # overlap
    [(10, b"\1" * 100), (10, b"\1" * 100)],      # the same extent twice
    [(10, b"")],                                 # empty
    [(-1, b"\1")],                               # before the stream
], ids=["overlap", "twice", "empty", "negative"])
def test_helper_leaves_what_it_cannot_account_for(deltas):
    assert crc32c_overwrite(0x1234, 1000, deltas) is None


# ---------------------------------------------------------------- plumbing
class Bed:
    """A cluster with an EC pool, a replicated pool and a compressing
    replicated pool."""

    def __init__(self, backend: str):
        self.cluster = MiniCluster(
            n_osds=K + M, cfg=make_cfg(ec_backend=backend)).start()
        self.client = self.cluster.client()
        self.client.create_pool(
            "ec", kind="ec", pg_num=4,
            ec_profile={"plugin": "tpu", "k": str(K), "m": str(M),
                        "backend": backend})
        self.client.create_pool("rep", size=3, pg_num=2)
        self.client.create_pool(
            "cz", size=3, pg_num=1,
            ec_profile={"compression_mode": "aggressive",
                        "compression_algorithm": "czlib",
                        "compression_required_ratio": "0.875",
                        "compression_min_blob_size": "1024"})
        self.rng = np.random.default_rng([len(backend), 0x6464])

    def holders(self, pool: str, oid: str) -> list:
        """[(osd, pgid, ObjectId)] of every copy or shard of an object."""
        pool_id = self.client._pool_id(pool)
        seed = self.client.osdmap.object_to_pg(pool_id, oid)
        up = self.client.osdmap.pg_to_up_osds(pool_id, seed)
        ec = pool == "ec"
        return [(self.cluster.osds[osd], PgId(pool_id, seed),
                 ObjectId(oid, shard=i if ec else -1))
                for i, osd in enumerate(up)]

    def osds(self) -> list:
        return list(self.cluster.osds.values())

    def check(self, pool: str, oid: str) -> None:
        """Every holder's stored ``d`` is the CRC-32C of its stream."""
        for osd, pgid, obj in self.holders(pool, oid):
            _stored(osd, pgid, obj)


def _digests(osds) -> dict:
    """How many extent applies derived their digest, how many swept."""
    return {n: sum(o.perf.get(n) for o in osds) for n in (FOLD, SWEEP)}


def _grew(before: dict, osds) -> dict:
    return {n: v - before[n] for n, v in _digests(osds).items()}


@pytest.fixture
def native_calls(monkeypatch):
    """The lengths handed to the native CRC while the fixture lives."""
    swept = []

    def counting(data, crc=0):
        swept.append(len(data))
        return native_crc32c(data, crc)
    monkeypatch.setattr(osd_daemon, "native_crc32c", counting)
    monkeypatch.setattr(checksum, "_native_crc32c", counting)
    return swept


def _stored(osd, pgid, obj) -> tuple[bytes, dict]:
    cid = CollectionId(pgid.pool, pgid.seed)
    stream = osd.store.read(cid, obj).to_bytes()
    attrs = dict(osd.store.getattrs(cid, obj))
    assert int(attrs["d"]) == native_crc32c(stream), (osd.name, obj)
    return stream, attrs


@pytest.fixture(scope="module", params=["numpy", "native"])
def bed(request):
    b = Bed(request.param)
    yield b
    b.cluster.stop()


# ------------------------------------------- one shard, one call at a time
def _shard(bed, oid: str, shard: int, rows: int = 3):
    """An EC object written whole, and the holder of one shard of it."""
    bed.client.write_full("ec", oid, bed.rng.bytes(rows * ROW))
    osd, pgid, obj = bed.holders("ec", oid)[shard]
    stream, attrs = _stored(osd, pgid, obj)
    assert len(stream) == rows * UNIT
    return osd, pgid, obj, stream, attrs


APPLIES = {
    # name: ([(offset, length), ...] on a 12 KiB shard stream, fold?)
    "data-leg": ([(UNIT, UNIT)], True),
    "unaligned": ([(5001, 1234)], True),
    "two-disjoint": ([(2 * UNIT, 100), (0, UNIT)], True),
    "grows": ([(2 * UNIT + 100, 2 * UNIT)], True),
    "hole": ([(5 * UNIT + 7, 300)], True),
    "overlapping": ([(0, UNIT), (UNIT - 1, 10)], False),
    "empty-extent": ([(20 * UNIT, 0)], False),
}


@pytest.mark.parametrize("xor", [False, True], ids=["plain", "xor"])
@pytest.mark.parametrize("name", sorted(APPLIES))
def test_apply_leaves_the_digest_of_the_stream(bed, name, xor):
    """The data leg writes its extents, the parity leg XORs them in;
    either way ``d`` is the stream's, folded where it can be."""
    spans, folds = APPLIES[name]
    shard = K if xor else 1
    osd, pgid, obj, stream, attrs = _shard(bed, f"apply-{name}-{xor}", shard)
    extents = [(off, bed.rng.bytes(n)) for off, n in spans]
    before = _digests([osd])
    assert osd._apply_partial(pgid, obj.name, shard, extents,
                              int(attrs["v"]) + 1,
                              prev_version=int(attrs["v"]), xor=xor) == 0
    # a parity delta is XORed into the bytes stored BEFORE the call
    writes = [(off, bytes(_xor(stream[off:off + len(data)].ljust(
        len(data), b"\0"), data)) if xor else data)
        for off, data in extents]
    want = bytearray(stream)
    for off, data in writes:
        end = off + len(data)
        if len(want) < end and len(data):
            want.extend(bytes(end - len(want)))
        want[off:end] = data
    after, new_attrs = _stored(osd, pgid, obj)
    if len(after) != len(want):
        # only an empty extent past the end leaves the length to the store
        assert name == "empty-extent"
        want.extend(bytes(len(after) - len(want)))
    assert after == bytes(want)
    assert int(new_attrs["v"]) == int(attrs["v"]) + 1
    assert _grew(before, [osd]) == \
        {FOLD: int(folds), SWEEP: int(not folds)}


def test_version_stamp_keeps_the_digest_and_sweeps_nothing(bed,
                                                           native_calls):
    """A shard the write does not touch: no extents, the new version,
    the digest it had, and no byte of the stream read for it."""
    osd, pgid, obj, stream, attrs = _shard(bed, "stamp", 2)
    del native_calls[:]
    before = _digests([osd])
    assert osd._apply_partial(pgid, obj.name, 2, [], int(attrs["v"]) + 1,
                              prev_version=int(attrs["v"])) == 0
    assert native_calls == []
    after, new_attrs = _stored(osd, pgid, obj)
    assert after == stream
    assert (int(new_attrs["d"]), int(new_attrs["v"])) == \
        (int(attrs["d"]), int(attrs["v"]) + 1)
    assert _grew(before, [osd]) == \
        {FOLD: 1, SWEEP: 0}


def test_an_extent_costs_one_native_call_of_its_own_length(bed,
                                                           native_calls):
    osd, pgid, obj, _stream, attrs = _shard(bed, "calls", 0)
    del native_calls[:]
    extents = [(0, bed.rng.bytes(UNIT)), (2 * UNIT, bed.rng.bytes(512))]
    assert osd._apply_partial(pgid, obj.name, 0, extents,
                              int(attrs["v"]) + 1) == 0
    assert sorted(native_calls) == [512, UNIT]
    _stored(osd, pgid, obj)


def test_a_stream_with_no_stored_digest_is_swept(bed):
    osd, pgid, obj, stream, attrs = _shard(bed, "no-d", 3)
    cid = CollectionId(pgid.pool, pgid.seed)
    osd.store.queue_transaction(Transaction().rmattr(cid, obj, "d"))
    before = _digests([osd])
    patch = bed.rng.bytes(100)
    assert osd._apply_partial(pgid, obj.name, 3, [(50, patch)],
                              int(attrs["v"]) + 1) == 0
    after, _ = _stored(osd, pgid, obj)
    assert after == stream[:50] + patch + stream[150:]
    assert _grew(before, [osd]) == \
        {FOLD: 0, SWEEP: 1}


def test_an_object_created_by_the_write_is_swept(bed):
    osd, pgid, _obj, _stream, _attrs = _shard(bed, "creator", 0)
    before = _digests([osd])
    patch = bed.rng.bytes(300)
    assert osd._apply_partial(pgid, "created", 0, [(1000, patch)], 7,
                              create_ok=True) == 0
    after, _ = _stored(osd, pgid, ObjectId("created", shard=0))
    assert after == bytes(1000) + patch
    assert _grew(before, [osd]) == \
        {FOLD: 0, SWEEP: 1}


@pytest.mark.parametrize("refusal", ["EAGAIN", "ENOENT"])
def test_a_refusal_leaves_stream_and_attributes_alone(bed, refusal):
    osd, pgid, obj, stream, attrs = _shard(bed, f"refuse-{refusal}", K + 1)
    before = _digests([osd])
    delta = [(0, bytes([0xFF]) * UNIT)]
    if refusal == "EAGAIN":
        assert osd._apply_partial(pgid, obj.name, K + 1, delta, 99,
                                  prev_version=12345, xor=True) == EAGAIN
    else:
        assert osd._apply_partial(pgid, "absent", K + 1, delta, 99,
                                  xor=True) == ENOENT
    assert _stored(osd, pgid, obj) == (stream, attrs)
    assert _grew(before, [osd]) == \
        {FOLD: 0, SWEEP: 0}


def test_a_torn_write_rolled_back_has_the_restored_streams_digest(bed):
    """The rollback stash restores the bytes, the stream's old length
    and a digest that is theirs."""
    osd, pgid, obj, stream, attrs = _shard(bed, "torn", 1)
    v = int(attrs["v"])
    extents = [(100, bed.rng.bytes(UNIT)), (3 * UNIT + 5, bed.rng.bytes(64))]
    assert osd._apply_partial(pgid, obj.name, 1, extents, v + 1,
                              prev_version=v) == 0
    torn, _ = _stored(osd, pgid, obj)
    assert torn != stream and len(torn) > len(stream)
    assert osd._pglog(pgid).rollback_object(obj.name, 1, v)
    restored, back = _stored(osd, pgid, obj)
    assert restored == stream
    assert (int(back["d"]), int(back["v"])) == (int(attrs["d"]), v)


# ----------------------------------------------------- through the client
def test_a_compressed_blobs_first_overwrite_folds(bed):
    """``_inflate_in_place`` leaves a raw stream with its digest, so the
    overwrite that inflates the blob derives ``d`` like any other."""
    bed.client.write_full("cz", "blob", COMPRESSIBLE)
    for osd, pgid, obj in bed.holders("cz", "blob"):
        assert _stored(osd, pgid, obj)[1]["cz"] == "czlib"
    before = _digests(bed.osds())
    bed.client.write("cz", "blob", b"RAW-PATCH", offset=40_000)
    want = COMPRESSIBLE[:40_000] + b"RAW-PATCH" + COMPRESSIBLE[40_009:]
    assert bed.client.read("cz", "blob") == want
    for osd, pgid, obj in bed.holders("cz", "blob"):
        stream, attrs = _stored(osd, pgid, obj)
        assert stream == want and "cz" not in attrs
    assert _grew(before, bed.osds()) == \
        {FOLD: 3, SWEEP: 0}


@pytest.mark.parametrize("off,n", [(20_000, 3), (49_990, 100), (60_000, 10)],
                         ids=["inside", "grows", "hole"])
def test_a_replicated_offset_write_keeps_its_len(bed, off, n):
    """``len`` of a replicated object comes from the lengths the write
    works with; it is the stream's, and what ``stat`` answers."""
    oid = f"rep-{off}"
    base = bed.rng.bytes(50_000)
    bed.client.write_full("rep", oid, base)
    before = _digests(bed.osds())
    patch = bed.rng.bytes(n)
    bed.client.write("rep", oid, patch, offset=off)
    want = bytearray(base)
    want.extend(bytes(max(0, off + n - len(want))))
    want[off:off + n] = patch
    assert bed.client.read("rep", oid) == bytes(want)
    assert bed.client.stat("rep", oid) == len(want)
    for osd, pgid, obj in bed.holders("rep", oid):
        stream, attrs = _stored(osd, pgid, obj)
        assert stream == bytes(want)
        assert int(attrs["len"]) == len(want)
    assert _grew(before, bed.osds()) == \
        {FOLD: 3, SWEEP: 0}


def test_a_replicated_object_created_at_an_offset(bed):
    bed.client.write("rep", "born-late", b"tail", offset=3000)
    assert bed.client.read("rep", "born-late") == bytes(3000) + b"tail"
    assert bed.client.stat("rep", "born-late") == 3004
    streams = [_stored(osd, pgid, obj)
               for osd, pgid, obj in bed.holders("rep", "born-late")]
    assert {s for s, _ in streams} == {bytes(3000) + b"tail"}
    assert int(streams[0][1]["len"]) == 3004      # the primary's


@pytest.mark.parametrize("backend", ["numpy", "native"])
def test_200_overwrites_then_a_clean_deep_scrub(backend):
    """Six applies a write, each with its digest derived, none swept;
    afterwards every shard's ``d`` is its bytes' and a deep scrub of
    the pool finds nothing.  A cluster of its own: nothing else applies
    an extent while it counts."""
    bed = Bed(backend)
    try:
        _overwrite_200(bed)
    finally:
        bed.cluster.stop()


def _overwrite_200(bed):
    rng = np.random.default_rng(0x200)
    size = 6 * ROW + 1234
    image = {f"img{i}": bytearray(rng.bytes(size)) for i in range(4)}
    for oid, data in image.items():
        bed.client.write_full("ec", oid, bytes(data))
    before = _digests(bed.osds())
    for i in range(200):
        oid = f"img{int(rng.integers(0, 4))}"
        if i % 2:
            n = UNIT
            off = int(rng.integers(0, size // UNIT)) * UNIT
        else:
            n = int(rng.integers(1, 3 * UNIT))
            off = int(rng.integers(0, size - n))
        data = rng.bytes(n)
        bed.client.write("ec", oid, data, offset=off)
        image[oid][off:off + n] = data
    assert _grew(before, bed.osds()) == \
        {FOLD: 200 * (K + M), SWEEP: 0}
    for oid, data in image.items():
        assert bed.client.read("ec", oid) == bytes(data)
        bed.check("ec", oid)
    bed.cluster.settle(0.2)
    assert bed.client.scrub_pool("ec", deep=True) == []


@pytest.mark.parametrize("kind", ["memstore", "filestore", "bluestore"])
def test_every_object_store_fills_with_zeros_as_the_fold_assumes(
        tmp_path, kind):
    """The derived digest leans on a store's zero fill past the old end
    and on attributes that come back as they were set: offset writes
    inside, across and past the end on each store, ``d`` held to the
    bytes each time."""
    c = store_cluster(tmp_path, kind)
    try:
        client = c.client()
        client.create_pool("p", size=3, pg_num=1)
        rng = np.random.default_rng(len(kind))
        want = bytearray(rng.bytes(20_000))
        client.write_full("p", "obj", bytes(want))
        osds = list(c.osds.values())
        before = _digests(osds)
        spans = [(5000, 4096), (19_000, 3000), (40_001, 777), (0, 1)]
        for off, n in spans:
            patch = rng.bytes(n)
            client.write("p", "obj", patch, offset=off)
            want.extend(bytes(max(0, off + n - len(want))))
            want[off:off + n] = patch
            pgid = PgId(client._pool_id("p"), 0)
            for osd in osds:
                stream, attrs = _stored(osd, pgid, ObjectId("obj"))
                assert stream == bytes(want)
                assert int(attrs["len"]) == len(want)
        assert _grew(before, osds) == {FOLD: 3 * len(spans), SWEEP: 0}
        assert client.scrub_pg("p", 0, deep=True).inconsistencies == []
    finally:
        c.stop()
