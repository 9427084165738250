"""The cache-served EC client read is assembled on the host (ISSUE 35).

On a jax pool (CPU backend here) a head-object read that the primary's
extent cache covers is answered from the cache's host runs: byte for
byte what the store path answers, with no device program and nothing
staged in either direction.  Cluster cases
share one MiniCluster (k=4 m=2, 4 KiB chunks: a 16 KiB stripe row).
"""

import numpy as np
import pytest

from ceph_tpu.ec.stripe import StripeInfo
from ceph_tpu.osd.extent_cache import ECExtentCache
from ceph_tpu.utils import staging

POOL = "hostread"
K, M, CHUNK = 4, 2, 4096
WIDTH = K * CHUNK
SIZES = (1024, 16384, 120_000, 1 << 20)


def _payload(size: int, salt: int = 0) -> bytes:
    return np.random.default_rng(size + salt).integers(
        0, 256, size, dtype=np.uint8).tobytes()


#: (offset, length) of a read as functions of the object's size;
#: length 0 reads to the end
RANGES = {
    "whole": lambda n: (0, 0),
    "from_offset": lambda n: (n // 2 + 1, 0),
    # starts and ends inside one chunk of the first row
    "in_row": lambda n: (100, 300),
    # starts mid-chunk, crosses chunk borders and (from 120,000 B up)
    # row borders, ends mid-chunk
    "across": lambda n: (n // 4 + 7, n // 2),
    # mid-row to the object's unaligned end
    "tail": lambda n: (n - 700, 700),
}


@pytest.fixture(scope="module")
def cluster():
    from ceph_tpu.tools.vstart import MiniCluster
    from tests.test_cluster import make_cfg

    # no read leases: every read has to reach the OSD to be counted
    c = MiniCluster(n_osds=6, cfg=make_cfg(osd_read_lease_ttl=0.0)).start()
    try:
        client = c.client()
        client.create_pool(POOL, kind="ec", pg_num=4,
                           ec_profile={"plugin": "tpu", "k": str(K),
                                       "m": str(M), "backend": "jax"})
        for size in SIZES:
            client.write_full(POOL, f"obj{size}", _payload(size))
        yield c, client
    finally:
        c.stop()


def _hits(c) -> int:
    return sum(o.perf.get("ec_read_cache_hit") for o in c.osds.values())


def _staged() -> tuple[int, ...]:
    pc = staging.stage_perf()
    return tuple(pc.get(n) for n in staging.COUNTERS)


def _read_served(c, client, oid: str, off: int, length: int) -> bytes:
    """One read that has to be served from the cache, staging nothing."""
    hits, staged = _hits(c), _staged()
    got = client.read(POOL, oid, offset=off, length=length)
    assert _hits(c) == hits + 1
    assert _staged() == staged
    return got


def _read_fanned(c, client, oid: str, off: int, length: int) -> bytes:
    """The same read through the stores (``ec_read_cache_serve=off``)."""
    hits = _hits(c)
    c.cfg.set("ec_read_cache_serve", "off")
    try:
        got = client.read(POOL, oid, offset=off, length=length)
    finally:
        c.cfg.set("ec_read_cache_serve", "on")
    assert _hits(c) == hits
    return got


@pytest.mark.parametrize("kind", list(RANGES))
@pytest.mark.parametrize("size", SIZES)
def test_cache_served_read_equals_payload_and_store_path(cluster, size,
                                                         kind):
    c, client = cluster
    off, length = RANGES[kind](size)
    payload = _payload(size)
    want = payload[off: off + length] if length else payload[off:]
    got = _read_served(c, client, f"obj{size}", off, length)
    assert isinstance(got, bytes)
    assert got == want
    assert _read_fanned(c, client, f"obj{size}", off, length) == want


def test_cache_served_read_stages_nothing_and_builds_no_mirror(cluster):
    """Ten reads in a row: the staging counters (bytes and copies)
    stand still in both directions, where the device detour staged k
    runs up after every write and the reply down each time."""
    c, client = cluster
    size = SIZES[2]
    client.write_full(POOL, "still", _payload(size, salt=1))  # stages
    hits, staged = _hits(c), _staged()
    for _ in range(10):
        assert client.read(POOL, "still") == _payload(size, salt=1)
    assert _hits(c) == hits + 10
    assert _staged() == staged


def test_overwrite_between_reads_serves_the_new_bytes(cluster):
    c, client = cluster
    size = 50_000
    first, second = _payload(size, salt=2), _payload(size, salt=3)
    client.write_full(POOL, "turn", first)
    assert _read_served(c, client, "turn", 0, 0) == first
    client.write_full(POOL, "turn", second)
    assert _read_served(c, client, "turn", 0, 0) == second
    assert _read_served(c, client, "turn", WIDTH - 5, 10) == \
        second[WIDTH - 5: WIDTH + 5]
    assert _read_fanned(c, client, "turn", 0, 0) == second


# ---------------------------------------------------------------- unit
def _filled(rng, k: int, chunk: int, total_rows: int, lead: int):
    """A cache holding k shard runs of ``total_rows`` rows that start
    ``lead`` rows into the shard, and the streams they were cut from."""
    cache = ECExtentCache()
    streams = [rng.integers(0, 256, (lead + total_rows) * chunk,
                            dtype=np.uint8) for _ in range(k)]
    for shard, s in enumerate(streams):
        cache.write("pg", "o", shard, lead * chunk,
                    s[lead * chunk:].tobytes(), version=1,
                    length=k * len(s))
    return cache, streams


@pytest.mark.parametrize("seed", range(12))
def test_read_rows_equals_ro_assemble(seed):
    rng = np.random.default_rng(3500 + seed)
    k = int(rng.integers(2, 11))
    chunk = int(rng.choice([4096, 8192, 16384]))
    total_rows = int(rng.integers(1, 24))
    lead = int(rng.integers(0, 3))
    cache, streams = _filled(rng, k, chunk, total_rows, lead)
    row0 = lead + int(rng.integers(0, total_rows))
    rows = int(rng.integers(1, lead + total_rows - row0 + 1))
    soff, slen = row0 * chunk, rows * chunk
    got = cache.read_rows("pg", "o", k, chunk, soff, slen)
    want = StripeInfo(k, 2, chunk).ro_assemble(
        [s[soff: soff + slen] for s in streams])
    assert bytes(got) == want.tobytes()
    assert len(got) == k * slen
    # the reply is its own buffer: a later write leaves it as it was
    cache.write("pg", "o", 0, soff, bytes(slen), version=2)
    assert bytes(got) == want.tobytes()


@pytest.mark.parametrize("gap", ["shard_missing", "run_short",
                                 "run_split", "before_run", "no_object"])
def test_read_rows_is_none_on_any_gap(gap):
    rng = np.random.default_rng(35)
    k, chunk, rows = 4, 64, 6
    cache, _streams = _filled(rng, k, chunk, rows, lead=1)
    off, length = chunk, rows * chunk
    assert cache.read_rows("pg", "o", k, chunk, off, length) is not None
    if gap == "shard_missing":
        cache.drop_shards("pg", "o", [2])
    elif gap == "run_short":
        length += chunk  # one row past every run's end
    elif gap == "before_run":
        off = 0  # the runs start one row in
    elif gap == "run_split":
        # shard 1 held as two runs with a hole: no ONE run covers it
        cache.drop_shards("pg", "o", [1])
        cache.write("pg", "o", 1, chunk, bytes(2 * chunk))
        cache.write("pg", "o", 1, 4 * chunk, bytes(3 * chunk))
    else:
        cache.invalidate("pg", "o")
    assert cache.read_rows("pg", "o", k, chunk, off, length) is None


def test_pool_profile_keys_nobody_reads_change_nothing(cluster):
    """A pool whose profile still carries the keys that chose among
    realizations that are gone encodes, decodes and checksums byte for
    byte as its twin without them, and its codec launches the
    platform's program."""
    from ceph_tpu.ops import ec_kernels, gf256, native

    c, client = cluster
    gone = {"kernel": "mxu", "kernel_race": "on", "csum_warm": "on"}
    client.create_pool("deadkeys", kind="ec", pg_num=4, ec_profile={
        "plugin": "tpu", "k": str(K), "m": str(M), "backend": "jax",
        **gone})
    payload = _payload(SIZES[2], salt=7)
    client.write_full("deadkeys", "o", payload)
    client.write_full(POOL, "twin", payload)
    assert client.read("deadkeys", "o") == payload
    assert _read_fanned(c, client, "twin", 0, 0) == payload
    osd = next(iter(c.osds.values()))
    dead = osd._pool_codec(client._pool_id("deadkeys"))
    plain = osd._pool_codec(client._pool_id(POOL))
    # carried, never asked for
    assert all(dead.profile[key] == val for key, val in gone.items())
    op = dead._jax_matmul(dead.matrix)
    assert op is plain._jax_matmul(plain.matrix)  # ONE program
    assert op.kernel == ec_kernels.platform_kernel() == "xla"
    assert op.label == f"ec_encode_xla_{M}x{K}"
    data = np.random.default_rng(36).integers(0, 256, (K, CHUNK),
                                              dtype=np.uint8)
    parity, csums = dead.encode_chunks_with_csums(data)
    p2, c2 = plain.encode_chunks_with_csums(data)
    assert np.array_equal(parity, p2) and np.array_equal(csums, c2)
    assert np.array_equal(parity, gf256.encode_region(dead.matrix, data))
    stack = np.concatenate([data, parity])
    assert list(csums) == [native.crc32c(r.tobytes()) for r in stack]
    have = {i: stack[i] for i in range(K + M) if i not in (1, 4)}
    for codec in (dead, plain):
        out = codec.decode_chunks([1, 4], dict(have))
        assert np.array_equal(out[1], stack[1])
        assert np.array_equal(out[4], stack[4])
