"""JAX/Pallas GF(2^8) kernel tests — byte-exact vs the numpy oracle.

Runs on the CPU backend (conftest forces JAX_PLATFORMS=cpu); the Pallas TPU
kernel body itself is additionally covered via interpret mode.
"""

import numpy as np
import pytest

from ceph_tpu.ops import gf256
from ceph_tpu.ops.ec_kernels import RegionMatmul, _terms

RNG = np.random.default_rng(3)


@pytest.mark.parametrize("k,m,maker", [
    (8, 3, gf256.vandermonde_matrix),
    (8, 4, gf256.cauchy_matrix),
    (8, 4, gf256.cauchy_good_matrix),
    (2, 2, gf256.vandermonde_matrix),
])
@pytest.mark.parametrize("L", [512, 4096, 40_000])
@pytest.mark.parametrize("kernel", ["xla", "pallas"])
def test_region_matmul_matches_oracle(kernel, k, m, maker, L):
    """Both realizations on one grid: the XLA graph (what this platform
    runs) and the Pallas lanes kernel (what a TPU runs), the latter in
    interpret mode."""
    M = maker(k, m)
    op = RegionMatmul(M, kernel=kernel, interpret=kernel == "pallas")
    assert op._use_pallas == (kernel == "pallas")
    assert op.label == f"ec_encode_{kernel}_{m}x{k}"
    data = RNG.integers(0, 256, (k, L), dtype=np.uint8)
    got = np.asarray(op(data))
    want = gf256.encode_region(M, data)
    assert np.array_equal(got, want)


def test_region_matmul_unaligned_length():
    M = gf256.vandermonde_matrix(4, 2)
    op = RegionMatmul(M)
    for L in (4, 100, 513, 4095):
        data = RNG.integers(0, 256, (4, L), dtype=np.uint8)
        want = gf256.encode_region(M, data)
        assert np.array_equal(np.asarray(op(data)), want), L


def test_region_matmul_decode_path():
    """Kernel applied to a decode matrix reconstructs erased shards."""
    k, m, L = 8, 3, 8192
    C = gf256.vandermonde_matrix(k, m)
    data = RNG.integers(0, 256, (k, L), dtype=np.uint8)
    parity = gf256.encode_region(C, data)
    stack = np.concatenate([data, parity])
    available = [0, 1, 3, 4, 6, 7, 8, 10]  # erased 2, 5, 9
    D = gf256.decode_matrix(C, k, available)
    rec = np.asarray(RegionMatmul(D)(stack[available]))
    assert np.array_equal(rec, data)


# lost shards of an 8+3 stripe: one data, one parity, data + parity,
# two data, three mixed; and of a 4+2 stripe (the ycsb cells' geometry)
RT_DECODE_CASES = (
    [(8, 3, lost, n) for lost in ((2,), (9,), (5, 10), (0, 6), (1, 7, 8))
     for n in (1, 2, 4)]
    + [(4, 2, lost, n) for lost in ((1,), (4,), (3, 5)) for n in (1, 2)])


@pytest.mark.parametrize("k,m,lost,n_parts", RT_DECODE_CASES)
def test_runtime_matrix_folded_decode_matches_oracle(k, m, lost, n_parts):
    """The decode every degraded-read window runs: ``n_parts`` ops'
    survivor rows as device lane buffers, folded and multiplied by the
    runtime-matrix program (decode_folded_device -> ec_decode_rt_fold)
    — data rows AND parity rows (recovery's rebuild; no benchmark cell
    draws a parity hole) against the numpy oracle."""
    import jax

    from ceph_tpu import ec
    from ceph_tpu.ops import ec_kernels
    from ceph_tpu.utils.perf import kernel_profiler
    codec = ec.factory("tpu", {"k": k, "m": m, "backend": "jax"})
    L = 2048
    use = [i for i in range(k + m) if i not in lost][:k]
    stacks, parts = [], []
    for _ in range(n_parts):
        data = RNG.integers(0, 256, (k, L), dtype=np.uint8)
        stack = np.concatenate(
            [data, gf256.encode_region(codec.matrix, data)])
        stacks.append(stack)
        parts.append(jax.device_put(
            ec_kernels.bytes_as_lanes(stack[use])))
    assert ec_kernels.generic_parts.__name__ == "ec_decode_rt_fold"
    sig = f"matmul/{len(lost)}x{k}/L{n_parts * L}/generic/f{n_parts}"
    before = kernel_profiler().dump()["signatures"].get(sig, {})
    dev = codec.decode_folded_device(list(lost), use, parts)
    assert dev.dtype == np.uint32 and dev.shape == (
        len(lost), n_parts * L // 4)
    got = codec.host_sync(dev, nbytes=n_parts * L)
    want = np.concatenate([st[list(lost)] for st in stacks], axis=1)
    assert np.array_equal(got, want)
    after = kernel_profiler().dump()["signatures"][sig]
    assert (after["compile"] + after["device"]
            == before.get("compile", 0) + before.get("device", 0) + 1)


def test_terms_fast_paths():
    """coef 0 contributes no terms; coef 1 is a single XOR term."""
    M = np.array([[0, 1, 3]], dtype=np.uint8)
    t = _terms(M)[0]
    js = [j for j, _, _ in t]
    assert 0 not in js
    assert (1, -1, 0) in t
    assert sum(1 for j, _, _ in t if j == 2) == 8


def test_pallas_interpret_mode_matches():
    """Run the actual Pallas kernel (interpret) on the CPU backend."""
    M = gf256.vandermonde_matrix(8, 3)
    op = RegionMatmul(M, interpret=True)
    assert op._use_pallas
    data = RNG.integers(0, 256, (8, 65536), dtype=np.uint8)
    want = gf256.encode_region(M, data)
    got = np.asarray(op(data))
    assert np.array_equal(got, want)


def test_zero_length_region():
    M = gf256.vandermonde_matrix(4, 2)
    for op in (RegionMatmul(M), RegionMatmul(M, interpret=True)):
        out = np.asarray(op(np.zeros((4, 0), dtype=np.uint8)))
        assert out.shape == (2, 0)


def test_decode_kernel_cache_reused():
    """Repeated decodes must reuse the compiled kernel, not re-trace."""
    from ceph_tpu import ec
    codec = ec.factory("tpu", {"k": 4, "m": 2, "backend": "jax"})
    chunks = codec.encode(b"z" * 4096)
    avail = {i: c for i, c in chunks.items() if i not in (0, 5)}
    codec.decode([0], dict(avail))
    n_ops = len(codec._jax_ops)
    codec.decode([0], dict(avail))
    assert len(codec._jax_ops) == n_ops  # same decode matrix -> same op


def test_decode_cache_true_lru():
    """Hot decode signatures survive eviction churn (true LRU, not
    FIFO-posing-as-LRU): touching an entry refreshes its recency."""
    from ceph_tpu import ec
    codec = ec.factory("tpu", {"k": 4, "m": 2, "backend": "numpy"})
    codec.DECODE_CACHE_CAP = 3
    hot = [0, 1, 2, 4]
    cold = ([0, 1, 2, 5], [0, 1, 3, 4], [0, 1, 3, 5], [0, 2, 3, 4])
    codec._get_decode_matrix(hot)
    for sig in cold[:3]:
        codec._get_decode_matrix(sig)
        codec._get_decode_matrix(hot)  # touch: must move to the end
    codec._get_decode_matrix(cold[3])  # overflow: evicts a COLD entry
    assert tuple(hot) in codec._decode_cache
    assert tuple(cold[0]) not in codec._decode_cache


def test_jax_op_cache_true_lru():
    """Same LRU contract for the compiled-kernel cache: the encode op
    (hottest entry) must not be evicted by one-shot decode matrices."""
    from ceph_tpu import ec
    from ceph_tpu.ops import gf256 as gf
    codec = ec.factory("tpu", {"k": 4, "m": 2, "backend": "jax"})
    codec.JAX_OPS_CAP = 2
    enc_key = codec._matmul_key(codec.matrix)  # THE shared definition
    data = RNG.integers(0, 256, (4, 512), dtype=np.uint8)
    for erased in ((0, 5), (1, 5), (2, 5)):
        chunks = codec.encode(data.tobytes())
        avail = {i: c for i, c in chunks.items() if i not in erased}
        codec.decode([erased[0]], avail)   # one-shot decode matrix
        codec.encode_chunks(data)          # touch the encode op
    assert enc_key in codec._jax_ops  # survived 3 one-shot evictions
    want = gf.encode_region(codec.matrix, data)
    assert np.array_equal(codec.encode_chunks(data), want)


def test_parity_only_decode_skips_inversion():
    """All k data chunks present + only parity wanted: one direct
    matmul against the coding matrix — no decode-matrix build."""
    from ceph_tpu import ec
    codec = ec.factory("tpu", {"k": 4, "m": 2, "backend": "numpy"})
    data = RNG.integers(0, 256, (4, 2048), dtype=np.uint8)
    chunks = {i: data[i] for i in range(4)}
    out = codec.decode_chunks([4, 5], chunks)
    want = gf256.encode_region(codec.matrix, data)
    assert np.array_equal(out[4], want[0])
    assert np.array_equal(out[5], want[1])
    assert codec._decode_cache == {}  # no inversion happened


def test_region_matmul_shape_cache_true_lru():
    """RegionMatmul's compile cache also refreshes on hit."""
    M = gf256.vandermonde_matrix(4, 2)
    op = RegionMatmul(M)
    hot = RNG.integers(0, 256, (4, 512), dtype=np.uint8)
    op(hot)
    hot_key = next(iter(op._shape_cache))
    for L in (1024, 1536, 2048):
        op(RNG.integers(0, 256, (4, L), dtype=np.uint8))
        op(hot)  # touch
    assert list(op._shape_cache)[-1] == hot_key


def test_batch_fold_equivalence():
    """(batch, k, L) folding into (k, batch*L) is exact."""
    from ceph_tpu import ec
    codec = ec.factory("tpu", {"k": 4, "m": 2, "backend": "jax"})
    stripes = RNG.integers(0, 256, (6, 4, 512), dtype=np.uint8)
    parity = codec.encode_batch(stripes)
    for b in range(6):
        want = gf256.encode_region(codec.matrix, stripes[b])
        assert np.array_equal(parity[b], want)
