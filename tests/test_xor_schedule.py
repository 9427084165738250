"""XOR scheduler + bitxor kernel + runtime auto-selection (ISSUE 8).

Three layers, hardest gate first: (1) the CSE'd XOR schedule must
equal the naive bit-matrix apply for ARBITRARY GF(2) matrices
(property tests over the numpy evaluator — a scheduler bug cannot
hide behind a lowering bug); (2) the bitxor device lowerings must be
byte-identical to the GF(2^8) oracle; (3) the per-signature runtime
selection must skip unsupported candidates instead of raising, pin
stably within a process, and surface every pick in
dump_kernel_profile.
"""

import numpy as np
import pytest

from ceph_tpu import ec
from ceph_tpu.ops import gf256
from ceph_tpu.ops import xor_schedule as xs
from ceph_tpu.ops.ec_kernels import (RegionMatmul, ScheduledXor,
                                     bitxor_schedule, gf_bitxor_graph,
                                     kernel_supports)
from ceph_tpu.utils.perf import kernel_profiler

RNG = np.random.default_rng(8)


# ------------------------------------------------------- the scheduler
@pytest.mark.parametrize("shape", [(1, 1), (3, 5), (8, 8), (16, 24),
                                   (24, 64), (7, 40)])
def test_schedule_matches_naive_apply(shape):
    """Property: schedule output == naive bitmatrix apply, random
    matrices (including all-zero rows) and random planes."""
    for trial in range(6):
        B = RNG.integers(0, 2, shape, dtype=np.uint8)
        if trial == 1 and shape[0] > 1:
            B[0] = 0  # an all-zero output row must come back zero
        sched = xs.build_schedule(B)
        planes = RNG.integers(0, 256, (shape[1], 53), dtype=np.uint8)
        got = xs.apply_schedule(sched, planes)
        assert np.array_equal(got, xs.naive_apply(B, planes)), \
            (shape, trial)


def test_schedule_deterministic():
    """Same matrix -> identical schedule (the pick-stability contract
    rides on deterministic construction)."""
    B = RNG.integers(0, 2, (16, 32), dtype=np.uint8)
    a, b = xs.build_schedule(B.copy()), xs.build_schedule(B.copy())
    assert a == b


def test_schedule_cse_shares_partial_sums():
    """The pairwise-matching CSE must beat the naive per-row XOR count
    on a real coding bit-matrix (the 2108.02692 win this PR imports)."""
    for maker, k, m in [(gf256.vandermonde_matrix, 8, 3),
                        (gf256.cauchy_good_matrix, 8, 4)]:
        sched = bitxor_schedule(maker(k, m))
        assert sched.xor_count() < sched.naive_xor_count(), (k, m)
    # dense random GF(2): plenty of shared pairs to hoist
    B = RNG.integers(0, 2, (16, 32), dtype=np.uint8)
    sched = xs.build_schedule(B)
    assert sched.xor_count() < sched.naive_xor_count()


def test_schedule_cse_cell_limit_falls_back():
    """Oversized matrices skip the CSE pass but stay correct."""
    n = 300  # 300*300 > CSE_CELL_LIMIT
    B = RNG.integers(0, 2, (n, n), dtype=np.uint8)
    sched = xs.build_schedule(B)
    assert not sched.ops
    planes = RNG.integers(0, 256, (n, 16), dtype=np.uint8)
    assert np.array_equal(xs.apply_schedule(sched, planes),
                          xs.naive_apply(B, planes))


# ------------------------------------------- bitxor kernel lowerings
@pytest.mark.parametrize("k,m,maker", [
    (8, 3, gf256.vandermonde_matrix),
    (8, 4, gf256.cauchy_matrix),
    (8, 4, gf256.cauchy_good_matrix),
    (2, 2, gf256.vandermonde_matrix),
])
@pytest.mark.parametrize("L", [512, 4096, 40_000])
def test_bitxor_matches_oracle(k, m, maker, L):
    """kernel=bitxor byte-identical to the numpy oracle across the
    same (k, m) x matrix-kind grid test_ec_kernels runs."""
    M = maker(k, m)
    op = RegionMatmul(M, kernel="bitxor")
    data = RNG.integers(0, 256, (k, L), dtype=np.uint8)
    want = gf256.encode_region(M, data)
    assert np.array_equal(np.asarray(op(data)), want)


def test_bitxor_pallas_interpret_matches():
    """The actual bitxor Pallas kernel body (interpret mode on CPU)."""
    M = gf256.vandermonde_matrix(8, 3)
    op = RegionMatmul(M, kernel="bitxor", interpret=True)
    assert op._use_pallas
    data = RNG.integers(0, 256, (8, 65536), dtype=np.uint8)
    assert np.array_equal(np.asarray(op(data)),
                          gf256.encode_region(M, data))


def test_bitxor_graph_embeddable():
    """gf_bitxor_graph is a plain jittable graph (the shard_map /
    fused-pass embedding form)."""
    import jax
    M = gf256.cauchy_good_matrix(6, 3)
    fn = jax.jit(gf_bitxor_graph(M))
    data = RNG.integers(0, 256, (6, 8192), dtype=np.uint8)
    assert np.array_equal(np.asarray(fn(data)),
                          gf256.encode_region(M, data))


def test_bitxor_decode_matrix():
    """bitxor applied to a decode matrix reconstructs erased shards."""
    k, m, L = 8, 3, 8192
    C = gf256.vandermonde_matrix(k, m)
    data = RNG.integers(0, 256, (k, L), dtype=np.uint8)
    stack = np.concatenate([data, gf256.encode_region(C, data)])
    avail = [0, 1, 3, 4, 6, 7, 8, 10]
    D = gf256.decode_matrix(C, k, avail)
    rec = np.asarray(RegionMatmul(D, kernel="bitxor")(stack[avail]))
    assert np.array_equal(rec, data)


def test_scheduled_xor_rows():
    """ScheduledXor (the plane-row executor the bitmatrix plugins
    share) == naive apply, plain and interpret-Pallas."""
    B = gf256.bitmatrix(gf256.cauchy_good_matrix(4, 2))
    planes = RNG.integers(0, 256, (B.shape[1], 999), dtype=np.uint8)
    want = xs.naive_apply(B, planes)
    assert np.array_equal(np.asarray(ScheduledXor(B)(planes)), want)
    sxi = ScheduledXor(B, interpret=True)
    assert sxi._use_pallas
    assert np.array_equal(np.asarray(sxi(planes)), want)


# ------------------------------------------------ viability predicate
def test_kernel_supports_predicate():
    M = gf256.vandermonde_matrix(8, 3)
    wide = gf256.vandermonde_matrix(40, 2)  # c = 40 > 32
    assert kernel_supports("xla", M)
    assert kernel_supports("bitxor", M)
    assert kernel_supports("mxu", M)
    assert not kernel_supports("mxu", wide)
    # pallas off-TPU only via interpret (conftest pins JAX_PLATFORMS=cpu)
    assert not kernel_supports("pallas", M)
    assert kernel_supports("pallas", M, interpret=True)
    assert not kernel_supports("nope", M)
    # the predicate is the guard RegionMatmul enforces by raising
    with pytest.raises(ValueError):
        RegionMatmul(wide, kernel="mxu")
    with pytest.raises(ValueError):
        RegionMatmul(M, kernel="pallas")


# ------------------------------------------- runtime auto-selection
def _pick_counters():
    perf = kernel_profiler()._perf
    return {n: perf.get(n)
            for n in kernel_profiler().PICK_COUNTERS}


def test_unsupported_pin_skips_not_raises():
    """Explicitly pinning mxu on a wide matrix must fall through with
    a booked skip — auto-selection never raises on an unsupported
    candidate (the ISSUE 8 hard gate)."""
    before = _pick_counters()
    codec = ec.factory("tpu", {"k": 40, "m": 2, "backend": "jax",
                               "kernel": "mxu"})
    data = RNG.integers(0, 256, (40, 1024), dtype=np.uint8)
    got = codec.encode_chunks(data)  # must not raise
    assert np.array_equal(got, gf256.encode_region(codec.matrix, data))
    after = _pick_counters()
    assert after["ec_kernel_pick_skip"] > before["ec_kernel_pick_skip"]
    (sig, picked), = codec.kernel_picks().items()
    assert picked != "mxu"
    assert kernel_profiler().picks()[sig]["skipped"] == ["mxu"]


def test_unknown_pin_books_skip_not_silence():
    """A typo'd profile kernel name must surface in the pick's skipped
    list (and the skip counter), not silently behave as auto."""
    before = _pick_counters()
    codec = ec.factory("tpu", {"k": 3, "m": 2, "backend": "jax",
                               "kernel": "bitxorr"})
    data = RNG.integers(0, 256, (3, 1024), dtype=np.uint8)
    got = codec.encode_chunks(data)  # must not raise
    assert np.array_equal(got, gf256.encode_region(codec.matrix, data))
    assert _pick_counters()["ec_kernel_pick_skip"] > \
        before["ec_kernel_pick_skip"]
    (sig, _picked), = codec.kernel_picks().items()
    assert "bitxorr" in kernel_profiler().picks()[sig]["skipped"]


def test_cpu_pick_is_pinned_deterministic():
    """Under JAX_PLATFORMS=cpu the auto pick pins without racing (no
    wall-clock dependence in tier-1): xla, mode=pinned."""
    codec = ec.factory("tpu", {"k": 4, "m": 2, "backend": "jax"})
    data = RNG.integers(0, 256, (4, 2048), dtype=np.uint8)
    codec.encode_chunks(data)
    (sig, picked), = codec.kernel_picks().items()
    assert picked == "xla"
    assert kernel_profiler().picks()[sig]["mode"] == "pinned"


def test_forced_race_pick_is_stable():
    """kernel_race=on runs the timed race even on CPU: ONE race per
    signature, the winner stays pinned for every later launch (pick
    stability within a process), and the race launches are booked."""
    codec = ec.factory("tpu", {"k": 5, "m": 2, "backend": "jax",
                               "kernel_race": "on"})
    data = RNG.integers(0, 256, (5, 3000), dtype=np.uint8)
    want = gf256.encode_region(codec.matrix, data)
    before = _pick_counters()
    assert np.array_equal(codec.encode_chunks(data), want)
    mid = _pick_counters()
    picks1 = codec.kernel_picks()
    assert len(picks1) == 1
    assert mid["ec_kernel_pick_auto"] == \
        before["ec_kernel_pick_auto"] + 1
    assert mid["ec_kernel_pick_race_launches"] > \
        before["ec_kernel_pick_race_launches"]
    # same signature again: no second race, same winner, bytes exact
    assert np.array_equal(codec.encode_chunks(data), want)
    assert codec.kernel_picks() == picks1
    assert _pick_counters()["ec_kernel_pick_auto"] == \
        mid["ec_kernel_pick_auto"]
    sig = next(iter(picks1))
    assert kernel_profiler().picks()[sig]["mode"] == "auto"


def test_csum_kernel_upgrades_after_race():
    """On a racing backend an uninformed fused-csum resolution stays
    provisional (xla) and freezes to the raced winner once the first
    plain flush has picked — never pinned xla forever."""
    codec = ec.factory("tpu", {"k": 4, "m": 2, "backend": "jax",
                               "kernel_race": "on"})
    assert codec._csum_graph_kernel() == "xla"
    assert getattr(codec, "_csum_kernel", None) is None  # still open
    data = RNG.integers(0, 256, (4, 2048), dtype=np.uint8)
    codec.encode_chunks(data)  # the race pins a winner for the matrix
    kern = codec._csum_graph_kernel()
    assert kern == codec._graph_kernel()
    assert codec._csum_kernel == kern  # frozen on the informed answer


def test_bitxor_pinned_codec_end_to_end():
    """kernel=bitxor through the codec surface: encode, decode (multi-
    erasure incl. parity), encode+csums — all byte-identical to the
    oracle, and the pick is visible in dump_kernel_profile."""
    from ceph_tpu.ops import native
    codec = ec.factory("tpu", {"k": 6, "m": 3, "backend": "jax",
                               "kernel": "bitxor"})
    data = RNG.integers(0, 256, (6, 4096), dtype=np.uint8)
    want = gf256.encode_region(codec.matrix, data)
    parity = codec.encode_chunks(data)
    assert np.array_equal(parity, want)
    chunks = {i: data[i] for i in range(6)} | \
        {6 + r: parity[r] for r in range(3)}
    for gone in [(0,), (1, 4), (2, 7), (0, 5, 8)]:
        have = {i: c for i, c in chunks.items() if i not in gone}
        out = codec.decode_chunks(list(gone), have)
        for g in gone:
            assert np.array_equal(out[g], chunks[g]), gone
    p2, csums = codec.encode_chunks_with_csums(data)
    assert np.array_equal(p2, want)
    stack = np.concatenate([data, want], axis=0)
    assert np.array_equal(
        csums, np.array([native.crc32c(row.tobytes())
                         for row in stack], dtype=np.uint32))
    dump = kernel_profiler().dump()
    assert any(v["picked"] == "bitxor" for v in dump["picks"].values())
    # kernel-tagged launch signatures split the per-candidate timings
    assert any(s.endswith("/bitxor") for s in dump["signatures"])


def test_bitxor_rides_batcher_and_mesh():
    """The ECBatcher's folded launches and the mesh-sharded fan-out
    ride the pinned bitxor kernel unchanged, byte-identical."""
    from ceph_tpu.ec.batcher import ECBatcher
    codec = ec.factory("tpu", {"k": 4, "m": 2, "backend": "jax",
                               "kernel": "bitxor", "shard": "2"})
    batcher = ECBatcher(window_us=1000, max_bytes=64 << 20)
    payloads = [RNG.integers(0, 256, (4, 2048), dtype=np.uint8)
                for _ in range(4)]
    for p in payloads:
        parity, _ = batcher.encode(codec, p)
        assert np.array_equal(np.asarray(parity),
                              gf256.encode_region(codec.matrix, p))
    # direct sharded launch (forced-host 2-device mesh from conftest)
    fold = RNG.integers(0, 256, (4, 4096), dtype=np.uint8)
    # the device result is uint32 lanes; host_sync views it as bytes
    out = codec.host_sync(codec._matmul_device(codec.matrix, fold,
                                               n_shard=2),
                          nbytes=fold.shape[1])
    assert np.array_equal(out, gf256.encode_region(codec.matrix, fold))


def test_bitxor_fused_csum_graph():
    """encode_csum_graph(kernel=bitxor): parity AND digests byte-
    identical to the native sweep."""
    import jax

    from ceph_tpu.models.stripe_codec import StripeCodec
    from ceph_tpu.ops import native
    codec = StripeCodec(4, 2)
    chunk = 1024
    fn = jax.jit(codec.encode_csum_graph(chunk, kernel="bitxor"))
    data = RNG.integers(0, 256, (4, 3 * chunk), dtype=np.uint8)
    parity, csums = fn(data)
    parity, csums = np.asarray(parity), np.asarray(csums)
    assert np.array_equal(parity,
                          gf256.encode_region(codec.matrix, data))
    stack = np.concatenate([data, parity], axis=0)
    blocks = stack.reshape(stack.shape[0], -1, chunk)
    want = np.array([[native.crc32c(blocks[r, b].tobytes())
                      for b in range(blocks.shape[1])]
                     for r in range(blocks.shape[0])], dtype=np.uint32)
    assert np.array_equal(csums, want)


# ------------------------------------- bitmatrix plugins on the device
@pytest.mark.parametrize("tech,k", [("liberation", 5),
                                    ("blaum_roth", 4),
                                    ("liber8tion", 6)])
def test_bitmatrix_jax_backend_matches_numpy(tech, k):
    """The jerasure-parity bit-matrix techniques route through the
    shared scheduled-XOR device kernel on the jax backend — encode and
    decode byte-identical to the numpy packet path."""
    prof = {"k": str(k), "m": "2", "technique": tech}
    cn = ec.factory("jerasure", dict(prof, backend="numpy"))
    cj = ec.factory("jerasure", dict(prof, backend="jax"))
    cj.JAX_APPLY_MIN_BYTES = 0  # small test chunks must hit the device
    data = RNG.integers(
        0, 256, k * cn.get_minimum_granularity() * 2 + 31,
        dtype=np.uint8).tobytes()
    chn, chj = cn.encode(data), cj.encode(data)
    assert set(chn) == set(chj)
    for i in chn:
        assert np.array_equal(chn[i], chj[i]), (tech, i)
    for gone in [(0,), (1, k), (k, k + 1)]:
        have = {i: v for i, v in chj.items() if i not in gone}
        dec = cj.decode(list(gone), dict(have))
        for g in gone:
            assert np.array_equal(dec[g], chj[g]), (tech, gone)
    # the shared executor is profiled under bitxor/ signatures
    assert any(s.startswith("bitxor/")
               for s in kernel_profiler().dump()["signatures"])


def test_bitmatrix_wide_code_hits_device_path():
    """A bit-matrix with a dimension >= 256 (liber8tion k=32 builds
    (16, 256)) must still engage the device kernel — the op-cache key
    once used bytes(B.shape), which raises there and silently latched
    the host path forever."""
    c = ec.factory("jerasure", {"k": "32", "m": "2",
                                "technique": "liber8tion",
                                "backend": "jax"})
    c.JAX_APPLY_MIN_BYTES = 0
    data = RNG.integers(0, 256, 32 * c.get_minimum_granularity(),
                        dtype=np.uint8).tobytes()
    cn = ec.factory("jerasure", {"k": "32", "m": "2",
                                 "technique": "liber8tion",
                                 "backend": "numpy"})
    chj, chn = c.encode(data), cn.encode(data)
    assert not c._xor_device_broken
    assert c._xor_ops, "device op never built for the wide bit-matrix"
    for i in chn:
        assert np.array_equal(chj[i], chn[i]), i


def test_bitmatrix_small_apply_stays_on_host():
    """Below JAX_APPLY_MIN_BYTES the jax backend keeps the vectorized
    numpy packet path — a sub-ms host XOR must not pay a device
    launch + per-shape jit compile on the op thread."""
    c = ec.factory("jerasure", {"k": "4", "m": "2",
                                "technique": "liber8tion",
                                "backend": "jax"})
    data = RNG.integers(0, 256, 4 * c.get_minimum_granularity(),
                        dtype=np.uint8).tobytes()
    chunks = c.encode(data)
    assert not c._xor_ops  # no device op was built for the tiny apply
    have = {i: v for i, v in chunks.items() if i != 0}
    dec = c.decode([0], have)
    assert np.array_equal(dec[0], chunks[0])
