"""XOR scheduler + its device executor (ScheduledXor).

Hardest gate first: (1) the CSE'd XOR schedule must equal the naive
bit-matrix apply for ARBITRARY GF(2) matrices (property tests over the
numpy evaluator — a scheduler bug cannot hide behind a lowering bug);
(2) ScheduledXor, the executor the bit-matrix code family shares, must
equal the naive apply, as a graph and as the Pallas body; (3) the
bitmatrix techniques reach it on the jax back-end.
"""

import numpy as np
import pytest

from ceph_tpu import ec
from ceph_tpu.ops import gf256
from ceph_tpu.ops import xor_schedule as xs
from ceph_tpu.ops.ec_kernels import ScheduledXor
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
    """Same matrix -> identical schedule."""
    B = RNG.integers(0, 2, (16, 32), dtype=np.uint8)
    a, b = xs.build_schedule(B.copy()), xs.build_schedule(B.copy())
    assert a == b


def test_schedule_cse_shares_partial_sums():
    """The pairwise-matching CSE must beat the naive per-row XOR count
    on a real coding bit-matrix (the 2108.02692 win this PR imports)."""
    for maker, k, m in [(gf256.vandermonde_matrix, 8, 3),
                        (gf256.cauchy_good_matrix, 8, 4)]:
        sched = xs.build_schedule(gf256.bitmatrix(maker(k, m)))
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


# ------------------------------------------- the schedule executor
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
