"""Mesh-sharded EC batch flushes on the forced 8-device CPU mesh.

conftest pins ``XLA_FLAGS=--xla_force_host_platform_device_count=8``
(the same virtual-device pattern tests/test_multiprocess_dcn.py builds
its per-process meshes from), so the shard_map fan-out path runs for
real across 8 devices in-process.  Every sharded result is asserted
byte-identical to BOTH the numpy gf256 oracle and the single-device
batcher — the mesh must be a pure parallelism change, never a math one
— including mixed-length batches and batches whose folded sum L is not
divisible by the fan-out before padding.
"""

import threading
import time

import numpy as np
import pytest

from ceph_tpu import ec
from ceph_tpu.ec.batcher import COUNTERS, GAUGES, HISTOGRAMS, ECBatcher
from ceph_tpu.ops import gf256

RNG = np.random.default_rng(23)


def _require_devices(n: int = 8):
    import jax

    if len(jax.devices()) < n:
        pytest.skip(f"needs {n} virtual devices (conftest forces 8)")


def _codec(shard="8", k=4, m=2):
    return ec.factory("tpu", {"k": k, "m": m, "backend": "jax",
                              "shard": shard})


def _burst_encode(batcher, codec, payloads, stagger=0.05):
    results = [None] * len(payloads)
    errors = []

    def writer(i):
        try:
            results[i] = batcher.encode(codec, payloads[i])
        except Exception as e:  # noqa: BLE001 - surfaced by the test
            errors.append(e)

    threads = [threading.Thread(target=writer, args=(i,))
               for i in range(len(payloads))]
    threads[0].start()
    time.sleep(stagger)  # leader enters its window first
    for t in threads[1:]:
        t.start()
    for t in threads:
        t.join()
    assert not errors, errors
    return results


def test_shard_devices_resolution():
    _require_devices()
    assert _codec("off").shard_devices() == 1
    assert _codec("8").shard_devices() == 8
    assert _codec("3").shard_devices() == 3
    assert _codec("100").shard_devices() == 8  # clamped to device count
    # auto falls through to single-device on the CPU platform
    assert _codec("auto").shard_devices() == 1
    # non-jax backends never fan out
    numpy_codec = ec.factory("tpu", {"k": 4, "m": 2, "backend": "numpy",
                                     "shard": "8"})
    assert numpy_codec.shard_devices() == 1


def test_sharded_matmul_byte_identical_and_fallback():
    """The raw mesh-sharded folded multiply equals the oracle; a column
    count that does not split into whole per-device uint32 lanes falls
    through to the single-device launch, still byte-identical."""
    _require_devices()
    codec = _codec("8")
    for N in (8 * 512, 8 * 768, 16 * 1024):   # divisible: sharded
        data = RNG.integers(0, 256, (4, N), dtype=np.uint8)
        out = codec.host_sync(codec._matmul_device(codec.matrix, data,
                                                   n_shard=8),
                              nbytes=N)
        assert np.array_equal(out, gf256.encode_region(codec.matrix,
                                                       data)), N
    for N in (4100, 513, 1000):               # indivisible: fall-through
        data = RNG.integers(0, 256, (4, N), dtype=np.uint8)
        out = codec.host_sync(codec._matmul_device(codec.matrix, data,
                                                   n_shard=8),
                              nbytes=N)
        assert np.array_equal(out, gf256.encode_region(codec.matrix,
                                                       data)), N


def test_sharded_burst_matches_oracle_and_single_device():
    """An 8-writer same-bucket burst through the sharded batcher: one
    folded launch fanned over the mesh, every op byte-identical to the
    oracle AND to the single-device batcher on the same payloads."""
    _require_devices()
    sharded, single = _codec("8"), _codec("off")
    L = 2048
    pays = [RNG.integers(0, 256, (4, L), dtype=np.uint8)
            for _ in range(8)]
    b_sh = ECBatcher(window_us=10_000_000, max_bytes=8 * 4 * L)
    res_sh = _burst_encode(b_sh, sharded, pays)
    b_sg = ECBatcher(window_us=10_000_000, max_bytes=8 * 4 * L)
    res_sg = _burst_encode(b_sg, single, pays)
    for data, (p_sh, _), (p_sg, _) in zip(pays, res_sh, res_sg):
        want = gf256.encode_region(sharded.matrix, data)
        assert np.array_equal(np.asarray(p_sh), want)
        assert np.array_equal(np.asarray(p_sg), want)
    assert b_sh.stats["launches"] == 1
    assert b_sh.stats["sharded_launches"] == 1
    assert b_sg.stats["sharded_launches"] == 0


def test_sharded_mixed_lengths_sumL_not_divisible():
    """Mixed lengths sharing one bucket, 3 ops: the pow2 stripe pad (4)
    is below the fan-out, so sum L is NOT divisible by 8 before the
    mesh padding — the flush must pad to the fan-out and stay exact."""
    _require_devices()
    codec = _codec("8")
    lens = [1000, 900, 1024]  # one shared 1024 bucket
    pays = [RNG.integers(0, 256, (4, L), dtype=np.uint8) for L in lens]
    b = ECBatcher(window_us=10_000_000, max_bytes=4 * sum(lens))
    results = _burst_encode(b, codec, pays)
    for data, (parity, _) in zip(pays, results):
        assert np.array_equal(np.asarray(parity),
                              gf256.encode_region(codec.matrix, data))
    assert b.stats["launches"] == 1
    # 3 ops pad to n2=4, then to the capped fan-out (4 divides 4)
    assert b.stats["sharded_launches"] == 1


def test_sharded_decode_burst_matches_oracle():
    """Coalesced degraded-read decodes fanned over the mesh: same
    survivor signature, reconstructed bytes identical to the per-op
    single-device decode and to the original data."""
    _require_devices()
    sharded, single = _codec("8"), _codec("off")
    L = 4096
    cases = []
    for _ in range(8):
        data = RNG.integers(0, 256, (4, L), dtype=np.uint8)
        parity = gf256.encode_region(sharded.matrix, data)
        cases.append((data, {0: data[0], 2: data[2], 3: data[3],
                             4: parity[0], 5: parity[1]}))  # shard 1 gone
    b = ECBatcher(window_us=10_000_000,
                  max_bytes=sum(5 * L for _ in cases))
    out = [None] * len(cases)
    errors = []

    def reader(i):
        try:
            out[i] = b.decode(sharded, [0, 1, 2, 3], dict(cases[i][1]))
        except Exception as e:  # noqa: BLE001
            errors.append(e)

    threads = [threading.Thread(target=reader, args=(i,))
               for i in range(len(cases))]
    threads[0].start()
    time.sleep(0.05)
    for t in threads[1:]:
        t.start()
    for t in threads:
        t.join()
    assert not errors, errors
    for i, (data, chunks) in enumerate(cases):
        ref = single.decode([0, 1, 2, 3], dict(chunks))
        for s in ref:
            assert np.array_equal(np.asarray(out[i][s]),
                                  np.asarray(ref[s])), (i, s)
            assert np.array_equal(np.asarray(out[i][s]), data[s]), (i, s)
    assert b.stats["launches"] == 1
    assert b.stats["sharded_launches"] == 1


def test_adaptive_window_shrinks_on_trickle_grows_on_burst():
    """The controller's two regimes: sequential idle flushes (a
    trickle) walk the window down toward the floor through REAL
    encodes; a stream with a measured arrival span steers it back up
    toward the span a target-sized group needs (driven at the
    controller level with crafted timestamps — thread scheduling
    cannot produce deterministic arrival spans)."""
    from types import SimpleNamespace

    codec = _codec("off")
    L = 512
    b = ECBatcher(window_us=500, adaptive=True, target_ops=4.0,
                  window_min_us=50, window_max_us=50_000)
    for _ in range(12):  # trickle: every launch flies alone
        b.encode(codec, RNG.integers(0, 256, (4, L), dtype=np.uint8))
    shrunk = b.window_us
    assert shrunk < 500
    assert b.window_us >= b.window_min_us
    # burst: flushes of 4 ops spread over 6ms (2ms arrival gap) —
    # the window must steer up toward the ~7.5ms a 4-op group needs
    # (gap * (target-1) * 1.25) and then HOLD there, not ratchet on
    # to the ceiling
    for _ in range(12):
        ops = [SimpleNamespace(submitted=i * 2_000_000) for i in range(4)]
        b._adapt(ops)
    # span 6ms over 3 gaps -> per-gap 2ms; a (target-1)=3-gap group
    # needs 6ms, x1.25 margin = 7500us
    est = 6e-3 / 3 * 3 * 1.25 * 1e6
    assert b.window_us > shrunk
    assert 0.5 * est < b.window_us < 2 * est  # converged near est
    assert b.window_us < b.window_max_us      # did NOT pin at ceiling
    # simultaneous arrivals need no window: steer back down
    for _ in range(20):
        b._adapt([SimpleNamespace(submitted=0) for _ in range(4)])
    assert b.window_us == b.window_min_us


def test_window0_passthrough_never_adapts():
    codec = _codec("off")
    b = ECBatcher(window_us=0, adaptive=True)
    assert not b.adaptive
    for _ in range(4):
        b.encode(codec, RNG.integers(0, 256, (4, 512), dtype=np.uint8))
    assert b.window_us == 0


def test_counters_registered_zeroed_stable_schema():
    """Every ec_batch_* counter/histogram/gauge registers (zeroed) at
    construction — even in pass-through — and the prometheus exporter
    renders a stable series set (histogram _sum/_count included)."""
    from ceph_tpu.mon.exporter import render_metrics
    from ceph_tpu.utils.perf import global_perf

    name = "osd.test_ec_batch_schema"
    perf = global_perf().create(name)
    try:
        ECBatcher(window_us=0, perf=perf)
        dump = perf.dump()
        for c in COUNTERS:
            assert dump[c] == 0, c
        for h in HISTOGRAMS:
            assert dump[h] == {"buckets_pow2": {}, "count": 0,
                               "sum": 0.0}, h
        for g in GAUGES:
            assert dump[g] == 0.0, g
        body = render_metrics()
        for c in COUNTERS:
            assert f'daemon_{c}{{daemon="{name}"}} 0' in body, c
        for h in HISTOGRAMS:
            assert f'daemon_{h}_count{{daemon="{name}"}} 0' in body, h
        # the live adaptive-window value exports as a GAUGE, not counter
        assert "# TYPE ceph_tpu_daemon_ec_batch_window_us_now gauge" \
            in body
    finally:
        global_perf().remove(name)
