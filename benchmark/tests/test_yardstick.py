"""The yardstick itself: the reference against the program's own field
arithmetic, the work model by hand-worked cases, the trace reduction on
a small trace recorded on a TPU v5e (``tiny.xplane.pb``: three launches
of each of two jitted functions inside one ``bench-window``)."""

from pathlib import Path

import numpy as np
import pytest

from benchmark import reference, trace_reduce, work

MIB = 1 << 20


@pytest.mark.parametrize("k,m", [(8, 3), (4, 2), (2, 1), (6, 3)])
def test_reference_matrix_is_the_programs(k, m):
    from ceph_tpu.ops import gf256
    assert np.array_equal(reference.coding_matrix(k, m),
                          gf256.vandermonde_matrix(k, m))


@pytest.mark.parametrize("k,m,size", [(8, 3, 300_000), (4, 2, 1024)])
def test_reference_encode_and_decode(k, m, size):
    from ceph_tpu.ops import gf256
    rng = np.random.default_rng(7)
    payload = rng.bytes(size)
    shards = reference.encode(payload, k, m, 4096)
    want = gf256.encode_region(gf256.vandermonde_matrix(k, m),
                               reference.scatter(payload, k, 4096))
    assert [shards[k + i] for i in range(m)] == [w.tobytes() for w in want]
    lost = list(rng.choice(k + m, size=m, replace=False))
    have = {i: s for i, s in enumerate(shards) if i not in lost}
    assert reference.decode(have, k, m, size, 4096) == payload


def test_reference_layout_is_raid0_in_stripe_units():
    payload = bytes(range(256)) * 64            # 16 KiB, k=2: 2 rows
    data = reference.scatter(payload, 2, 4096)
    assert data[0].tobytes() == payload[0:4096] + payload[8192:12288]
    assert data[1].tobytes() == payload[4096:8192] + payload[12288:]
    assert reference.gather(data, len(payload), 4096) == payload


def test_work_by_hand():
    assert work.write_bytes(4 * MIB, 8, 3) == 5.5 * MIB
    assert work.read_bytes(4 * MIB, 8, 0) == 0          # parity hole
    assert work.read_bytes(4 * MIB, 8, 1) == 4.5 * MIB
    assert work.read_bytes(4 * MIB, 8, 2) == 5 * MIB
    ops = [("write", 1024, 0), ("read", 1024, 0), ("read", 1024, 1)]
    assert work.needed_bytes(ops, 4, 2) == 1536 + 0 + 1280
    with pytest.raises(ValueError):
        work.needed_bytes([("scrub", 1, 0)], 4, 2)


def test_roofline_share():
    # 819 MB needed at 819 GB/s is 1 ms; over 10 ms of device time: 10%
    assert work.roofline_share(819e6, 819e9, 0.010) == pytest.approx(10.0)
    assert work.roofline_share(0, 819e9, 0.010) is None
    assert work.roofline_share(819e6, 819e9, 0.0) is None


def test_union_and_gaps():
    iv = [(0, 10, "a"), (5, 20, "b"), (30, 40, "c"), (35, 38, "d")]
    assert trace_reduce.union(iv, 0, 100) == [(0, 20), (30, 40)]
    assert trace_reduce.union(iv, 8, 33) == [(8, 20), (30, 33)]
    assert trace_reduce.gaps([(0, 20), (30, 40)], 0, 50) == \
        [(20, 30), (40, 50)]


@pytest.fixture(scope="module")
def tiny_trace():
    return trace_reduce.load(str(Path(__file__).with_name("tiny.xplane.pb")))


def test_recorded_trace_reduces(tiny_trace):
    r = trace_reduce.reduce(tiny_trace)
    assert r["devices"] == 1
    # the annotated window, not the span of the device events
    assert r["window_s"] == pytest.approx(0.023897679)
    # no two operations of one core overlap: busy is their sum
    assert r["busy_s"] == pytest.approx(9.5962e-05)
    assert r["device_s"] == pytest.approx(r["busy_s"])
    assert 0 < r["busy_s"] < r["window_s"]
    assert len(r["device_ops"]) <= 10
    assert r["device_ops"][0][1] == pytest.approx(3.8787e-05)
    assert sum(s for _n, s in r["device_ops"]) == pytest.approx(r["busy_s"])
    # the same trace read as one chip of four: three ran nothing
    assert trace_reduce.reduce(tiny_trace, chips=4)["busy_s"] == \
        pytest.approx(r["busy_s"] / 4)
    idle = dict(r["idle_gaps"])
    assert set(idle) == {"client-write", "client-read", "host-unannotated"}
    assert sum(idle.values()) == pytest.approx(r["window_s"] - r["busy_s"])


def test_recorded_trace_has_the_planes_the_reduction_expects(tiny_trace):
    ev = trace_reduce.device_events(tiny_trace)
    assert list(ev) == ["/device:TPU:0"]
    assert len(ev["/device:TPU:0"]) == 21
    names = {n for _s, _e, n in
             trace_reduce.host_annotations(tiny_trace, "client-")}
    assert names == {"client-write", "client-read"}


def test_readers_return_nothing_where_there_is_nothing():
    from benchmark.readers import (counter_ratio, histogram_mean,
                                   trace_idle_share, trace_roofline)
    ctx = {"counters": {"a": 3.0, "b": 0.0, "h.sum": 0.0, "h.count": 0.0},
           "trace": None, "peaks": None, "needed_bytes": 0}
    assert counter_ratio.read(ctx, ["a"], ["b"]) is None
    assert counter_ratio.read(ctx, ["a"], ["missing"]) is None
    assert counter_ratio.read(ctx, ["b"], ["a"], scale=1000) == 0.0
    assert histogram_mean.read(ctx, "h") is None
    assert trace_idle_share.read(ctx) is None
    assert trace_roofline.read(ctx) is None
