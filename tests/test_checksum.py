"""Device-native CRC32C (ops/checksum.py): the GF(2)-linear tree
formulation must be byte-exact with the native/CPU crc32c, and the
fused encode+csum pass must agree with encode-then-CPU-crc."""

import numpy as np
import pytest

from ceph_tpu.ops import native
from ceph_tpu.ops.checksum import CrcPlan, crc32c_ref

RNG = np.random.default_rng(77)


@pytest.mark.parametrize("nbytes", [4, 8, 12, 100, 4096, 12288, 65536])
def test_device_crc_matches_native(nbytes):
    import jax

    plan = CrcPlan(nbytes)
    fn = jax.jit(plan.device_fn())
    data = RNG.integers(0, 256, (4, nbytes), dtype=np.uint8)
    got = np.asarray(fn(data.view(np.uint32)))
    want = np.array([native.crc32c(bytes(r)) for r in data], np.uint32)
    assert np.array_equal(got, want)


def test_ref_crc_matches_native():
    for n in (0, 1, 3, 17, 1000):
        buf = bytes(RNG.integers(0, 256, n, dtype=np.uint8))
        assert crc32c_ref(buf) == native.crc32c(buf)


def test_bad_lengths_rejected():
    with pytest.raises(ValueError):
        CrcPlan(6)
    with pytest.raises(ValueError):
        CrcPlan(0)


@pytest.mark.parametrize("plugin,prof,backend", [
    ("jerasure", {"k": "3", "m": "2"}, "numpy"),
    ("jerasure", {"k": "3", "m": "2"}, "native"),
    ("jerasure", {"k": "3", "m": "2"}, "jax"),
    ("tpu", {"k": "4", "m": "2"}, "jax"),
    # a subclass that owns its parity math (coupled layers)
    ("clay", {"k": "4", "m": "2", "d": "5"}, "jax"),
])
def test_plugin_encode_chunks_with_csums(plugin, prof, backend):
    """One way to checksum an encode on every backend: the plain encode
    and the native CRC32C of each of the k+m rows."""
    from ceph_tpu import ec

    codec = ec.factory(plugin, dict(prof, backend=backend))
    k = codec.k
    data = RNG.integers(0, 256, (k, 16384), dtype=np.uint8)
    parity, csums = codec.encode_chunks_with_csums(data)
    assert np.array_equal(parity, codec.encode_chunks(data))
    stack = np.vstack([data, parity])
    assert csums.dtype == np.uint32 and csums.shape == (k + codec.m,)
    assert list(csums) == [native.crc32c(r.tobytes()) for r in stack]
