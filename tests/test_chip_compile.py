"""The served EC path's device programs, compiled for a described TPU v5e.

No chip is attached here: the TPU compiler is asked to compile each
program for a ``v5e:2x2`` topology's first device at deployment size
(64 objects of 4 MiB on RS k=8,m=3 = 8 rows x 8 Mi uint32 lanes).  It
refuses what the chip would refuse (scoped VMEM, HBM) and its memory
analysis shows what a program costs beside its arguments.  Every
program must hold no uint8<->uint32 ``bitcast-convert`` (bytes are
viewed as lanes on the host) and need no more temporary memory than
its arguments.

The topology is described inside a module-scoped fixture, never at
import: only one process may load the TPU library, and under xdist
every worker imports every test file.
"""

from __future__ import annotations

import os
import re

import numpy as np
import pytest

from ceph_tpu import ec
from ceph_tpu.ops import ec_kernels, gf256

N_OBJ = 64
CHUNK = (4 << 20) // 8          # one shard's bytes of a 4 MiB object
FOLD_LANES = N_OBJ * CHUNK // 4  # 8 Mi lanes per row


@pytest.fixture(scope="module")
def topo():
    import jax
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        t = topologies.get_topology_desc(platform="tpu",
                                         topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure to describe
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described device is written to the persistent
    # cache but cannot be read back without a chip: keep it off
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield t
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding
    return SingleDeviceSharding(topo.devices[0])


def _lanes(shape, sharding):
    import jax
    import jax.numpy as jnp
    return jax.ShapeDtypeStruct(shape, jnp.uint32, sharding=sharding)


_BYTE_LANE_CAST = re.compile(
    r"(u8|s8)\[[^\]]*\][^\n]*bitcast-convert\([^\n]*(u32|s32)\["
    r"|(u32|s32)\[[^\]]*\][^\n]*bitcast-convert\([^\n]*(u8|s8)\[")


def _check(jitted, *args):
    """Compile for the described chip; the compiler raises what the
    chip's would (VMEM, HBM).  No byte<->lane cast, temp <= arguments."""
    lowered = jitted.lower(*args)
    assert "bitcast_convert" not in lowered.as_text()
    compiled = lowered.compile()
    assert not _BYTE_LANE_CAST.search(compiled.as_text())
    mem = compiled.memory_analysis()
    arg_bytes = sum(int(np.prod(a.shape)) * 4 for a in args)
    assert mem.temp_size_in_bytes <= arg_bytes, (
        mem.temp_size_in_bytes, arg_bytes)
    return compiled


def _pallas_op(M, kernel):
    """A RegionMatmul built as it is on a TPU (Pallas-lowered where the
    realization is a kernel body) — jax.default_backend() is the CPU
    here, so the launch-mode switch is set by hand."""
    op = ec_kernels.RegionMatmul(M, kernel=kernel, interpret=True)
    op._interpret = False
    assert op._use_pallas
    return op


RS83 = gf256.vandermonde_matrix(8, 3)


@pytest.mark.parametrize("kernel", ["pallas", "xla"])
def test_rs_8_3_encode_fold(one_chip, kernel):
    op = (_pallas_op(RS83, kernel) if kernel == "pallas"
          else ec_kernels.RegionMatmul(RS83, kernel=kernel))
    compiled = _check(op.lanes_fn(FOLD_LANES),
                      _lanes((8, FOLD_LANES), one_chip))
    if kernel == "pallas":
        assert "tpu_custom_call" in compiled.as_text()


def test_two_erasure_decode(one_chip):
    """Shards 1 and 9 lost: the folded decode is one product with the
    (2, 8) combination matrix, at the fold's width."""
    codec = ec.factory("tpu", {"k": 8, "m": 3, "backend": "numpy"})
    use = [0, 2, 3, 4, 5, 6, 7, 8]
    R = codec._fold_decode_matrix([1, 9], use)
    assert R.shape == (2, 8)
    _check(_pallas_op(R, "pallas").lanes_fn(FOLD_LANES),
           _lanes((8, FOLD_LANES), one_chip))


def _cauchy_good_bits():
    return gf256.bitmatrix(gf256.cauchy_good_matrix(8, 3))


def _liber8tion_bits():
    # the technique whose packet rows really ride ScheduledXor
    return ec.factory("jerasure", {"k": 8, "m": 2, "technique":
                                   "liber8tion",
                                   "backend": "numpy"}).bitmatrix


@pytest.mark.parametrize("bits,shape", [(_cauchy_good_bits, (24, 64)),
                                        (_liber8tion_bits, (16, 64))])
def test_scheduled_xor(one_chip, bits, shape):
    B = bits()
    assert B.shape == shape
    op = ec_kernels.ScheduledXor(B, interpret=True)
    op._interpret = False
    # one 4 MiB object's 64 packet rows, and a 64x wider launch
    for n4 in ((4 << 20) // 64 // 4, 1 << 20):
        compiled = _check(op.lanes_fn(n4), _lanes((64, n4), one_chip))
        assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("n_parts", [2, 16])
def test_batcher_fold_and_launch(one_chip, n_parts):
    """The batcher's device fold (per-op staged lane buffers side by
    side) and the kernel launch are ONE jitted program."""
    op = _pallas_op(RS83, "pallas")
    w4 = CHUNK // 4
    compiled = _check(op.folded_fn(n_parts, w4),
                      *[_lanes((8, w4), one_chip)] * n_parts)
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("what", ["static", "generic"])
def test_four_chip_sharded_fold(topo, what):
    """The mesh-sharded folded launch (ec_shard=4 on a four-chip host):
    lanes in, lanes out, the length axis split over the 2x2 mesh, and
    no collective — columns are independent."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from ceph_tpu.parallel.distributed import (make_folded_generic,
                                               make_folded_matmul)
    mesh = Mesh(np.array(topo.devices).reshape(4), ("shard",))
    cols = NamedSharding(mesh, P(None, "shard"))
    n4 = 4 * CHUNK // 4  # a four-op fold
    x = jax.ShapeDtypeStruct((8, n4), jnp.uint32, sharding=cols)
    if what == "static":
        compiled = _check(jax.jit(make_folded_matmul(RS83, mesh)), x)
    else:
        v = jax.ShapeDtypeStruct((2, 8, 8), jnp.uint32,
                                 sharding=NamedSharding(mesh, P()))
        compiled = _check(jax.jit(make_folded_generic(mesh)), v, x)
    text = compiled.as_text()
    for coll in ("all-reduce", "all-gather", "all-to-all",
                 "collective-permute"):
        assert coll not in text


@pytest.mark.parametrize("nbytes", [512 << 10, 1 << 20, 4096])
def test_scrub_crc_kernel(one_chip, nbytes):
    """The deep scrub's verify program as a TPU runs it (the Pallas
    kernel, eight rows of L/4 words as ``u32[8 * L/512, 128]``) at the cells' length buckets:
    the 512 KiB shard streams of ``rados_write_4m_scrub``, the 1 MiB of
    ``rbd_randwrite_4k``, the 4 KiB of the ycsb cells.  A change that
    makes it slow to compile for the v5e fails here, not in a cell's
    ``setup_s``."""
    import time

    import jax

    from ceph_tpu.ec.verify import ROWS
    from ceph_tpu.ops.checksum import crc32c_rows_pallas
    t0 = time.monotonic()
    compiled = _check(jax.jit(crc32c_rows_pallas(ROWS, nbytes)),
                      _lanes((ROWS * nbytes // 512, 128), one_chip))
    assert "tpu_custom_call" in compiled.as_text()
    assert time.monotonic() - t0 < 30.0
