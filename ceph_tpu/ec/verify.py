"""Folded CRC32C verify: the deep-scrub half of the batching seam.

A deep scrub holds every stored shard of a range of a PG to its stored
digest (osd/scrub.py ``_scrub_shard_map``, the one pass the scheduled
scrub and the operator's verb both run).  The shards of a chunk, padded
to one length bucket, stack into ``(n, L)`` rows whose CRC32Cs one call
of ``ECBatcher.verify`` returns: no codec is needed, so replicated
pools scrub through the same seam.

Ragged lengths ride the fold on a ZERO PREFIX: the raw (init-0)
register passes over leading zeros unchanged, so a row's standard
CRC32C differs from that of the bytes it ends in by two constants of
the lengths alone (``unpad_digests``), and the device never sees a
length.

Two back-ends, byte-exact against each other:

- device: ONE program a length bucket, ``u32[ROWS, L/4]`` ->
  ``u32[ROWS]``, one realization a platform as the region multiply has
  (``ops/checksum``: the Pallas kernel ``crc32c_lanes_<L>`` on a TPU,
  ``CrcPlan.device_fn``'s XLA graph elsewhere, the same algebra); a
  launch is ``ROWS`` rows, the last group
  of a fold padded with zero rows, so that a bucket has one shape
  whatever a chunk holds.  The batcher compiles it off the IO path when
  an OSD first stores into the bucket (``ECBatcher.expect_verify``),
  stages its groups through utils/staging (counted h2d, one counted d2h
  a flush) and runs it inside ``ceph:ec-flush``;
- host: one native sweep over the folded buffer
  (``native.crc32c_blocks``), one call a launch.  On an
  accelerator that is a counted fall-through
  (``ec_scrub_host_digest``): ``Deployment.health()`` refuses a run
  whose digests the host computed.

``mode`` is the ``osd_scrub_fold`` option: ``auto`` is the device
program on an accelerator and the host sweep on a CPU host (where the
program would burn the cores the C sweep uses better); ``device``
forces the program (the tier-1 tests run it on CPU-jax); ``native``
forces the sweep.
"""

from __future__ import annotations

import contextlib
import threading

import numpy as np

from ..ops import native
from ..ops.checksum import (CrcPlan, _crc32c_of_zeros,
                            crc32c_ref, crc32c_rows_pallas)
from ..utils import staging

#: rows of one launch of the device program
ROWS = 8
#: the shortest length bucket (eight 128-lane rows of words: what the
#: TPU's kernel takes)
MIN_BUCKET = 4096


def verify_bucket(length: int) -> int:
    """The length bucket a stored stream of ``length`` bytes is
    verified in: the next power of two, ``MIN_BUCKET`` at least."""
    return max(MIN_BUCKET, 1 << max(0, length - 1).bit_length())


def unpad_digests(digests: np.ndarray, bucket: int,
                  lengths) -> np.ndarray:
    """Standard CRC32C of each row's last ``lengths[i]`` bytes, from the
    standard CRC32C of the whole ``bucket``-byte row whose other bytes,
    in front, are zero: ``crc(0^p || d) = raw(d) ^ crc(0^(p+n))`` and
    ``crc(d) = raw(d) ^ crc(0^n)``."""
    fix = np.array([_crc32c_of_zeros(int(n)) for n in lengths],
                   dtype=np.uint32)
    return digests ^ np.uint32(_crc32c_of_zeros(bucket)) ^ fix


class CrcVerifier:
    """Digest engine for the batcher's ``verify`` op kind: rows
    ``(n, L)`` uint8 -> ``(n,)`` uint32 standard CRC32C.  Stateless
    but for the per-bucket programs; one shared instance a mode."""

    def __init__(self, mode: str = "auto"):
        self.mode = mode
        self._fns: dict[int, object] = {}
        self._lock = threading.Lock()
        self._backend = "native"
        if mode in ("auto", "device"):
            try:
                import jax  # noqa: F401
                if mode == "device" or not staging.backend_is_cpu():
                    self._backend = "jax"
            except Exception:  # noqa: BLE001 - no jax: host sweep
                pass

    @property
    def on_device(self) -> bool:
        """Whether the digests are a device program's."""
        return self._backend == "jax"

    # identity the batch signature carries: two verifiers configured
    # differently must not coalesce (their flush paths differ)
    def fold_sig(self) -> tuple:
        return ("crc32c", self._backend)

    def program(self, nbytes: int):
        """The bucket's one program: ``ROWS`` rows of ``nbytes/4`` words
        as ``u32[ROWS * nbytes/512, 128]`` (``stage`` lays them out so)
        -> ``u32[ROWS]``."""
        with self._lock:
            fn = self._fns.get(nbytes)
        if fn is None:
            import jax
            if jax.default_backend() == "tpu":
                fn = jax.jit(crc32c_rows_pallas(ROWS, nbytes))
            else:
                graph = CrcPlan(nbytes).device_fn()
                fn = jax.jit(lambda lines: graph(lines.reshape(ROWS, -1)))
            with self._lock:
                fn = self._fns.setdefault(nbytes, fn)
        return fn

    # ----------------------------------------------- the device's steps
    @staticmethod
    def stage(rows: np.ndarray, record: bool = True) -> list:
        """``(n, L)`` bytes as groups of ``ROWS`` rows of uint32 lanes
        on the device, 128 lanes to a line, the last group filled with
        zero rows: a counted h2d a group."""
        n, L = rows.shape
        lanes = rows.view("<u4")
        out = []
        for r in range(0, n, ROWS):
            group = lanes[r:r + ROWS]
            if group.shape[0] < ROWS:
                group = np.concatenate(
                    [group, np.zeros((ROWS - group.shape[0], L // 4),
                                     np.uint32)])
            if L % 512 == 0:   # every bucket; a test's short rows not
                group = group.reshape(-1, 128)
            out.append(staging.device_put_landed(group, force=False,
                                                 record=record))
        return out

    def launch(self, groups: list, nbytes: int) -> list:
        """Dispatch the bucket's program on every group; returns when
        the results are ready on the device."""
        fn = self.program(nbytes)
        outs = [fn(g) for g in groups]
        for o in outs:
            o.block_until_ready()
        return outs

    @staticmethod
    def host_sync_bulk(devs, sig: str | None = None) -> list:
        """The flush's one counted device->host copy (the codec's
        protocol surface, ``ECBatcher._sync_flush``)."""
        return staging.fetch_recorded(devs, sig=sig)

    def digests(self, rows: np.ndarray, phase=None,
                sync=None) -> np.ndarray:
        """Per-row standard CRC32C of a ``(n, L)`` uint8 fold
        (L % 4 == 0 — every length bucket is).  Returns ``(n,)``
        uint32 host array.  The one path of a fold on either back-end:
        a batcher's flush passes ``phase(name)``, the context it books
        each step under (``stage_in`` / ``launch`` / ``fetch``; the
        host sweep is all ``launch``), and ``sync``, its counted
        fetch."""
        rows = np.ascontiguousarray(rows, dtype=np.uint8)
        n, L = rows.shape
        if L % 4:
            raise ValueError("fold width must be a multiple of 4")
        phase = phase or (lambda _name: contextlib.nullcontext())
        if not self.on_device:
            with phase("launch"):
                return host_digests(rows)
        with phase("stage_in"):
            groups = self.stage(rows)
        with phase("launch"):
            outs = self.launch(groups, L)
        with phase("fetch"):
            host = sync(outs) if sync is not None else \
                self.host_sync_bulk(outs, sig=f"sync/verify/L{L}")
        return np.concatenate(host)[:n]


def host_digests(rows: np.ndarray) -> np.ndarray:
    """The host sweep; on an accelerator a counted fall-through."""
    if not staging.backend_is_cpu():
        staging.stage_perf().inc("ec_scrub_host_digest")
    n, L = rows.shape
    if native.available():
        return np.array(native.crc32c_blocks(rows.reshape(-1), L),
                        dtype=np.uint32)
    return np.array([crc32c_ref(r.tobytes()) for r in rows],
                    dtype=np.uint32)


_SINGLETONS: dict[str, CrcVerifier] = {}
_SINGLETON_LOCK = threading.Lock()


def verifier(mode: str = "auto") -> CrcVerifier:
    """Process-wide verifier per mode — the compiled programs are the
    expensive part and every OSD in a test cluster shares one process."""
    with _SINGLETON_LOCK:
        v = _SINGLETONS.get(mode)
        if v is None:
            v = _SINGLETONS[mode] = CrcVerifier(mode)
        return v
