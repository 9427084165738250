"""Folded CRC32C verify: the deep-scrub half of the batching seam.

Deep scrub's per-object loop (osd/scrub.py `_scrub_map_local`) pays one
python round-trip per object — listing, read, crc, compare — so a
full-store scrub is bounded by interpreter overhead, not checksum
bandwidth.  This module gives scrub the batcher's fold WITHOUT needing
a codec (replicated pools scrub too): many objects' stored bytes,
zero-padded to one length bucket, stack into a single ``(n, L)``
launch whose rows each produce a standard CRC32C.

Variable lengths ride the fold through the GF(2) zero-extension
identity (ops/checksum.crc32c_extend_zeros): appending ``p`` zero
bytes maps a stored digest through a precomputed 32x32 matrix, so the
EXPECTED digest of the padded row is derived host-side from the
write-time digest — the device never sees the raw length and never
inflates or re-reads anything.

Two interchangeable backends, byte-exact against each other:

- ``jax``: ``CrcPlan.device_fn`` jitted per bucket length — the
  VPU-friendly select+xor tree (see ops/checksum.py), one launch per
  flush, digests for every row in one device pass;
- ``native``: one ``ct_crc32c`` ctypes sweep over the folded buffer
  (``crc32c_blocks``) — still one python call per LAUNCH instead of
  one per object, which is where the per-object loop's time goes.

``mode`` mirrors the ``osd_scrub_fold`` option: ``auto`` picks jax on
real accelerators and the native sweep on CPU hosts (the CRC tree on
CPU-jax burns the same cores the C sweep uses better); ``device``
forces the jit path (the tier-1 CPU-jax smoke exercises the graph);
``native`` forces the host sweep.
"""

from __future__ import annotations

import threading

import numpy as np

from ..ops import native
from ..ops.checksum import CrcPlan, crc32c_ref
from ..utils import staging


def _host_crc(data) -> int:
    if native.available():
        return native.crc32c(data)
    return crc32c_ref(bytes(data))


class CrcVerifier:
    """Digest engine for the batcher's ``verify`` op kind: rows
    ``(n, L)`` uint8 -> ``(n,)`` uint32 standard CRC32C.  Stateless
    but for the per-bucket jit cache; one shared instance per OSD."""

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

    # identity the batch signature carries: two verifiers configured
    # differently must not coalesce (their flush paths differ)
    def fold_sig(self) -> tuple:
        return ("crc32c", self._backend)

    def _device_fn(self, nbytes: int):
        with self._lock:
            fn = self._fns.get(nbytes)
        if fn is None:
            import jax
            fn = jax.jit(CrcPlan(nbytes).device_fn())
            with self._lock:
                self._fns[nbytes] = fn
        return fn

    def digests(self, rows: np.ndarray) -> np.ndarray:
        """Per-row standard CRC32C of a ``(n, L)`` uint8 fold
        (L % 4 == 0 — every length bucket is).  Returns ``(n,)``
        uint32 host array."""
        rows = np.ascontiguousarray(rows, dtype=np.uint8)
        n, L = rows.shape
        if L % 4:
            raise ValueError("fold width must be a multiple of 4")
        if self._backend == "jax":
            lanes = rows.view("<u4").reshape(n, L // 4)
            out = self._device_fn(L)(lanes)
            return np.asarray(out, dtype=np.uint32)
        if native.available():
            return np.array(native.crc32c_blocks(rows.reshape(-1), L),
                            dtype=np.uint32)
        return np.array([crc32c_ref(r.tobytes()) for r in rows],
                        dtype=np.uint32)


_SINGLETONS: dict[str, CrcVerifier] = {}
_SINGLETON_LOCK = threading.Lock()


def verifier(mode: str = "auto") -> CrcVerifier:
    """Process-wide verifier per mode — the jit cache is the expensive
    part and every OSD in a test cluster shares one process."""
    with _SINGLETON_LOCK:
        v = _SINGLETONS.get(mode)
        if v is None:
            v = _SINGLETONS[mode] = CrcVerifier(mode)
        return v
