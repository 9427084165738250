"""DeviceArena: HBM-resident stripe bytes, staged once, evicted by LRU.

The device half of the stripe plane (ROADMAP "device-resident stripe
plane"): stripe/shard extents that the OSD hot path will feed back into
folded kernel launches stay resident as device arrays keyed by
``(pg, object, shard, extent, gen)`` instead of being re-``device_put`` on
every op — the per-op host->device hop is exactly the marshalling tax
BENCH_SWEEP_CPU measures (kernel 1.27 GB/s vs e2e 0.25 GB/s) and the
EC-systems literature pins as the online-EC bottleneck
(arXiv:1709.05365: coding pipeline overhead, not GF math).

Semantics:

- ``put`` views a host byte buffer as uint32 lanes (bytes live on the
  host, lanes on the device: the tail is zero-padded to a whole lane),
  stages it through the shared staging helper
  (utils/staging.device_put_landed — h2d bytes/latency metered) and
  inserts it under the key; an already-device input inserts without
  re-staging.
- ``get`` is an LRU touch; hit/miss land on the ``ec_kernels``
  registry (``ec_arena_hits`` / ``ec_arena_misses``) so the cache's
  effectiveness shows up in ``perf dump`` next to the staging plane
  it exists to bypass.
- the byte budget (``ec_arena_max_bytes``) evicts least-recently-used
  entries (``ec_arena_evictions``); eviction only drops the DEVICE
  copy — owners (the extent cache) keep the host bytes and re-stage on
  the next device read, so an undersized arena degrades to the old
  per-op staging behavior instead of losing data.

Holders must treat returned arrays as IMMUTABLE and never donate them
into a launch (donation deletes the buffer out from under the arena);
the batcher's ownership rule (ec/batcher.py ``_PendingOp.dev_owned``)
encodes exactly this.
"""

from __future__ import annotations

import collections
import threading

import numpy as np

from ..utils import staging
from ..utils.perf import CounterType

#: registered (zeroed) on the ec_kernels registry next to the staging
#: counters — one stable schema whether or not an arena ever filled
COUNTERS = ("ec_arena_hits", "ec_arena_misses", "ec_arena_evictions")
GAUGES = ("ec_arena_bytes",)


def _ensure_counters(pc) -> None:
    # under the staging plane's registration lock: add() RESETS an
    # existing counter, so two arenas constructing concurrently (one
    # per OSD in a MiniCluster process) must not both see has()==False
    with staging._REG_LOCK:
        for n in COUNTERS:
            if not pc.has(n):
                pc.add(n)
        for g in GAUGES:
            if not pc.has(g):
                pc.add(g, CounterType.U64)


class DeviceArena:
    """LRU byte-budgeted map of key -> device array."""

    def __init__(self, max_bytes: int = 64 << 20):
        self._max = int(max_bytes)
        self._lock = threading.Lock()
        self._lru: collections.OrderedDict = collections.OrderedDict()
        self._bytes = 0
        self._perf = staging.stage_perf()
        _ensure_counters(self._perf)

    @property
    def nbytes(self) -> int:
        with self._lock:
            return self._bytes

    def get(self, key):
        with self._lock:
            hit = self._lru.get(key)
            if hit is None:
                self._perf.inc("ec_arena_misses")
                return None
            self._lru.move_to_end(key)
            self._perf.inc("ec_arena_hits")
            return hit[0]

    def put(self, key, buf):
        """Insert (staging a host buffer once) and return the device
        array.  Replaces any prior entry under the key — the caller
        mutated the bytes, so the old device copy is stale."""
        if isinstance(buf, (bytes, bytearray, memoryview)):
            buf = np.frombuffer(bytes(buf), dtype=np.uint8)
        if isinstance(buf, np.ndarray):
            if buf.dtype != np.uint32:
                buf = np.ascontiguousarray(buf, dtype=np.uint8).reshape(-1)
                if buf.size % 4:
                    buf = np.pad(buf, (0, -buf.size % 4))
                buf = buf.view(np.uint32)
            dev = staging.device_put_landed(buf, force=False)
        else:
            dev = buf  # already device-resident: no re-staging
        nbytes = int(getattr(dev, "nbytes", 0))
        evicted = 0
        with self._lock:
            old = self._lru.pop(key, None)
            if old is not None:
                self._bytes -= old[1]
            self._lru[key] = (dev, nbytes)
            self._bytes += nbytes
            while self._bytes > self._max and len(self._lru) > 1:
                _k, (_d, nb) = self._lru.popitem(last=False)
                self._bytes -= nb
                evicted += 1
            self._perf.set("ec_arena_bytes", self._bytes)
        if evicted:
            self._perf.inc("ec_arena_evictions", evicted)
        return dev

    def drop(self, key) -> None:
        with self._lock:
            old = self._lru.pop(key, None)
            if old is not None:
                self._bytes -= old[1]
                self._perf.set("ec_arena_bytes", self._bytes)

    def drop_where(self, pred) -> int:
        """Drop every entry whose key matches ``pred`` (the
        invalidation fan-out: an object's runs, a PG's objects).  The
        arena is budget-bounded, so the scan is small."""
        with self._lock:
            victims = [k for k in self._lru if pred(k)]
            for k in victims:
                _d, nb = self._lru.pop(k)
                self._bytes -= nb
            if victims:
                self._perf.set("ec_arena_bytes", self._bytes)
            return len(victims)

    def clear(self) -> None:
        with self._lock:
            self._lru.clear()
            self._bytes = 0
            self._perf.set("ec_arena_bytes", 0)
