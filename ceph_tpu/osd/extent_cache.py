"""ECExtentCache: hot shard extents for the partial-write pipeline.

The capability of the reference's ECExtentCache
(src/osd/ECExtentCache.{h,cc}: an LRU of shard extents backing RMW
reads so overlapping partial writes don't re-read what the pipeline
just touched).  The primary consults it before fanning old-byte reads
for a parity-delta overwrite and refills it with the bytes it reads
and writes; anything that mutates shard state outside the primary's
write pipeline (recovery pushes, rollbacks, removes, map changes)
invalidates.

Host runs are the source of truth, and every reader in the OSD is
served from them: the partial-write pipeline's old-byte and row reads
(``read``) and the cache-served client read (``read_rows``: the k data
shards' runs taken under the lock once and interleaved into the reply
in one pass).  A client read's consumer is the wire, so it touches no
device: nothing is staged, launched or fetched for it on any backend.
The cache holds nothing on a device.
"""

from __future__ import annotations

import collections
import threading

import numpy as np

#: the read scale-out counter schema (hot-tier admission telemetry,
#: lease grant/revoke flow, balanced non-primary serving) — registered
#: zeroed at OSD boot so the exporter and the prom recording rules see
#: a standing series per daemon before any read lands
READ_SCALEOUT_COUNTERS = (
    "ec_read_tier_hit", "ec_read_tier_miss",
    "ec_read_tier_admit", "ec_read_tier_evict",
    "read_lease_grant", "read_lease_ride", "read_lease_revoke",
    "balanced_read_serve", "balanced_read_bounce")


def register_read_scaleout_counters(perf) -> None:
    """Register the read scale-out counters on ``perf`` (idempotent:
    re-adding an existing counter would RESET it)."""
    for name in READ_SCALEOUT_COUNTERS:
        if not perf.has(name):
            perf.add(name)


class _Extents:
    """Non-overlapping sorted (off, bytearray) runs for one shard."""

    __slots__ = ("runs",)

    def __init__(self):
        self.runs: list[tuple[int, bytearray]] = []

    def nbytes(self) -> int:
        return sum(len(b) for _o, b in self.runs)

    def write(self, off: int,
              data: bytes | bytearray | memoryview) -> None:
        """Insert/overwrite [off, off+len) and merge adjacent runs.
        ``data`` is bytes or any buffer of bytes (a stream's row as the
        write handler holds it): the run is this one copy of it, so
        the caller's buffer is free to change afterwards."""
        end = off + len(data)
        merged_off = off
        buf = bytearray(data)
        keep: list[tuple[int, bytearray]] = []
        for roff, rbuf in self.runs:
            rend = roff + len(rbuf)
            if rend < off or roff > end:
                keep.append((roff, rbuf))  # untouched
                continue
            # overlap/adjacency: fold the old run around the new bytes
            if roff < merged_off:
                buf = rbuf[: merged_off - roff] + buf
                merged_off = roff
            if rend > end:
                buf = buf + rbuf[len(rbuf) - (rend - end):]
                end = rend
        keep.append((merged_off, buf))
        keep.sort(key=lambda t: t[0])
        self.runs = keep

    def read(self, off: int, length: int) -> bytes | None:
        """The exact bytes if FULLY covered, else None."""
        end = off + length
        for roff, rbuf in self.runs:
            if roff <= off and off + length <= roff + len(rbuf):
                return bytes(rbuf[off - roff: end - roff])
        return None

    def covering(self, off: int,
                 length: int) -> tuple[int, bytearray] | None:
        """(run offset, run buffer) of the run fully covering the
        range, else None.  The buffer is returned WITHOUT copying (the
        hit path must stay O(1)): writes never mutate a run buffer in
        place — they build fresh ones and replace the list — so a
        reference snapshotted under the cache lock stays content-stable
        outside it."""
        for roff, rbuf in self.runs:
            if roff <= off and off + length <= roff + len(rbuf):
                return roff, rbuf
        return None


class ECExtentCache:
    def __init__(self, max_bytes: int = 8 << 20, on_evict=None):
        self._max = max_bytes
        self._bytes = 0
        # eviction telemetry hook: called once per whole-object LRU
        # eviction (capacity pressure only — invalidations are not
        # evictions).  Must be cheap and lock-free; fired OUTSIDE the
        # cache lock.
        self._on_evict = on_evict
        self._lock = threading.Lock()
        # key: (pgid, oid) -> shard -> _Extents; LRU by key
        self._lru: collections.OrderedDict = collections.OrderedDict()
        # object version the cached bytes correspond to (the pipeline
        # updates it with every write it caches; external mutation
        # paths invalidate instead)
        self._ver: dict = {}
        # whole-object logical length at that version, when the
        # pipeline knows it (write paths carry total_len) — a
        # cache-served client read needs it to trim stripe padding
        self._len: dict = {}

    def pgids(self) -> set:
        """PGs with cached entries (map-change invalidation scans only
        these, not the whole cluster's placement)."""
        with self._lock:
            return {k[0] for k in self._lru}

    def version(self, pgid, oid: str) -> int | None:
        with self._lock:
            return self._ver.get((pgid, oid))

    def object_len(self, pgid, oid: str) -> int | None:
        """Whole-object length at the cached version (None when no
        write-through recorded it)."""
        with self._lock:
            return self._len.get((pgid, oid))

    def read(self, pgid, oid: str, shard: int, off: int,
             length: int) -> bytes | None:
        with self._lock:
            shards = self._lru.get((pgid, oid))
            if shards is None:
                return None
            ext = shards.get(shard)
            data = ext.read(off, length) if ext is not None else None
            if data is None:
                return None
            self._lru.move_to_end((pgid, oid))
            return data

    def read_rows(self, pgid, oid: str, k: int, chunk: int, off: int,
                  length: int) -> memoryview | None:
        """Data shards 0..k-1 over [off, off+length) (whole ``chunk``
        rows) interleaved into the ro bytes they stripe — the inverse
        of ``StripeInfo.ro_scatter`` — or None when any shard's range
        is not covered by one run.  One look under the lock for all k
        runs (``covering`` hands the buffers out uncopied), then one
        pass outside it: each shard's rows land in their column of the
        (rows, k, chunk) reply by a strided copy.  The reply is a fresh
        buffer; callers slice the view for free and copy only what
        they send."""
        with self._lock:
            shards = self._lru.get((pgid, oid))
            if shards is None:
                return None
            runs = []
            for shard in range(k):
                ext = shards.get(shard)
                cov = ext.covering(off, length) if ext is not None else None
                if cov is None:
                    return None
                runs.append(cov)
            self._lru.move_to_end((pgid, oid))
        rows = length // chunk
        out = np.empty((rows, k, chunk), dtype=np.uint8)
        for shard, (roff, rbuf) in enumerate(runs):
            out[:, shard] = np.frombuffer(
                rbuf, np.uint8, length, off - roff).reshape(rows, chunk)
        return out.reshape(-1).data

    def write(self, pgid, oid: str, shard: int, off: int,
              data: bytes | bytearray | memoryview,
              version: int | None = None,
              length: int | None = None) -> None:
        """Write ``data`` (bytes or a memoryview of bytes) through at
        ``off`` of the shard's stream; the cache keeps its own copy."""
        if not data:
            return
        with self._lock:
            key = (pgid, oid)
            shards = self._lru.get(key)
            if shards is None:
                shards = {}
                self._lru[key] = shards
            ext = shards.setdefault(shard, _Extents())
            self._bytes -= ext.nbytes()
            ext.write(off, data)
            self._bytes += ext.nbytes()
            if version is not None:
                self._ver[key] = version
            if length is not None:
                self._len[key] = length
            self._lru.move_to_end(key)
            evictions = 0
            while self._bytes > self._max and self._lru:
                k, dropped = self._lru.popitem(last=False)
                self._ver.pop(k, None)
                self._len.pop(k, None)
                self._bytes -= sum(e.nbytes() for e in dropped.values())
                evictions += 1
        if self._on_evict is not None:
            for _ in range(evictions):
                self._on_evict()

    def drop_shards(self, pgid, oid: str, shards) -> None:
        """Drop specific shards' cached runs, leaving the object's
        other shards cached.  The parity-delta write path needs this:
        deltas are applied shard-locally by the parity holders, so the
        primary never learns the resulting parity bytes — cached parity
        runs from an earlier full/row write would claim stale bytes at
        the advanced version."""
        shards = set(shards)
        with self._lock:
            ent = self._lru.get((pgid, oid))
            if ent is not None:
                for s in [s for s in ent if s in shards]:
                    self._bytes -= ent.pop(s).nbytes()
                if not ent:
                    self._lru.pop((pgid, oid), None)
                    self._ver.pop((pgid, oid), None)
                    self._len.pop((pgid, oid), None)

    def invalidate(self, pgid, oid: str | None = None) -> None:
        with self._lock:
            if oid is not None:
                key = (pgid, oid)
                dropped = self._lru.pop(key, None)
                self._ver.pop(key, None)
                self._len.pop(key, None)
                if dropped:
                    self._bytes -= sum(e.nbytes()
                                       for e in dropped.values())
            else:
                for key in [k for k in self._lru if k[0] == pgid]:
                    dropped = self._lru.pop(key)
                    self._ver.pop(key, None)
                    self._len.pop(key, None)
                    self._bytes -= sum(e.nbytes()
                                       for e in dropped.values())

    def clear(self) -> None:
        with self._lock:
            self._lru.clear()
            self._ver.clear()
            self._len.clear()
            self._bytes = 0
