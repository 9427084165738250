"""Cross-op EC batching: coalesce stripe work into single folded launches.

The OSD hot path issues one synchronous encode (or degraded-read decode)
per client op, paying a full host->device->host round trip — and
potentially a recompile — per call.  Columns of a GF(2^8) region matmul
are independent, so concurrent full-stripe encodes (and decodes) from
different ops/PGs that share a ``(matrix, k, m)`` signature fold into ONE
``(k, sum L)`` launch (the ``TpuCode.encode_batch`` fold, the
``(batch, k+m, chunk)`` HBM layout of SURVEY.md §5) with results
scattered back per op.  arXiv:1709.05365 measures online-EC throughput
dominated by exactly this per-request coding overhead; arXiv:2108.02692
locates the order-of-magnitude wins in batching/fusing region work.

Wide/local codes ride the same seam: signatures carry the codec's
``fold_sig()`` identity (two codecs sharing a matrix's bytes must not
coalesce), LRC/SHEC decodes fold over the codec's ``fold_rows`` —
narrow ``(|group|, sum L)`` repair-equation launches for single
failures — and CLAY folds at sub-chunk granularity through its
``*_chunks_folded`` entry points plus the ``repair`` op kind (one
folded MSR repair pass per storm signature).  See ec/README.md
"Wide & local codes".

Mechanics (no flusher thread, so nothing can leak at shutdown; the one
background thread is the accelerator-only program warm-up below):

- a submitting thread appends its op to the queue for its signature and
  BLOCKS until its results are ready;
- the first op queued per signature is the *leader*: it waits out the
  coalescing window (``window_us``) on a condition variable, then flushes
  everything queued behind it (flush reason ``window``, or ``idle`` when
  it expired alone);
- an arrival that pushes a signature's pending source bytes past
  ``max_bytes`` flushes immediately itself (reason ``size``), waking the
  leader;
- ``window_us == 0`` is pass-through: the op executes inline through the
  codec's own per-op entry points — bit-identical to the unbatched path.

Mesh fan-out: when the codec resolves a device fan-out > 1 (profile key
``shard`` / the ``ec_shard`` option — see MatrixErasureCode.
shard_devices), a flushed batch's folded ``(k, sum L)`` launch shards
its length axis across the device mesh (parallel/distributed.
make_folded_matmul): an 8-chip pool encodes an 8-writer burst in ~one
chip-time.  Single-device and CPU fall-through stays byte-identical.

Adaptive window: with ``adaptive=True`` the coalescing window resizes
itself per flush from the observed ops-per-launch (EWMA toward
``target_ops``, clamped to [window_min_us, window_max_us]), so a
lightly-loaded OSD stops paying a fixed window as pure latency while a
bursty one grows it to coalesce more.  ``window_us == 0`` still means
pass-through — the controller never engages.

Length-bucketed padding: each op's chunk length pads up to a
power-of-two-or-1.5x-half-step bucket and the stripe count per launch
pads to a power of two (rounded to the device fan-out when sharded), so
the ``RegionMatmul`` compile cache sees a bounded set of shapes.  Zero
columns encode/decode to zero under a linear code, so the padding is
sliced away without affecting bytes.

Warm-up: stripe counts and lengths are bucketed so that the set of
folded programs is bounded, but each is a compile of seconds on an
accelerator, and one met in the IO path stalls every op of its flush.  A
pool does not say how large its objects will be, so the FIRST op of a
(codec, length bucket) starts ``_warm_bucket`` in a daemon thread: every
stripe count up to ``WARM_MAX_FOLD`` (or what ``max_bytes`` admits) for
the encode and for the decode of 1..m lost shards — folded decodes take
their matrix as data, so a count of lost shards is one program — runs
once on zeros.  Compiled programs are shared process-wide, so one
batcher warms for all (``_WARM_CLAIMED``); ``warm_wait`` joins the
threads.  The CPU platform never warms (its ops fold on the host).
One bucket is known before its first op: a sub-object overwrite encodes
its delta stripe, one stripe row, so the OSD tells the batcher at a
pool's whole-object writes to ``expect`` that length, and the bucket's
encodes (not its decodes) compile beside the write.  A deep scrub's
verify program (ec/verify.py: ONE shape a length bucket, ``u32[8,
L/4]``) is claimed the same way when an OSD first stores a stream of a
bucket (``expect_verify``): at default settings it will scrub what it
stores.

Checksums: an op submitted ``with_csums`` gets the CRC32C of each of
its k+m chunks from the native sweep in the flush's carve, over the
parity its folded launch produced — the same digests on every backend
and the ones ``encode_chunks_with_csums`` returns per op.  The sweep
and the copy of every op's parity out of the launch are one native
call a flush (``matrix_code.carve_with_csums``).

Tracing: an op submitted with ``trace=(tracer, parent_ctx)`` gets an
``ec-batch-wait`` span covering queued -> flushed, and each flush emits
ONE shared ``ec-flush`` span (parented under the first traced op's wait
span) tagged with the batch signature, n_ops, bucket length, pad-waste
ratio, shard fan-out and flush reason; every coalesced op's wait span
tags the flush span's id, so the collector reconstructs the fan-in
across traces (utils/tracer.py build_tree + tools/trace_tool.py).

Timeline: an op submitted with ``op=<TrackedOp>`` (utils/tracked_op.py)
is marked ``ec_queued`` when it joins its group, ``ec_taken`` when a
flush takes it and ``ec_done`` when the flush's result is on the host —
the same ``now_ns()`` readings that stamp ``submitted`` / ``taken_at``,
start and end the ``ec-batch-wait`` span, start the ``ec-flush`` span
and feed ``ec_batch_wait_us`` / ``ec_batch_flush_us``, so counters,
spans and timelines cannot disagree.  Each flush runs inside a
``ceph:ec-flush`` trace annotation on the thread that leads it, with
``ceph:stage-in`` / ``ceph:launch`` / ``ceph:fetch`` / ``ceph:carve``
inside, and books the same four as ``ec_flush_*`` TIME counters:
assembling the launch's input, dispatch until the result is ready
(``block_until_ready``), the copy to the host after that, and carving
the per-op results out of it (with the CRC sweep of the ops that want
csums).
"""

from __future__ import annotations

import contextlib
import functools
import threading
import time
from typing import Callable, Sequence

import numpy as np

from ..utils import staging
from ..utils.tracer import annotate, now_ns
from .interface import ChunkMap
from .matrix_code import MatrixErasureCode, carve_with_csums


def stage_width(bucket: int) -> int:
    """Bytes one op occupies in a device fold: its length bucket rounded
    up to whole 128-lane tiles (512 bytes), so every staged buffer — and
    any number of them side by side — is a whole number of tiles (only
    the 768-byte half-step bucket is not one already)."""
    return -(-bucket // 512) * 512


def _as_bytes(host: np.ndarray) -> np.ndarray:
    """A fetched launch result as bytes: jax-backend results come back
    as uint32 lanes and are viewed (zero-copy) on the host."""
    return host.view(np.uint8) if host.dtype == np.uint32 else host


@functools.lru_cache(maxsize=8)
def _zero_lanes(n_rows: int, w4: int):
    """Shared device zeros filling a fold's empty stripe slots (one
    h2d per shape, then reused by every flush; a handful of shapes —
    each entry is device memory)."""
    return staging.device_put_landed(
        np.zeros((n_rows, w4), dtype=np.uint32), record=False)

#: (codec, bucket, fan-out) families whose folded programs some batcher
#: of this process has warmed or is warming, and the threads doing it
_WARM_LOCK = threading.Lock()
_WARM_CLAIMED: set = set()
_WARM_THREADS: list = []

FLUSH_WINDOW = "window"
FLUSH_SIZE = "size"
FLUSH_IDLE = "idle"

#: perf counters the batcher registers on the registry it is handed —
#: ALWAYS registered (zeroed) even when batching is off/pass-through, so
#: `perf dump` and the prometheus exporter expose one stable schema
#: across backends
COUNTERS = ("ec_batch_launches", "ec_batch_coalesced_ops",
            "ec_batch_bytes", "ec_batch_flush_window",
            "ec_batch_flush_size", "ec_batch_flush_idle",
            "ec_batch_sharded_launches",
            # an encode flush's carve with digests: native calls, rows
            "ec_carve_native_calls", "ec_carve_rows")
HISTOGRAMS = ("ec_batch_ops_per_launch", "ec_batch_bytes_per_launch",
              "ec_batch_sharded_devices_per_launch",
              "ec_batch_sharded_shard_bytes",
              # latency decomposition (microseconds, exemplar-linked
              # when the op rides a sampled trace): queued -> taken by
              # a flusher, and taken -> launch complete
              "ec_batch_wait_us", "ec_batch_flush_us")
#: what a flush spends its time on, per flush, on the thread that leads
#: it (module docstring, "Timeline"); warm-up launches book nothing
FLUSH_PHASES = ("stage_in", "launch", "fetch", "carve")
TIMES = tuple(f"ec_flush_{p}" for p in FLUSH_PHASES)
#: settable gauges (CounterType.U64): the live adaptive-window value
GAUGES = ("ec_batch_window_us_now",)


@contextlib.contextmanager
def inline_flush(tracked):
    """An EC call that runs on the op's own thread with no batch: the
    whole of it is the op's ``flush`` phase."""
    if tracked is None:
        yield
        return
    tracked.mark("ec_taken")
    try:
        yield
    finally:
        tracked.mark("ec_done")


def bucket_len(length: int) -> int:
    """Pad target for one op's chunk length: powers of two PLUS the
    1.5x half-steps between them (512, 768, 1024, 1536, 2048, ...),
    with a 512-byte floor (the uint32-lane tiling quantum of
    RegionMatmul).  Still a bounded set of shapes for the compile
    cache — two per octave instead of one — but a just-over-pow2 chunk
    (the 4 KiB + header case) now pads <= 50% instead of almost 2x."""
    b = 512
    while b < length:
        half = b + (b >> 1)
        if length <= half:
            return half
        b <<= 1
    return b


def _pow2(n: int) -> int:
    p = 1
    while p < n:
        p <<= 1
    return p


def shard_pad(n2: int, n_shard: int) -> tuple[int, int]:
    """(effective fan-out, padded stripe count) a flush uses for a
    pow2-padded stripe count ``n2`` on an ``n_shard``-device pool: the
    fan-out caps at the stripe count (a 2-op flush on an 8-chip pool
    shards 2 ways instead of inflating the fold 4x with empty slots),
    then the count rounds up to a multiple of the fan-out so sum L
    splits into whole per-device slices.  ONE definition shared by the
    flush paths and the bench warm-up loops — hand-copied shape rules
    would silently drift and leak cold compiles into timed bursts."""
    ns = max(1, min(n_shard, n2))
    return ns, -(-n2 // ns) * ns


class _PendingOp:
    """One submitted encode/decode riding a folded launch."""

    __slots__ = ("codec", "streams", "chunks", "want", "length",
                 "with_csums", "callback", "deadline", "submitted",
                 "taken", "taken_at", "done", "parity", "csums",
                 "decoded", "error", "tspan", "dev", "trace", "tracked")

    def __init__(self, codec, *, streams=None, chunks=None, want=None,
                 length=0, with_csums=False, callback=None, trace=None,
                 tracked=None):
        self.codec = codec
        self.streams = streams      # encode: (k, L) uint8
        self.chunks = chunks        # decode: shard -> (L,) uint8
        self.want = want            # decode: shard ids to produce
        self.length = length
        self.with_csums = with_csums
        self.callback = callback
        self.deadline = 0.0         # time.monotonic(): a cv deadline
        self.submitted = 0          # now_ns() when it joined its group
        self.taken = False          # removed from the queue by a flusher
        self.taken_at = 0           # now_ns() of the take
        self.trace = trace          # (tracer, parent ctx) of a traced op
        self.tracked = tracked      # the op's TrackedOp (its timeline)
        self.done = False
        self.parity = None
        self.csums = None
        self.decoded = None
        self.error: BaseException | None = None
        self.tspan = None           # ec-batch-wait span (traced ops)
        # device-resident ingest (jax backend on an accelerator): the
        # op's source bytes viewed as uint32 lanes on the host and
        # staged ONCE in the SUBMITTING thread, padded to the bucket's
        # stage width — the flush folds device buffers instead of host
        # bytes
        self.dev = None


class ECBatcher:
    """Coalesces concurrent same-signature EC stripe work per launch.

    Thread-safe; blocking ``encode``/``decode`` are the only entry
    points, so every pending op has a live waiter and none can leak.
    """

    #: adaptive-window controller constants: EWMA weight of the newest
    #: launch, the multiplicative shrink step per solo flush, and the
    #: probe cadence — every PROBE_EVERY-th flush the next leader waits
    #: the MAX window, so a batcher parked at the floor can still see a
    #: burst arrive and grow back (without probes, a floor-length
    #: window flushes every op alone and the controller is blind to
    #: load returning; the amortized latency cost of a probe is
    #: (window_max - window) / PROBE_EVERY, well under the fixed
    #: window it replaces)
    ADAPT_ALPHA = 0.25
    ADAPT_SHRINK = 0.7
    PROBE_EVERY = 16

    #: adaptive-window resizes quieter than this ratio (vs the last
    #: journaled value) stay out of the event journal — the journal
    #: narrates regime changes, not every controller step
    EVENT_RESIZE_RATIO = 1.5

    #: widest fold (ops per launch) the background warm-up compiles for
    #: a bucket; a wider one — small objects under a long window —
    #: compiles when it first happens
    WARM_MAX_FOLD = 16

    def __init__(self, *, window_us: float = 500.0,
                 max_bytes: int = 8 << 20, perf=None,
                 adaptive: bool = False, target_ops: float = 4.0,
                 window_min_us: float = 50.0,
                 window_max_us: float = 4000.0, events=None):
        self.window_us = float(window_us)
        self.max_bytes = int(max_bytes)
        # adaptive coalescing window: resize window_us from the observed
        # ops-per-launch (EWMA toward target_ops, clamped to
        # [window_min_us, window_max_us]) so a lightly-loaded OSD stops
        # paying the full window as pure latency while a bursty one
        # grows it to coalesce more.  window_us == 0 disables batching
        # outright (pass-through) and the controller never engages.
        self.adaptive = bool(adaptive) and self.window_us > 0
        # a target below 2 degenerates the controller (every 1-op flush
        # satisfies n_ops >= target, so grow pins the window at the
        # ceiling and shrink becomes unreachable) — and "coalesce 1 op"
        # is not a coalescing target at all, that's what the floor/off
        # settings are for
        self.target_ops = max(2.0, float(target_ops))
        self.window_min_us = max(1.0, float(window_min_us))
        self.window_max_us = max(self.window_min_us, float(window_max_us))
        self._ops_ewma = self.target_ops  # neutral start: no drift
        self._flushes_since_probe = 0
        self._probe_next = False
        self._cv = threading.Condition()
        # CPU-jax launch serialization: concurrent folded launches on
        # the host platform thrash one shared compute threadpool (a
        # launch's wall time inflates ~3x under overlap, measured), so
        # flush COMPUTE sections serialize behind this lock there —
        # real accelerators keep overlapping (async dispatch pipelines
        # transfer and compute; see _launch_ctx)
        self._launch_lock = threading.Lock()
        self._groups: dict[tuple, list[_PendingOp]] = {}
        self._group_bytes: dict[tuple, int] = {}
        self.stats = {"launches": 0, "ops": 0, "bytes": 0,
                      "sharded_launches": 0,
                      FLUSH_WINDOW: 0, FLUSH_SIZE: 0, FLUSH_IDLE: 0}
        self._perf = perf
        # optional event journal (utils/event_log.EventLog): adaptive
        # window regime changes
        self._events = events
        self._event_window = self.window_us
        if perf is not None:
            perf.add_many(COUNTERS)
            from ..utils.perf import CounterType
            for h in HISTOGRAMS:
                perf.add(h, CounterType.HISTOGRAM)
            for t in TIMES:
                perf.add(t, CounterType.TIME)
            for g in GAUGES:
                perf.add(g, CounterType.U64)
            perf.set("ec_batch_window_us_now", round(self.window_us, 1))

    # ------------------------------------------------------------- public
    def encode(self, codec, data_chunks: np.ndarray, *,
               with_csums: bool = False,
               callback: Callable | None = None,
               trace: tuple | None = None, op=None):
        """Encode one op's (k, L) data chunks; returns (parity, csums)
        exactly as the per-op codec entry points would.  Blocks until the
        folded launch carrying this op completes; ``callback(parity,
        csums)`` (if given) fires before the call returns.  ``trace`` is
        an optional ``(tracer, parent_ctx)`` pair: the op gets an
        ``ec-batch-wait`` span (queued -> flushed) and its flush one
        shared ``ec-flush`` span — the latency decomposition the span
        tree lost when ops started coalescing.  ``op`` is the client
        op's TrackedOp, which takes the timeline marks (module
        docstring)."""
        tracked = op
        data_chunks = np.ascontiguousarray(data_chunks, dtype=np.uint8)
        L = int(data_chunks.shape[-1]) if data_chunks.ndim else 0
        kind = (codec.encode_fold_kind()
                if isinstance(codec, MatrixErasureCode) else None)
        if not (data_chunks.ndim == 2
                and data_chunks.shape[0] == codec.k  # bad shape:
                # per-op path raises the codec's own error without
                # poisoning coalesced neighbors
                and L > 0):
            kind = None
        if kind == "subchunk" and L % codec.get_sub_chunk_count():
            # sub-chunk codecs fold host bytes at plane granularity; a
            # misaligned length takes the codec's own error per op
            kind = None
        if self.window_us <= 0 or kind is None:
            with inline_flush(tracked):
                return self._passthrough_encode(codec, data_chunks,
                                                with_csums, callback)
        # codec identity/sub-chunk layout rides the signature: the rest
        # is matrix-derived, and two codecs sharing a matrix's
        # bytes+shape (a wide code vs a plain one, or two sub-chunk
        # layouts) must not coalesce into one fold
        if kind == "subchunk":
            # exact-L folding: sub-chunk segments cannot pad inside an
            # op (the plane reshape would cross real-byte boundaries)
            sig = ("enc", codec.fold_sig(), codec.matrix.tobytes(),
                   codec.k, codec.m, bool(with_csums), L)
            flush = self._flush_encode_subchunk
        else:
            sig = ("enc", codec.fold_sig(), codec.matrix.tobytes(),
                   codec.k, codec.m, bool(with_csums), bucket_len(L))
            flush = self._flush_encode
        op = _PendingOp(codec, streams=data_chunks, length=L,
                        with_csums=with_csums, callback=callback,
                        trace=trace, tracked=tracked)
        if kind == "plain":
            self._warm_bucket_once(codec, L, sig)
            self._stage_encode_op(op, sig[-1])
        self._submit(sig, op, data_chunks.nbytes, flush)
        if op.error is not None:
            raise op.error
        return op.parity, op.csums

    def decode(self, codec, want: Sequence[int], chunks: ChunkMap, *,
               callback: Callable | None = None,
               trace: tuple | None = None, op=None) -> ChunkMap:
        """Batched counterpart of ``ErasureCode.decode``: present shards
        pass through, missing ones reconstruct via a coalesced
        decode_chunks launch shared with concurrent same-signature ops
        (same survivor set, same (matrix, k, m), same length bucket)."""
        tracked = op
        want = list(want)
        need = sorted(i for i in want if i not in chunks)
        if not need:
            out = {i: chunks[i] for i in want}
            if callback is not None:
                callback(out)
            return out
        arrays = {i: np.ascontiguousarray(c, dtype=np.uint8)
                  for i, c in chunks.items()}
        lengths = {int(c.shape[-1]) for c in arrays.values()}
        kind = (codec.decode_fold_kind()
                if isinstance(codec, MatrixErasureCode) else None)
        if not (len(lengths) == 1
                and all(c.ndim == 1 for c in arrays.values())
                and 0 not in lengths):
            kind = None
        if self.window_us <= 0:  # pass-through: skip the fold-rows
            # resolution (rank work) an inline op would never use
            kind = None
        avail = tuple(sorted(arrays))
        if kind == "plain" and codec.fold_rows(need, avail) is None:
            # this erasure cannot fold (not enough survivors / no
            # invertible subset): the per-op path surfaces the codec's
            # own error without poisoning coalesced neighbors
            kind = None
        if kind == "subchunk" and \
                next(iter(lengths)) % codec.get_sub_chunk_count():
            kind = None
        if kind is None:
            with inline_flush(tracked):
                return self._passthrough_decode(codec, want, chunks,
                                                callback)
        L = lengths.pop()
        if kind == "subchunk":
            sig = ("dec", codec.fold_sig(), codec.matrix.tobytes(),
                   codec.k, codec.m, avail, tuple(need), L)
            flush = self._flush_decode_subchunk
        else:
            sig = ("dec", codec.fold_sig(), codec.matrix.tobytes(),
                   codec.k, codec.m, avail, tuple(need), bucket_len(L))
            flush = self._flush_decode
        # the callback is fired below by THIS thread, after present
        # shards merge back in — not by the flusher
        op = _PendingOp(codec, chunks=arrays, want=need, length=L,
                        trace=trace, tracked=tracked)
        if kind == "plain":
            self._warm_bucket_once(codec, L, sig)
            self._stage_decode_op(op, sig)
        nbytes = sum(c.nbytes for c in arrays.values())
        self._submit(sig, op, nbytes, flush)
        if op.error is not None:
            raise op.error
        out = dict(op.decoded)
        for i in want:
            if i in chunks:
                out[i] = chunks[i]
        out = {i: out[i] for i in want}
        if callback is not None:
            self._fire(op, callback, out)
            if op.error is not None:
                raise op.error
        return out

    def repair(self, codec, lost: int, helper_subchunks: ChunkMap,
               L: int, *, trace: tuple | None = None) -> np.ndarray:
        """Batched sub-chunk repair (CLAY MSR): concurrent repairs of
        the SAME lost chunk from the same helper set — the recovery-
        storm shape, one downed OSD's shard rebuilt across many
        objects — fold into one repair pass whose parity-check matmuls
        run once over the whole launch (repair_chunk_folded).  Returns
        the repaired chunk exactly as ``codec.repair_chunk`` would."""
        foldable = (self.window_us > 0
                    and hasattr(codec, "repair_chunk_folded")
                    and L > 0
                    and L % codec.get_sub_chunk_count() == 0)
        if not foldable:
            out = codec.repair_chunk(lost, helper_subchunks, L)
            self._account(1, sum(np.asarray(c).nbytes
                                 for c in helper_subchunks.values()),
                          FLUSH_IDLE)
            return out
        sig = ("rep", codec.fold_sig(), lost,
               tuple(sorted(helper_subchunks)), L)
        op = _PendingOp(codec, chunks=dict(helper_subchunks),
                        want=[lost], length=L, trace=trace)
        nbytes = sum(np.asarray(c).nbytes
                     for c in helper_subchunks.values())
        self._submit(sig, op, nbytes, self._flush_repair)
        if op.error is not None:
            raise op.error
        return op.decoded

    def verify(self, verifier, rows: np.ndarray, *,
               trace: tuple | None = None) -> np.ndarray:
        """Batched digest verification (deep scrub, ec/verify.py):
        concurrent scrub chunks whose objects padded to the same
        length bucket fold into ONE flush — (n, L) uint8 rows in,
        (n,) uint32 standard CRC32C out, rows scattered back per op.
        The ``verifier`` rides the codec slot (it carries the same
        ``_backend`` / ``fold_sig`` protocol surface) but no coding
        matrix — replicated pools verify through the same seam."""
        rows = np.ascontiguousarray(rows, dtype=np.uint8)
        n, L = rows.shape
        if n == 0:
            return np.zeros(0, dtype=np.uint32)
        if self.window_us <= 0:
            out = verifier.digests(rows)
            self._account(1, rows.nbytes, FLUSH_IDLE)
            return out
        sig = ("ver", verifier.fold_sig(), L)
        op = _PendingOp(verifier, streams=rows, length=L, trace=trace)
        self._submit(sig, op, rows.nbytes, self._flush_verify)
        if op.error is not None:
            raise op.error
        return op.decoded

    def expect_verify(self, verifier, bucket: int) -> None:
        """This OSD has stored into the length bucket ``bucket``
        (ec/verify.verify_bucket), and at default settings it will
        scrub what it stores: compile the bucket's verify program off
        the IO path now.  Nothing where the bucket is warm or warming,
        where the digests are the host's, and on the CPU platform."""
        if not self._stages_on_ingest(verifier):
            return
        key = ("ver", verifier.fold_sig(), bucket)
        if key in _WARM_CLAIMED:  # the per-op check: no lock
            return
        with _WARM_LOCK:
            if key in _WARM_CLAIMED:
                return
            _WARM_CLAIMED.add(key)
            t = threading.Thread(target=self._warm_verify,
                                 args=(verifier, bucket),
                                 name="ec-fold-warm", daemon=True)
            _WARM_THREADS.append(t)
        t.start()

    def _warm_verify(self, verifier, bucket: int) -> None:
        """One uncounted launch of the bucket's one program, on zeros
        (a failure is counted and logged like ``_warm_bucket``'s)."""
        try:
            rows = np.zeros((1, bucket), dtype=np.uint8)
            verifier.launch(verifier.stage(rows, record=False), bucket)
        except Exception:  # noqa: BLE001 - counted, logged
            import traceback

            from ..utils.log import dout
            staging.stage_perf().inc("ec_fold_warm_failed")
            dout("ec", 0)("verify-program warm-up failed for L%d: %s",
                          bucket, traceback.format_exc())

    def expect(self, codec, length: int) -> None:
        """Encodes of chunk length ``length`` will come for this codec
        though none has yet (an EC pool's overwrites encode one stripe
        row, whatever the size of the objects written whole so far):
        compile that bucket's folded ENCODE programs off the IO path
        now.  Nothing where the bucket is warm or warming, where ops
        do not fold, and on the CPU platform."""
        if self.window_us <= 0 \
                or not isinstance(codec, MatrixErasureCode) \
                or codec.encode_fold_kind() != "plain":
            return
        sig = ("enc", codec.fold_sig(), codec.matrix.tobytes(),
               codec.k, codec.m, False, bucket_len(length))
        self._warm_bucket_once(codec, length, sig, decodes=False)

    def _warm_bucket_once(self, codec, length: int, sig: tuple,
                          decodes: bool = True) -> None:
        """First sight, process-wide, of this codec's ops at this length
        bucket on an accelerator: compile its folded programs off the IO
        path (module docstring, "Warm-up").  ``decodes=False`` claims
        and compiles the encodes alone; an encode (``sig[0]``) asks for
        nothing more than that claim, the bucket's first decode still
        warms the whole bucket."""
        if not self._stages_on_ingest(codec):
            return
        # the fan-out is part of every folded program's shape
        whole = sig[1:5] + (sig[-1], codec.shard_devices())
        encodes = whole + ("enc",)

        def claimed() -> bool:
            return whole in _WARM_CLAIMED or (
                sig[0] == "enc" and encodes in _WARM_CLAIMED)
        if claimed():  # the per-op check: no lock
            return
        with _WARM_LOCK:
            if claimed():
                return
            _WARM_CLAIMED.add(whole if decodes else encodes)
            t = threading.Thread(target=self._warm_bucket,
                                 args=(codec, length, decodes),
                                 name="ec-fold-warm", daemon=True)
            _WARM_THREADS.append(t)
        t.start()

    def _warm_bucket(self, codec, length: int,
                     decodes: bool = True) -> None:
        """Every folded program ops of chunk length ``length`` can ask
        for (the encodes alone without ``decodes``).  A failure is
        logged and counted (``ec_fold_warm_failed``): the op that needs
        the program meets the same failure itself."""
        k, m = codec.k, codec.m
        widest = min(self.WARM_MAX_FOLD,
                     _pow2(-(-self.max_bytes // (k * length))))
        try:
            w = 1
            while w <= widest:
                self.warm(codec, length, w)
                for r in range(1, m + 1 if decodes else 1):
                    self.warm(codec, length, w, lost=range(r),
                              avail=range(r, r + k))
                w *= 2
        except Exception:  # noqa: BLE001 - counted, logged
            import traceback

            from ..utils.log import dout
            staging.stage_perf().inc("ec_fold_warm_failed")
            dout("ec", 0)("folded-program warm-up failed for %s L%d: %s",
                          codec.fold_sig(), length,
                          traceback.format_exc())

    @staticmethod
    def warm_wait(timeout: float | None = None) -> bool:
        """Join the process's warm-up threads; False when one is still
        compiling after ``timeout`` seconds."""
        deadline = None if timeout is None else time.monotonic() + timeout
        with _WARM_LOCK:
            threads = list(_WARM_THREADS)
        for t in threads:
            t.join(None if deadline is None
                   else max(0.0, deadline - time.monotonic()))
            if t.is_alive():
                return False
        with _WARM_LOCK:
            _WARM_THREADS[:] = [t for t in _WARM_THREADS if t.is_alive()]
        return True

    def warm(self, codec, length: int, n_ops: int, *,
             lost: Sequence[int] = (),
             avail: Sequence[int] | None = None) -> None:
        """Run ONE folded launch of ``n_ops`` zero-filled ops of chunk
        length ``length`` through the flush path, without the window
        wait and uncounted, so that its program is compiled before
        traffic needs it: an encode when ``lost`` is empty, else the
        decode of ``lost`` from the shards ``avail``.  Foldable matrix
        codecs only."""
        if lost:
            need = sorted(lost)
            ids = sorted(avail if avail is not None else
                         [i for i in range(codec.chunk_count)
                          if i not in need])
            sig = ("dec", codec.fold_sig(), codec.matrix.tobytes(),
                   codec.k, codec.m, tuple(ids), tuple(need),
                   bucket_len(length))
            zero = np.zeros(length, dtype=np.uint8)
            ops = [_PendingOp(codec, chunks={i: zero for i in ids},
                              want=need, length=length)
                   for _ in range(n_ops)]
            for op in ops:
                self._stage_decode_op(op, sig)
            self._flush_decode(sig, ops, None)
        else:
            sig = ("enc", codec.fold_sig(), codec.matrix.tobytes(),
                   codec.k, codec.m, False, bucket_len(length))
            zero = np.zeros((codec.k, length), dtype=np.uint8)
            ops = [_PendingOp(codec, streams=zero, length=length)
                   for _ in range(n_ops)]
            for op in ops:
                self._stage_encode_op(op, sig[-1])
            self._flush_encode(sig, ops, None)
        for op in ops:
            if op.error is not None:
                raise op.error

    def pending_ops(self) -> int:
        """Ops queued and not yet taken by a flusher (0 when quiescent)."""
        with self._cv:
            return sum(len(q) for q in self._groups.values())

    # ------------------------------------------- device-resident ingest
    @staticmethod
    def _stages_on_ingest(codec) -> bool:
        """Whether ops stage to the device as they are submitted: jax
        pools on an accelerator.  On the CPU platform a per-op memcpy
        "to device" plus an XLA concat costs ~3x the one host fold it
        replaces, so host bytes stay host and the flush folds them once
        (still exactly one metered d2h per flush)."""
        return (getattr(codec, "_backend", None) == "jax"
                and not staging.backend_is_cpu())

    def _stage_lanes(self, op: _PendingOp, rows: np.ndarray,
                     bucket: int) -> None:
        """Pad (n_rows, L) host bytes to the bucket's stage width, view
        them as uint32 lanes (zero-copy) and ``device_put`` ONCE —
        metered by ec_stage_h2d_* — so the flush folds device buffers
        inside its launch program instead of a host memcpy + an
        implicit whole-fold h2d, and staging parallelizes across
        submitters instead of serializing in the flusher."""
        w = stage_width(bucket)
        if op.length < w:
            rows = np.pad(rows, ((0, 0), (0, w - op.length)))
        op.dev = staging.device_put_landed(
            np.ascontiguousarray(rows).view(np.uint32), force=False,
            exemplar=self._op_exemplar(op))

    def _stage_encode_op(self, op: _PendingOp, bucket: int) -> None:
        """Stage one encode op's (k, L) source bytes in the SUBMITTING
        thread.  A staging failure surfaces on an accelerator; on the
        CPU platform (tests forcing the plane) it is counted and the
        flush folds host bytes."""
        if not self._stages_on_ingest(op.codec):
            return
        try:
            self._stage_lanes(op, op.streams, bucket)
        except Exception:  # noqa: BLE001 - counted, raised off-CPU
            staging.fallthrough("ec_stage_encode_host_fallback")
            op.dev = None

    def _stage_decode_op(self, op: _PendingOp, sig: tuple) -> None:
        """Decode counterpart: stack the op's survivor chunks (sorted
        shard order, the flush's row layout) into ONE (n_rows, width)
        lane buffer in the submitting thread."""
        if not self._stages_on_ingest(op.codec):
            return
        # only the codec's fold rows feed the decode (for MDS codes the
        # first k sorted survivors — every present data shard is there;
        # wide/local codes pick their repair-equation participants or
        # an invertible subset) — staging any other survivor row would
        # be pure h2d/HBM waste
        ids = self._fold_rows_for(op.codec, sig)
        try:
            self._stage_lanes(op, np.stack([op.chunks[s] for s in ids]),
                              sig[-1])
        except Exception:  # noqa: BLE001 - counted, raised off-CPU
            staging.fallthrough("ec_stage_decode_host_fallback")
            op.dev = None

    @staticmethod
    def _fold_rows_for(codec, sig: tuple) -> list[int]:
        """Survivor rows a folded decode launch consumes, resolved
        through the codec's fold protocol (decode() already verified
        they exist for this signature)."""
        rows = codec.fold_rows(list(sig[6]), sig[5])
        if rows is None:  # cannot happen after decode()'s gate, but a
            # flush must never crash the group on a protocol slip
            rows = [s for s in sig[5]
                    if s < codec.chunk_count][: codec.k]
        return rows

    # ----------------------------------------------------------- tracing
    @staticmethod
    def _sig_tag(sig: tuple) -> str:
        """Human-readable batch-signature tag (the raw sig embeds the
        whole coding matrix): kind/codec/k.m/length-bucket."""
        if sig[0] == "rep":
            return f"rep/{sig[1][0]}/lost{sig[2]}/L{sig[-1]}"
        if sig[0] == "ver":
            return f"ver/{sig[1][0]}/L{sig[-1]}"
        return f"{sig[0]}/{sig[1][0]}/k{sig[3]}m{sig[4]}/L{sig[-1]}"

    def _note_queued(self, op: _PendingOp, sig: tuple) -> None:
        """The op joins its group: ONE reading stamps ``submitted``,
        marks the timeline ``ec_queued`` and starts the op's
        ec-batch-wait span (queued -> taken)."""
        op.submitted = now_ns()
        if op.tracked is not None:
            op.tracked.mark("ec_queued", op.submitted)
        if op.trace is not None:
            tracer, ctx = op.trace
            op.tspan = tracer.start("ec-batch-wait", parent=ctx,
                                    start_ns=op.submitted,
                                    sig=self._sig_tag(sig))

    def _trace_flush(self, sig: tuple, ops: list[_PendingOp],
                     reason: str):
        """One shared ec-flush span per flush, parented under the first
        traced op's wait span; every traced op's wait span finishes
        and tags the flush span's id, so collector-side assembly
        (build_tree / trace_tool) reconstructs the fan-in across the
        coalesced ops' separate traces.  The wait spans end and the
        flush span starts on the reading of the take (``taken_at``,
        the ``ec_taken`` mark)."""
        tops = [o for o in ops if o.tspan is not None]
        if not tops:
            return None
        lead = tops[0].tspan
        fspan = lead._tracer.start("ec-flush", parent=lead.ctx,
                                   start_ns=tops[0].taken_at or None,
                                   sig=self._sig_tag(sig),
                                   n_ops=len(ops), reason=reason)
        for o in tops:
            o.tspan.tag("flush_span", fspan.span_id)
            o.tspan.tag("flush_reason", reason)
            o.tspan.finish(o.taken_at or None)
        return fspan

    @staticmethod
    def _trace_flush_done(fspan, *, bucket: int, src_cols: int,
                          padded_cols: int, n_shard: int) -> None:
        """Close the flush span with the launch-shape tags: bucket
        length, pad-waste ratio (padded columns that carried no op
        bytes), and the device fan-out."""
        if fspan is None:
            return
        waste = (1.0 - src_cols / padded_cols) if padded_cols else 0.0
        fspan.tag("bucket", bucket)
        fspan.tag("pad_waste", round(waste, 4))
        fspan.tag("n_shard", n_shard)
        fspan.finish()

    # ------------------------------------------------- submit/wait machinery
    def _submit(self, sig: tuple, op: _PendingOp, nbytes: int,
                flush) -> None:
        ops = reason = None
        with self._cv:
            q = self._groups.setdefault(sig, [])
            self._note_queued(op, sig)
            if q:
                # the group's window is the LEADER's: a follower must
                # not cut a longer (probe) window short with its own
                # shorter deadline — with a uniform window the leader's
                # deadline is the earliest anyway, so this is the same
                # flush point the per-op deadline always produced
                op.deadline = q[0].deadline
            else:
                w = self.window_us
                if self.adaptive and self._probe_next:
                    self._probe_next = False
                    w = self.window_max_us
                op.deadline = time.monotonic() + w * 1e-6
            q.append(op)
            total = self._group_bytes.get(sig, 0) + nbytes
            self._group_bytes[sig] = total
            if total >= self.max_bytes:
                ops, reason = self._take_locked(sig), FLUSH_SIZE
            else:
                while not op.done:
                    now = time.monotonic()
                    if not op.taken and now >= op.deadline:
                        ops = self._take_locked(sig)
                        reason = (FLUSH_WINDOW if len(ops) > 1
                                  else FLUSH_IDLE)
                        break
                    self._cv.wait(timeout=None if op.taken
                                  else max(0.0, op.deadline - now))
        if ops is not None:
            with annotate("ceph:ec-flush", n_ops=len(ops), reason=reason):
                flush(sig, ops, reason)
        if not op.done:  # flushed by another thread after we broke out
            with self._cv:
                while not op.done:
                    self._cv.wait()

    def _take_locked(self, sig: tuple) -> list[_PendingOp]:
        ops = self._groups.pop(sig, [])
        self._group_bytes.pop(sig, None)
        now = now_ns()
        for o in ops:
            o.taken = True
            o.taken_at = now
            if o.tracked is not None:
                o.tracked.mark("ec_taken", now)
        return ops

    @staticmethod
    def _op_exemplar(op: _PendingOp):
        """The op's sampled trace_id (exemplar), or None: a trace
        context only rides a sampled op."""
        return int(op.trace[1][0]) if op.trace is not None else None

    def _complete(self, ops: list[_PendingOp], src_bytes: int,
                  reason: str, n_shard: int = 1,
                  shard_bytes: int = 0) -> None:
        p = self._perf
        # the results are on the host: one reading ends the flush for
        # the histogram and for every op's timeline
        now = now_ns()
        for o in ops:
            if o.tracked is not None:
                o.tracked.mark("ec_done", now)
        if p is not None and ops:
            # wait (queued -> taken) per op, flush (taken -> done) once
            # per launch; sampled ops pin their trace_id on the bucket
            lead_ex = None
            for o in ops:
                ex = self._op_exemplar(o)
                if lead_ex is None:
                    lead_ex = ex
                if o.taken_at:
                    p.hinc("ec_batch_wait_us",
                           max(0, o.taken_at - o.submitted) / 1e3,
                           exemplar=ex)
            t0 = min((o.taken_at for o in ops if o.taken_at),
                     default=0)
            if t0:
                p.hinc("ec_batch_flush_us", max(0, now - t0) / 1e3,
                       exemplar=lead_ex)
        if reason is not None:  # None: a warm-up launch, uncounted
            self._account(len(ops), src_bytes, reason, n_shard,
                          shard_bytes)
            self._adapt(ops)
        with self._cv:
            for o in ops:
                o.done = True
            self._cv.notify_all()

    def _shard_fanout(self, codec, n2: int) -> tuple[int, int]:
        """(fan-out, padded stripe count) for this flush — the codec's
        resolved shard count run through shard_pad (capped at the
        stripe count, count rounded up to the fan-out)."""
        sd = getattr(codec, "shard_devices", None)
        if sd is None:
            return 1, n2
        return shard_pad(n2, sd())

    def _adapt(self, ops: list[_PendingOp]) -> None:
        """One controller step per flush: EWMA the launch's op count,
        then grow the window when coalescing is paying and shrink it
        toward the floor when launches fly nearly alone (a trickle
        gains nothing from waiting — the fixed-window latency tax this
        controller exists to remove).

        Sizing is RATE-BASED: any flush that actually coalesced (>= 2
        ops) measures the ops' arrival span and the window STEERS
        halfway toward the span a target-sized group needs (x1.25
        margin) — converging from BOTH sides, so sustained load settles
        near the target-sized window instead of ratcheting to the
        ceiling (a grow-only x-step pins at window_max under any load
        meeting the target, taxing every op with the max window), and
        simultaneous arrivals that need no window at all walk it back
        down.  A multiplicative step alone also cannot climb when the
        coalescing-vs-window curve is a step at the launch latency —
        every probe's gain would be undone by the shrinks between
        probes; steering to the measured span clears the step in one
        move."""
        if not self.adaptive:
            return
        n_ops = len(ops)
        with self._cv:
            a = self.ADAPT_ALPHA
            self._ops_ewma = (1 - a) * self._ops_ewma + a * n_ops
            self._flushes_since_probe += 1
            if self._flushes_since_probe >= self.PROBE_EVERY:
                self._flushes_since_probe = 0
                self._probe_next = True
            w = self.window_us
            if n_ops >= 2:
                # direct evidence of a stream: steer toward the window
                # a target-sized group needs at the observed rate
                span = (max(o.submitted for o in ops)
                        - min(o.submitted for o in ops)) / 1e9
                est = (span / (n_ops - 1)
                       * (self.target_ops - 1) * 1.25 * 1e6)
                w = 0.5 * w + 0.5 * est
            elif self._ops_ewma < max(1.5, self.target_ops / 2):
                # launches flying alone: waiting buys nothing
                w = w * self.ADAPT_SHRINK
            w = min(self.window_max_us, max(self.window_min_us, w))
            self.window_us = w
            # regime-change journaling INSIDE the cv: the decision must
            # be atomic with the _event_window check-and-set (two
            # racing flushers would double-journal one resize) AND the
            # emit must happen in decision order, or concurrent resizes
            # journal with an incoherent prev_us chain.  EventLog.emit
            # is an O(1) ring append under its own leaf lock — holding
            # the cv over it cannot stall a flush.
            if self._events is not None and (
                    w >= self._event_window * self.EVENT_RESIZE_RATIO
                    or w <= self._event_window / self.EVENT_RESIZE_RATIO):
                self._events.emit(
                    "batch",
                    f"ec batch window resized to {w:.0f}us",
                    window_us=round(w, 1),
                    prev_us=round(self._event_window, 1),
                    ops_ewma=round(self._ops_ewma, 2))
                self._event_window = w
        if self._perf is not None:
            # the CLAMPED value: the gauge must report the window the
            # batcher actually uses, not the controller's raw estimate
            self._perf.set("ec_batch_window_us_now", round(w, 1))

    def _fire(self, op: _PendingOp, callback: Callable, *args) -> None:
        try:
            callback(*args)
        except BaseException as e:  # surfaced to the op's own waiter
            op.error = e

    def _account(self, n_ops: int, src_bytes: int, reason: str,
                 n_shard: int = 1, shard_bytes: int = 0) -> None:
        with self._cv:
            self.stats["launches"] += 1
            self.stats["ops"] += n_ops
            self.stats["bytes"] += src_bytes
            self.stats[reason] += 1
            if n_shard > 1:
                self.stats["sharded_launches"] += 1
        p = self._perf
        if p is not None:
            p.inc("ec_batch_launches")
            p.inc("ec_batch_coalesced_ops", n_ops)
            p.inc("ec_batch_bytes", src_bytes)
            p.inc(f"ec_batch_flush_{reason}")
            p.hinc("ec_batch_ops_per_launch", n_ops)
            p.hinc("ec_batch_bytes_per_launch", src_bytes)
            if n_shard > 1:
                p.inc("ec_batch_sharded_launches")
                p.hinc("ec_batch_sharded_devices_per_launch", n_shard)
                p.hinc("ec_batch_sharded_shard_bytes", shard_bytes)

    # ------------------------------------------------------- pass-through
    def _passthrough_encode(self, codec, data_chunks, with_csums,
                            callback):
        if with_csums:
            enc_csum = getattr(codec, "encode_chunks_with_csums", None)
            if enc_csum is not None:
                parity, csums = enc_csum(data_chunks)
            else:
                parity, csums = codec.encode_chunks(data_chunks), None
        else:
            parity, csums = codec.encode_chunks(data_chunks), None
        self._account(1, data_chunks.nbytes, FLUSH_IDLE)
        if callback is not None:
            callback(parity, csums)
        return parity, csums

    def _passthrough_decode(self, codec, want, chunks, callback):
        out = codec.decode(want, chunks)
        self._account(1, sum(np.asarray(c).nbytes
                             for c in chunks.values()), FLUSH_IDLE)
        if callback is not None:
            callback(out)
        return out

    # ------------------------------------------------------------ flushes
    def _launch_ctx(self, codec):
        """Context the flush's compute section runs under: on CPU-jax
        a per-batcher lock (overlapping launches thrash the one host
        threadpool), elsewhere a no-op (device queues pipeline)."""
        if (getattr(codec, "_backend", None) == "jax"
                and staging.backend_is_cpu()):
            return self._launch_lock
        return contextlib.nullcontext()

    @staticmethod
    def _fold_host_rows(parts, lengths, width: int, n_rows: int,
                        n_str: int) -> np.ndarray:
        """Assemble the (n_rows, n_str * width) host fold with
        ``np.empty`` + pad-only zeroing: every op's columns are fully
        overwritten, so only the per-op pad tails and the empty
        trailing slots need zeros — a whole-fold ``np.zeros`` pays a
        page-touching memset of the entire launch tensor per flush
        (~20% of a CPU flush, measured) for bytes that are about to be
        overwritten anyway."""
        folded = np.empty((n_rows, n_str * width), dtype=np.uint8)
        col = 0
        for part, length in zip(parts, lengths):
            folded[:, col:col + length] = part
            if length < width:
                folded[:, col + length:col + width] = 0
            col += width
        if col < folded.shape[1]:
            folded[:, col:] = 0
        return folded

    @staticmethod
    def _fold_parts(ops: list[_PendingOp], n_str: int) -> list:
        """The flush's device fold as a list of ``n_str`` equal-width
        lane buffers: the ops' ingest-staged buffers, then shared zeros
        for the empty stripe slots.  The codec concatenates and
        launches them as ONE jitted program keyed by (n_str, width), so
        the fold never materializes on its own and the compile cache
        sees one shape per (stripe count, bucket)."""
        parts = [o.dev for o in ops]
        if len(parts) < n_str:
            zero = _zero_lanes(*parts[0].shape)
            parts.extend([zero] * (n_str - len(parts)))
        return parts

    @contextlib.contextmanager
    def _flush_phase(self, phase: str, reason):
        """One of FLUSH_PHASES of the running flush: a trace annotation
        on this thread and, for a counted launch (``reason`` None is a
        warm-up), its seconds on ``ec_flush_<phase>``."""
        t0 = now_ns()
        try:
            with annotate("ceph:" + phase.replace("_", "-")):
                yield
        finally:
            if reason is not None and self._perf is not None:
                self._perf.tinc(f"ec_flush_{phase}", (now_ns() - t0) / 1e9)

    def _sync_flush(self, codec, devs, fspan, sig: tuple):
        """The flush's SINGLE device->host copy (ec_stage_d2h_* meters
        it; the bench asserts copies/flush == 1): every output of the
        folded launch materializes in one host_sync_bulk event, shown
        as a ``staging`` child span of the flush when traced."""
        sig_str = f"sync/flush/{self._sig_tag(sig)}"
        if fspan is not None:
            with fspan._tracer.start("staging", parent=fspan.ctx,
                                     dir="d2h") as sp:
                out = codec.host_sync_bulk(devs, sig=sig_str)
                sp.tag("bytes", sum(o.nbytes for o in out))
            return out
        return codec.host_sync_bulk(devs, sig=sig_str)

    def _carve_parity(self, ops: list[_PendingOp], parity: np.ndarray,
                      stride: int) -> None:
        """Each op's parity out of the launch's host copy (op i's
        columns start at ``i * stride``) into an array of its own: the
        launch buffer is the flush's, and an op keeps its parity.  Ops
        that asked for digests (``with_csums`` rides the signature, so
        a flush's ops all did or none did) get the copy and their k+m
        CRC-32C in ONE native call, which hands the interpreter away
        once a flush (``ec_carve_native_calls``, ``ec_carve_rows``)."""
        cols = [i * stride for i in range(len(ops))]
        if not ops[0].with_csums:
            for o, c in zip(ops, cols):
                o.parity = parity[:, c: c + o.length].copy()
            return
        parities, sums = carve_with_csums([o.streams for o in ops],
                                          parity, cols)
        for o, p, s in zip(ops, parities, sums):
            o.parity, o.csums = p, s
        if self._perf is not None:
            self._perf.inc("ec_carve_native_calls")
            self._perf.inc("ec_carve_rows", sums.size)

    def _flush_encode(self, sig: tuple, ops: list[_PendingOp],
                      reason: str) -> None:
        bucket = sig[-1]
        codec = ops[0].codec
        k = codec.k
        src_bytes = sum(o.streams.nbytes for o in ops)
        ns, shard_bytes = 1, 0
        padded_cols = 0
        fspan = self._trace_flush(sig, ops, reason)
        try:
            # stripe-count padding, then the mesh fan-out: the
            # shard_pad stripe count splits sum L into whole per-device
            # column slices (a bounded shape set: pow2 rounded to the
            # fan-out)
            ns, n2 = self._shard_fanout(codec, _pow2(len(ops)))
            padded_cols = n2 * bucket
            with self._launch_ctx(codec):
                with self._flush_phase("stage_in", reason):
                    if all(o.dev is not None for o in ops):
                        # device-resident plane: the staged lane
                        # buffers fold and launch as ONE program,
                        # ONE metered d2h per flush
                        stride = stage_width(bucket)
                        fold = self._fold_parts(ops, n2)
                    else:
                        # host fold (CPU platform): one memcpy into
                        # the launch tensor, viewed as lanes by the
                        # codec, one launch whose internal transfer
                        # is the single h2d, and the same ONE
                        # metered d2h per flush as the device fold
                        stride = bucket
                        fold = self._fold_host_rows(
                            [o.streams for o in ops],
                            [o.length for o in ops], bucket, k, n2)
                with self._flush_phase("launch", reason):
                    dev_parity = codec._matmul_device(
                        codec.matrix, fold, n_shard=ns)
                nbytes_fold = k * n2 * stride
                with self._flush_phase("fetch", reason):
                    (parity,) = self._sync_flush(
                        codec, (dev_parity,), fspan, sig)
                parity = _as_bytes(parity)
            shard_bytes = nbytes_fold // ns if ns > 1 else 0
            with self._flush_phase("carve", reason):
                self._carve_parity(ops, parity, stride)
            for o in ops:
                if o.callback is not None:
                    self._fire(o, o.callback, o.parity, o.csums)
        except BaseException as e:
            for o in ops:
                o.error = e
        finally:
            self._trace_flush_done(
                fspan, bucket=bucket,
                src_cols=sum(o.length for o in ops),
                padded_cols=padded_cols, n_shard=ns)
            self._complete(ops, src_bytes, reason, ns, shard_bytes)

    def _flush_decode(self, sig: tuple, ops: list[_PendingOp],
                      reason: str) -> None:
        bucket = sig[-1]
        codec = ops[0].codec
        avail, want = sig[5], list(sig[6])
        src_bytes = sum(sum(c.nbytes for c in o.chunks.values())
                        for o in ops)
        ns, shard_bytes = 1, 0
        padded_cols = 0
        fspan = self._trace_flush(sig, ops, reason)
        try:
            ns, n2 = self._shard_fanout(codec, _pow2(len(ops)))
            padded_cols = n2 * bucket
            if getattr(codec, "_backend", None) == "jax":
                # device-resident plane: the survivor stacks (staged at
                # ingest off-CPU, host-folded on the CPU fall-through)
                # feed ONE folded decode that runs device-to-device
                # (decode_folded_device — decode matrix product +
                # parity product with NO per-matmul host sync), and
                # every waiter's rows carve out of ONE bulk d2h copy
                # per launch.  No donation: the stacked survivors feed
                # both the decode product and the parity-from-data
                # product.
                # the codec's fold rows only — the exact rows
                # _stage_decode_op staged and decode_folded_device
                # consumes (MDS: first k sorted survivors; wide/local
                # codes: repair-equation participants or an invertible
                # subset)
                avail_ids = self._fold_rows_for(codec, sig)
                with self._launch_ctx(codec):
                    with self._flush_phase("stage_in", reason):
                        if all(o.dev is not None for o in ops):
                            stride = stage_width(bucket)
                            folded = self._fold_parts(ops, n2)
                        else:
                            stride = bucket
                            folded = np.empty(
                                (len(avail_ids), n2 * bucket),
                                dtype=np.uint8)
                            for i, o in enumerate(ops):
                                c0 = i * bucket
                                for j, s in enumerate(avail_ids):
                                    folded[j, c0: c0 + o.length] = \
                                        o.chunks[s]
                                if o.length < bucket:
                                    folded[:, c0 + o.length:
                                           c0 + bucket] = 0
                            if len(ops) < n2:
                                folded[:, len(ops) * bucket:] = 0
                    with self._flush_phase("launch", reason):
                        out_dev = codec.decode_folded_device(
                            want, avail_ids, folded, n_shard=ns)
                    with self._flush_phase("fetch", reason):
                        (out_np,) = self._sync_flush(
                            codec, (out_dev,), fspan, sig)
                    out_np = _as_bytes(out_np)
                shard_bytes = (len(avail_ids) * n2 * stride // ns
                               if ns > 1 else 0)
                with self._flush_phase("carve", reason):
                    for i, o in enumerate(ops):
                        o.decoded = {
                            s: out_np[j,
                                      i * stride: i * stride + o.length
                                      ].copy()
                            for j, s in enumerate(want)}
            else:
                flat = {s: np.zeros(n2 * bucket, dtype=np.uint8)
                        for s in avail}
                for i, o in enumerate(ops):
                    for s, c in o.chunks.items():
                        flat[s][i * bucket: i * bucket + o.length] = \
                            np.asarray(c)
                with self._flush_phase("launch", reason):
                    out = codec.decode_chunks(want, flat, n_shard=ns)
                shard_bytes = (sum(c.nbytes for c in flat.values())
                               // ns if ns > 1 else 0)
                for i, o in enumerate(ops):
                    # copy out of the launch buffer (see _flush_encode)
                    o.decoded = {
                        s: row[i * bucket: i * bucket + o.length].copy()
                        for s, row in out.items()}
        except BaseException as e:
            for o in ops:
                o.error = e
        finally:
            self._trace_flush_done(
                fspan, bucket=bucket,
                src_cols=sum(o.length for o in ops),
                padded_cols=padded_cols, n_shard=ns)
            self._complete(ops, src_bytes, reason, ns, shard_bytes)

    # ------------------------------------------- sub-chunk codec flushes
    # CLAY (and any REQUIRE_SUB_CHUNKS codec exposing *_chunks_folded)
    # folds at plane granularity: the ops' exact-L segments fold on the
    # HOST (the plane transpose is O(bytes) numpy), and the codec's
    # folded entry point runs its coupling gathers once and its MDS
    # plane matmuls as the same (k, sum L) folded device launches the
    # plain flushes ride — sharded over the mesh when the pool fans out.

    def _flush_encode_subchunk(self, sig: tuple, ops: list[_PendingOp],
                               reason: str) -> None:
        L = sig[-1]
        codec = ops[0].codec
        k = codec.k
        src_bytes = sum(o.streams.nbytes for o in ops)
        ns, shard_bytes = 1, 0
        padded_cols = 0
        fspan = self._trace_flush(sig, ops, reason)
        try:
            ns, n2 = self._shard_fanout(codec, _pow2(len(ops)))
            padded_cols = n2 * L
            with self._launch_ctx(codec):
                folded = self._fold_host_rows(
                    [np.asarray(o.streams) for o in ops],
                    [L] * len(ops), L, k, n2)
                # zero stripe slots encode to zero parity (linear code:
                # zero data -> zero uncoupled planes -> zero parity),
                # so the pow2 padding slices away clean
                with self._flush_phase("launch", reason):
                    parity = codec.encode_chunks_folded(folded, n2, L,
                                                        n_shard=ns)
            shard_bytes = folded.nbytes // ns if ns > 1 else 0
            self._carve_parity(ops, parity, L)
            for o in ops:
                if o.callback is not None:
                    self._fire(o, o.callback, o.parity, o.csums)
        except BaseException as e:
            for o in ops:
                o.error = e
        finally:
            self._trace_flush_done(
                fspan, bucket=L, src_cols=sum(o.length for o in ops),
                padded_cols=padded_cols, n_shard=ns)
            self._complete(ops, src_bytes, reason, ns, shard_bytes)

    def _flush_decode_subchunk(self, sig: tuple, ops: list[_PendingOp],
                               reason: str) -> None:
        L = sig[-1]
        codec = ops[0].codec
        avail = [s for s in sig[5] if s < codec.chunk_count]
        want = list(sig[6])
        src_bytes = sum(sum(c.nbytes for c in o.chunks.values())
                        for o in ops)
        ns, shard_bytes = 1, 0
        padded_cols = 0
        fspan = self._trace_flush(sig, ops, reason)
        try:
            ns, n2 = self._shard_fanout(codec, _pow2(len(ops)))
            padded_cols = n2 * L
            with self._launch_ctx(codec):
                folded = np.empty((len(avail), n2 * L), dtype=np.uint8)
                for i, o in enumerate(ops):
                    c0 = i * L
                    for j, s in enumerate(avail):
                        folded[j, c0: c0 + L] = np.asarray(o.chunks[s])
                if len(ops) < n2:
                    folded[:, len(ops) * L:] = 0
                with self._flush_phase("launch", reason):
                    out = codec.decode_chunks_folded(
                        want, avail, folded, n2, L, n_shard=ns)
            shard_bytes = folded.nbytes // ns if ns > 1 else 0
            for i, o in enumerate(ops):
                o.decoded = {
                    s: out[j, i * L: (i + 1) * L].copy()
                    for j, s in enumerate(want)}
        except BaseException as e:
            for o in ops:
                o.error = e
        finally:
            self._trace_flush_done(
                fspan, bucket=L, src_cols=sum(o.length for o in ops),
                padded_cols=padded_cols, n_shard=ns)
            self._complete(ops, src_bytes, reason, ns, shard_bytes)

    def _flush_verify(self, sig: tuple, ops: list[_PendingOp],
                      reason: str) -> None:
        """Folded digest flush: every op's (n_i, L) rows concatenate
        into one (sum n_i, L) buffer whose digests scatter back per op.
        On the device the buffer goes in as groups of ``verify.ROWS``
        rows (the last one filled with zero rows), the bucket's ONE
        program runs on each, and all their digests come back in one
        counted copy: ``stage_in`` / ``launch`` / ``fetch`` as in an
        encode's flush.  The host sweep is one native call, booked as
        the launch."""
        ver = ops[0].codec
        L = sig[-1]
        src_bytes = sum(o.streams.nbytes for o in ops)
        n_rows = sum(o.streams.shape[0] for o in ops)
        fspan = self._trace_flush(sig, ops, reason)
        try:
            folded = (ops[0].streams if len(ops) == 1
                      else np.concatenate([o.streams for o in ops]))
            with self._launch_ctx(ver):
                digs = ver.digests(
                    folded, phase=lambda p: self._flush_phase(p, reason),
                    sync=lambda outs: self._sync_flush(ver, outs, fspan,
                                                       sig))
            row = 0
            for o in ops:
                n = o.streams.shape[0]
                o.decoded = digs[row:row + n]
                row += n
        except BaseException as e:
            for o in ops:
                o.error = e
        finally:
            self._trace_flush_done(fspan, bucket=L,
                                   src_cols=n_rows, padded_cols=n_rows,
                                   n_shard=1)
            self._complete(ops, src_bytes, reason)

    def _flush_repair(self, sig: tuple, ops: list[_PendingOp],
                      reason: str) -> None:
        """Folded MSR repair flush: same lost chunk, same helper set,
        same L — the whole group rides ONE repair_chunk_folded pass
        (no stripe-count padding: the repair solve's shapes already
        vary by plane count, and a zero segment would buy nothing)."""
        L = sig[-1]
        codec = ops[0].codec
        lost = sig[2]
        src_bytes = sum(sum(np.asarray(c).nbytes
                            for c in o.chunks.values()) for o in ops)
        ns = 1
        fspan = self._trace_flush(sig, ops, reason)
        try:
            ns, _n2 = self._shard_fanout(codec, len(ops))
            with self._launch_ctx(codec), \
                    self._flush_phase("launch", reason):
                outs = codec.repair_chunk_folded(
                    lost, [o.chunks for o in ops], L, n_shard=ns)
            for o, chunk in zip(ops, outs):
                o.decoded = chunk
        except BaseException as e:
            for o in ops:
                o.error = e
        finally:
            self._trace_flush_done(
                fspan, bucket=L, src_cols=len(ops) * L,
                padded_cols=len(ops) * L, n_shard=ns)
            self._complete(ops, src_bytes, reason, ns,
                           src_bytes // ns if ns > 1 else 0)
