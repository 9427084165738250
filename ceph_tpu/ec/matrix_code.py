"""Shared implementation of GF(2^8) matrix codes (RS/Cauchy families).

The role jerasure's matrix techniques and ISA-L's ec_encode_data play for
the reference plugins (wrappers ErasureCodeJerasure.cc:121-240,
ErasureCodeIsa.cc:290-563): hold an (m, k) coding matrix, multiply regions
through a backend — numpy oracle, native C++ (AVX2), or JAX/TPU — and build
cached inverted decode matrices per erasure signature (the reference's
ErasureCodeIsaTableCache LRU, ErasureCodeIsa.cc:513-563).
"""

from __future__ import annotations

import threading
import time
from typing import Sequence

import numpy as np

from ..ops import gf256
from ..ops import native
from ..utils import staging
from ..utils.perf import kernel_profiler
from .interface import ChunkMap, ErasureCode, ErasureCodeError, Flags


#: mesh-sharded jitted ops, shared process-wide like the single-device
#: ones (ec_kernels.region_matmul): the OSDs of one process each hold a
#: codec, and a jit per codec is a compile per OSD
_SHARDED_OPS: dict = {}
_SHARDED_LOCK = threading.Lock()


def _shared_sharded(key: tuple, build):
    with _SHARDED_LOCK:
        if key not in _SHARDED_OPS:
            if len(_SHARDED_OPS) >= 256:
                _SHARDED_OPS.pop(next(iter(_SHARDED_OPS)))
            _SHARDED_OPS[key] = build()
        return _SHARDED_OPS[key]


def _concat_parts(parts):
    """Per-op device lane buffers side by side (an eager concat: the
    sharded launches take one array)."""
    import jax.numpy as jnp
    return jnp.concatenate(parts, axis=1)


def _row_addrs(a: np.ndarray, col: int = 0) -> list[int]:
    """Address of each row of a 2-D uint8 array whose rows are
    contiguous (a C-ordered array, or a column slice of a wider one),
    from column ``col`` on."""
    base, step = a.ctypes.data + col, a.strides[0]
    return [base + r * step for r in range(a.shape[0])]


def _rows_in_place(a) -> np.ndarray:
    """``a`` as a 2-D uint8 array with contiguous rows: itself where it
    is one, else a C-ordered copy."""
    a = np.asarray(a)
    if a.dtype != np.uint8 or a.ndim != 2 or a.strides[1] != 1 \
            or a.strides[0] < 0:
        a = np.ascontiguousarray(a, dtype=np.uint8)
    if a.ndim != 2:
        raise ValueError(f"rows must be 2-D, got shape {a.shape}")
    return a


def carve_with_csums(streams: Sequence[np.ndarray], launch: np.ndarray,
                     cols: Sequence[int]) -> tuple[list, np.ndarray]:
    """One encode flush's carve with digests, in ONE native call: op i's
    parity is copied out of the launch buffer ``launch`` (m rows; the
    op's columns start at ``cols[i]`` and are as many as its source
    ``streams[i]``, a (k, L_i) array, has) into an (m, L_i) array of its
    own, and its k+m digests are taken as ``row_csums`` orders them.
    Returns (the ops' parity arrays, uint32[n_ops, k+m]).  The rows are
    read where they lie: nothing is stacked or flattened for the call."""
    launch = _rows_in_place(launch)
    streams = [_rows_in_place(s) for s in streams]
    k, m = streams[0].shape[0], launch.shape[0]
    if any(s.shape[0] != k or c < 0 or c + s.shape[1] > launch.shape[1]
           for s, c in zip(streams, cols, strict=True)):
        raise ValueError("an op's rows do not lie inside the launch")
    lens = [s.shape[1] for s in streams]
    parities = [np.empty((m, n), dtype=np.uint8) for n in lens]
    sums = native.crc32c_rows(
        [a for s in streams for a in _row_addrs(s)],
        [a for c in cols for a in _row_addrs(launch, c)],
        [a for p in parities for a in _row_addrs(p)], lens, k, m)
    return parities, sums


def row_csums(streams: np.ndarray, parity: np.ndarray) -> np.ndarray:
    """CRC-32C of each data stream, then of each parity row, as
    uint32[k+m]: the digest a shard stores beside its bytes (``dcsum``).
    The native sweep reads every row where it lies, in one call
    (``native.crc32c_rows`` with no copy: the parity is already the
    op's own)."""
    streams, parity = _rows_in_place(streams), _rows_in_place(parity)
    if parity.shape[1] != streams.shape[1]:
        raise ValueError("data and parity rows differ in length")
    return native.crc32c_rows(
        _row_addrs(streams), _row_addrs(parity), None, [streams.shape[1]],
        streams.shape[0], parity.shape[0])[0]


def _pick_backend(name: str) -> str:
    if name == "auto":
        return "native" if native.available() else "numpy"
    if name not in ("native", "numpy", "jax"):
        raise ErasureCodeError(f"unknown backend {name!r}")
    return name


class MatrixErasureCode(ErasureCode):
    """Systematic GF(2^8) matrix code over a pluggable region backend."""

    #: subclasses set this in _init_from_profile
    matrix: np.ndarray

    #: cache bounds (class attrs so tests can shrink them)
    JAX_OPS_CAP = 64
    DECODE_CACHE_CAP = 256

    def _init_matrix_backend(self) -> None:
        self._backend = _pick_backend(self.profile.get("backend", "auto"))
        self._decode_cache: dict[tuple[int, ...], np.ndarray] = {}
        # compiled-kernel cache keyed by matrix bytes (encode matrix plus
        # decode matrices), so repeated decodes reuse their compilation.
        # True LRU: hits re-insert at the dict's end, eviction pops the
        # front (the ErasureCodeIsaTableCache semantics, ref :513-563) —
        # a hot entry must survive churn from one-shot signatures.
        self._jax_ops: dict[bytes, object] = {}
        # sharded OSD workers (and batcher flushers) hit these caches
        # concurrently; the LRU touch is pop+reinsert, which must not
        # interleave
        self._cache_lock = threading.Lock()
        if self._backend == "jax":
            self._jax_matmul(self.matrix)  # build the encode op eagerly

    _MISS = object()  # cache-miss sentinel: a stored None is a HIT
    # (the sharded-matmul builder caches None for "mesh can't be
    # built" so the single-device fall-through doesn't re-attempt
    # mesh construction on every flush)

    def _jax_op_cached(self, key: bytes, build):
        with self._cache_lock:
            op = self._jax_ops.pop(key, self._MISS)
            if op is not self._MISS:
                self._jax_ops[key] = op  # LRU touch: re-insert at end
                return op
        op = build()  # trace-lazy, but still outside the lock
        with self._cache_lock:
            hit = self._jax_ops.pop(key, self._MISS)
            if hit is not self._MISS:
                op = hit  # another thread built it first: keep one
            elif len(self._jax_ops) > self.JAX_OPS_CAP:
                self._jax_ops.pop(next(iter(self._jax_ops)))
            self._jax_ops[key] = op
        return op

    @staticmethod
    def _matmul_key(M: np.ndarray) -> bytes:
        """Kernel-LRU key of a single-device region op: matrix bytes +
        shape (ONE definition — the true-LRU tests key off it too)."""
        return M.tobytes() + bytes(M.shape)

    def _jax_matmul(self, M: np.ndarray):
        """The platform's region multiply compiled for ``M``
        (ec_kernels.region_matmul: the Pallas kernel on a TPU, the XLA
        graph elsewhere)."""
        def build():
            from ..ops import ec_kernels  # deferred: jax import is heavy
            # process-wide: the OSDs of one process share compilations
            return ec_kernels.region_matmul(M)

        return self._jax_op_cached(self._matmul_key(M), build)

    @staticmethod
    def _matmul_sig(M: np.ndarray, L: int, kernel: str,
                    n_shard: int = 1) -> str:
        return (f"matmul/{M.shape[0]}x{M.shape[1]}/L{L}"
                + (f"/s{n_shard}" if n_shard > 1 else "")
                + f"/{kernel}")

    def _jax_matmul_sharded(self, M: np.ndarray, n_shard: int):
        """shard_map'd folded region multiply over a flat n_shard-device
        mesh (parallel/distributed.make_folded_matmul) — the multi-chip
        fan-out for folded (k, sum L) launches.  Cached in the same
        kernel LRU as the single-device ops, keyed by (matrix, fan-out).
        Returns ``(op, mesh)`` — the mesh rides along so the call site
        can pre-stage a HOST fold straight into its sharding
        (distributed.stage_folded: one h2d slice per device, no
        device-0 landing + on-mesh reshard) — or None when the mesh
        cannot be built (fewer devices than requested appeared since
        resolution) so callers fall back to the single-device launch
        rather than raising off the IO path."""
        def build():
            import jax  # deferred: jax import is heavy

            from ..parallel.distributed import make_folded_matmul
            from ..parallel.mesh import make_flat_mesh
            try:
                mesh = make_flat_mesh(n_shard)
            except (ValueError, RuntimeError):
                return None
            # lanes in, lanes out (gf_lanes_graph inside the body)
            return _shared_sharded(
                (n_shard, M.shape, M.tobytes()),
                lambda: (jax.jit(make_folded_matmul(M, mesh)), mesh))

        key = (b"shard:" + n_shard.to_bytes(4, "little")
               + M.tobytes() + bytes(M.shape))
        return self._jax_op_cached(key, build)

    def shard_devices(self) -> int:
        """Resolved device fan-out for folded launches (1 = single
        device, the PR-1 path).  Profile key ``shard`` (seeded from the
        ``ec_shard`` option by the OSD): ``off`` -> 1; an integer N ->
        min(N, device count); ``auto`` engages the whole accelerator
        pool but falls through to 1 on the CPU platform — one XLA:CPU
        device already uses every host core, so fanning virtual devices
        only adds dispatch overhead (forced-host CPU meshes opt in with
        an explicit N, as the mesh tests and benches do)."""
        if self._backend != "jax":
            return 1
        cached = getattr(self, "_shard_devices_cached", None)
        if cached is not None:
            return cached
        mode = str(self.profile.get("shard", "auto")).lower()
        n = 1
        if mode not in ("off", "false", "no", "0"):
            try:
                import jax
                ndev = len(jax.devices())
                if mode in ("auto", "on", "true", "yes"):
                    n = ndev if jax.default_backend() != "cpu" else 1
                else:
                    n = min(int(mode), ndev)
            except (ValueError, RuntimeError):
                n = 1
        n = max(1, n)
        self._shard_devices_cached = n
        return n

    def get_flags(self) -> Flags:
        return (Flags.PARITY_DELTA_OPTIMIZATION | Flags.ZERO_PADDING |
                Flags.OPTIMIZED_SUPPORTED | Flags.PARTIAL_READ_OPTIMIZATION |
                Flags.PARTIAL_WRITE_OPTIMIZATION)

    # -- batcher fold protocol ---------------------------------------------
    # The ECBatcher folds concurrent same-signature ops into one
    # (k, sum L) launch.  These hooks tell it HOW this codec folds:
    #
    # - fold_sig(): the codec-identity component of every flush
    #   signature.  The raw signature is otherwise matrix-derived, and
    #   two codecs sharing a matrix's bytes+shape need not share
    #   DECODE/sub-chunk semantics (a wide code's locality selection, a
    #   coupled-layer code's plane layout) — without this component
    #   they would coalesce into one fold and one of them would get the
    #   other's math.
    # - encode_fold_kind()/decode_fold_kind(): "plain" = the op is one
    #   region matmul against self.matrix / a decode-matrix product
    #   (the PR 1-8 path), "subchunk" = the codec folds through its own
    #   *_chunks_folded entry points (CLAY's coupled planes), None =
    #   not foldable (pass-through).
    # - fold_rows(): which survivor rows a folded "plain" decode
    #   launch consumes, in stack order — the base class takes the
    #   first k sorted survivors (every k-subset of an MDS code
    #   decodes); non-MDS codes pick an invertible (or locality)
    #   subset instead.  None = this erasure cannot fold (pass-through
    #   surfaces the codec's own error per op).

    def fold_sig(self) -> tuple:
        return ("mat",)

    def encode_fold_kind(self) -> str | None:
        return ("plain" if type(self).encode_chunks
                is MatrixErasureCode.encode_chunks else None)

    def decode_fold_kind(self) -> str | None:
        return ("plain" if type(self).decode_chunks
                is MatrixErasureCode.decode_chunks else None)

    def fold_rows(self, want: Sequence[int],
                  avail: Sequence[int]) -> list[int] | None:
        rows = [i for i in avail if i < self.chunk_count][: self.k]
        return rows if len(rows) == self.k else None

    # -- region multiply through the selected backend ----------------------
    def _matmul_device(self, M: np.ndarray, rows, *, n_shard: int = 1,
                       generic: bool = False):
        """Backend-resident region multiply: on the jax backend the
        result STAYS a device array (no host sync), so callers folding
        many stripes into one launch pay one host sync for the whole
        batch instead of one per op.  Other backends return numpy
        bytes directly.

        Bytes live on the host and uint32 lanes on the device: host
        ``(c, L)`` uint8 rows are viewed as lanes (zero-padded to the
        lane quantum) BEFORE the copy in; a device input is lanes
        already; a list of per-op device lane buffers folds and
        launches as one jitted program.  The jax result is a device
        ``(r, n4)`` uint32 array — ``host_sync(dev, nbytes=L)`` views it
        as bytes after the copy out.

        ``n_shard > 1`` fans the launch over a flat device mesh, length
        axis sharded (make_folded_matmul) — engaged only when the lane
        count splits evenly per device; anything else falls through to
        the single-device launch, byte-identical.

        ``generic=True`` launches the program that takes the matrix as
        a runtime operand (ec_kernels.gf_generic_lanes) instead of one
        compiled for ``M``: what folded decodes use, because a served
        read decodes from whichever k shards answered first and each
        survivor set is a matrix of its own."""
        if self._backend == "native":
            return native.encode_region(M, rows)
        if self._backend == "jax":
            from ..ops import ec_kernels
            parts = None
            if isinstance(rows, (list, tuple)):
                parts = list(rows)
                n4 = len(parts) * int(parts[0].shape[-1])
            else:
                if isinstance(rows, np.ndarray) and rows.dtype == np.uint8:
                    rows = ec_kernels.bytes_as_lanes(rows)
                n4 = int(rows.shape[-1])
            L = 4 * n4
            if generic:
                return self._matmul_generic(M, rows, parts, L, n_shard)
            ident = self._matmul_key(M)  # one tobytes() a launch
            if n_shard > 1 and n4 % n_shard == 0:
                # None = no mesh: fall through to the single-device
                # launch below
                ent = self._jax_matmul_sharded(M, n_shard)
                if ent is not None:
                    op, mesh = ent
                    if parts is not None:
                        rows = _concat_parts(parts)
                    elif isinstance(rows, np.ndarray):
                        # host fold: land it pre-sharded (one metered
                        # h2d, a column slice per device) instead of a
                        # device-0 landing + on-mesh reshard
                        from ..parallel.distributed import stage_folded
                        rows = stage_folded(rows, mesh)
                    # a shard_map body embeds the xla graph
                    return self._profiled_launch(
                        op, rows, self._matmul_sig(M, L, "xla", n_shard),
                        ident=ident)
            # _jax_matmul(M) under the key made above
            op = self._jax_op_cached(
                ident, lambda: ec_kernels.region_matmul(M))
            sig = self._matmul_sig(M, L, op.kernel)
            if parts is not None:
                return self._profiled_launch(
                    op.encode_parts, parts, sig + f"/f{len(parts)}",
                    ident=ident)
            return self._profiled_launch(op.encode_lanes, rows, sig,
                                         ident=ident)
        return gf256.encode_region(M, rows)

    def _matmul_generic(self, M: np.ndarray, rows, parts, L: int,
                        n_shard: int):
        """The runtime-matrix launch behind ``generic=True``: one
        compiled program per (r, c, width[, fan-out]) shape."""
        from ..ops import ec_kernels
        v = ec_kernels.coef_table(M)
        sig = f"matmul/{M.shape[0]}x{M.shape[1]}/L{L}"
        if n_shard > 1 and (L // 4) % n_shard == 0:
            ent = self._jax_generic_sharded(n_shard)
            if ent is not None:
                op, mesh = ent
                if parts is not None:
                    rows = _concat_parts(parts)
                elif isinstance(rows, np.ndarray):
                    from ..parallel.distributed import stage_folded
                    rows = stage_folded(rows, mesh)
                return self._profiled_launch(
                    lambda x: op(v, x), rows,
                    f"{sig}/s{n_shard}/generic")
        if parts is not None:
            return self._profiled_launch(
                lambda ps: ec_kernels.generic_parts(v, *ps), parts,
                f"{sig}/generic/f{len(parts)}")
        return self._profiled_launch(
            lambda x: ec_kernels.generic_lanes(v, x), rows,
            f"{sig}/generic")

    @staticmethod
    def _jax_generic_sharded(n_shard: int):
        """``(op, mesh)`` of the mesh-sharded runtime-matrix multiply —
        one per fan-out for the whole process — or None when the mesh
        cannot be built (same contract as _jax_matmul_sharded)."""
        def build():
            import jax

            from ..parallel.distributed import make_folded_generic
            from ..parallel.mesh import make_flat_mesh
            try:
                mesh = make_flat_mesh(n_shard)
            except (ValueError, RuntimeError):
                return None
            return jax.jit(make_folded_generic(mesh)), mesh

        return _shared_sharded(("generic", n_shard), build)

    #: (sig, input shape, matrix bytes) triples launched once in this
    #: process: jit compiles per input shape and compiled ops are shared
    #: process-wide (ec_kernels.region_matmul), so the FIRST launch of a
    #: triple is the XLA compile and is profiled as such
    _LAUNCHED: set = set()
    _LAUNCHED_LOCK = threading.Lock()

    def _profiled_launch(self, op, rows, sig: str, ident: bytes = b""):
        """One timed device launch: elapsed measured around
        ``block_until_ready`` (dispatch + device execute, NOT the
        host-side copy — that's host_sync's slice).  A signature's
        first launch IS the XLA compile and is recorded as a compile
        event; the sync a caller pays right after is unchanged —
        callers materialize the folded result immediately anyway, so
        blocking here adds no sync the hot path wasn't already paying
        per launch."""
        t0 = time.perf_counter()
        out = op(rows)
        if hasattr(out, "block_until_ready"):
            out = out.block_until_ready()
        dt = time.perf_counter() - t0
        shape = (tuple(rows[0].shape) + (len(rows),)
                 if isinstance(rows, list) else tuple(rows.shape))
        key = (sig, shape, ident)
        with self._LAUNCHED_LOCK:
            first = key not in self._LAUNCHED
            if first:
                if len(self._LAUNCHED) > 8192:
                    self._LAUNCHED.clear()
                self._LAUNCHED.add(key)
        kernel_profiler().note("compile" if first else "device", sig, dt)
        return out

    def host_sync(self, dev, sig: str | None = None, *,
                  nbytes: int | None = None):
        """Materialize a device result on the host, timing the
        device->host transfer as the profiler's host-sync slice (a
        numpy input passes through untimed — non-jax backends never
        left the host).  ``nbytes`` says the result is uint32 lanes of
        an ``nbytes``-wide byte region: it is viewed as bytes (and its
        pad columns trimmed) on the host, after the copy.  Default
        signature carries the result shape so the per-signature dump
        splits syncs the same way it splits launches."""
        if isinstance(dev, np.ndarray):
            return dev
        if sig is None:
            shape = "x".join(str(d) for d in getattr(dev, "shape", ()))
            sig = f"sync/{shape}"
        t0 = time.perf_counter()
        out = np.asarray(dev)
        kernel_profiler().note("sync", sig, time.perf_counter() - t0)
        if nbytes is not None:
            from ..ops import ec_kernels
            out = ec_kernels.lanes_as_bytes(out, nbytes)
        return out

    def host_sync_bulk(self, devs, sig: str | None = None) -> list:
        """Materialize SEVERAL device results as ONE metered
        device->host copy event (utils/staging.fetch_recorded): the
        flush-plane contract — a folded launch's outputs leave the
        device together, booked as one ``ec_stage_d2h`` copy.  Lane
        results come back as uint32 lanes (callers view them with
        ec_kernels.lanes_as_bytes); numpy inputs pass through untimed,
        same as host_sync."""
        from ..utils import staging
        return staging.fetch_recorded(devs, sig=sig)

    def _fold_decode_matrix(self, want: Sequence[int],
                            use: Sequence[int]) -> np.ndarray:
        """(len(want), k) combination matrix taking the survivor rows
        ``use`` straight to the wanted rows, in ``want`` order: decode
        rows for data shards, coding-matrix rows times the decode
        matrix for parity shards — one region product per folded
        decode, bytes identical to decode_chunks' two-step path (GF
        arithmetic is exact)."""
        key = ("fold", tuple(want), tuple(use))
        with self._cache_lock:
            hit = self._decode_cache.get(key)
        if hit is not None:
            return hit
        if all(i in use for i in range(self.k)):
            D = np.eye(self.k, dtype=np.uint8)  # use == data rows
        else:
            D = self._get_decode_matrix(use)
        full = np.concatenate(
            [np.eye(self.k, dtype=np.uint8), self.matrix], axis=0)
        R = np.ascontiguousarray(
            gf256.gf_matmul(full[list(want)], D), dtype=np.uint8)
        with self._cache_lock:
            if len(self._decode_cache) > self.DECODE_CACHE_CAP:
                self._decode_cache.pop(next(iter(self._decode_cache)))
            self._decode_cache[key] = R
        return R

    def decode_folded_device(self, want: Sequence[int],
                             avail: Sequence[int], stacked, *,
                             n_shard: int = 1):
        """Device-resident folded decode: ``stacked`` holds the rows of
        the first k survivors in ``avail`` (sorted) order — a host
        ``(k, N)`` uint8 fold, a device lanes array, or a list of
        per-op device lane buffers (the ECBatcher's folds).  Returns
        the reconstructed rows in ``want`` order as ONE region product
        (device lanes on the jax backend, no host sync): the caller
        carves every waiter's slice out of one bulk copy per launch.
        The product runs the runtime-matrix program, so a new survivor
        set costs no compile."""
        avail = [i for i in avail if i < self.chunk_count]
        if len(avail) < self.k:
            raise ErasureCodeError(
                f"cannot decode: only {len(avail)} of {self.k} chunks")
        use = avail[: self.k]
        R = self._fold_decode_matrix(list(want), use)
        if not isinstance(stacked, (list, tuple)) \
                and stacked.shape[0] != self.k:
            stacked = stacked[: self.k]
        return self._matmul_device(R, stacked, n_shard=n_shard,
                                   generic=True)

    def _matmul(self, M: np.ndarray, rows: np.ndarray, *,
                n_shard: int = 1) -> np.ndarray:
        rows = np.ascontiguousarray(rows, dtype=np.uint8)
        return self.host_sync(
            self._matmul_device(M, rows, n_shard=n_shard),
            nbytes=int(rows.shape[-1]))

    def encode_chunks(self, data_chunks: np.ndarray) -> np.ndarray:
        data_chunks = np.ascontiguousarray(data_chunks, dtype=np.uint8)
        if data_chunks.shape[0] != self.k:
            raise ErasureCodeError(
                f"expected {self.k} data chunks, got {data_chunks.shape[0]}")
        return self._matmul(self.matrix, data_chunks)

    def encode_chunks_with_csums(
            self, data_chunks: np.ndarray
    ) -> tuple[np.ndarray, np.ndarray]:
        """(parity, per-chunk CRC32C over data+parity rows): the encode
        (a subclass's own, where it owns the parity math) and the
        native CRC sweep over what it produced, on every backend — the
        same digests the batcher's flushes carve."""
        data_chunks = np.ascontiguousarray(data_chunks, dtype=np.uint8)
        parity = self.encode_chunks(data_chunks)
        return parity, row_csums(data_chunks, parity)

    def _get_decode_matrix(self, available: Sequence[int]) -> np.ndarray:
        key = tuple(available[: self.k])
        with self._cache_lock:
            hit = self._decode_cache.pop(key, None)
            if hit is not None:
                # LRU touch: re-insert at the end so hot signatures
                # survive eviction churn from one-shot ones
                self._decode_cache[key] = hit
                return hit
        hit = gf256.decode_matrix(self.matrix, self.k, list(key))
        with self._cache_lock:
            # signature LRU, ref :513-563
            if len(self._decode_cache) > self.DECODE_CACHE_CAP:
                self._decode_cache.pop(next(iter(self._decode_cache)))
            self._decode_cache[key] = hit
        return hit

    def decode_chunks(self, want: Sequence[int], chunks: ChunkMap, *,
                      n_shard: int = 1) -> ChunkMap:
        avail = sorted(i for i in chunks if i < self.chunk_count)
        if len(avail) < self.k:
            raise ErasureCodeError(
                f"cannot decode: only {len(avail)} of {self.k} chunks")
        use = avail[: self.k]
        L = chunks[use[0]].shape[-1]
        stack = np.stack([np.ascontiguousarray(chunks[i], dtype=np.uint8)
                          for i in use])
        out: ChunkMap = {}
        if self._backend == "jax":
            # ONE decode realization on the device, folded or not: the
            # wanted rows as one product with the runtime-matrix program
            # (decode_folded_device), so no survivor set is a compile
            make = [i for i in want if i >= self.k or i not in chunks]
            if make:
                rows = self.host_sync(
                    self.decode_folded_device(make, use, stack,
                                              n_shard=n_shard),
                    nbytes=int(L))
                out.update(zip(make, rows))
            return {i: out[i] if i in out else chunks[i] for i in want}
        want_data = [i for i in want if i < self.k]
        want_parity = [i for i in want if i >= self.k]
        data_full: np.ndarray | None = None
        if want_data or want_parity:
            missing_data = [i for i in range(self.k) if i not in chunks]
            if not missing_data:
                # all k data rows present: the first k sorted survivors
                # ARE the data rows in order — wanted parity is one
                # direct matmul against the coding matrix below, with no
                # decode-matrix build/inversion
                data_full = stack if want_parity else None
            else:
                D = self._get_decode_matrix(use)
                if want_parity or len(missing_data) > 1:
                    data_full = self._matmul(D, stack, n_shard=n_shard)
                else:
                    # single-row recovery: multiply only the needed rows
                    data_full = np.zeros((self.k, L), dtype=np.uint8)
                    sub = self._matmul(D[want_data], stack,
                                       n_shard=n_shard)
                    for r, i in enumerate(want_data):
                        data_full[i] = sub[r]
            for i in want_data:
                out[i] = chunks[i] if i in chunks else data_full[i]
        if want_parity:
            parity = self._matmul(self.matrix[[i - self.k for i in want_parity]],
                                  data_full, n_shard=n_shard)
            for r, i in enumerate(want_parity):
                out[i] = parity[r]
        return out

    # -- parity delta (RMW write path; ref ErasureCodeJerasure.h:115-122,
    # ECUtil.cc:519-566 encode_parity_delta) ------------------------------
    def apply_delta(self, delta: np.ndarray, data_shard: int,
                    parity_chunks: ChunkMap) -> None:
        """The plugin interface's per-shard fold, on the host.  The OSD
        does not come here: by linearity an overwrite's parity deltas
        are ``encode_chunks`` of its delta stripe, and that is where
        its multiply runs on every back-end.  On a jax pool this is a
        host fall-through: raised on an accelerator, counted on the
        CPU platform."""
        if not 0 <= data_shard < self.k:
            raise ErasureCodeError(f"not a data shard: {data_shard}")
        if self._backend == "jax":
            try:
                raise ErasureCodeError(
                    "a parity delta multiplied on the host of a jax pool")
            except ErasureCodeError:
                staging.fallthrough("ec_delta_host_fallback")
        delta = np.ascontiguousarray(delta, dtype=np.uint8)
        for pid, buf in parity_chunks.items():
            if not self.k <= pid < self.chunk_count:
                raise ErasureCodeError(f"not a parity shard: {pid}")
            coef = int(self.matrix[pid - self.k, data_shard])
            if self._backend == "native":
                native.region_mac(buf, delta, coef)
            else:
                buf ^= gf256.gf_mul(np.uint8(coef), delta)
