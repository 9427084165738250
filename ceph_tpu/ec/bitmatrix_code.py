"""GF(2) bit-matrix erasure codes — the liberation-family technique path.

The capability of jerasure's packed-word bit-matrix techniques
(/root/reference/src/erasure-code/jerasure/ErasureCodeJerasure.h:135-336:
liberation, blaum_roth, liber8tion — RAID-6 codes whose schedules are
pure XOR over w sub-stripes per chunk).  The reference's actual
matrices live in the absent jerasure submodule.  Two of the three
techniques here ARE the published constructions: blaum_roth (ring R_p
companion-matrix powers — blaum_roth_bitmatrix) and liberation
(Plank's FAST'08 minimum-density placement — liberation_bitmatrix,
verified MDS + minimum-density at construction).  liber8tion (w=8)
remains an own MDS construction with the published parameter envelope:
the exact published bit placements were produced by large-scale
search and cannot be re-derived blind (bounded deterministic and
seeded searches over permutation-plus-extra-bit blocks at w=8 found
no minimum-density solution here), so it uses the dense-but-correct
companion-matrix RAID-6 pair and says so.  All share the execution
shape: a (w·m, w·k) GF(2) matrix applied as XORs of packet rows —
exactly the formulation the MXU bitmatrix kernel executes
(ops/ec_kernels.py:88).

Packetization is GRANULE-LOCAL: the byte stream is processed in
independent granules of w·SIMD_ALIGN bytes, each split into w packets.
Any granule-aligned sub-range therefore encodes identically to the same
bytes inside a larger call — the property the OSD's row-ranged encode
relies on (a whole-object encode and a later row rmw must agree).
"""

from __future__ import annotations

import threading
import time

import numpy as np

from .interface import (ChunkMap, ErasureCode, ErasureCodeError, Flags,
                        SIMD_ALIGN)

# primitive polynomials over GF(2) for the word sizes the techniques use
_POLYS = {4: 0x13, 5: 0x25, 6: 0x43, 7: 0x89, 8: 0x11D}


def gfw_mul(a: int, b: int, w: int) -> int:
    """Carry-less multiply mod the primitive polynomial of GF(2^w)."""
    poly = _POLYS[w]
    r = 0
    while b:
        if b & 1:
            r ^= a
        b >>= 1
        a <<= 1
        if a >> w:
            a ^= poly
    return r


def element_bitmatrix(e: int, w: int) -> np.ndarray:
    """The w x w GF(2) matrix of multiply-by-e in GF(2^w): column j is
    the bit vector of e * x^j (the companion-matrix representation that
    turns field math into XOR schedules)."""
    M = np.zeros((w, w), dtype=np.uint8)
    for j in range(w):
        v = gfw_mul(e, 1 << j, w)
        for i in range(w):
            M[i, j] = (v >> i) & 1
    return M


def blaum_roth_bitmatrix(k: int, w: int) -> np.ndarray:
    """The PUBLISHED Blaum-Roth RAID-6 construction (Blaum & Roth,
    lowest-density MDS codes over the ring R_p = GF(2)[x]/M_p(x) with
    M_p = 1 + x + ... + x^(p-1), p = w+1 prime — the same matrix
    jerasure's blaum_roth technique builds): symbols are polynomials of
    degree < w; P = sum(d_i), Q = sum(x^i * d_i).  Multiply-by-x in the
    quotient basis {1..x^(w-1)} is the companion matrix whose last
    column is ALL-ONES (x^w = x^(p-1) == sum of all lower powers mod
    M_p); block i of Q is its i-th power.  MDS for k <= w because x has
    order p and x^i + x^j is a unit in R_p for i != j (mod p)."""
    p = w + 1
    if any(p % d == 0 for d in range(2, p)) or p < 3:
        raise ErasureCodeError(f"blaum_roth needs w+1 prime (w={w})")
    if k > w:
        raise ErasureCodeError(f"blaum_roth: k={k} > w={w}")
    # companion matrix of multiply-by-x in R_p
    C = np.zeros((w, w), dtype=np.uint8)
    for j in range(w - 1):
        C[j + 1, j] = 1
    C[:, w - 1] = 1  # x^w reduces to 1 + x + ... + x^(w-1)
    B = np.zeros((2 * w, k * w), dtype=np.uint8)
    ident = np.eye(w, dtype=np.uint8)
    Ci = ident
    for i in range(k):
        B[:w, i * w:(i + 1) * w] = ident
        B[w:, i * w:(i + 1) * w] = Ci
        Ci = (C @ Ci) % 2
    _assert_mds(B, k, w)
    return B


def liberation_bitmatrix(k: int, w: int) -> np.ndarray:
    """The PUBLISHED Liberation construction (Plank, FAST'08 "The
    RAID-6 Liberation Codes"; jerasure's liberation technique): w
    prime, k <= w, m = 2.  P blocks are identities; Q block X_0 = I
    and for i >= 1, X_i is the cyclic shift sigma^i (one at
    (r, (r+i) mod w)) plus ONE extra bit at row y = i(w-1)/2 mod w,
    column (y + i - 1) mod w.  The Q drive then carries exactly
    kw + k - 1 ones — the minimum-density bound the paper proves —
    and the code is MDS; both properties are asserted here at
    construction so a placement regression can never ship bytes."""
    if w < 2 or any(w % d == 0 for d in range(2, w)):
        raise ErasureCodeError(f"liberation needs prime w (got {w})")
    if k > w:
        raise ErasureCodeError(f"liberation: k={k} > w={w}")
    B = np.zeros((2 * w, k * w), dtype=np.uint8)
    ident = np.eye(w, dtype=np.uint8)
    for i in range(k):
        B[:w, i * w:(i + 1) * w] = ident
        X = np.zeros((w, w), dtype=np.uint8)
        for r in range(w):
            X[r, (r + i) % w] = 1
        if i > 0:
            y = (i * (w - 1) // 2) % w
            X[y, (y + i - 1) % w] ^= 1
        B[w:, i * w:(i + 1) * w] = X
    if int(B[w:].sum()) != k * w + k - 1:
        raise ErasureCodeError("liberation density regression")
    _assert_mds(B, k, w)
    return B


def _assert_mds(B: np.ndarray, k: int, w: int) -> None:
    """Every 2-erasure pattern of the systematic (k+2, k) code must
    decode (construction-time guard for the bit-matrix families)."""
    import itertools as _it
    full = np.concatenate([np.eye(k * w, dtype=np.uint8), B])
    for gone in _it.combinations(range(k + 2), 2):
        keep = [i for i in range(k + 2) if i not in gone][:k]
        rows = np.concatenate([full[i * w:(i + 1) * w] for i in keep])
        _gf2_invert(rows)  # raises if singular


def raid6_bitmatrix(k: int, w: int) -> np.ndarray:
    """(2w, kw) bit-matrix of the RAID-6 pair over GF(2^w):
    P = XOR of all data, Q = sum alpha^i * d_i  (alpha = x, primitive).
    MDS for k <= 2^w - 1: every 2x2 minor of [[1..1],[a^i]] inverts."""
    if k > (1 << w) - 1:
        raise ErasureCodeError(f"k={k} > {(1 << w) - 1} for w={w}")
    B = np.zeros((2 * w, k * w), dtype=np.uint8)
    ident = np.eye(w, dtype=np.uint8)
    alpha_i = 1
    for i in range(k):
        B[:w, i * w:(i + 1) * w] = ident
        B[w:, i * w:(i + 1) * w] = element_bitmatrix(alpha_i, w)
        alpha_i = gfw_mul(alpha_i, 2, w)
    _assert_mds(B, k, w)
    return B


def _gf2_invert(M: np.ndarray) -> np.ndarray:
    """Invert a square GF(2) matrix (Gauss-Jordan over bits)."""
    n = M.shape[0]
    A = np.concatenate([M.astype(np.uint8) % 2,
                        np.eye(n, dtype=np.uint8)], axis=1)
    for col in range(n):
        piv = next((r for r in range(col, n) if A[r, col]), None)
        if piv is None:
            raise ErasureCodeError("bitmatrix not invertible")
        if piv != col:
            A[[col, piv]] = A[[piv, col]]
        rows = [r for r in range(n) if r != col and A[r, col]]
        A[rows] ^= A[col]
    return A[:, n:]


class BitMatrixErasureCode(ErasureCode):
    """Systematic GF(2) bit-matrix code executed as XORs of packet rows.

    Subclasses set self.w and self.bitmatrix ((w*m, w*k)) in
    _init_from_profile.  Chunks are processed in granules of
    w*SIMD_ALIGN bytes; every chunk length must be granule-aligned
    (get_chunk_size/minimum granularity enforce it)."""

    w: int
    bitmatrix: np.ndarray

    def _init_bitmatrix(self) -> None:
        # backend resolution mirrors the matrix codes: numpy/native run
        # the vectorized host XOR path; the jax backend routes packet
        # rows through the SHARED scheduled-XOR device kernel
        # (ops/ec_kernels.ScheduledXor — the same bitxor executor the
        # GF(2^8) auto-tuner races), so the liberation family touches
        # the device path instead of staying numpy-only
        from .matrix_code import _pick_backend
        self._backend = _pick_backend(self.profile.get("backend", "auto"))
        self._granule = self.w * SIMD_ALIGN
        self._decode_cache: dict[tuple, np.ndarray] = {}
        # matrix-bytes -> ScheduledXor, LRU-bounded; built lazily so
        # non-jax deployments never pay the jax import
        self._xor_ops: dict[bytes, object] = {}
        self._xor_lock = threading.Lock()
        self._xor_shapes_seen: set[tuple] = set()
        # latched on the first device-path failure: a persistently
        # broken path must not be re-attempted (and re-swallowed) per
        # apply, and the fall-through must be VISIBLE, not silent —
        # booked on the ec_kernels registry (ec_bitxor_host_fallback)
        self._xor_device_broken = False

    def get_flags(self) -> Flags:
        # no PARITY_DELTA: a parity byte depends on data bytes at OTHER
        # offsets (cross-packet mixing), so the view-positional delta
        # contract of the matrix codes does not hold — overwrites take
        # the rmw path
        return Flags.ZERO_PADDING

    def get_minimum_granularity(self) -> int:
        return self._granule

    def get_chunk_size(self, stripe_width: int) -> int:
        per = -(-stripe_width // self.k)
        return -(-per // self._granule) * self._granule

    # -- packet algebra ----------------------------------------------------
    def _rows(self, chunks: np.ndarray) -> np.ndarray:
        """(n, L) chunks -> (G, n*w, S) packet rows per granule."""
        n, L = chunks.shape
        if L % self._granule:
            raise ErasureCodeError(
                f"chunk length {L} not a multiple of the {self._granule}"
                f"-byte granule (w={self.w})")
        g = L // self._granule
        return chunks.reshape(n, g, self.w, SIMD_ALIGN) \
            .transpose(1, 0, 2, 3).reshape(g, n * self.w, SIMD_ALIGN)

    def _unrows(self, rows: np.ndarray, n: int) -> np.ndarray:
        g = rows.shape[0]
        return rows.reshape(g, n, self.w, SIMD_ALIGN) \
            .transpose(1, 0, 2, 3).reshape(n, g * self._granule)

    def _xor_kernel(self, B: np.ndarray):
        """The shared scheduled-XOR device op for bit-matrix ``B``
        (LRU per matrix: the encode drive plus the decode combination
        matrices of hot erasure signatures)."""
        # NOT bytes(B.shape): bit-matrix dims reach 256+ (liber8tion
        # k=32 is (16, 256)) and bytes() raises there
        key = B.tobytes() + repr(B.shape).encode()
        with self._xor_lock:
            op = self._xor_ops.pop(key, None)
            if op is not None:
                self._xor_ops[key] = op  # LRU touch
                return op
        from ..ops.ec_kernels import ScheduledXor
        op = ScheduledXor(B)
        with self._xor_lock:
            hit = self._xor_ops.pop(key, None)
            if hit is not None:
                op = hit
            elif len(self._xor_ops) > 64:
                self._xor_ops.pop(next(iter(self._xor_ops)))
            self._xor_ops[key] = op
        return op

    def _apply_bits_device(self, B: np.ndarray,
                           rows: np.ndarray) -> np.ndarray:
        """jax-backend packet apply: granule-local (G, nr, S) rows
        flatten to (nr, G*S) plane rows — XOR is positionwise, so the
        re-layout is exact — and ONE scheduled-XOR launch produces
        every output packet row.  Launches land in the kernel
        profiler under ``bitxor/RxC/L...`` (first shape = compile)."""
        from ..ops.ec_kernels import bytes_as_lanes, lanes_as_bytes
        from ..utils.perf import kernel_profiler
        g, nr, s = rows.shape
        flat = np.ascontiguousarray(
            rows.transpose(1, 0, 2).reshape(nr, g * s))
        op = self._xor_kernel(B)
        sig = f"bitxor/{B.shape[0]}x{B.shape[1]}/L{g * s}"
        # bytes are viewed as lanes on the host, either side of the
        # copies: the device program is lanes in, lanes out
        x32 = bytes_as_lanes(flat, op.block)
        t0 = time.perf_counter()
        dev = op.encode_lanes(x32).block_until_ready()
        dt = time.perf_counter() - t0
        shape_key = (sig, flat.shape)
        with self._xor_lock:
            first = shape_key not in self._xor_shapes_seen
            if first:
                self._xor_shapes_seen.add(shape_key)
        kernel_profiler().note("compile" if first else "device",
                               sig, dt)
        t0 = time.perf_counter()
        out = np.asarray(dev)
        kernel_profiler().note("sync", sig, time.perf_counter() - t0)
        out = lanes_as_bytes(out, g * s)
        return out.reshape(B.shape[0], g, s).transpose(1, 0, 2)

    #: below this many source bytes an apply stays on the host numpy
    #: path even on the jax backend: a sub-ms vectorized XOR beats a
    #: device launch + sync, and the bound also caps how many shapes
    #: ever pay the (~0.1-0.2s on CPU-jax, measured) one-time jit
    #: compile on the op thread
    JAX_APPLY_MIN_BYTES = 1 << 16

    def _note_device_broken(self) -> None:
        """Called from the ``except`` around a failed device apply:
        re-raises off the CPU platform; on it, books the device->host
        fall-through where operators look (``ec_bitxor_host_fallback``
        on the ec_kernels registry) and latches the path off for this
        codec."""
        from ..utils import staging
        staging.fallthrough("ec_bitxor_host_fallback")
        self._xor_device_broken = True

    def _apply_bits(self, B: np.ndarray, rows: np.ndarray) -> np.ndarray:
        """out[:, r] = XOR of rows[:, c] where B[r, c] — per granule."""
        if (self._backend == "jax" and rows.shape[0] > 0
                and not self._xor_device_broken
                and rows.nbytes >= self.JAX_APPLY_MIN_BYTES):
            try:
                return self._apply_bits_device(B, rows)
            except Exception:  # noqa: BLE001 - host path fall-through
                self._note_device_broken()
        g, _nr, s = rows.shape
        out = np.zeros((g, B.shape[0], s), dtype=np.uint8)
        for r in range(B.shape[0]):
            idx = np.nonzero(B[r])[0]
            if idx.size:
                out[:, r] = np.bitwise_xor.reduce(rows[:, idx], axis=1)
        return out

    # -- encode/decode -----------------------------------------------------
    def encode_chunks(self, data_chunks: np.ndarray) -> np.ndarray:
        rows = self._rows(np.ascontiguousarray(data_chunks,
                                               dtype=np.uint8))
        parity = self._apply_bits(self.bitmatrix, rows)
        return self._unrows(parity, self.m)

    def _decode_combo(self, want: tuple, avail: tuple) -> np.ndarray:
        """Combination matrix mapping avail shards' packet rows to the
        wanted shards' packet rows (cached per erasure signature)."""
        key = (want, avail)
        C = self._decode_cache.get(key)
        if C is not None:
            return C
        w, k = self.w, self.k
        full = np.concatenate([np.eye(k * w, dtype=np.uint8),
                               self.bitmatrix], axis=0)
        S = np.concatenate([full[s * w:(s + 1) * w] for s in avail])
        R = _gf2_invert(S)
        Wm = np.concatenate([full[s * w:(s + 1) * w] for s in want])
        C = (Wm.astype(np.uint8) @ R.astype(np.uint8)) % 2
        if len(self._decode_cache) > 64:
            self._decode_cache.pop(next(iter(self._decode_cache)))
        self._decode_cache[key] = C
        return C

    def decode_chunks(self, want, chunks: ChunkMap) -> ChunkMap:
        avail = tuple(sorted(chunks))[: self.k]
        if len(avail) < self.k:
            raise ErasureCodeError(
                f"need {self.k} shards, have {sorted(chunks)}")
        wanted = tuple(sorted(want))
        C = self._decode_combo(wanted, avail)
        data = np.stack([np.asarray(chunks[s], dtype=np.uint8)
                         for s in avail])
        rows = self._rows(data)
        out_rows = self._apply_bits(C, rows)
        out = self._unrows(out_rows, len(wanted))
        return {s: out[i] for i, s in enumerate(wanted)}
