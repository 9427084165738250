"""Device-native CRC32C: checksums computed in the SAME XLA pass as
parity (the Checksummer-on-the-batch north star; ref
src/common/Checksummer.h:13 crc32c, BlueStore per-blob csum
src/os/bluestore/BlueStore.cc:6080-6086).

CRC is bit-serial in its textbook form — useless on a vector unit.  But
CRC32C is GF(2)-LINEAR in the message: crc(A xor B) = crc(A) xor crc(B)
(for the raw, init-0 variant), and appending k zero bytes multiplies
the crc state by a fixed 32x32 GF(2) matrix M^k (zlib's crc32_combine
math).  That turns the whole computation into a balanced binary tree:

  leaf:    crc of each 4-byte word = xor of 32 precomputed constants
           selected by the word's bits (an affine map; no tables, no
           gathers — 32 select+xor lanes on the VPU);
  combine: crc(L || R) = apply(M^{|R|}, crc(L)) xor crc(R) xor C_lvl,
           with one precomputed matrix + affine constant per LEVEL
           (all power-of-two lengths, so log2(n) constants total).

Everything is elementwise uint32 math over lanes — fully batched
across chunks (the scrub fold's device function, ec/verify.py).  The affine
constants absorb the init/final-xor convention, so the result is
byte-exact standard CRC32C (verified against the native/CPU
implementation in tests and by the bench digest gate).
"""

from __future__ import annotations

import functools

import numpy as np

from .native import crc32c as _native_crc32c

_POLY = 0x82F63B78  # Castagnoli, reflected


# ------------------------------------------------------------ host math
def _crc_table() -> np.ndarray:
    tab = np.zeros(256, dtype=np.uint64)
    for i in range(256):
        c = i
        for _ in range(8):
            c = (c >> 1) ^ (_POLY if c & 1 else 0)
        tab[i] = c
    return tab


_TAB = _crc_table()


def crc32c_ref(data: bytes, crc: int = 0) -> int:
    """Reference CRC32C (matches ops.native.crc32c)."""
    c = crc ^ 0xFFFFFFFF
    for b in data:
        c = (c >> 8) ^ int(_TAB[(c ^ b) & 0xFF])
    return (c ^ 0xFFFFFFFF) & 0xFFFFFFFF


def _raw(data: bytes) -> int:
    """Init-0, no-final-xor crc — the LINEAR functional."""
    c = 0
    for b in data:
        c = (c >> 8) ^ int(_TAB[(c ^ b) & 0xFF])
    return c & 0xFFFFFFFF


def _gf2_matmul(a: np.ndarray, b: np.ndarray) -> np.ndarray:
    """Product of 32x32 GF(2) matrices, each stored as 32 uint32
    column-masks (zlib gf2_matrix_square convention: row i of the
    operator is a[i], applying to vector v = xor of a[i] for set bits
    of v)."""
    out = np.zeros(32, dtype=np.uint64)
    for i in range(32):
        v = int(b[i])
        acc = 0
        for j in range(32):
            if v >> j & 1:
                acc ^= int(a[j])
        out[i] = acc
    return out


def _zero_operator(nbytes: int) -> np.ndarray:
    """M^{nbytes}: the matrix appending nbytes zero bytes applies to a
    raw crc state (zlib crc32_combine's op, built by squaring)."""
    # one-zero-BIT operator on the reflected crc state
    odd = np.zeros(32, dtype=np.uint64)
    odd[0] = _POLY
    for i in range(1, 32):
        odd[i] = 1 << (i - 1)
    even = _gf2_matmul(odd, odd)
    op4 = _gf2_matmul(even, even)      # 4 bits
    op8 = _gf2_matmul(op4, op4)        # one byte
    out = np.zeros(32, dtype=np.uint64)
    for i in range(32):
        out[i] = 1 << i                # identity
    cur = op8
    n = nbytes
    while n:
        if n & 1:
            out = _gf2_matmul(cur, out)
        cur = _gf2_matmul(cur, cur)
        n >>= 1
    return out


#: M^{2^j} ladder (j-th entry appends 2^j zero bytes), built once by
#: repeated squaring; 48 rungs cover pads past 256 TiB
_POW2_ZERO_OPS: list[np.ndarray] = []


def _pow2_zero_ops() -> list[np.ndarray]:
    if not _POW2_ZERO_OPS:
        ops = [_zero_operator(1)]
        for _ in range(47):
            ops.append(_gf2_matmul(ops[-1], ops[-1]))
        _POW2_ZERO_OPS.extend(ops)
    return _POW2_ZERO_OPS


def _shift_zeros(v: int, nzeros: int) -> int:
    """M^nzeros · v: a raw crc state after nzeros more zero bytes,
    popcount(nzeros) matrix-VECTOR products through the shared pow2
    operator ladder."""
    if nzeros > 0:
        ops = _pow2_zero_ops()
        j = 0
        while nzeros:
            if nzeros & 1:
                op, acc = ops[j], 0
                for b in range(32):
                    if v >> b & 1:
                        acc ^= int(op[b])
                v = acc
            nzeros >>= 1
            j += 1
    return v


def crc32c_extend_zeros(crc: int, nzeros: int) -> int:
    """Standard CRC32C of `data || 0^nzeros` given crc32c(data).

    Appending zero bytes injects no message bits, so the raw state
    evolves purely linearly: raw' = M^nzeros · raw.  Converting the
    standard crc to raw (xor 0xFFFFFFFF twice around the operator)
    gives the folded-scrub identity — a stored whole-object digest can
    be re-expressed as the digest of the object padded to any bucket
    length without touching the bytes.

    No per-pad-length matrix builds, so a full-store scrub's ragged pad
    counts cost microseconds each instead of a fresh squaring chain per
    distinct length."""
    v = (crc ^ 0xFFFFFFFF) & 0xFFFFFFFF
    return (_shift_zeros(v, nzeros) ^ 0xFFFFFFFF) & 0xFFFFFFFF


@functools.lru_cache(maxsize=1024)
def _crc32c_of_zeros(nbytes: int) -> int:
    return crc32c_extend_zeros(0, nbytes)  # crc32c(b"") == 0


def crc32c_overwrite(crc: int, length: int,
                     deltas: list) -> tuple[int, int] | None:
    """(CRC32C, length) of a stream after extents of it were
    overwritten, from its CRC32C and length before: the stored digest
    follows a write at the cost of the write, not of the stream.

    ``deltas`` is ``[(offset, old ^ new), ...]``, the old bytes read as
    zeros past the old end (what a store's zero fill leaves there).
    CRC32C is affine over GF(2): streams S, S' of one length L have
    crc(S') = crc(S) ^ raw(S ^ S'), with ``raw`` the init-0 register;
    leading zeros leave it at 0 and t trailing zeros multiply it by
    M^t, so an extent's share is M^(L - offset - n) · raw(delta) with
    raw(delta) = crc(delta) ^ crc(0^n).  A stream that grows is first
    extended by zeros.  One native sweep of each delta is all that
    reads bytes.

    None where the extents overlap each other, one is empty (a store
    may or may not grow a stream for it) or starts before 0: the
    caller sweeps the stream."""
    ext = sorted(deltas, key=lambda e: e[0])
    end = 0
    for off, delta in ext:
        if off < end or not len(delta):
            return None
        end = off + len(delta)
    new_len = max(length, end)
    crc = crc32c_extend_zeros(crc, new_len - length)
    for off, delta in ext:
        n = len(delta)
        raw = _native_crc32c(delta) ^ _crc32c_of_zeros(n)
        if raw:
            crc ^= _shift_zeros(raw, new_len - off - n)
    return crc, new_len


class CrcPlan:
    """Precomputed constants for device CRC32C over fixed-length
    chunks (nbytes = n_words * 4, n_words a power of two)."""

    def __init__(self, nbytes: int):
        if nbytes % 4 or nbytes < 4:
            raise ValueError("chunk length must be a multiple of 4")
        n_words = nbytes // 4
        self.nbytes = nbytes
        self.n_words = n_words
        # pad the word count up to a power of two WITH A ZERO PREFIX:
        # the raw (init-0) crc of leading zeros is zero and contributes
        # nothing through the combine, so raw(0^p || data) == raw(data)
        # — arbitrary chunk lengths ride the same balanced tree
        p = 1
        while p < n_words:
            p *= 2
        self.padded_words = p
        # leaf: raw crc of a single little-endian word, bit-decomposed
        self.leaf_bits = np.array(
            [_raw(int(1 << j).to_bytes(4, "little")) for j in range(32)],
            dtype=np.uint32)
        # per-level combine operator: level l merges blocks of
        # 4*2^l bytes, so the left half shifts by that many zero bytes
        self.level_ops = []
        blk = 4
        while blk < 4 * p:
            self.level_ops.append(
                _zero_operator(blk).astype(np.uint32))
            blk *= 2
        # affine fix-up: raw crc is linear, the STANDARD crc adds the
        # init/final xor.  Processing data from init state I gives
        # M^n·I ^ raw(data), so
        #   crc_std(data) = raw(data) ^ M^n·0xFFFFFFFF ^ 0xFFFFFFFF —
        # one constant; every tree stage stays purely linear.
        op_n = _zero_operator(nbytes)
        init_evolved = 0
        for j in range(32):
            init_evolved ^= int(op_n[j])  # apply to the all-ones state
        self.final_xor = np.uint32(
            (init_evolved ^ 0xFFFFFFFF) & 0xFFFFFFFF)

    # ------------------------------------------------------ device graph
    def device_fn(self):
        """jax fn: lanes (..., n_words) uint32 (little-endian words of
        the chunk) -> (...,) uint32 standard CRC32C per chunk."""
        import jax.numpy as jnp

        leaf_bits = jnp.asarray(self.leaf_bits)
        level_ops = [jnp.asarray(op) for op in self.level_ops]
        final_xor = jnp.uint32(self.final_xor)

        def apply_op(op, v):
            # v: (...,) uint32 state; op: (32,) uint32 rows
            acc = jnp.zeros_like(v)
            for j in range(32):
                bit = (v >> j) & jnp.uint32(1)
                acc = acc ^ (bit * op[j])
            return acc

        pad = self.padded_words - self.n_words

        def fn(lanes):
            if pad:
                shape = lanes.shape[:-1] + (pad,)
                lanes = jnp.concatenate(
                    [jnp.zeros(shape, jnp.uint32), lanes], axis=-1)
            # leaf crcs: affine map per word
            acc = jnp.zeros_like(lanes)
            for j in range(32):
                bit = (lanes >> j) & jnp.uint32(1)
                acc = acc ^ (bit * leaf_bits[j])
            # balanced tree combine
            cur = acc
            for op in level_ops:
                left = cur[..., 0::2]
                right = cur[..., 1::2]
                cur = apply_op(op, left) ^ right
            return cur[..., 0] ^ final_xor

        return fn

    # ------------------------------------------------------- CPU oracle
    def reference(self, chunk: bytes) -> int:
        return crc32c_ref(chunk)
