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


def _apply_bits(op, v, jnp):
    """``op . v`` over GF(2), elementwise on uint32 words: ``op[j]``
    where bit j of the word is set, all XORed."""
    acc = None
    for j in range(32):
        if not op[j]:
            continue
        term = jnp.where((v & jnp.uint32(1 << j)) != 0,
                         jnp.uint32(op[j]), jnp.uint32(0))
        acc = term if acc is None else acc ^ term
    return acc


@functools.lru_cache(maxsize=1)
def _leaf_bits() -> tuple:
    """x^32 mod P as an operator on a little-endian word: the raw crc
    of the word with bit j alone, for each j."""
    return tuple(_raw(int(1 << j).to_bytes(4, "little"))
                 for j in range(32))


class CrcPlan:
    """Precomputed constants for device CRC32C over fixed-length
    chunks (``nbytes`` a multiple of 4): one program, elementwise
    uint32 math and contiguous halvings, no strided slice and no
    gather.

    The words of a chunk, little-endian, and the CRC register are the
    same representation of a polynomial over GF(2) (reflected: bit j
    is the coefficient of x^(31-j)); the raw CRC of a word w is
    w * x^32 mod P and ``_zero_operator(n)`` multiplies by x^(8n) mod
    P.  Multiplications commute, so the tree runs on the WORDS and the
    x^32 (``leaf``) is applied once, to the one word a chunk has been
    folded into.  A level folds the array's first half onto its second:
    ``M^(bytes of half) . first ^ second``, two contiguous slices.  The
    words are laid out ``(rows of LANES words, LANES)``: the levels halve
    the rows until one is left, then the lanes; each level multiplies
    half of what the last one left, so a chunk's every word is
    multiplied once (32 select-xors) in all."""

    #: words in the minor axis of the fold (a TPU vector's lanes)
    LANES = 128

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
        self.lanes = min(p, self.LANES)
        self.leaf_bits = _leaf_bits()
        # the operator of each level, first level first: the first half
        # moves over the bytes of the second
        self.level_ops = []
        half = p // 2
        while half >= 1:
            self.level_ops.append(
                [int(v) for v in _zero_operator(4 * half)])
            half //= 2
        # affine fix-up: raw crc is linear, the STANDARD crc adds the
        # init/final xor.  Processing data from init state I gives
        # M^n·I ^ raw(data), so
        #   crc_std(data) = raw(data) ^ M^n·0xFFFFFFFF ^ 0xFFFFFFFF —
        # one constant; every tree stage stays purely linear.
        self.final_xor = _crc32c_of_zeros(nbytes)

    # ------------------------------------------------------ device graph
    def device_fn(self):
        """jax fn: lanes (..., n_words) uint32 (little-endian words of
        the chunk) -> (...,) uint32 standard CRC32C per chunk."""
        import jax.numpy as jnp

        def apply_op(op, v):
            return _apply_bits(op, v, jnp)

        pad = self.padded_words - self.n_words
        lanes = self.lanes

        def fn(words):
            if pad:
                shape = words.shape[:-1] + (pad,)
                words = jnp.concatenate(
                    [jnp.zeros(shape, jnp.uint32), words], axis=-1)
            batch = words.shape[:-1]
            cur = words.reshape(batch + (-1, lanes))
            ops = iter(self.level_ops)
            while cur.shape[-2] > 1:           # halve the rows
                h = cur.shape[-2] // 2
                cur = apply_op(next(ops), cur[..., :h, :]) \
                    ^ cur[..., h:, :]
            cur = cur[..., 0, :]
            while cur.shape[-1] > 1:           # then the lanes
                h = cur.shape[-1] // 2
                cur = apply_op(next(ops), cur[..., :h]) ^ cur[..., h:]
            return apply_op(self.leaf_bits, cur[..., 0]) \
                ^ jnp.uint32(self.final_xor)

        return fn

    # ------------------------------------------------------- CPU oracle
    def reference(self, chunk: bytes) -> int:
        return crc32c_ref(chunk)


# ------------------------------------------------------- the TPU's kernel
#: rows of words (128 lanes each) one grid step of the kernel folds
CRC_BLOCK_ROWS = 512


def crc32c_rows_pallas(n_rows: int, nbytes: int, *,
                       interpret: bool = False):
    """``u32[n_rows * nbytes/512, 128] -> u32[n_rows]``, the standard
    CRC32C of each of ``n_rows`` rows of ``nbytes/4`` words, as ONE
    Pallas kernel (``crc32c_lanes_<nbytes>``): what a TPU runs for a
    scrub's verify.  The rows come as the host lays them out anyway,
    128 words to a line (a ``[n_rows, nbytes/4]`` operand costs the
    device a relayout of a third of the kernel's time: my chip run, PR
    40).  ``nbytes`` is a power of two of at least 4096 (eight lines).

    The algebra is ``CrcPlan``'s.  A row of the batch is ``S`` rows of
    128 words; a grid step takes ``CRC_BLOCK_ROWS`` of them into VMEM
    and folds first half onto second until eight are left (whole
    vectors all the way), and the eight-row partial joins an
    accumulator that the next step first moves over its block
    (``M^(block bytes)``): Horner over the blocks.  The last step folds
    the accumulator's eight rows and then its 128 lanes with rolls, so
    that the row's word ends at ``[7, 127]``, multiplies it by x^32 and
    adds the length's constant."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    words = nbytes // 4
    if nbytes < 4096 or words & (words - 1):
        raise ValueError("the kernel takes power-of-two rows of at "
                         "least 4096 bytes")
    S = words // 128
    bs = min(S, CRC_BLOCK_ROWS)
    G = S // bs
    ints = lambda op: [int(v) for v in op]  # noqa: E731
    block_ops = []
    h = bs // 2
    while h >= 8:
        block_ops.append(ints(_zero_operator(h * 512)))
        h //= 2
    op_block = ints(_zero_operator(bs * 512))
    sub_ops = [(h, ints(_zero_operator(h * 512))) for h in (4, 2, 1)]
    lane_ops = [(h, ints(_zero_operator(h * 4)))
                for h in (64, 32, 16, 8, 4, 2, 1)]
    leaf = _leaf_bits()
    final_xor = _crc32c_of_zeros(nbytes)
    roll = jnp.roll if interpret else pltpu.roll

    def kernel(x_ref, o_ref, acc_ref):
        g = pl.program_id(1)

        @pl.when(g == 0)
        def _():
            acc_ref[...] = jnp.zeros_like(acc_ref)

        cur = x_ref[...]
        for op in block_ops:
            half = cur.shape[0] // 2
            cur = _apply_bits(op, cur[:half], jnp) ^ cur[half:]
        if G > 1:
            cur = _apply_bits(op_block, acc_ref[...], jnp) ^ cur
        acc_ref[...] = cur

        @pl.when(g == G - 1)
        def _():
            v = acc_ref[...]
            for half, op in sub_ops:
                v = roll(_apply_bits(op, v, jnp), half, 0) ^ v
            for half, op in lane_ops:
                v = roll(_apply_bits(op, v, jnp), half, 1) ^ v
            o_ref[...] = _apply_bits(leaf, v, jnp) ^ jnp.uint32(final_xor)

    call = pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((n_rows * 8, 128), jnp.uint32),
        grid=(n_rows, G),
        in_specs=[pl.BlockSpec((bs, 128), lambda r, g: (r * G + g, 0))],
        out_specs=pl.BlockSpec((8, 128), lambda r, g: (r, 0)),
        scratch_shapes=[pltpu.VMEM((8, 128), jnp.uint32)],
        interpret=interpret,
        name=f"crc32c_lanes_{nbytes}",
    )

    def fn(lines):
        return call(lines).reshape(n_rows, 8, 128)[:, 7, 127]

    return fn
