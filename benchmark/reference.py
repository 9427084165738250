"""Plain reference of what an EC pool has to store and return.

GF(2^8) Reed-Solomon (polynomial 0x11d), the systematic matrix that
jerasure's ``reed_sol_van`` derives from the extended Vandermonde
matrix, and the RAID-0 layout of a whole-object write over k shard
streams in ``stripe_unit`` cells.  Written for the benchmark in plain
numpy: it imports nothing of the program and takes nothing the program
made.  Speed does not matter here; it runs outside the window.
"""

from __future__ import annotations

import functools

import numpy as np

POLY = 0x11D


@functools.lru_cache(maxsize=None)
def _tables() -> tuple[np.ndarray, np.ndarray]:
    """(exp, log) of the generator 2."""
    exp = np.zeros(512, np.int32)
    log = np.zeros(256, np.int32)
    x = 1
    for i in range(255):
        exp[i] = x
        log[x] = i
        x <<= 1
        if x & 0x100:
            x ^= POLY
    exp[255:510] = exp[:255]
    return exp, log


@functools.lru_cache(maxsize=None)
def mul_table() -> np.ndarray:
    """(256, 256) uint8: a * b."""
    exp, log = _tables()
    a = np.arange(256)
    t = exp[(log[a][:, None] + log[a][None, :])].astype(np.uint8)
    t[0, :] = 0
    t[:, 0] = 0
    return t


def gf_mul(a: int, b: int) -> int:
    return int(mul_table()[a, b])


def gf_inv(a: int) -> int:
    if a == 0:
        raise ZeroDivisionError("0 has no inverse in GF(2^8)")
    exp, log = _tables()
    return int(exp[255 - log[a]])


def mat_mul(a: np.ndarray, b: np.ndarray) -> np.ndarray:
    a = np.asarray(a, np.uint8)
    b = np.asarray(b, np.uint8)
    out = np.zeros((a.shape[0], b.shape[1]), np.uint8)
    mt = mul_table()
    for j in range(a.shape[1]):
        out ^= mt[a[:, j][:, None], b[j][None, :]]
    return out


def mat_inv(a: np.ndarray) -> np.ndarray:
    """Gauss-Jordan over GF(2^8)."""
    a = np.array(a, np.uint8)
    n = a.shape[0]
    aug = np.concatenate([a, np.eye(n, dtype=np.uint8)], axis=1)
    mt = mul_table()
    for col in range(n):
        piv = next((r for r in range(col, n) if aug[r, col]), None)
        if piv is None:
            raise ValueError("singular matrix")
        if piv != col:
            aug[[col, piv]] = aug[[piv, col]]
        aug[col] = mt[gf_inv(int(aug[col, col])), aug[col]]
        for r in range(n):
            if r != col and aug[r, col]:
                aug[r] ^= mt[int(aug[r, col]), aug[col]]
    return aug[:, n:].copy()


def coding_matrix(k: int, m: int) -> np.ndarray:
    """(m, k) parity rows of systematic RS: the extended Vandermonde
    matrix (row 0 = e_0, last row = e_{k-1}, row i = powers of i) times
    the inverse of its top k rows, each parity row scaled so that its
    first coefficient is 1."""
    rows = k + m
    v = np.zeros((rows, k), np.uint8)
    v[0, 0] = 1
    v[rows - 1, k - 1] = 1
    for i in range(1, rows - 1):
        acc = 1
        for j in range(k):
            v[i, j] = acc
            acc = gf_mul(acc, i)
    c = mat_mul(v[k:], mat_inv(v[:k]))
    for i in range(m):
        if c[i, 0] not in (0, 1):
            c[i] = mul_table()[gf_inv(int(c[i, 0])), c[i]]
    return c


def region_mul(matrix: np.ndarray, streams: np.ndarray) -> np.ndarray:
    """(r, k) matrix times (k, n) byte streams -> (r, n)."""
    mt = mul_table()
    out = np.zeros((matrix.shape[0], streams.shape[1]), np.uint8)
    for i in range(matrix.shape[0]):
        for j in range(matrix.shape[1]):
            c = int(matrix[i, j])
            if c == 1:
                out[i] ^= streams[j]
            elif c:
                out[i] ^= mt[c][streams[j]]
    return out


def scatter(payload: bytes, k: int, stripe_unit: int) -> np.ndarray:
    """Whole object -> (k, rows * stripe_unit) data streams: byte x
    lives in stream (x // unit) % k at (x // (k * unit)) * unit +
    x % unit; the last row is padded with zeros."""
    width = k * stripe_unit
    rows = -(-len(payload) // width)
    buf = np.zeros(rows * width, np.uint8)
    buf[:len(payload)] = np.frombuffer(payload, np.uint8)
    return np.ascontiguousarray(
        buf.reshape(rows, k, stripe_unit).transpose(1, 0, 2)
        .reshape(k, rows * stripe_unit))


def gather(streams: np.ndarray, length: int, stripe_unit: int) -> bytes:
    """Inverse of scatter."""
    k = streams.shape[0]
    rows = streams.shape[1] // stripe_unit
    return (streams.reshape(k, rows, stripe_unit).transpose(1, 0, 2)
            .reshape(-1)[:length].tobytes())


def encode(payload: bytes, k: int, m: int, stripe_unit: int) -> list[bytes]:
    """The k + m shard streams a whole-object write has to store."""
    data = scatter(payload, k, stripe_unit)
    parity = region_mul(coding_matrix(k, m), data)
    return [s.tobytes() for s in data] + [p.tobytes() for p in parity]


def decode(shards: dict[int, bytes], k: int, m: int, length: int,
           stripe_unit: int) -> bytes:
    """The object from any k of its shard streams (index -> bytes)."""
    if len(shards) < k:
        raise ValueError(f"{len(shards)} shards, need {k}")
    use = sorted(shards)[:k]
    full = np.concatenate([np.eye(k, dtype=np.uint8), coding_matrix(k, m)])
    have = np.stack([np.frombuffer(shards[i], np.uint8) for i in use])
    data = region_mul(mat_inv(full[use]), have)
    return gather(data, length, stripe_unit)
