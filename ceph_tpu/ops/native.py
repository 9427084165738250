"""ctypes bindings for the native GF(2^8) library (native/libcephtpu.so).

Builds the shared object on first use via `make` if it is missing or stale —
the moral equivalent of the reference's dlopen plugin path
(ErasureCodePlugin.cc:138 loading libec_<name>.so), with the version check
replaced by an mtime staleness check.
"""

from __future__ import annotations

import ctypes
import os
import subprocess
import threading

import numpy as np

_NATIVE_DIR = os.path.join(os.path.dirname(__file__), "..", "..", "native")
_SO_PATH = os.path.join(_NATIVE_DIR, "build", "libcephtpu.so")
_LOCK = threading.Lock()


class NativeUnavailable(RuntimeError):
    pass


def _stale() -> bool:
    if not os.path.exists(_SO_PATH):
        return True
    so_m = os.path.getmtime(_SO_PATH)
    for f in os.listdir(_NATIVE_DIR):
        if f.endswith((".cc", ".h")) and os.path.getmtime(
            os.path.join(_NATIVE_DIR, f)
        ) > so_m:
            return True
    return False


_LIB_RESULT: ctypes.CDLL | Exception | None = None


def lib() -> ctypes.CDLL:
    """Load (building if needed) the native library; caches failure too so a
    broken toolchain doesn't re-run `make` on every probe."""
    global _LIB_RESULT
    if _LIB_RESULT is not None:
        if isinstance(_LIB_RESULT, Exception):
            raise _LIB_RESULT
        return _LIB_RESULT
    try:
        _LIB_RESULT = _load()
    except Exception as e:  # noqa: BLE001 - cache any load/build failure
        _LIB_RESULT = NativeUnavailable(str(e))
        raise _LIB_RESULT
    return _LIB_RESULT


def _load() -> ctypes.CDLL:
    with _LOCK:
        if _stale():
            try:
                subprocess.run(
                    ["make", "-s"], cwd=_NATIVE_DIR, check=True,
                    capture_output=True, text=True,
                )
            except (subprocess.CalledProcessError, FileNotFoundError) as e:
                detail = getattr(e, "stderr", "") or str(e)
                raise NativeUnavailable(f"native build failed: {detail}")
        L = ctypes.CDLL(_SO_PATH)
        u8p = ctypes.POINTER(ctypes.c_uint8)
        L.ct_init.restype = ctypes.c_int
        L.ct_gf_mul.restype = ctypes.c_uint8
        L.ct_gf_mul.argtypes = [ctypes.c_uint8, ctypes.c_uint8]
        L.ct_gf_inv.restype = ctypes.c_uint8
        L.ct_gf_inv.argtypes = [ctypes.c_uint8]
        for name in ("ct_vandermonde_matrix", "ct_cauchy_matrix",
                     "ct_cauchy_good_matrix"):
            fn = getattr(L, name)
            fn.restype = ctypes.c_int
            fn.argtypes = [ctypes.c_int, ctypes.c_int, u8p]
        L.ct_mat_inv.restype = ctypes.c_int
        L.ct_mat_inv.argtypes = [ctypes.c_int, u8p, u8p]
        L.ct_decode_matrix.restype = ctypes.c_int
        L.ct_decode_matrix.argtypes = [
            u8p, ctypes.c_int, ctypes.c_int, ctypes.POINTER(ctypes.c_int), u8p]
        L.ct_region_mac.restype = None
        L.ct_region_mac.argtypes = [u8p, u8p, ctypes.c_size_t, ctypes.c_uint8]
        L.ct_encode.restype = None
        L.ct_encode.argtypes = [u8p, ctypes.c_int, ctypes.c_int, u8p, u8p,
                                ctypes.c_size_t]
        L.ct_encode_ptrs.restype = None
        L.ct_encode_ptrs.argtypes = [
            u8p, ctypes.c_int, ctypes.c_int, ctypes.POINTER(u8p),
            ctypes.POINTER(u8p), ctypes.c_size_t]
        L.ct_lincomb_rows.restype = None
        L.ct_lincomb_rows.argtypes = [
            ctypes.POINTER(u8p), ctypes.POINTER(u8p),
            ctypes.POINTER(u8p), ctypes.c_uint8, ctypes.c_uint8,
            ctypes.c_int, ctypes.c_size_t]
        L.ct_crc32c.restype = ctypes.c_uint32
        L.ct_crc32c.argtypes = [ctypes.c_uint32, u8p, ctypes.c_size_t]
        L.ct_crc32c_rows.restype = None
        L.ct_crc32c_rows.argtypes = [
            ctypes.c_void_p, ctypes.c_void_p, ctypes.c_void_p,
            ctypes.c_void_p, ctypes.c_int, ctypes.c_int, ctypes.c_int,
            ctypes.c_void_p]
        L.ct_xxhash32.restype = ctypes.c_uint32
        L.ct_xxhash32.argtypes = [ctypes.c_uint32, u8p, ctypes.c_size_t]
        L.ct_xxhash64.restype = ctypes.c_uint64
        L.ct_xxhash64.argtypes = [ctypes.c_uint64, u8p, ctypes.c_size_t]
        L.chacha20_xor.restype = None
        L.chacha20_xor.argtypes = [u8p, u8p, ctypes.c_uint32, u8p,
                                   ctypes.c_uint64]
        L.ct_init()
        return L


def _u8p(a: np.ndarray):
    return a.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8))


def available() -> bool:
    try:
        lib()
        return True
    except NativeUnavailable:
        return False


def vandermonde_matrix(k: int, m: int) -> np.ndarray:
    out = np.empty((m, k), dtype=np.uint8)
    if lib().ct_vandermonde_matrix(k, m, _u8p(out)) != 0:
        raise ValueError(f"bad (k={k}, m={m})")
    return out


def cauchy_matrix(k: int, m: int) -> np.ndarray:
    out = np.empty((m, k), dtype=np.uint8)
    if lib().ct_cauchy_matrix(k, m, _u8p(out)) != 0:
        raise ValueError(f"bad (k={k}, m={m})")
    return out


def cauchy_good_matrix(k: int, m: int) -> np.ndarray:
    out = np.empty((m, k), dtype=np.uint8)
    if lib().ct_cauchy_good_matrix(k, m, _u8p(out)) != 0:
        raise ValueError(f"bad (k={k}, m={m})")
    return out


def mat_inv(A: np.ndarray) -> np.ndarray:
    A = np.ascontiguousarray(A, dtype=np.uint8)
    n = A.shape[0]
    out = np.empty((n, n), dtype=np.uint8)
    if lib().ct_mat_inv(n, _u8p(A), _u8p(out)) != 0:
        raise np.linalg.LinAlgError("singular")
    return out


def decode_matrix(C: np.ndarray, k: int, available_ids: list[int]) -> np.ndarray:
    C = np.ascontiguousarray(C, dtype=np.uint8)
    m = C.shape[0]
    if not (0 < k <= 256 and k + m <= 256):
        raise ValueError(f"bad (k={k}, m={m})")
    if len(available_ids) < k:
        raise ValueError(f"need >= {k} available chunk ids")
    if any(not 0 <= i < k + m for i in available_ids[:k]):
        raise ValueError(f"chunk id out of range in {available_ids[:k]}")
    avail = (ctypes.c_int * k)(*available_ids[:k])
    out = np.empty((k, k), dtype=np.uint8)
    if lib().ct_decode_matrix(_u8p(C), k, m, avail, _u8p(out)) != 0:
        raise np.linalg.LinAlgError("singular decode set")
    return out


def encode_region(G: np.ndarray, data: np.ndarray) -> np.ndarray:
    """parity (m,L) = G (m,k) @ data (k,L), native kernels (AVX2 if present)."""
    G = np.ascontiguousarray(G, dtype=np.uint8)
    data = np.ascontiguousarray(data, dtype=np.uint8)
    m, k = G.shape
    assert data.shape[0] == k
    L = data.shape[1]
    parity = np.empty((m, L), dtype=np.uint8)
    lib().ct_encode(_u8p(G), m, k, _u8p(data), _u8p(parity), L)
    return parity


def region_mac(dst: np.ndarray, src: np.ndarray, coef: int) -> None:
    """dst ^= coef * src over GF(2^8), in place. Both must be uint8."""
    if dst.dtype != np.uint8 or src.dtype != np.uint8:
        raise TypeError("region_mac requires uint8 arrays")
    if not (dst.flags.c_contiguous and src.flags.c_contiguous):
        raise ValueError("region_mac requires contiguous arrays")
    if src.size < dst.size:
        raise ValueError(f"src ({src.size}) shorter than dst ({dst.size})")
    lib().ct_region_mac(_u8p(dst), _u8p(src), dst.size, coef)


def encode_region_ptrs(G: np.ndarray, rows: list[np.ndarray],
                       L: int) -> np.ndarray:
    """Like encode_region but gathering input rows by pointer — the shape of
    the decode path where survivor chunks live in separate buffers (the
    reference marshals shard_id_map -> char*[] the same way,
    ErasureCodeJerasure.cc:121-163)."""
    G = np.ascontiguousarray(G, dtype=np.uint8)
    m, k = G.shape
    if len(rows) < k:
        raise ValueError(f"need {k} input rows")
    for r in rows[:k]:
        if r.dtype != np.uint8 or not r.flags.c_contiguous or r.size < L:
            raise ValueError("rows must be contiguous uint8 of >= L bytes")
    out = np.empty((m, L), dtype=np.uint8)
    u8p = ctypes.POINTER(ctypes.c_uint8)
    in_ptrs = (u8p * k)(*[_u8p(r) for r in rows[:k]])
    out_ptrs = (u8p * m)(*[_u8p(out[i]) for i in range(m)])
    lib().ct_encode_ptrs(_u8p(G), m, k, in_ptrs, out_ptrs, L)
    return out


def lincomb_rows_ptrs(dst_ptrs: np.ndarray, a_ptrs: np.ndarray,
                      b_ptrs: np.ndarray | None,
                      ca: int, cb: int, L: int) -> None:
    """Like lincomb_rows, but the rows are given as uint64 ADDRESS
    arrays (base + offset computed with numpy) — one ctypes cast per
    call instead of one per row, which is what makes thousands of tiny
    coupling rows per encode affordable."""
    n = len(dst_ptrs)
    if n == 0:
        return
    u8pp = ctypes.POINTER(ctypes.POINTER(ctypes.c_uint8))
    d = np.ascontiguousarray(dst_ptrs, dtype=np.uint64)
    a = np.ascontiguousarray(a_ptrs, dtype=np.uint64)
    bp = None
    if b_ptrs is not None:
        b = np.ascontiguousarray(b_ptrs, dtype=np.uint64)
        bp = b.ctypes.data_as(u8pp)
    lib().ct_lincomb_rows(d.ctypes.data_as(u8pp),
                          a.ctypes.data_as(u8pp), bp, ca, cb, n, L)


def crc32c(data: bytes | np.ndarray, crc: int = 0) -> int:
    """Standard CRC-32C (init/xorout 0xFFFFFFFF folded in; chainable by
    passing a previous result as ``crc``) — the checksum family Ceph's
    Checksummer dispatches (src/common/Checksummer.h:13)."""
    a = np.frombuffer(data, dtype=np.uint8) if isinstance(
        data, (bytes, bytearray, memoryview)) else np.ascontiguousarray(
            data, dtype=np.uint8)
    return int(lib().ct_crc32c(ctypes.c_uint32(crc).value, _u8p(a), a.size))


def crc32c_rows(src, par, dst, lens, k: int, m: int) -> np.ndarray:
    """CRC-32C of the rows of n groups in ONE native call, as
    uint32[n, k+m]: each group's k ``src`` rows, then its m ``par``
    rows, which are first copied to the group's m ``dst`` rows and
    summed there (``dst`` None: summed where they lie, nothing copied).
    Every digest starts from the standard initial value.  Rows are given
    by ADDRESS (ints or a uint64 array), group-major (``src`` n*k,
    ``par`` / ``dst`` n*m), and packed with the lengths into one uint64
    array, so the call marshals two pointers whatever n is; group i's
    rows are ``lens[i]`` bytes.  The caller keeps every row alive for
    the call.  One call hands the interpreter away once, where a call a
    row hands it away k+m times.  Callers: an encode flush's carve
    (``matrix_code.carve_with_csums``, ``row_csums``) and
    ``crc32c_blocks``."""
    n = len(lens)
    parts = (src, par, lens) if dst is None else (src, par, dst, lens)
    sizes = [len(p) for p in parts]
    if sizes[:-1] != ([n * k, n * m] if dst is None
                      else [n * k, n * m, n * m]):
        raise ValueError(f"row address counts {sizes[:-1]} do not match "
                         f"{n} ops of k={k} m={m}")
    out = np.empty((n, k + m), dtype=np.uint32)
    if n == 0:
        return out
    addr = np.concatenate([np.asarray(p, dtype=np.uint64) for p in parts])
    at, ptr = [], addr.ctypes.data
    for size in sizes:
        at.append(ptr)
        ptr += 8 * size
    if dst is None:
        at.insert(2, None)
    lib().ct_crc32c_rows(*at, n, k, m, out.ctypes.data)
    return out


def crc32c_blocks(data, block: int) -> list[int]:
    """Per-block CRC-32C over one contiguous buffer (the BlueStore
    per-page csum sweep) in ONE native call: every block is a one-row
    group of ``crc32c_rows``, summed from the standard initial value
    (no seed) — the store ingest path sums hundreds of pages per MiB,
    where a ctypes round-trip a page would dwarf the checksum itself.
    The tail block may be short."""
    a = np.frombuffer(data, dtype=np.uint8) if isinstance(
        data, (bytes, bytearray, memoryview)) else np.ascontiguousarray(
            data, dtype=np.uint8)
    offs = np.arange(0, a.size, block, dtype=np.uint64)
    lens = np.minimum(np.uint64(block), np.uint64(a.size) - offs)
    sums = crc32c_rows(np.uint64(a.ctypes.data) + offs, (), None, lens,
                       1, 0)
    return sums[:, 0].tolist()


def xxhash32(data: bytes | np.ndarray, seed: int = 0) -> int:
    """XXH32 (public xxHash spec) — the non-crc member of the reference
    Checksummer dispatch (src/common/Checksummer.h:13)."""
    a = np.frombuffer(data, dtype=np.uint8) if isinstance(
        data, (bytes, bytearray, memoryview)) else np.ascontiguousarray(
            data, dtype=np.uint8)
    return int(lib().ct_xxhash32(ctypes.c_uint32(seed).value, _u8p(a),
                                 a.size))


def xxhash64(data: bytes | np.ndarray, seed: int = 0) -> int:
    """XXH64 (public xxHash spec)."""
    a = np.frombuffer(data, dtype=np.uint8) if isinstance(
        data, (bytes, bytearray, memoryview)) else np.ascontiguousarray(
            data, dtype=np.uint8)
    return int(lib().ct_xxhash64(ctypes.c_uint64(seed).value, _u8p(a),
                                 a.size))


CSUM_FUNCS = {"crc32c": crc32c, "xxhash32": xxhash32, "xxhash64": xxhash64}


def checksummer(kind: str):
    """Checksummer dispatch (src/common/Checksummer.h:13 template
    switch): pick a checksum family by name."""
    try:
        return CSUM_FUNCS[kind]
    except KeyError:
        raise ValueError(f"unknown checksum {kind!r}") from None


def chacha20_xor(key: bytes, nonce: bytes, data: bytes,
                 counter: int = 0) -> bytes:
    """ChaCha20 keystream XOR (RFC 8439): encrypt == decrypt.  The
    messenger's secure-mode cipher (the crypto_onwire role; the
    reference uses AES-GCM via openssl, this library is dependency-free
    so the wire cipher is ChaCha20 + the messenger's HMAC tag)."""
    if len(key) != 32 or len(nonce) != 12:
        raise ValueError("chacha20 wants a 32-byte key, 12-byte nonce")
    buf = np.frombuffer(bytes(data), dtype=np.uint8).copy()
    k = np.frombuffer(key, dtype=np.uint8)
    n = np.frombuffer(nonce, dtype=np.uint8)
    if buf.size:
        lib().chacha20_xor(_u8p(k), _u8p(n), counter, _u8p(buf), buf.size)
    return buf.tobytes()
