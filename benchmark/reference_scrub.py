"""Plain reference of what a deep scrub has to compute and report.

CRC-32C (Castagnoli; reflected, initial value and final xor
``0xFFFFFFFF``: RFC 3720's, what Ceph's ``ceph_crc32c`` and BlueStore's
per-blob checksum are) of a byte string, bit by bit from the
polynomial, and what a deep scrub must report for a set of stored
shards.  Written for the benchmark and its tests in plain Python and
numpy: it imports nothing of the program and takes nothing the program
made.  Speed does not matter here.
"""

from __future__ import annotations

import numpy as np

POLY = 0x82F63B78     # x^32 + ... of Castagnoli, bit-reflected


def _table() -> np.ndarray:
    tab = np.zeros(256, np.uint32)
    for byte in range(256):
        c = byte
        for _ in range(8):
            c = (c >> 1) ^ (POLY if c & 1 else 0)
        tab[byte] = c
    return tab


TABLE = _table()


def crc32c(data: bytes) -> int:
    """CRC-32C of ``data``."""
    c = 0xFFFFFFFF
    for b in bytes(data):
        c = (c >> 8) ^ int(TABLE[(c ^ b) & 0xFF])
    return c ^ 0xFFFFFFFF


def expected_findings(shards: dict, planted=()) -> list[tuple]:
    """What a deep scrub must report for ``shards``, ``{(object, shard):
    (stored bytes, stored digest, version)}``: nothing where every
    stored digest is the CRC-32C of its bytes and an object's shards
    hold one version; else, sorted, a ``(object, shard,
    "digest_mismatch")`` for each shard whose bytes are not what its
    digest says and a ``(object, shard, "stale_version")`` for each that
    is behind its object's newest.  ``planted`` is what the caller put
    there, ``(object, shard, kind)``: the two have to agree, or the
    stores are not what the caller thinks."""
    newest: dict = {}
    for (obj, _shard), (_data, _digest, version) in shards.items():
        newest[obj] = max(newest.get(obj, version), version)
    found = []
    for (obj, shard), (data, digest, version) in shards.items():
        if crc32c(data) != digest:
            found.append((obj, shard, "digest_mismatch"))
        if version != newest[obj]:
            found.append((obj, shard, "stale_version"))
    found.sort()
    if found != sorted(tuple(p) for p in planted):
        raise AssertionError(f"the stores hold {found}, planted was "
                             f"{sorted(planted)}")
    return found
