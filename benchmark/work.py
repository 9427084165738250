"""The bytes the erasure-code arithmetic has to move for the traffic a
run completed, from the client operations and the pool's geometry
alone.  Nothing here reads the program's launch counters: a change that
launches more or fewer kernels moves the share, not the yardstick.

- A whole-object write of S user bytes on k+m reads S and writes
  S*m/k of parity.
- A read of an object whose placement group has lost h data positions
  reads S (k surviving chunks of S/k) and writes S/k for each lost data
  position.  A read that lost no data position needs no arithmetic.

S is the user's bytes, not the stripe the program pads them to: the pad
is the program's, and shows as a lower share.
"""

from __future__ import annotations


def write_bytes(size: int, k: int, m: int) -> float:
    return size + size * m / k


def read_bytes(size: int, k: int, data_holes: int) -> float:
    if data_holes <= 0:
        return 0.0
    return size + size * data_holes / k


def needed_bytes(ops, k: int, m: int) -> float:
    """``ops``: iterable of (kind, user_bytes, data_holes) of the
    operations completed in the window; kind is "write" or "read"."""
    total = 0.0
    for kind, size, holes in ops:
        if kind == "write":
            total += write_bytes(size, k, m)
        elif kind == "read":
            total += read_bytes(size, k, holes)
        else:
            raise ValueError(f"unknown operation kind {kind!r}")
    return total


def roofline_share(needed: float, peak_bytes_per_s: float,
                   device_seconds: float) -> float | None:
    """Least seconds the chip could take (bytes over HBM bandwidth; the
    arithmetic is a few XORs a byte, so bytes bound it) over the device
    seconds the trace counted, in percent.  Both in chip-seconds: four
    chips sum their device time against one chip's peak.  None where
    either side is missing."""
    if needed <= 0 or device_seconds <= 0:
        return None
    return 100.0 * (needed / peak_bytes_per_s) / device_seconds
