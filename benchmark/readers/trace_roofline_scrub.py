"""Roofline share of the device's work in a window that encodes and
scrubs: every byte the window's writes needed of the erasure-code
arithmetic (``work.py``) plus every byte whose digest a device program
produced for a scrub (the growth of ``osd.scrub_verified_bytes``: the
CRC reads each once), over the peak named by ``bound`` in
``peaks.json``, over the device seconds of every operation in the
trace, whatever implements them.  Nothing where the program counts no
scrubbed bytes."""

from .. import work

COUNTER = "osd.scrub_verified_bytes"


def read(ctx, bound="hbm_bytes_per_s"):
    t = ctx["trace"]
    if not t or not ctx["peaks"] or COUNTER not in ctx["counters"]:
        return None
    return work.roofline_share(
        ctx["needed_bytes"] + ctx["counters"][COUNTER],
        ctx["peaks"][bound], t["device_s"])
