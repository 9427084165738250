"""Roofline share of the erasure-code kernels: the bytes the completed
operations needed (``work.py``), over the peak named by ``bound`` in
``peaks.json``, over the device seconds of every operation in the
trace."""

from .. import work


def read(ctx, bound="hbm_bytes_per_s"):
    t = ctx["trace"]
    if not t or not ctx["peaks"]:
        return None
    return work.roofline_share(ctx["needed_bytes"], ctx["peaks"][bound],
                               t["device_s"])
