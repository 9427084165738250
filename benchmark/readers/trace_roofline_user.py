"""Roofline share of a kernel whose needed bytes are a fixed multiple
of the window's user bytes, whatever implements it: user bytes times
``bytes_per_user_byte`` from the metric's file, over the peak named by
``bound`` in ``peaks.json``, over the device seconds of every operation
in the trace."""

from .. import work


def read(ctx, bytes_per_user_byte, bound="hbm_bytes_per_s"):
    t = ctx["trace"]
    if not t or not ctx["peaks"]:
        return None
    return work.roofline_share(ctx["user_bytes"] * bytes_per_user_byte,
                               ctx["peaks"][bound], t["device_s"])
