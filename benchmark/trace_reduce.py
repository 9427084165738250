"""From the profiler's ``.xplane.pb`` to busy intervals, idle share,
device seconds per operation name and the longest idle gaps.

Read with ``jax.profiler.ProfileData`` (nothing but JAX).  A device is a
plane named ``/device:TPU:<n>``; its operations are the events of the
line ``XLA Ops`` (where a plane has no such line, of every line but the
step and module summaries, which would cover their own operations
twice).  The window is the host annotation the benchmark wraps around
its measured window (``WINDOW``); device events are clipped to it.  A
gap is named by the host annotation (``client-*``, written by the
benchmark around its client calls) that overlaps it longest.
"""

from __future__ import annotations

import bisect
import glob
import os

WINDOW = "bench-window"
DEVICE_PREFIX = "/device:TPU:"
OP_LINE = "XLA Ops"
SUMMARY_LINES = ("Steps", "XLA Modules", "XLA TraceMe", "Framework Ops",
                 "Framework Name Scope", "Source code")
GAP_PREFIX = "client-"
#: an operation's name is its HLO text: the head says which it is
NAME_CHARS = 120


def find_xplane(log_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(
        log_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return paths[-1]


def load(path: str):
    from jax.profiler import ProfileData
    return ProfileData.from_file(path)


def device_events(data) -> dict[str, list[tuple[float, float, str]]]:
    """plane name -> [(start_ns, end_ns, name)] of device operations."""
    out = {}
    for plane in data.planes:
        if not plane.name.startswith(DEVICE_PREFIX):
            continue
        lines = list(plane.lines)
        ops = [ln for ln in lines if ln.name == OP_LINE] or \
              [ln for ln in lines if ln.name not in SUMMARY_LINES]
        out[plane.name] = [
            (float(e.start_ns), float(e.start_ns + e.duration_ns), e.name)
            for ln in ops for e in ln.events if e.duration_ns > 0]
    return out


def host_annotations(data, prefix: str) -> list[tuple[float, float, str]]:
    """[(start_ns, end_ns, name)] of host events whose name starts with
    ``prefix``, over every host thread."""
    out = []
    for plane in data.planes:
        if not plane.name.startswith("/host:"):
            continue
        for ln in plane.lines:
            for e in ln.events:
                if e.name.startswith(prefix):
                    out.append((float(e.start_ns),
                                float(e.start_ns + e.duration_ns), e.name))
    return out


def union(intervals, lo: float, hi: float) -> list[tuple[float, float]]:
    """Merged intervals, clipped to [lo, hi]."""
    merged: list[list[float]] = []
    for s, e in sorted((max(s, lo), min(e, hi)) for s, e, *_ in intervals):
        if e <= s:
            continue
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return [(s, e) for s, e in merged]


def gaps(busy, lo: float, hi: float) -> list[tuple[float, float]]:
    out, at = [], lo
    for s, e in busy:
        if s > at:
            out.append((at, s))
        at = max(at, e)
    if hi > at:
        out.append((at, hi))
    return out


def _name_gaps(idle, spans) -> dict[str, float]:
    """Idle seconds (ns here) by the annotation that covers most of
    each gap; ``host-unannotated`` where none does."""
    spans = sorted(spans)
    starts = [s for s, _e, _n in spans]
    longest = max((e - s for s, e, _n in spans), default=0.0)
    by_name: dict[str, float] = {}
    for lo, hi in idle:
        cover: dict[str, float] = {}
        first = bisect.bisect_left(starts, lo - longest)
        for s, e, name in spans[first:bisect.bisect_left(starts, hi)]:
            ov = min(e, hi) - max(s, lo)
            if ov > 0:
                cover[name] = cover.get(name, 0.0) + ov
        name = max(cover.items(), key=lambda kv: kv[1])[0] if cover \
            else "host-unannotated"
        by_name[name] = by_name.get(name, 0.0) + (hi - lo)
    return by_name


def reduce(data, chips: int = 1, top: int = 10) -> dict:
    """The numbers the per-layer readers and the result line take from
    a trace.  Seconds throughout.

    - ``window_s``: length of the annotated window (without the
      annotation, from the first to the last device event);
    - ``busy_s``: seconds in which an operation ran on a device, the
      union of its intervals, averaged over the ``chips`` the cell uses
      (a chip that ran nothing may have no plane in the trace);
    - ``device_s``: summed duration of every device operation over all
      devices (chip-seconds);
    - ``device_ops``: the ``top`` operation names by summed seconds;
    - ``idle_gaps``: the idle seconds of the busiest device by what the
      host was doing in each gap.
    """
    events = device_events(data)
    if not events or not any(events.values()):
        return {"devices": len(events), "busy_s": 0.0, "window_s": 0.0,
                "device_s": 0.0, "device_ops": [], "idle_gaps": []}
    window = host_annotations(data, WINDOW)
    if window:
        lo, hi = min(w[0] for w in window), max(w[1] for w in window)
    else:
        lo = min(s for ev in events.values() for s, _e, _n in ev)
        hi = max(e for ev in events.values() for _s, e, _n in ev)
    per_op: dict[str, float] = {}
    busy_by_dev = {}
    device_ns = 0.0
    for dev, evs in events.items():
        busy_by_dev[dev] = union(evs, lo, hi)
        for s, e, name in evs:
            d = min(e, hi) - max(s, lo)
            if d > 0:
                per_op[name] = per_op.get(name, 0.0) + d
                device_ns += d
    busy_ns = {d: sum(e - s for s, e in b) for d, b in busy_by_dev.items()}
    busiest = max(busy_ns, key=busy_ns.get)
    spans = host_annotations(data, GAP_PREFIX)
    by_name = _name_gaps(gaps(busy_by_dev[busiest], lo, hi), spans)
    def rank(d):
        return [[n[:NAME_CHARS], s / 1e9] for n, s in
                sorted(d.items(), key=lambda kv: -kv[1])[:top]]

    return {
        "devices": len(events),
        "window_s": (hi - lo) / 1e9,
        "busy_s": sum(busy_ns.values()) / max(chips, len(busy_ns)) / 1e9,
        "device_s": device_ns / 1e9,
        "device_ops": rank(per_op),
        "idle_gaps": rank(by_name),
    }
