"""Host<->device staging plane: the one device_put/landing helper and
the ``ec_stage_*`` accounting every staged byte rides through.

The BENCH_SWEEP_CPU numbers that motivated the device-resident stripe
plane (kernel 1.27 GB/s vs e2e 0.25 GB/s — arXiv:1709.05365's
pipeline-overhead wall) are a data-movement story, so the movement
itself must be observable: every batcher host->device ingest and
every flush's single device->host copy lands here as bytes + copies +
a pow2-microsecond histogram on the process-wide ``ec_kernels``
registry (next to the KernelProfiler's compile/device/sync slices, so
``dump_kernel_profile`` scrapes and the exporter see the whole
decomposition with zero extra wiring).

Scope note: these counters meter the BATCHER's staging plane
specifically — ``ec_stage_d2h_copies`` divided by the batcher's launch
count is the "one device->host copy per flush" contract the bench
asserts.  Codec-internal per-op syncs (pass-through paths, non-batched
callers) keep riding KernelProfiler's ``sync`` slice instead.

``device_put_landed`` is ``jax.device_put`` + ``block_until_ready``
(the copy has landed when it returns) + the accounting.  The hot ingest
path skips the wait (``force=False``) — it would serialize every
submitter behind its own transfer — and lets the flush's launch order
everything at once.

Bytes live on the host and uint32 lanes on the device: what this plane
puts on an accelerator for the EC path is already viewed as lanes
(ops/ec_kernels.bytes_as_lanes), and what it fetches is viewed as bytes
only after the copy.

Fall-throughs: the host paths around device work are either gone or
counted here (``FALLTHROUGHS``, one counter per site).  ``fallthrough``
re-raises on an accelerator — there a device failure must surface — and
counts on the CPU platform, where a few tests still lean on the host
path.
"""

from __future__ import annotations

import threading

import numpy as np

from .perf import CounterType, PerfCounters, global_perf
from .tracer import now_ns

#: registered (zeroed) on the ``ec_kernels`` registry at first use, so
#: perf dump / the exporter expose one stable schema whether or not the
#: device-resident plane ever engaged
COUNTERS = ("ec_stage_h2d_bytes", "ec_stage_h2d_copies",
            "ec_stage_d2h_bytes", "ec_stage_d2h_copies")
HISTOGRAMS = ("ec_stage_h2d_us", "ec_stage_d2h_us")
#: one counter per host fall-through site around device work (all zero
#: on a healthy run — chip_smoke.py asserts it)
FALLTHROUGHS = ("ec_stage_encode_host_fallback",
                "ec_stage_decode_host_fallback",
                "ec_bitxor_host_fallback",
                "ec_fold_warm_failed",
                # apply_delta on a jax pool: a GF multiply on the host
                # (the OSD folds an overwrite's deltas with one encode
                # of the delta stripe and never calls it)
                "ec_delta_host_fallback",
                # a scrub's digests swept on the host of an accelerator
                # (ec/verify.host_digests): osd_scrub_fold=native there
                "ec_scrub_host_digest")

_REG_LOCK = threading.Lock()
_CPU_BACKEND: bool | None = None


def backend_is_cpu() -> bool:
    """Whether the default jax backend is host CPU.  Cached: the
    ingest plane asks per op.  On CPU every host->device copy is a
    real memcpy over the same memory bus the kernel reads — per-op
    staging + an XLA concat costs ~3x the one host fold it replaces
    (measured: 23ms vs 7ms per 8 MiB flush), so the ingest plane only
    engages on real accelerators, where the DMA overlaps compute and
    the fold assembles at HBM bandwidth."""
    global _CPU_BACKEND
    if _CPU_BACKEND is None:
        import jax
        _CPU_BACKEND = jax.default_backend() == "cpu"
    return _CPU_BACKEND


def stage_perf() -> PerfCounters:
    """The ``ec_kernels`` registry with the staging schema ensured —
    idempotent (PerfCounters.add RESETS an existing counter, so the
    late registrants here must check first)."""
    pc = global_perf().create("ec_kernels")
    with _REG_LOCK:
        for n in COUNTERS + FALLTHROUGHS:
            if not pc.has(n):
                pc.add(n)
        for h in HISTOGRAMS:
            if not pc.has(h):
                pc.add(h, CounterType.HISTOGRAM)
    return pc


def fallthrough(counter: str) -> None:
    """Called from an ``except`` block around device work whose caller
    can still do the job on the host: off the CPU platform the active
    exception is re-raised (a device failure must not hide behind a
    host path); on the CPU platform it is counted on ``ec_kernels``."""
    if not backend_is_cpu():
        raise  # noqa: PLE0704 - re-raises the caller's active exception
    stage_perf().inc(counter)


def fallthrough_counts() -> dict:
    pc = stage_perf()
    return {n: int(pc.get(n)) for n in FALLTHROUGHS}


def note_h2d(nbytes: int, seconds: float | None = None,
             exemplar=None) -> None:
    """``seconds=None`` books bytes + the copy count but NOT latency:
    an unforced ``device_put`` on an async backend returns at dispatch,
    so timing it would pollute the histogram (and any bandwidth
    derived from it) with numbers far above the real transfer.
    ``exemplar`` is the staging op's sampled trace_id (or None)."""
    pc = stage_perf()
    pc.inc("ec_stage_h2d_bytes", int(nbytes))
    pc.inc("ec_stage_h2d_copies")
    if seconds is not None:
        pc.hinc("ec_stage_h2d_us", seconds * 1e6, exemplar=exemplar)


def note_d2h(nbytes: int, seconds: float, exemplar=None) -> None:
    pc = stage_perf()
    pc.inc("ec_stage_d2h_bytes", int(nbytes))
    pc.inc("ec_stage_d2h_copies")
    pc.hinc("ec_stage_d2h_us", seconds * 1e6, exemplar=exemplar)


def device_put_landed(host: np.ndarray, *, force: bool = True,
                      record: bool = True, exemplar=None):
    """Stage a host buffer to the default device and (optionally) wait
    for it to LAND (``block_until_ready``).  ``record=True`` books the
    copy against the ``ec_stage_h2d_*`` counters; benches that time the
    transfer themselves still record (the counters are cumulative
    telemetry, not the bench's own clock)."""
    import jax

    t0 = now_ns()
    dev = jax.device_put(host)
    if force:
        dev.block_until_ready()
    if record:
        # latency is only meaningful when the transfer was waited for
        # (or the backend is synchronous CPU): an unforced put on an
        # async backend times DISPATCH, not the copy
        dt = ((now_ns() - t0) / 1e9
              if force or backend_is_cpu() else None)
        note_h2d(getattr(host, "nbytes", len(host)), dt,
                 exemplar=exemplar)
    return dev


def fetch_recorded(devs, *, sig: str | None = None):
    """Materialize one or more device buffers on the host as ONE
    metered device->host copy event (the flush-plane "exactly one copy
    per flush" contract: a fused launch's parity AND csums leave the
    device together, so they are booked together).  Returns a list of
    numpy arrays in input order.  Numpy inputs pass through unmetered —
    they never left the host.

    ``ec_stage_d2h_us`` times ``np.asarray`` alone.  The batcher's
    flushes wait for the launch first (``_profiled_launch`` blocks
    until ready), so there it reads the copy; a caller that hands over
    a buffer still being computed gets the rest of the computation in
    the same number."""
    devs = list(devs)
    if all(isinstance(d, np.ndarray) for d in devs):
        return devs
    from .perf import kernel_profiler

    t0 = now_ns()
    out = [d if isinstance(d, np.ndarray) else np.asarray(d)
           for d in devs]
    dt = (now_ns() - t0) / 1e9
    nbytes = sum(o.nbytes for o, d in zip(out, devs)
                 if not isinstance(d, np.ndarray))
    note_d2h(nbytes, dt)
    if sig is None:
        sig = "sync/bulk"
    kernel_profiler().note("sync", sig, dt)
    return out
