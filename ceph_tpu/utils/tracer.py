"""Distributed tracing: span trees across the client -> primary ->
shard fan-out.

The capability of the reference's tracer (src/common/tracer.h:10-35 —
jaeger spans started per op, child spans per pipeline stage; ZTracer
child spans per EC sub-op, src/osd/ECCommon.cc:1046-1051), re-shaped
for this runtime: every entity (client, osd, mon) owns a Tracer that
records finished spans into a bounded ring; a trace CONTEXT — the
(trace_id, span_id) pair — rides message fields, so a child span on
the receiving daemon links to its remote parent without any shared
state.  Aggregation is collector-style: each daemon dumps its local
spans for a trace id (admin socket verb), and the operator (or
MiniCluster.collect_trace) merges the rings into one tree — the same
shape jaeger assembles from per-service reports.

Tracing is off unless the op carries a context (zero overhead on the
hot path: one falsy check per handler).

Head sampling (the always-on mode): a root op calls ``sample_root``
instead of ``start`` — with ``sample_rate`` <= 0 it returns None at
zero cost (no RNG draw, no allocation); otherwise the op is SAMPLED
with that probability.  A sampled root is a normal span whose context
propagates on the wire, so the one head decision covers the whole
client -> primary -> shard fan-out (the OpenTelemetry parent-based
sampler shape: a child traces iff the message carries a context).  An
UNSAMPLED root still gets a lightweight local-only span (``sampled``
False, context never propagated) held in a small bounded side ring —
the flight-recorder feed: when the op later crosses the slow-op
complaint threshold, ``promote()`` force-retains it retroactively into
the ordinary rings, so SLOW_OPS evidence survives even at low sample
rates.  ``trace_sampled`` / ``trace_dropped`` / ``trace_leaked``
counters land on the owning daemon's perf registry when one is given.

One clock: ``now_ns()`` is what every stamp of the program's timing
instruments reads — spans here, ``TrackedOp`` marks
(utils/tracked_op.py), the batcher's and the staging plane's timers,
the messenger's receive stamp.  It counts nanoseconds since the epoch
(``time.perf_counter_ns()`` plus an offset to ``time.time_ns()`` taken
once at import): monotone, so a difference is a duration, and epoch-
based like the host plane of the JAX profiler, so a span and a
``TraceAnnotation`` (``annotate``) lie on one axis.  ``clock_sync``
writes an annotation that carries its own ``now_ns()`` reading, so a
trace proves the alignment (tools/trace_tool.py ``--xplane``); should
the profiler's clock ever not be the epoch, ``_EPOCH_OFFSET_NS`` is
the one place to change.
"""

from __future__ import annotations

import contextlib
import itertools
import random
import sys
import threading
import time
from collections import deque
from dataclasses import dataclass, field

_EPOCH_OFFSET_NS = time.time_ns() - time.perf_counter_ns()


def now_ns() -> int:
    """Nanoseconds since the epoch on the monotone clock (module
    docstring, "One clock")."""
    return time.perf_counter_ns() + _EPOCH_OFFSET_NS


#: the phase annotations' common prefix in a profiler trace
ANNOTATION_PREFIX = "ceph:"
CLOCK_SYNC = ANNOTATION_PREFIX + "clock-sync"
_NO_ANNOTATION = contextlib.nullcontext()
_trace_annotation = None


def annotate(name: str, **meta):
    """Context manager that puts ``name`` (a ``ceph:<phase>`` of the
    mark vocabulary) on the calling thread's line of the JAX
    profiler's trace: for seams that are synchronous on one thread.
    Without a profiler session it costs a constructor call; in a
    process that never imported jax (a client child) it costs a dict
    lookup and imports nothing."""
    global _trace_annotation
    ta = _trace_annotation
    if ta is None:
        if "jax" not in sys.modules:
            return _NO_ANNOTATION
        from jax.profiler import TraceAnnotation as ta
        _trace_annotation = ta
    return ta(name, **meta)


def clock_sync() -> None:
    """An empty ``ceph:clock-sync`` annotation whose metadata is the
    ``now_ns()`` reading taken just before it opens: its distance to
    the event's own start in the trace is the offset between this
    module's clock and the profiler's."""
    with annotate(CLOCK_SYNC, now_ns=str(now_ns())):
        pass


@dataclass
class Span:
    trace_id: int
    span_id: int
    parent_id: int          # 0 = root
    name: str
    service: str            # entity that produced it (client.x / osd.N)
    #: now_ns() readings; ``start``/``end`` give them as the seconds
    #: since the epoch the dump formats carry
    start_ns: int = field(default_factory=now_ns)
    end_ns: int = 0
    tags: dict = field(default_factory=dict)
    _tracer: "Tracer | None" = None
    # head-sampling verdict: False = local-only flight-recorder span
    # (context must NOT propagate; lives in the unsampled side ring
    # until promoted or aged out)
    sampled: bool = True

    @property
    def start(self) -> float:
        return self.start_ns / 1e9

    @property
    def end(self) -> float:
        return self.end_ns / 1e9

    @property
    def ctx(self) -> tuple[int, int]:
        """The propagation context a child on another daemon parents
        itself under (trace.h's trace context role)."""
        return (self.trace_id, self.span_id)

    def tag(self, key: str, value) -> "Span":
        self.tags[key] = value
        return self

    def finish(self, end_ns: int | None = None) -> None:
        """Idempotent: async completions can race teardown.  The
        check-and-set must be ATOMIC with the ring append — two racing
        finishers both passing a bare `if self.end` check would each
        _record() the span and double-append it to the ring — so a
        tracer-owned span delegates the whole close to the tracer,
        under its lock.  ``end_ns`` closes the span on a reading the
        caller already took (the mark that ends the same phase)."""
        if self._tracer is not None:
            self._tracer._finish(self, end_ns)
        elif not self.end_ns:
            self.end_ns = end_ns or now_ns()

    def __enter__(self) -> "Span":
        return self

    def __exit__(self, *exc) -> None:
        self.finish()


def _span_dict(s: Span, now: int) -> dict:
    """ONE dict shape for every dump path (spans_for and the no-id
    dump used to diverge — the id-less shape dropped start/end and
    broke build_tree's start-sort on merged dumps).  Unfinished spans
    keep end=0 and carry in_flight=True with the duration measured to
    `now` (a now_ns() reading), so hung ops are visible in the same
    tree.  ``dur_ns`` is exact; the seconds are floats."""
    end = s.end_ns
    dur_ns = (end or now) - s.start_ns
    d = {"trace_id": s.trace_id, "span_id": s.span_id,
         "parent_id": s.parent_id, "name": s.name,
         "service": s.service, "start": s.start, "end": s.end,
         "dur_ms": round(dur_ns / 1e6, 3), "dur_ns": dur_ns,
         "tags": dict(s.tags)}
    if not end:
        d["in_flight"] = True
    return d


class Tracer:
    """Per-entity span factory + bounded finished-span ring."""

    KEEP = 2048  # finished spans retained (ring; ops tooling window)
    UNSAMPLED_KEEP = 128  # recent unsampled roots (flight-recorder feed)

    #: per-service sampling counters, registered on the daemon's perf
    #: registry when one is supplied (idempotent: has-before-add)
    PERF_COUNTERS = ("trace_sampled", "trace_dropped", "trace_leaked")

    def __init__(self, service: str, sample_rate: float = 0.0,
                 perf=None, rng: random.Random | None = None):
        self.service = service
        self.sample_rate = max(0.0, min(1.0, float(sample_rate)))
        self._ids = itertools.count(1)
        self._seed = (hash(service) & 0xFFFF) << 32
        self._lock = threading.Lock()
        self._rng = rng if rng is not None else random.Random()
        self._done: deque[Span] = deque(maxlen=self.KEEP)
        # started-but-unfinished spans, so dumps can show hung ops;
        # bounded like the ring (a leaked span must not grow it forever)
        self._live: dict[int, Span] = {}
        # recent UNSAMPLED root spans: the retroactive-retention window
        # the slow-op flight recorder promotes from (bounded — aged-out
        # spans are simply gone, exactly like the dropped traces)
        self._unsampled: deque[Span] = deque(maxlen=self.UNSAMPLED_KEEP)
        self._perf = perf
        if perf is not None:
            for name in self.PERF_COUNTERS:
                if not perf.has(name):
                    perf.add(name)

    def set_sample_rate(self, rate) -> None:
        """Config-live knob (the trace_sample_rate observer target)."""
        self.sample_rate = max(0.0, min(1.0, float(rate)))

    def _next_id(self) -> int:
        return self._seed | next(self._ids)

    def start(self, name: str, parent: tuple | None = None,
              start_ns: int | None = None, **tags) -> Span:
        """Start a span.  parent = a (trace_id, span_id) context from a
        message (remote parent) or a local Span.ctx; None starts a new
        root trace.  ``start_ns`` opens it on a now_ns() reading the
        caller already took: where a TrackedOp mark opens the same
        phase, span and mark share the reading."""
        if parent:
            trace_id, parent_id = int(parent[0]), int(parent[1])
        else:
            trace_id, parent_id = self._next_id(), 0
        span = Span(trace_id, self._next_id(), parent_id, name,
                    self.service, start_ns=start_ns or now_ns(),
                    tags=dict(tags), _tracer=self)
        with self._lock:
            self._live[span.span_id] = span
            while len(self._live) > self.KEEP:
                # overflow = leaked spans (owners that never finish):
                # close them into the done ring tagged leaked=True —
                # silently discarding them destroyed exactly the
                # hung-op evidence the live table exists to keep
                leaked = self._live.pop(next(iter(self._live)))
                leaked.end_ns = now_ns()
                leaked.tags["leaked"] = True
                self._done.append(leaked)
                if self._perf is not None:
                    self._perf.inc("trace_leaked")
        return span

    def sample_root(self, name: str, **tags) -> Span | None:
        """Head-sampling entry point for ROOT ops (client writes/reads,
        recovery storms, scrub).  Returns None at zero cost when
        sampling is off; a normal propagating span (``sampled`` True,
        counted trace_sampled) with probability ``sample_rate``; and
        otherwise a local-only unsampled span (counted trace_dropped)
        held in the bounded side ring for retroactive slow-op
        retention.  Callers propagate ``span.ctx`` on the wire ONLY
        when ``span.sampled`` — that is the one head decision covering
        the whole fan-out."""
        rate = self.sample_rate
        if rate <= 0.0:
            return None
        if rate >= 1.0 or self._rng.random() < rate:
            if self._perf is not None:
                self._perf.inc("trace_sampled")
            return self.start(name, **tags)
        if self._perf is not None:
            self._perf.inc("trace_dropped")
        span = Span(self._next_id(), self._next_id(), 0, name,
                    self.service, tags=dict(tags), _tracer=self,
                    sampled=False)
        with self._lock:
            self._unsampled.append(span)
        return span

    def promote(self, span: Span) -> None:
        """Force-retain an unsampled root span (the tail-based flight
        recorder: the op it roots crossed the slow-op threshold, so
        its evidence must survive the side ring's churn).  Idempotent;
        a span that already aged out of the side ring is re-adopted
        all the same."""
        with self._lock:
            if span.sampled:
                return
            span.sampled = True
            span.tags["retained"] = True
            try:
                self._unsampled.remove(span)
            except ValueError:
                pass  # aged out of the side ring; adopt anyway
            if span.end_ns:
                self._done.append(span)
            else:
                self._live[span.span_id] = span

    def _finish(self, span: Span, end_ns: int | None = None) -> None:
        """Atomic close: end-stamp check-and-set + ring append under
        ONE lock hold, so racing finishers record the span exactly
        once (Span.finish docstring has the failure mode).  An
        unsampled span just gets end-stamped — it already sits in the
        bounded side ring (or was promoted, flipping sampled)."""
        with self._lock:
            if span.end_ns:
                return
            span.end_ns = end_ns or now_ns()
            if not span.sampled:
                return
            self._live.pop(span.span_id, None)
            self._done.append(span)

    def spans_for(self, trace_id: int) -> list[dict]:
        now = now_ns()
        with self._lock:
            spans = [s for s in self._done if s.trace_id == trace_id]
            spans += [s for s in self._live.values()
                      if s.trace_id == trace_id]
        return [_span_dict(s, now) for s in spans]

    def dump(self, trace_id: int | None = None) -> list[dict]:
        if trace_id is not None:
            return self.spans_for(trace_id)
        now = now_ns()
        with self._lock:
            spans = list(self._done) + list(self._live.values())
        return [_span_dict(s, now) for s in spans]


def build_tree(spans: list[dict]) -> list[dict]:
    """Assemble collector-merged span dicts into parent->children trees
    (roots returned; orphans whose parent span is missing from the
    window become roots too, tagged so)."""
    by_id = {s["span_id"]: dict(s, children=[]) for s in spans}
    roots = []
    for s in by_id.values():
        parent = by_id.get(s["parent_id"])
        if parent is not None:
            parent["children"].append(s)
        else:
            if s["parent_id"]:
                s["orphan"] = True
            roots.append(s)
    for s in by_id.values():
        s["children"].sort(key=lambda c: c["start"])
    roots.sort(key=lambda c: c["start"])
    return roots
