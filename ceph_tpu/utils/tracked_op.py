"""Op tracking: per-op event timelines, in-flight dump, slow-op detection.

The capability of the reference's TrackedOp/OpTracker
(src/common/TrackedOp.{h,cc} — SURVEY.md §2.2): every in-flight operation
records timestamped state marks; operators can dump in-flight and historic
ops; ops exceeding a threshold are counted as slow.

The timeline.  A mark is one ``now_ns()`` reading (utils/tracer.py, the
program's one clock) and a list append, always on.  Marks come from one
vocabulary, ``MARKS``, which says for each mark the PHASE it opens: the
interval from a mark to the next one belongs to that phase.  When the op
finishes — where its reply is handed to the messenger, not where its
handler returns — the tracker adds every interval to the phase's TIME
counter on the daemon's registry (``<kind>_phase_<phase>``) and the whole
of it to ``<kind>_timeline``.  The intervals partition the timeline, so
the phase sums equal the timeline's sum by construction.  ``kind`` is
``op`` for a client operation on its primary and ``subop`` for a shard
sub-operation (whose phases fold to ``queue`` and ``apply``).

A sub-op's ``apply`` is cut in three where threads take its work over:
the handler's return (``sub_op_applied``, on the handler's thread), the
durability of its transactions (``sub_op_committed``, the kv-sync
thread's reading for the batch that held them) and the acknowledgement
handed to the messenger (``commit_sent``, on the store's finisher).  The
same close books the three parts as ``subop_apply_<part>`` beside the
phases; they add up to ``subop_phase_apply``, sub-op for sub-op.  A
client op whose fan-out wait a reply ended carries that reply's queue
(``reply_queue_ns``: receive stamp to handler start, inside the wait),
and its close books it as ``op_reply_queue``, one sample an op.

Flight-recorder extension (the tail-based sampling half of the tracing
story): an op may carry its ROOT SPAN.  When the op crosses the
complaint threshold — at finish, or mid-flight via ``note_inflight_slow``
from the daemon's tick — the tracker promotes an unsampled span out of
the tracer's side ring (retroactive retention) and fires ``on_slow``
exactly once per op, which the daemon uses to journal a ``slow_op``
cluster event.  Historic entries of slow traced ops carry ``trace_id``
so ``dump_historic_slow_ops`` can attach the full merged trace.
"""

from __future__ import annotations

import collections
import itertools
import operator
import threading

from .perf import CounterType
from .tracer import now_ns

#: mark -> the phase it opens.  Names follow the reference's
#: ``TrackedOp::mark_event`` strings where it has one.  A mark outside
#: the vocabulary (tests, ad-hoc callers) opens ``prepare``.
MARKS = {
    "initiated": "queue",            # the messenger's receive stamp
    "queued_for_pg": "queue",        # handed to the op scheduler
    "reached_pg": "prepare",         # the handler starts
    # queued on the object's lock: a write always passes through it,
    # a primary's read only when it finds the object held
    "waiting_for_obj_lock": "obj_lock",
    "started": "prepare",            # lock held / peering gate passed
    "waiting_for_subreads": "subread_wait",   # sub-reads sent
    "sub_reads_rec": "prepare",      # k sub-read replies are in
    "ec_queued": "batch_wait",       # EC op queued in the batcher
    "ec_taken": "flush",             # taken by a flush
    "ec_done": "prepare",            # the flush's result is on the host
    "waiting_for_subops": "subwrite_wait",    # sub-writes sent
    "sub_op_commit_rec": "prepare",  # the last sub-write ack is in
    # a shard sub-op's cuts of its apply (``apply_parts``)
    "sub_op_applied": "prepare",     # the handler returned
    "sub_op_committed": "prepare",   # its transactions are durable
    "commit_sent": "prepare",        # reply handed to the messenger
    "done": "prepare",
}

#: the phases of a client operation, in the order of its path
OP_PHASES = ("queue", "obj_lock", "prepare", "subread_wait",
             "batch_wait", "flush", "subwrite_wait")
#: a sub-operation knows two: waiting in queues, and the rest
SUBOP_PHASES = ("queue", "apply")
KIND_PHASES = {"op": OP_PHASES, "subop": SUBOP_PHASES}


def phase_of(kind: str, mark: str) -> str:
    phase = MARKS.get(mark, "prepare")
    if kind == "subop" and phase != "queue":
        return "apply"
    return phase


#: kind -> phase -> TIME counter name (None: the whole timeline's)
_COUNTERS = {kind: {**{p: f"{kind}_phase_{p}" for p in phases},
                    None: f"{kind}_timeline"}
             for kind, phases in KIND_PHASES.items()}
#: kind -> mark -> phase, and the phase of a mark outside MARKS: what
#: ``phase_of`` says, looked up once a mark at every close
_PHASES = {kind: ({m: phase_of(kind, m) for m in MARKS},
                  phase_of(kind, ""))
           for kind in KIND_PHASES}
_AT = operator.itemgetter(0)


#: a sub-op's apply, cut at the handler's return and at durability:
#: handler start -> handler return -> durable -> ack handed to the
#: messenger
SUBOP_APPLY_PARTS = ("handler", "commit", "finish")
_APPLY_COUNTERS = tuple(f"subop_apply_{p}" for p in SUBOP_APPLY_PARTS)
#: beside the phases, inside subwrite_wait / subread_wait
_REPLY_QUEUE = "op_reply_queue"


def phase_counters(kind: str) -> tuple[str, ...]:
    """The TIME counters one kind of op books at finish."""
    return tuple(_COUNTERS[kind].values())


def register_phase_counters(perf) -> None:
    """Every phase counter, the sub-op's apply parts and the reply
    queue, zeroed: 0 is a reading."""
    names = [n for kind in KIND_PHASES for n in phase_counters(kind)]
    for name in names + [*_APPLY_COUNTERS, _REPLY_QUEUE]:
        if not perf.has(name):
            perf.add(name, CounterType.TIME)


class TrackedOp:
    __slots__ = ("tracker", "op_id", "key", "kind", "desc", "start_ns",
                 "end_ns", "events", "done", "span", "slow_noted",
                 "reply_queue_ns")

    def __init__(self, tracker: "OpTracker", op_id: int, desc: str,
                 span=None, start_ns: int | None = None,
                 kind: str = "op", key=None):
        self.tracker = tracker
        self.op_id = op_id
        self.key = op_id if key is None else key
        self.kind = kind
        self.desc = desc
        self.start_ns = start_ns or now_ns()
        self.events: list[tuple[int, str]] = [(self.start_ns, "initiated")]
        self.done = False
        self.end_ns = 0  # the close's reading, once done
        # root span (utils/tracer.Span) when the op is traced — sampled
        # or unsampled; the flight recorder promotes the latter on slow
        self.span = span
        self.slow_noted = False  # on_slow fired (once per op)
        # the queue of the reply that ended the op's fan-out wait
        self.reply_queue_ns: int | None = None

    @property
    def start(self) -> float:
        return self.start_ns / 1e9

    def mark(self, event: str, at_ns: int | None = None) -> int:
        """Append ``event`` at ``at_ns`` (a now_ns() reading the caller
        already took) or now; returns the reading, so a span that
        covers the phase the mark opens starts on the same one."""
        if at_ns is None:
            at_ns = now_ns()
        self.events.append((at_ns, event))
        return at_ns

    def last_ns(self) -> int:
        """The newest mark's reading."""
        return self.events[-1][0]

    def finish(self, at_ns: int | None = None) -> None:
        """Close the timeline, once: racing finishers (a reply leaving
        beside a sweep) are told apart under the tracker's lock.
        ``at_ns``: the reading of the mark that ended it."""
        if not self.done:
            self.tracker._finish(self, at_ns)

    def age(self) -> float:
        end = self.end_ns if self.done else now_ns()
        return (end - self.start_ns) / 1e9

    def intervals(self) -> dict[str, int]:
        """phase -> nanoseconds, over consecutive marks.  Marks of one
        op come from several threads, so they are put in time order
        first; the values then sum to last mark minus first.  A mark
        read after the close (a handler that returned as its reply left
        on another thread) lies outside the timeline."""
        ev = sorted(self.events, key=_AT)
        if self.done and ev[-1][0] > self.end_ns:
            ev = [e for e in ev if e[0] <= self.end_ns]
        table, other = _PHASES[self.kind]
        out: dict[str, int] = {}
        for (t0, name), (t1, _n) in zip(ev, ev[1:]):
            phase = table.get(name, other)
            out[phase] = out.get(phase, 0) + (t1 - t0)
        return out

    def reply_queued(self, wait: str, reply: tuple | None) -> None:
        """The reply that ended the fan-out wait opened by the mark
        ``wait``: from its receive stamp to its handler's start
        (``reply``, the two readings; None where the primary read the
        shard itself: 0), held inside the wait.  The close books it as
        ``op_reply_queue``; no mark, so the phases stay as they are."""
        opened = next((t for t, e in reversed(self.events) if e == wait),
                      None)
        if opened is None:
            return
        recv, start = reply or (0, 0)
        self.reply_queue_ns = max(start - max(recv, opened), 0) if recv \
            else 0

    def commit_cuts(self) -> tuple[int, int]:
        """The readings that cut a closed sub-op's apply: its handler's
        return (``sub_op_applied``) and its transactions' durability
        (``sub_op_committed``), each held between the cut before it and
        the close.  Where the reply left inside the handler there is no
        ``sub_op_applied`` and both cuts fall on the close; a durability
        read before the handler returned cuts at the return."""
        end = self.end_ns
        applied = committed = None
        for t, name in self.events:
            if name == "sub_op_applied":
                applied = t
            elif name == "sub_op_committed":
                committed = t
        applied = end if applied is None else min(applied, end)
        committed = applied if committed is None \
            else min(max(committed, applied), end)
        return applied, committed

    def apply_parts(self, apply_ns: int) -> tuple[int, int, int]:
        """A closed sub-op's ``apply`` (``apply_ns``, as ``intervals``
        gives it) in ``SUBOP_APPLY_PARTS``, cut by ``commit_cuts``; the
        parts add up to ``apply_ns``."""
        applied, committed = self.commit_cuts()
        commit, finish = committed - applied, self.end_ns - committed
        return apply_ns - commit - finish, commit, finish

    def dump(self) -> dict:
        d = {
            "id": self.op_id, "description": self.desc,
            "age_seconds": self.age(), "done": self.done,
            "events": [{"at": t / 1e9, "event": e}
                       for t, e in sorted(self.events, key=_AT)],
        }
        if self.kind != "op":
            d["kind"] = self.kind
        if self.span is not None:
            d["trace_id"] = self.span.trace_id
            d["trace_sampled"] = bool(self.span.sampled)
        return d

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.finish()
        return False


class OpTracker:
    def __init__(self, history_size: int = 256, slow_op_seconds: float = 5.0,
                 on_slow=None, perf=None, lat_counter: str = "op_lat_us"):
        """``on_slow(op)`` fires at most once per op, OUTSIDE the
        tracker lock, the first time the op is seen past the complaint
        threshold (at finish, or mid-flight from note_inflight_slow) —
        the daemon's hook for journaling the ``slow_op`` event.

        ``perf``/``lat_counter`` name a pow2 histogram every finished
        client op's end-to-end latency lands in (the SLO ``client_op``
        signal: receive stamp to reply sent); sampled-trace ops attach
        their trace_id as the bucket exemplar so the p99 bucket
        resolves to waterfalls.  The same finish books the op's phase
        intervals (module docstring)."""
        self._ids = itertools.count(1)
        self._inflight: dict = {}
        # finished ops; dumped when an operator asks, not on the IO path
        self._history: collections.deque[TrackedOp] = collections.deque(
            maxlen=history_size)
        self._slow_threshold = slow_op_seconds
        self._slow_count = 0
        self._on_slow = on_slow
        self._perf = perf
        self._lat_counter = lat_counter
        self._lock = threading.Lock()
        if perf is not None:
            register_phase_counters(perf)

    def bind_perf(self, perf, lat_counter: str | None = None) -> None:
        """Late-bind the latency registry (the daemon builds its
        tracker before its perf registry exists) and register the
        phase counters on it."""
        self._perf = perf
        if lat_counter is not None:
            self._lat_counter = lat_counter
        register_phase_counters(perf)

    def create(self, desc: str, span=None, start_ns: int | None = None,
               kind: str = "op", key=None) -> TrackedOp:
        """``key`` names the op for ``inflight(key)`` — the daemon
        finds a client op again by (client, tid) where its reply
        leaves; an op of the same key still in flight is finished
        first (a client that reuses a tid has given up on it)."""
        op = TrackedOp(self, next(self._ids), desc, span=span,
                       start_ns=start_ns, kind=kind, key=key)
        with self._lock:
            stale = self._inflight.get(op.key)
            self._inflight[op.key] = op
        if stale is not None:
            stale.finish()
        return op

    def inflight(self, key) -> TrackedOp | None:
        return self._inflight.get(key)

    def _retain_trace(self, op: TrackedOp) -> None:
        """Force-retain an unsampled root span the moment its op turns
        slow (the tail-based decision: evidence first, bookkeeping
        after).  Must run outside the tracker lock — the tracer has its
        own leaf lock."""
        span = op.span
        if span is not None and not span.sampled \
                and span._tracer is not None:
            span._tracer.promote(span)

    def _note_slow(self, op: TrackedOp) -> bool:
        """Check-and-set the once-per-op slow flag.  Caller holds
        _lock."""
        if op.slow_noted:
            return False
        op.slow_noted = True
        self._slow_count += 1
        return True

    def _book(self, op: TrackedOp, age: float) -> None:
        perf = self._perf
        spent = op.intervals()
        names = _COUNTERS[op.kind]
        samples = [(names[phase], spent.get(phase, 0) / 1e9)
                   for phase in KIND_PHASES[op.kind]]
        samples.append((names[None], sum(spent.values()) / 1e9))
        if op.kind == "subop":
            handler, commit, finish = op.apply_parts(spent.get("apply", 0))
            samples += ((_APPLY_COUNTERS[0], handler / 1e9),
                        (_APPLY_COUNTERS[1], commit / 1e9),
                        (_APPLY_COUNTERS[2], finish / 1e9))
        elif op.reply_queue_ns is not None:
            samples.append((_REPLY_QUEUE, op.reply_queue_ns / 1e9))
        perf.tinc_many(samples)
        if op.kind == "op":
            span = op.span
            perf.hinc(
                self._lat_counter, age * 1e6,
                exemplar=span.trace_id
                if span is not None and span.sampled else None)

    def _finish(self, op: TrackedOp, at_ns: int | None = None) -> None:
        newly_slow = False
        with self._lock:
            if op.done:
                return
            op.end_ns = op.mark("done", at_ns)
            op.done = True
            age = op.age()
            if self._inflight.get(op.key) is op:
                del self._inflight[op.key]
            if age >= self._slow_threshold:
                newly_slow = self._note_slow(op)
            self._history.append(op)
        if self._perf is not None:
            self._book(op, age)
        if newly_slow:
            self._retain_trace(op)
            if self._on_slow is not None:
                try:
                    self._on_slow(op)
                except Exception:  # noqa: BLE001 - recorder must not kill IO
                    pass

    def note_inflight_slow(self) -> list[TrackedOp]:
        """Tick-driven flight-recorder sweep: ops that crossed the
        complaint threshold WHILE STILL IN FLIGHT (a wedged op may
        never finish — its evidence must not wait for a finish that
        never comes).  Promotes their traces, fires on_slow once each,
        and returns the newly-slow ops."""
        with self._lock:
            newly = [o for o in self._inflight.values()
                     if o.age() >= self._slow_threshold
                     and self._note_slow(o)]
        for op in newly:
            self._retain_trace(op)
            if self._on_slow is not None:
                try:
                    self._on_slow(op)
                except Exception:  # noqa: BLE001
                    pass
        return newly

    def dump_ops_in_flight(self) -> list[dict]:
        with self._lock:
            return [o.dump() for o in self._inflight.values()]

    def dump_historic_ops(self) -> list[dict]:
        with self._lock:
            ops = list(self._history)
        return [o.dump() for o in ops]

    def slow_ops(self) -> list[dict]:
        """Currently in-flight ops past the slow threshold."""
        with self._lock:
            return [o.dump() for o in self._inflight.values()
                    if o.age() >= self._slow_threshold]

    def dump_historic_slow_ops(self) -> list[dict]:
        """Completed ops whose total duration crossed the complaint
        threshold (the reference's dump_historic_slow_ops verb — the
        history entry's age_seconds was fixed at finish time, so it IS
        the op's duration).  Traced entries carry trace_id; the daemon
        verb attaches the merged trace."""
        with self._lock:
            ops = [o for o in self._history
                   if o.age() >= self._slow_threshold]
        return [o.dump() for o in ops]

    def slow_op_count(self) -> int:
        """Cumulative count of ops seen past the threshold (finished
        or swept mid-flight; each op counts once)."""
        with self._lock:
            return self._slow_count

    def slow_summary(self, max_ops: int = 3) -> dict:
        """The health-mux feed: currently-blocked slow ops (these drive
        — and clear — HEALTH_WARN SLOW_OPS), the cumulative count, and
        the worst in-flight offenders by age."""
        with self._lock:
            slow = sorted((o for o in self._inflight.values()
                           if o.age() >= self._slow_threshold),
                          key=lambda o: o.start_ns)
            return {
                "inflight": len(slow),
                "total": self._slow_count,
                "complaint_time": self._slow_threshold,
                "worst": [{"description": o.desc,
                           "age_seconds": round(o.age(), 3)}
                          for o in slow[:max_ops]],
            }
