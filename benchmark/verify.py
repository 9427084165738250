"""What decides ``correct``: what the timed path stored and returned,
against the plain reference, once the window has closed.

Every comparison is exact, so every limit is 0:

- ``answers_missing``: operations of the window that never answered;
- ``answers_wrong``: kept answers of the window's reads that are not a
  version of the object the read could have seen;
- ``readbacks_wrong``: objects of a seeded sample, read back through
  the client after the window, that are not a version that may be the
  last one;
- ``shards_wrong``: shard streams of those objects, as the live OSDs'
  stores hold them (data and parity), that differ from the reference's
  encoding of the version read back;
- ``nothing_compared``: 1 where one of those comparisons had nothing
  to compare.

Which versions a read may see.  Callers overlap, so an object's writes
are ordered only where one was acknowledged before the next was sent.
A read sent at s and answered at a may return version v when v's write
was sent before a, and no other write of the object was sent after v's
acknowledgement and acknowledged before s.  Version 0 is what set-up
wrote.  A write that failed may have been applied: it stays possible
and rules out nothing.
"""

from __future__ import annotations

import math
import sys
from dataclasses import dataclass

import numpy as np

from . import reference
from .generators.closed_loop import TAG, Op, Plan

INF = math.inf


@dataclass
class History:
    """One object's writes: version -> (sent, acknowledged)."""
    writes: dict[int, tuple[float, float]]

    def may_see(self, version: int, sent: float, answered: float) -> bool:
        w = self.writes.get(version)
        if w is None or w[0] >= answered:
            return False
        # the latest-sent write among those acknowledged before the
        # read was sent: anything acknowledged before it was sent is gone
        bar = max((s for v, (s, a) in self.writes.items()
                   if a < sent and v != version), default=-INF)
        return w[1] >= bar

    def candidates(self, sent: float, answered: float) -> list[int]:
        return [v for v in sorted(self.writes, reverse=True)
                if self.may_see(v, sent, answered)]


def histories(ops: list[Op], populated: set[int]) -> dict[int, History]:
    out = {k: History({0: (-INF, -INF)}) for k in populated}
    for op in ops:
        if op.kind != "write":
            continue
        acked = op.t_done if op.ok else INF
        out.setdefault(op.key, History({})).writes[op.version] = (
            op.t_submit, acked)
    return out


def _which(plan: Plan, key: int, got, hist: History | None, sent: float,
           answered: float) -> int | None:
    """The version ``got`` is, among those the read may see."""
    if hist is None or got is None:
        return None
    if plan.tag:
        if len(got) != plan.object_bytes:
            return None
        k, v = TAG.unpack_from(got)
        if k != key or not hist.may_see(v, sent, answered):
            return None
        return v if got == plan.payload(key, v) else None
    for v in hist.candidates(sent, answered):
        if got == plan.payload(key, v):
            return v
    return None


def sample_keys(plan: Plan, ops: list[Op], hists: dict[int, History],
                count: int) -> list[int]:
    """Objects to read back: drawn from the seed among those the window
    touched and that hold an acknowledged version, the most-used one
    always among them."""
    uses: dict[int, int] = {}
    for op in ops:
        uses[op.key] = uses.get(op.key, 0) + 1
    live = sorted(k for k in uses if k in hists and any(
        a < INF for _s, a in hists[k].writes.values()))
    if not live:
        return []
    rng = np.random.default_rng([plan.seed, 0x7661])
    pick = set(rng.choice(live, size=min(count, len(live)),
                          replace=False).tolist())
    pick.add(max(live, key=lambda k: (uses[k], -k)))
    return sorted(pick)


def compare(plan: Plan, ops: list[Op], source, k: int, m: int,
            stripe_unit: int, populated: set[int], sample: int) -> dict:
    """``source``: ``read(name)`` and ``stored_shards(name)`` of the
    system under test (or of a control put in its place)."""
    hists = histories(ops, populated)
    missing = sum(1 for op in ops if op.t_done is None)
    wrong = compared = short = 0
    for op in ops:
        if op.kind != "read" or not op.ok:
            continue
        if op.answer_len != plan.object_bytes:
            short += 1
        if op.answer is None:
            continue
        compared += 1
        if _which(plan, op.key, op.answer, hists.get(op.key),
                  op.t_submit, op.t_done) is None:
            wrong += 1
    rb_wrong = rb_compared = sh_wrong = sh_compared = 0
    for key in sample_keys(plan, ops, hists, sample):
        name = plan.name(key)
        try:
            got = source.read(name)
        except Exception as e:  # noqa: BLE001 - an answer that says the wrong thing
            print(f"read-back of {name} failed: {e!r}", file=sys.stderr)
            got = None
        rb_compared += 1
        v = _which(plan, key, got, hists[key], INF, INF)
        if v is None:
            rb_wrong += 1
            v = hists[key].candidates(INF, INF)[0]
        want = reference.encode(bytes(plan.payload(key, v)), k, m,
                                stripe_unit)
        for shard, have in source.stored_shards(name).items():
            sh_compared += 1
            if have != want[shard]:
                sh_wrong += 1
    reads = any(op.kind == "read" for op in ops)
    nothing = int(rb_compared == 0 or sh_compared == 0
                  or (reads and compared == 0))
    zero = lambda v: {"value": v, "limit": 0}  # noqa: E731
    return {
        "answers_missing": zero(missing),
        "answers_wrong": zero(wrong + short),
        "readbacks_wrong": zero(rb_wrong),
        "shards_wrong": zero(sh_wrong),
        "nothing_compared": zero(nothing),
        "answers_compared": {"value": compared, "limit": None},
        "readbacks_compared": {"value": rb_compared, "limit": None},
        "shards_compared": {"value": sh_compared, "limit": None},
    }


def is_correct(compared: dict) -> bool:
    return all(c["value"] <= c["limit"] for c in compared.values()
               if c["limit"] is not None)
