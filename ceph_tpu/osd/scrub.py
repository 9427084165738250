"""Scrub: consistency auditing of PG replicas/shards, with repair.

The capability of the reference's scrubber (src/osd/scrubber/ — SURVEY.md
§2.5: shallow scrub compares object metadata across replicas, deep scrub
compares full-data digests via scrub_backend.cc; EC shards check local
checksums; `pg repair` rewrites bad copies from the authoritative one) and
of the EC consistency checker tool
(src/erasure-code/consistency/ceph_ec_consistency_checker.cc: re-encode
parity from data shards and compare).

ONE pass (``_ScrubPass``), run by the PG's primary in chunks of
``osd_scrub_chunk_max`` object names under the scheduler's ``scrub``
class, whoever started it: the operator's verb (``MScrubRequest``, the
``ceph pg [deep-]scrub / repair`` role; the reply is sent when the pass
has ended) or the schedule (``_scrub_tick``: a PG its primary has not
scrubbed for ``osd_scrub_min_interval``; deep, repairing what it finds,
its name cursor persisted in the PG's scrub meta object so that a
restarted primary resumes where it stopped).  A chunk:

1. **holds its objects** (``_scrub_take_range``): the pass takes a
   shared place on the lock of every object of the range that has an
   op in flight, and on any other the moment a writer comes for it
   (``OSDDaemon._obj_lock``).  A write of an object of the chunk either
   has been acknowledged by every shard before the maps are taken, or
   waits until they have been compared; reads go on, and writes outside
   the range do not wait.  (Upstream's ``write_blocked_by_scrub``; EC
   pools, whose mutations pass through the object's lock.)
2. **asks every member for its map of the range** (``MScrubShard`` with
   the range; ``_scrub_shard_map``, the one implementation of "read the
   stored shards of a range and hold each to its stored digest"): size
   and version an object, and when deep the CRC32C of the stored bytes,
   all of a chunk's in one fold through ``ECBatcher.verify``
   (ec/verify.py: a device program where ``osd_scrub_fold`` says so,
   the native sweep elsewhere).  A map that does not come is asked for
   again (``SCRUB_RESEND_S``), and after ``SCRUB_SENDS`` the pass fails
   with an error its caller sees.
3. **compares** (each copy against its stored digest, the copies
   against each other: ``_scrub_compare_ec`` /
   ``_scrub_compare_replicated``), repairs if asked, gives the objects
   up and queues the next chunk.

- replicated: every copy must match the authoritative (max-version) one;
- EC: each shard's stored digest attr must match its recomputed data (the
  per-shard local check), every member holds every object at one
  version, and with repair a bad shard is rebuilt from the others.
"""

from __future__ import annotations

import time
import zlib
from dataclasses import dataclass, field

import numpy as np

from ..ec.verify import unpad_digests, verifier, verify_bucket
from ..msg.messages import (MPGPull, MPGPush, MScrubMap, MScrubRequest,
                            MScrubResult, MScrubShard, PgId)
from ..utils.log import dout
from ..utils.tracer import annotate, now_ns
from .objectstore import (CollectionId, NoSuchCollection, NoSuchObject,
                          ObjectId, Transaction)
from .snaps import to_oid, vname_of

#: a chunk's map that has not come after this long is asked for again,
#: and the pass fails when a member has been asked this often in vain
SCRUB_RESEND_S = 2.0
SCRUB_SENDS = 5
ETIMEDOUT = -110

#: what a finding can be (``scrub_finding_<kind>`` counts each)
FINDING_KINDS = ("read_error", "digest_missing", "digest_mismatch",
                 "missing_shard", "stale_version", "missing_copy",
                 "size_mismatch", "replica_digest_mismatch")
#: findings that recovery can mend without the repair verb
RECOVERABLE = ("missing_shard", "stale_version", "missing_copy")
COUNTERS = ("scrub_verified_bytes", "scrub_verify_launches",
            "scrub_mismatches", "scrub_digest_missing",
            "scrub_auto_chunks",
            *(f"scrub_finding_{k}" for k in FINDING_KINDS))
#: a chunk from taken to compared; of that, waiting for its objects;
#: a client write from queueing behind a chunk to its start
TIMES = ("scrub_chunk", "scrub_chunk_lock_wait", "op_scrub_wait")


@dataclass
class _ScrubPass:
    """One pass over one PG, on its primary."""

    pgid: PgId
    deep: bool
    repair: bool
    scheduled: bool = False        # the schedule's: cursor persisted
    waiters: list = field(default_factory=list)   # (client, tid)
    queued: list = field(default_factory=list)    # verbs for a next pass
    cursor: str | None = None      # last name compared
    issues: list = field(default_factory=list)
    repaired: int = 0
    objects: int = 0
    started: float = 0.0
    span: object = None  # head-sampled root span (finished at the end)
    # ---- the chunk in flight
    tid: int | None = None
    upto: str | None = None        # its last name (None: to the end)
    held: dict = field(default_factory=dict)      # name -> _ObjHold
    awaited: int = 0               # places on locks not yet granted
    taken_ns: int = 0
    waiting_for: set = field(default_factory=set)
    maps: dict = field(default_factory=dict)      # osd -> scrub map
    sent_at: float = 0.0
    sends: int = 0

    def covers(self, name: str) -> bool:
        """Whether the chunk in flight holds the object ``name``."""
        return (self.cursor is None or name > self.cursor) and \
            (self.upto is None or name <= self.upto)


class ScrubMixin:
    """Scrub handlers; mixed into OSDDaemon."""

    # ------------------------------------------------------------ a shard
    def _scrub_verifier(self):
        return verifier(str(self.cfg["osd_scrub_fold"]))

    def _scrub_expect(self, nbytes: int) -> None:
        """This OSD stores a stream of ``nbytes``: at default settings
        it will scrub it, so its length bucket's verify program is
        compiled now, off the IO path (accelerators only)."""
        bucket = verify_bucket(nbytes)
        if bucket in self._scrub_buckets:
            return
        self._scrub_buckets.add(bucket)
        if self.cfg["osd_scrub_auto"]:
            self._ec_batcher.expect_verify(self._scrub_verifier(), bucket)

    def _scrub_shard_map(self, pgid: PgId, deep: bool,
                         after: str | None = None,
                         upto: str | None = None) -> dict:
        """``(vname, shard) -> {size, version[, digest, stored_digest]}``
        of what this OSD stores of the PG's objects ``after < name <=
        upto``: the one implementation of a scrub's read side.  Deep:
        the stored bytes of the range, in front of zeros up to their
        length bucket, go through ``ECBatcher.verify`` one bucket a
        call, and ``digest`` is the CRC32C of the bytes alone."""
        cid = CollectionId(pgid.pool, pgid.seed)
        out: dict = {}
        try:
            oids = self.store.list_objects(cid)
        except Exception:  # noqa: BLE001 - no collection yet
            return out
        buckets: dict[int, list] = {}
        with annotate("ceph:scrub-chunk"):
            for oid in sorted(oids, key=lambda o: (o.name, o.shard,
                                                   o.generation)):
                if oid.shard <= -2:
                    continue  # PG metadata (pglog/snapmapper/cursor)
                if (after is not None and oid.name <= after) or \
                        (upto is not None and oid.name > upto):
                    continue
                key = (vname_of(oid), oid.shard)  # clones: vnames
                try:
                    attrs = self.store.getattrs(cid, oid)
                    entry = {"version": int(attrs.get("v", 0))}
                    if deep:
                        data = self.store.read(cid, oid).to_array()
                        entry["size"] = len(data)
                        entry["stored_digest"] = attrs.get("d")
                        entry["digest"] = None
                        if entry["stored_digest"] is None:
                            if len(data):
                                self.perf.inc("scrub_digest_missing")
                        else:
                            buckets.setdefault(
                                verify_bucket(len(data)), []).append(
                                    (entry, data))
                    else:
                        entry["size"] = self.store.stat(cid, oid)["size"]
                    out[key] = entry
                except (NoSuchObject, NoSuchCollection):
                    continue  # removed since the listing: not there
                except Exception as e:  # noqa: BLE001 - unreadable
                    out[key] = {"error": repr(e)}
            if buckets:
                self._scrub_verify(buckets)
        return out

    def _scrub_verify(self, buckets: dict[int, list]) -> None:
        """Fill in ``digest`` of a map's entries: one fold a length
        bucket through the batcher."""
        ver = self._scrub_verifier()
        for blen, items in sorted(buckets.items()):
            # what lies in front of a short stream is zero (the fold
            # fills its last launch with zero rows itself)
            rows = np.empty((len(items), blen), dtype=np.uint8)
            for i, (_entry, data) in enumerate(items):
                pad = blen - len(data)
                rows[i, :pad] = 0
                rows[i, pad:] = data
            digs = unpad_digests(
                self._ec_batcher.verify(ver, rows), blen,
                [len(data) for _e, data in items])
            self.perf.inc("scrub_verify_launches")
            for (entry, _data), d in zip(items, digs):
                entry["digest"] = int(d)
            if ver.on_device:
                # the roofline's numerator: bytes a device program read
                self.perf.inc("scrub_verified_bytes",
                              sum(len(data) for _e, data in items))

    def _handle_scrub_shard(self, conn, m: MScrubShard) -> None:
        conn.send(MScrubMap(m.tid, m.pgid, self.osd_id,
                            self._scrub_shard_map(m.pgid, m.deep,
                                                  m.after, m.upto)))

    # -------------------------------------------------- the primary: start
    def _handle_scrub_request(self, conn, m: MScrubRequest) -> None:
        up = self.osdmap.pg_to_up_osds(m.pgid.pool, m.pgid.seed)
        if self._primary_of(up) != self.osd_id:
            conn.send(MScrubResult(m.tid, m.pgid, -116, []))
            return
        self._scrub_begin(m.pgid, m.deep, m.repair,
                          waiter=(m.client, m.tid))

    def _scrub_begin(self, pgid: PgId, deep: bool, repair: bool, *,
                     waiter: tuple | None = None,
                     scheduled: bool = False) -> None:
        """Start a pass of the PG, or queue the verb behind the one
        that runs (its findings would not be those of a whole pass)."""
        key = (pgid.pool, pgid.seed)
        with self._scrub_lock:
            running = self._scrub_passes.get(key)
            if running is not None:
                if waiter is not None:
                    running.queued.append((deep, repair, waiter))
                return
            ps = _ScrubPass(pgid, deep, repair, scheduled=scheduled,
                            started=time.time())
            if waiter is not None:
                ps.waiters.append(waiter)
            self._scrub_passes[key] = ps
        # scrubs are ROOT ops for the head sampler (trace_sample_rate):
        # the span covers request -> chunks -> compare/repair
        ps.span = self.tracer.sample_root(
            "scrub", pg=self._pgstr(pgid), deep=deep)
        if scheduled:
            ps.cursor = self._scrub_cursor_load(
                CollectionId(pgid.pool, pgid.seed))
            self.events.emit(
                "scrub", f"pg {self._pgstr(pgid)} auto deep-scrub start",
                pg=self._pgstr(pgid), event="scrub_start",
                start_ts=ps.started, done=0, total=0)
        self._scrub_chunk_begin(ps)

    def _scrub_enqueue(self, pgid: PgId, fn) -> None:
        """Run ``fn()`` on the PG's worker under the ``scrub`` class
        (never dropped: the pass has no other way on), or here where
        there is no scheduler (``osd_op_queue=fifo``)."""
        if self._use_mclock:
            self.scheduler.enqueue(
                "scrub", (lambda _c, _m: fn(), None, None),
                key=(pgid.pool, pgid.seed), force=True)
        else:
            fn()

    # -------------------------------------------------- the primary: chunks
    def _scrub_names(self, pgid: PgId, after: str | None) -> list:
        """Sorted names of the objects this OSD holds of the PG past
        ``after``."""
        try:
            oids = self.store.list_objects(
                CollectionId(pgid.pool, pgid.seed))
        except NoSuchCollection:
            return []
        return sorted({o.name for o in oids if o.shard > -2
                       and (after is None or o.name > after)})

    def _scrub_chunk_begin(self, ps: _ScrubPass) -> None:
        names = self._scrub_names(ps.pgid, ps.cursor)
        chunk = names[:int(self.cfg["osd_scrub_chunk_max"])]
        # the last chunk has no upper end: what only another member
        # holds past my last name is in its map too
        ps.upto = chunk[-1] if len(chunk) < len(names) else None
        ps.taken_ns = now_ns()
        if ps.scheduled:
            self.perf.inc("scrub_auto_chunks")
        if self._scrub_take_range(ps):
            self._scrub_chunk_send(ps)

    def _scrub_take_range(self, ps: _ScrubPass) -> bool:
        """Hold the chunk's objects (module docstring, 1.); True if no
        op in flight has to be waited for."""
        def granted(_hold) -> bool:
            with self._pending_lock:
                ps.awaited -= 1
                go = ps.awaited == 0
            if go:
                self._scrub_enqueue(ps.pgid,
                                    lambda: self._scrub_chunk_send(ps))
            return True   # the place is kept until the chunk is done

        with self._pending_lock:
            self._scrub_chunks[ps.pgid] = ps
            for key, st in self._obj_locks.items():
                if key[0] == ps.pgid and ps.covers(key[1]):
                    if not self._scrub_hold_locked(ps, key, st, granted):
                        ps.awaited += 1
            return ps.awaited == 0

    def _scrub_release(self, ps: _ScrubPass) -> None:
        with self._pending_lock:
            self._scrub_chunks.pop(ps.pgid, None)
            holds = list(ps.held.values())
            ps.held = {}
            ps.awaited = 0
        for hold in holds:
            self._obj_unlock(hold.key, hold)

    def _scrub_chunk_send(self, ps: _ScrubPass) -> None:
        if self._scrub_passes.get((ps.pgid.pool, ps.pgid.seed)) is not ps:
            return   # the pass ended while it waited for its objects
        self.perf.tinc("scrub_chunk_lock_wait",
                       (now_ns() - ps.taken_ns) / 1e9)
        up = self.osdmap.pg_to_up_osds(ps.pgid.pool, ps.pgid.seed)
        if self._primary_of(up) != self.osd_id:
            self._scrub_pass_end(ps, result=-116)  # the PG moved on
            return
        members = {u for u in up if u is not None}
        with self._scrub_lock:
            ps.tid = next(self._tids)
            ps.waiting_for = set(members)
            ps.maps = {}
            ps.sent_at = time.time()
            ps.sends = 1
            self._pending_scrubs[ps.tid] = ps
        for osd in members - {self.osd_id}:
            self.messenger.send_message(
                f"osd.{osd}", MScrubShard(ps.tid, ps.pgid, ps.deep,
                                          after=ps.cursor, upto=ps.upto))
        if self.osd_id in members:
            self._on_scrub_map(ps.tid, self.osd_id, self._scrub_shard_map(
                ps.pgid, ps.deep, ps.cursor, ps.upto))

    def _scrub_resend(self, now: float) -> None:
        """Heartbeat hook: ask again for the maps that have not come; a
        member that never answers fails the pass."""
        with self._scrub_lock:
            late = [ps for ps in self._pending_scrubs.values()
                    if now - ps.sent_at > SCRUB_RESEND_S]
            for ps in late:
                ps.sent_at = now
                ps.sends += 1
        for ps in late:
            if ps.sends > SCRUB_SENDS:
                dout("osd", 1)("%s: scrub %s: no map from %s", self.name,
                               ps.pgid, sorted(ps.waiting_for))
                self._scrub_enqueue(
                    ps.pgid, lambda ps=ps: self._scrub_pass_end(
                        ps, result=ETIMEDOUT))
                continue
            for osd in list(ps.waiting_for):
                if osd != self.osd_id:
                    self.messenger.send_message(
                        f"osd.{osd}",
                        MScrubShard(ps.tid, ps.pgid, ps.deep,
                                    after=ps.cursor, upto=ps.upto))

    def _handle_scrub_map(self, conn, m: MScrubMap) -> None:
        self._on_scrub_map(m.tid, m.from_osd, m.objects)

    def _on_scrub_map(self, tid: int, from_osd: int, objects: dict) -> None:
        with self._scrub_lock:
            ps = self._pending_scrubs.get(tid)
            if ps is None or from_osd not in ps.waiting_for:
                return   # an answer to a question asked twice
            ps.maps[from_osd] = objects
            ps.waiting_for.discard(from_osd)
            if ps.waiting_for:
                return
            del self._pending_scrubs[tid]
            ps.tid = None
        self._scrub_chunk_compare(ps)

    # ------------------------------------------------------------- compare
    def _scrub_chunk_compare(self, ps: _ScrubPass) -> None:
        pool = self.osdmap.pools[ps.pgid.pool]
        issues: list[dict] = []
        names = set()
        for osd, omap_ in ps.maps.items():
            for key, entry in omap_.items():
                names.add(key[0])
                if "error" in entry:
                    issues.append({"osd": osd, "object": key[0],
                                   "shard": key[1], "kind": "read_error",
                                   "detail": entry["error"]})
                elif ps.deep and entry.get("stored_digest") is None:
                    # a non-empty object with no stored digest is NOT
                    # clean — it is unverifiable, which deep scrub must
                    # surface (every write path stamps "d"; an absent
                    # one means an interrupted transaction or attr rot)
                    if entry.get("size", 0) > 0:
                        issues.append({"osd": osd, "object": key[0],
                                       "shard": key[1],
                                       "kind": "digest_missing"})
                elif ps.deep and entry["digest"] != entry["stored_digest"]:
                    issues.append({"osd": osd, "object": key[0],
                                   "shard": key[1],
                                   "kind": "digest_mismatch"})
        if pool.kind == "ec":
            issues += self._scrub_compare_ec(ps)
        else:
            issues += self._scrub_compare_replicated(ps)
        for issue in issues:
            self.perf.inc(f"scrub_finding_{issue['kind']}")
            if issue["kind"] == "digest_mismatch":
                self.perf.inc("scrub_mismatches")
                self.events.emit(
                    "scrub",
                    f"pg {self._pgstr(ps.pgid)} deep-scrub: digest "
                    f"mismatch {issue['object']}/{issue['shard']}",
                    severity="warn", pg=self._pgstr(ps.pgid),
                    object=issue["object"], shard=issue["shard"],
                    kind="digest_mismatch")
        if ps.repair and issues:
            ps.repaired += self._scrub_repair(ps, issues)
        ps.issues += issues
        ps.objects += len(names)
        last = ps.upto is None
        if not last:
            ps.cursor = ps.upto
        self.perf.tinc("scrub_chunk", (now_ns() - ps.taken_ns) / 1e9)
        self._scrub_release(ps)
        if last:
            self._scrub_pass_end(ps)
            return
        if ps.scheduled:
            self._scrub_cursor_store(
                CollectionId(ps.pgid.pool, ps.pgid.seed), ps.cursor)
            self.events.emit(
                "scrub",
                f"pg {self._pgstr(ps.pgid)} auto deep-scrub progress",
                pg=self._pgstr(ps.pgid), event="scrub_progress",
                start_ts=ps.started, done=ps.objects, total=ps.objects)
        # yield the worker between chunks: client ops on this PG
        # interleave, mclock paces the scrub class
        self._scrub_enqueue(ps.pgid, lambda: self._scrub_chunk_begin(ps))

    def _scrub_pass_end(self, ps: _ScrubPass, result: int = 0) -> None:
        """The pass is over (``result`` < 0: it failed): counters, the
        journal, the schedule, the verbs that waited for it, and the
        pass that verbs queued meanwhile."""
        key = (ps.pgid.pool, ps.pgid.seed)
        with self._scrub_lock:
            if self._scrub_passes.get(key) is not ps:
                return   # ended already (a failure beside the last map)
            del self._scrub_passes[key]
            if ps.tid is not None:
                self._pending_scrubs.pop(ps.tid, None)
                ps.tid = None
        self._scrub_release(ps)
        issues = ps.issues
        if ps.scheduled and result == 0:
            self._scrub_cursor_store(
                CollectionId(ps.pgid.pool, ps.pgid.seed), None)
        st = self._scrub_auto.get(key)
        if st is not None:
            st["due"] = time.time() + float(
                self.cfg["osd_scrub_min_interval"])
        if result == 0:
            self.perf.inc("scrubs")
        if ps.span is not None:
            ps.span.tag("errors", len(issues)).tag("repaired", ps.repaired)
            ps.span.finish()
        what = ("auto deep-" if ps.scheduled
                else "deep-" if ps.deep else "") + "scrub"
        self.events.emit(
            "scrub",
            f"pg {self._pgstr(ps.pgid)} {what} "
            + ("done" if result == 0 else f"failed ({result})")
            + f": {ps.objects} objects"
            + (f", {len(issues)} inconsistencies" if issues else ""),
            severity="warn" if issues or result else "info",
            pg=self._pgstr(ps.pgid), deep=ps.deep, event="scrub_done",
            start_ts=ps.started, done=ps.objects, total=ps.objects,
            errors=len(issues), repaired=ps.repaired)
        if issues:
            self.perf.inc("scrub_errors", len(issues))
            dout("osd", 1)("%s: scrub %s found %d inconsistencies",
                           self.name, ps.pgid, len(issues))
            if not ps.repair and any(i["kind"] in RECOVERABLE
                                     for i in issues):
                # close the detect->repair->converge loop: a scrub that
                # SEES recoverable damage re-arms recovery even without
                # the explicit repair verb — a rebuild lost to a racing
                # map change or swept read must not leave a permanent
                # hole that only an operator command would fix (the
                # round-3 thrash fixed point: 4/5 shards healthy,
                # recovery idle, nothing ever retried)
                self._requery_pg(ps.pgid, force_full=True)
        for client, tid in ps.waiters:
            self.messenger.send_message(
                client, MScrubResult(tid, ps.pgid, result, issues,
                                     ps.repaired))
        if ps.queued:
            deep = any(q[0] for q in ps.queued)
            repair = any(q[1] for q in ps.queued)
            self._scrub_begin(ps.pgid, deep, repair, waiter=ps.queued[0][2])
            with self._scrub_lock:
                nxt = self._scrub_passes.get(key)
                if nxt is not None:
                    nxt.waiters += [q[2] for q in ps.queued[1:]]

    def _scrub_compare_replicated(self, ps: _ScrubPass) -> list[dict]:
        issues = []
        names: dict[str, dict[int, dict]] = {}
        for osd, omap_ in ps.maps.items():
            for (name, _shard), entry in omap_.items():
                if "error" not in entry:
                    names.setdefault(name, {})[osd] = entry
        for name, per_osd in names.items():
            # authority: max version; then majority digest
            auth_v = max(e["version"] for e in per_osd.values())
            auth_size = max((e["size"] for e in per_osd.values()
                             if e["version"] == auth_v), default=0)
            for osd, e in per_osd.items():
                if e["version"] != auth_v:
                    issues.append({"osd": osd, "object": name, "shard": -1,
                                   "kind": "stale_version"})
                elif e["size"] != auth_size:
                    # same version, truncated copy (lost tail)
                    issues.append({"osd": osd, "object": name, "shard": -1,
                                   "kind": "size_mismatch"})
            if ps.deep:
                digests = [e["digest"] for e in per_osd.values()
                           if e["version"] == auth_v]
                if len(set(digests)) > 1:
                    issues.append({"osd": None, "object": name, "shard": -1,
                                   "kind": "replica_digest_mismatch"})
            missing = set(ps.maps) - set(per_osd)
            for osd in missing:
                issues.append({"osd": osd, "object": name, "shard": -1,
                               "kind": "missing_copy"})
        return issues

    def _scrub_compare_ec(self, ps: _ScrubPass) -> list[dict]:
        """Cross-shard EC comparison: every up shard member must hold an
        entry for every object at the authoritative version (a missing or
        stale shard is a scrub finding, not just a recovery condition)."""
        issues = []
        up = self.osdmap.pg_to_up_osds(ps.pgid.pool, ps.pgid.seed)
        shard_owner = {shard: osd for shard, osd in enumerate(up)
                       if osd is not None and osd in ps.maps}
        names: dict[str, int] = {}
        for omap_ in ps.maps.values():
            for (name, _shard), entry in omap_.items():
                if "error" not in entry:
                    names[name] = max(names.get(name, 0), entry["version"])
        for name, auth_v in names.items():
            for shard, osd in shard_owner.items():
                entry = ps.maps[osd].get((name, shard))
                if entry is None or "error" in entry:
                    issues.append({"osd": osd, "object": name,
                                   "shard": shard, "kind": "missing_shard"})
                elif entry["version"] != auth_v:
                    issues.append({"osd": osd, "object": name,
                                   "shard": shard, "kind": "stale_version"})
        return issues

    # -------------------------------------------------------------- repair
    def _scrub_repair(self, ps: _ScrubPass, issues: list[dict]) -> int:
        """Repair by re-running recovery against the scrub findings:
        replicated bad/stale/missing copies get pushed from the
        authoritative copy; EC bad shards are rebuilt from survivors."""
        pool = self.osdmap.pools[ps.pgid.pool]
        repaired = 0
        if pool.kind == "ec":
            for issue in issues:
                if issue["kind"] in ("digest_mismatch", "digest_missing",
                                     "read_error", "missing_shard",
                                     "stale_version"):
                    # version: the object's authoritative version from the
                    # scrub maps, NOT the pg-wide counter
                    name = issue["object"]
                    v = max((e["version"] for om in ps.maps.values()
                             for (n, _s), e in om.items()
                             if n == name and "error" not in e), default=0)
                    self._rebuild_shard(ps.pgid, name, issue["shard"],
                                        issue["osd"], version=v, force=True)
                    repaired += 1
            return repaired
        cid = CollectionId(ps.pgid.pool, ps.pgid.seed)
        # which copies does scrub consider bad, per object?
        bad: dict[str, set[int]] = {}
        for issue in issues:
            if issue["osd"] is not None:
                bad.setdefault(issue["object"], set()).add(issue["osd"])
        for issue in issues:
            name = issue["object"]
            target = issue["osd"]
            if target is None:
                continue
            if self.osd_id in bad.get(name, ()):
                # my own copy is flagged: pull from a good peer instead of
                # propagating my (possibly corrupt) bytes
                if target == self.osd_id:
                    good = [o for o, om in ps.maps.items()
                            if o not in bad.get(name, ())
                            and (name, -1) in om]
                    if good:
                        self.messenger.send_message(
                            f"osd.{good[0]}",
                            MPGPull(ps.pgid, [name], force=True))
                        repaired += 1
                continue
            obj = to_oid(name)
            if target == self.osd_id or not self.store.exists(cid, obj):
                continue
            data, attrs = self._read_obj_raw(cid, obj)
            v = int(attrs.get("v", 0))
            omap = self.store.omap_get(cid, obj)
            self.messenger.send_message(
                f"osd.{target}",
                MPGPush(ps.pgid, -1,
                        {name: (v, data, None, omap,
                                self._push_attrs(attrs))},
                        force=True))
            repaired += 1
        return repaired


    # ------------------------------------------------------- the schedule
    #
    # The reference's osd_scrub_min/max_interval scheduler
    # (src/osd/scrubber/osd_scrub_sched.cc): the primary of a PG starts
    # the pass above when the PG is due, deep and repairing.  Its name
    # cursor is persisted in the PG's scrub meta object's omap
    # (kill/revive resumes where it stopped); chunks run on the PG's
    # worker under the scrub mclock class.

    SCRUB_META = "scrub_cursor"  # per-PG meta object (shard -2)

    def _scrub_meta_oid(self) -> ObjectId:
        return ObjectId(self.SCRUB_META, shard=-2)

    def _scrub_cursor_load(self, cid: CollectionId) -> str | None:
        try:
            raw = self.store.omap_get(
                cid, self._scrub_meta_oid()).get("cursor")
        except (NoSuchObject, NoSuchCollection):
            return None
        return bytes(raw).decode() if raw else None

    def _scrub_cursor_store(self, cid: CollectionId,
                            cursor: str | None) -> None:
        obj = self._scrub_meta_oid()
        tx = Transaction()
        if not self.store.exists(cid, obj):
            if cursor is None:
                return
            tx.touch(cid, obj)
        if cursor is None:
            tx.omap_rmkeys(cid, obj, ["cursor"])
        else:
            tx.omap_setkeys(cid, obj, {"cursor": cursor.encode()})
        self.store.queue_transaction(tx)

    def _scrub_tick(self, now: float) -> None:
        """Heartbeat hook: ask again for late maps, and start the pass
        of every PG I lead that is due.  One pass in flight a PG."""
        self._scrub_resend(now)
        if not self.cfg["osd_scrub_auto"] or self.osdmap is None:
            return
        mn = float(self.cfg["osd_scrub_min_interval"])
        mx = max(float(self.cfg["osd_scrub_max_interval"]), mn)
        for pool_id, seed, up in self._pools_pgs_for_me():
            if self._primary_of(up) != self.osd_id:
                continue
            key = (pool_id, seed)
            st = self._scrub_auto.get(key)
            if st is None:
                # deterministic per-PG stagger spreads a cold fleet's
                # first cycles across [min, max); a PERSISTED cursor
                # means a cycle died mid-flight (OSD restart) — resume
                # promptly instead of waiting a whole interval
                frac = (zlib.crc32(f"{self.osd_id}/{pool_id}/{seed}"
                                   .encode()) & 0xFFFF) / 0x10000
                resume = self._scrub_cursor_load(
                    CollectionId(pool_id, seed)) is not None
                st = self._scrub_auto[key] = {
                    "due": now if resume else now + mn + frac * (mx - mn)}
            if now < st["due"] or key in self._scrub_passes:
                continue
            st["due"] = now + mn   # moved on again when the pass ends
            pgid = PgId(pool_id, seed)
            self._scrub_enqueue(pgid, lambda pgid=pgid: self._scrub_begin(
                pgid, True, True, scheduled=True))


# ---------------------------------------------------------------------------
# Fault injection (the ECInject role, src/osd/ECInject.{h,cc}: arm
# read/write/parity errors checked from the IO paths; driven by tests)
# ---------------------------------------------------------------------------

class FaultInjection:
    def __init__(self):
        self.corrupt_data: set = set()   # (pgid, name, shard)
        self.drop_shard_writes: set = set()  # shard ids to drop

    def corrupt_object(self, store, pgid: PgId, name: str,
                       shard: int = -1, offset: int = 0) -> bool:
        """Flip a byte in a stored object (silent corruption for scrub
        tests) — bypasses the transaction path on purpose."""
        cid = CollectionId(pgid.pool, pgid.seed)
        oid = ObjectId(name, shard=shard)
        if hasattr(store, "_dev"):  # bluestore: rot a byte on the device
            try:
                onode = store._onode(cid, oid)
            except (NoSuchObject, NoSuchCollection):
                return False
            idx = offset // 4096
            if idx >= len(onode.pages) or onode.pages[idx][0] < 0:
                return False
            with store._lock:
                store._flush_deferred()  # the device must hold the page
                phys = onode.pages[idx][0]
                page = bytearray(store._dev_read(phys))
                page[offset % 4096] ^= 0xFF
                store._dev_write(phys, page)
                store._dev.flush()
            return True
        try:
            obj = store._mem._obj(cid, oid) if hasattr(store, "_mem") \
                else store._obj(cid, oid)
        except NoSuchObject:
            return False
        if not obj.data:
            return False
        obj.data[offset] ^= 0xFF
        return True
