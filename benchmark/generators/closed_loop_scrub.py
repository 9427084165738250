"""Closed-loop traffic with a deep scrub of the pool going round and
round beside it: ``closed_loop``'s callers to the letter (the mix file's
parameters are its), plus one more thread, the operator, who from the
window's start to its close deep-scrubs the pool through the client's
verb as the mix's ``scrub`` block says (the configuration's): PG 0 to
the last, ``pgs_in_flight`` at a time (1), and again from PG 0 when the
pool is done.  A scrub in flight at the close is waited for.  The
scrubs are no operations of the window: they are in no metric's count.

The run is no result, and ``run`` raises ``ScrubFailed`` (the process
leaves with a non-zero code and the reason on standard error), if a
scrub returned a finding, raised or did not end, if no PG's scrub ended
inside the window, or if one more pass of the pool after the close,
with the writers stopped, reports anything or is not a whole pass: it
has to end one scrub a PG and, where the digests are a device
program's, put through it exactly the bytes the pool's stores hold
(``stored``, read from the stores and not from the scrub).

That quiet pass runs before the harness reads the counters again, so
the window's growth holds it.  What it added is kept under names of
its own (registry ``bench_scrub``: ``quiet_verified_bytes``,
``quiet_chunk_seconds``, ``quiet_chunks``), which the reader
``counter_ratio_net`` takes off the cell's own scrub metrics.
"""

from __future__ import annotations

import sys
import threading
import time

from . import closed_loop


class ScrubFailed(RuntimeError):
    """The window's scrubs say the run is no result."""


def make_plan(traffic: dict, seed: int):
    plan = closed_loop.make_plan(traffic, seed)
    scrub = dict(traffic["scrub"])
    if not scrub["deep"] or scrub["order"] != "pg_seed" \
            or int(scrub["pgs_in_flight"]) != 1:
        raise ValueError("the operator deep-scrubs PG after PG in seed "
                         "order, one in flight")
    plan.scrub = scrub
    return plan


def scrub_pg(dep, seed: int) -> list:
    """The operator's verb on one PG of the deployment's pool: returns
    its findings when the pass has ended."""
    return list(dep.client.scrub_pg(dep.pool, seed,
                                    deep=True).inconsistencies)


#: what the quiet pass grows, and the name each growth is kept under
QUIET = {"osd.scrubs": None,
         "osd.scrub_verified_bytes": "quiet_verified_bytes",
         "osd.scrub_chunk.sum_seconds": "quiet_chunk_seconds",
         "osd.scrub_chunk.count": "quiet_chunks"}


def note_quiet(grew: dict) -> None:
    """Keep the quiet pass's growth where ``Deployment.counters()``
    finds it (``bench_scrub.<name>``)."""
    from ceph_tpu.utils.perf import global_perf
    reg = global_perf().create("bench_scrub")
    for counter, name in QUIET.items():
        if name is not None:
            if not reg.has(name):
                reg.add(name)
            reg.inc(name, grew[counter])


def stored(dep, pgs: int) -> tuple[int, int]:
    """Shard streams and bytes the pool's stores hold, PG metadata
    left out: what one whole deep scrub has to read."""
    from ceph_tpu.osd.objectstore import CollectionId, NoSuchCollection
    streams = held = 0
    for osd in dep.cluster.osds.values():
        for seed in range(pgs):
            cid = CollectionId(dep.pool_id, seed)
            try:
                oids = osd.store.list_objects(cid)
            except NoSuchCollection:
                continue
            for oid in oids:
                if oid.shard > -2:
                    streams += 1
                    held += osd.store.stat(cid, oid)["size"]
    return streams, held


def on_device(dep) -> bool:
    """Whether this deployment's scrub digests are a device program's
    (a chip run whose digests the host made is refused by
    ``Deployment.health()``)."""
    from ceph_tpu.ec.verify import verifier
    return verifier(str(dep.cluster.cfg["osd_scrub_fold"])).on_device


def run(plan, dep, seconds: float, annotate: bool = False,
        grace: float = 60.0):
    pgs = int(dep.config["pool"]["pg_num"])
    if dep.config["scrub"] != plan.scrub or plan.scrub["pool"] != dep.pool:
        raise ValueError("the mix's scrub block is not the "
                         "configuration's")
    ended: list[tuple[int, float, float]] = []   # (seed, start, end)
    findings: list[dict] = []
    raised: list[str] = []
    start = time.perf_counter()
    close = start + seconds

    def operator() -> None:
        while True:
            for seed in range(pgs):
                t = time.perf_counter()
                if t >= close:
                    return
                try:
                    found = scrub_pg(dep, seed)
                except Exception as e:  # noqa: BLE001 - the run's end
                    raised.append(f"pg {seed}: {e!r}")
                    return
                ended.append((seed, t, time.perf_counter()))
                findings.extend(dict(f, pg=seed) for f in found)

    op = threading.Thread(target=operator, daemon=True,
                          name="bench-scrub-operator")
    op.start()
    ops, t0, t1 = closed_loop.run(plan, dep, seconds, annotate, grace)
    op.join(max(0.0, t1 + grace - time.perf_counter()))
    inside = [e for e in ended if e[2] <= t1]
    took = [e[2] - e[1] for e in ended]
    print(f"scrub: {len(inside)} PG scrubs ended in the window "
          f"({len(inside) / pgs:.2f} passes of {pgs} PGs), "
          f"{len(ended) - len(inside)} after its close; a PG's scrub "
          f"took {sum(took) / max(1, len(took)):.3f} s on average, "
          f"{max(took, default=0.0):.3f} s at most",
          file=sys.stderr, flush=True)
    why = []
    if op.is_alive():
        why.append("a scrub did not end")
    if raised:
        why.append(f"a scrub raised: {raised[0]}")
    if findings:
        why.append(f"{len(findings)} findings on a healthy pool, the "
                   f"first {findings[0]}")
    if not inside and not why:
        why.append("no PG's scrub ended inside the window")
    if not why:
        # the writers have stopped: what a pass finds now is in the
        # stores, not in the timing
        before = dep.counters()
        quiet = [dict(f, pg=seed) for seed in range(pgs)
                 for f in scrub_pg(dep, seed)]
        after = dep.counters()
        grew = {n: after[n] - before.get(n, 0.0) for n in QUIET}
        note_quiet(grew)
        if quiet:
            why.append(f"{len(quiet)} findings after the close, the "
                       f"first {quiet[0]}")
        streams, held = stored(dep, pgs)
        want = held if on_device(dep) else 0
        print(f"scrub: the quiet pass ended "
              f"{grew['osd.scrubs']:.0f} PG scrubs and put "
              f"{grew['osd.scrub_verified_bytes']:.0f} B through the "
              f"device program; the stores hold {streams} streams of "
              f"{held} B", file=sys.stderr, flush=True)
        if grew["osd.scrubs"] != pgs \
                or grew["osd.scrub_verified_bytes"] != want:
            why.append(f"the quiet pass was not one whole pass: "
                       f"{grew['osd.scrubs']:.0f} PG scrubs of {pgs}, "
                       f"{grew['osd.scrub_verified_bytes']:.0f} B "
                       f"verified on the device of {want}")
    if why:
        raise ScrubFailed("; ".join(why))
    return ops, t0, t1
