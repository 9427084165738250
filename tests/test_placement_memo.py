"""Placement once per map: a map received through ``apply_map_push`` is
sealed and memoises ``pg_to_up_osds``; the monitor's working map never
is.  The memo must answer as a fresh computation does on every map the
program can make, must not ride a copy into the next epoch, and must
not show in the encoding; an OSD derives "my PGs" once per map it
holds, so a heartbeat tick at an unchanged epoch computes nothing."""

import sys
import threading
import time

import pytest

from ceph_tpu.mon.maps import (PLACEMENT_COUNTERS, OSDMap, PoolSpec,
                               apply_map_push)
from ceph_tpu.msg.messages import MMapPush
from ceph_tpu.tools.vstart import MiniCluster
from ceph_tpu.utils.perf import PerfCounters
from tests.test_cluster import make_cfg

REP, EC = 1, 2   # pool ids of the hand-made map


def _working_map() -> OSDMap:
    """A monitor's working map: two OSDs a host on three hosts and one
    each on three more, a replicated pool and an EC 6+3 pool as wide
    as the cluster (an OSD down leaves a hole: there is no spare)."""
    m = OSDMap()
    for i in range(9):
        m.add_osd(i, f"host{min(i // 2, 3) if i < 6 else i}")
        m.mark_up(i)
    m.add_pool(PoolSpec(REP, "rep", size=3, pg_num=4))
    m.add_pool(PoolSpec(EC, "ec", kind="ec", size=9, pg_num=4,
                        ec_profile={"k": "6", "m": "3"}))
    return m


def _osd_back(m: OSDMap) -> None:
    m.mark_up(3)
    m.osds[3].in_cluster = True


def _temp_set(m: OSDMap) -> None:
    up = m.pg_to_up_osds(REP, 1)
    m.pg_temp[(REP, 1)] = list(reversed(up))
    m.primary_temp[(REP, 1)] = up[1]
    m.primary_temp[(REP, 2)] = m.pg_to_up_osds(REP, 2)[-1]


def _temp_clear(m: OSDMap) -> None:
    del m.pg_temp[(REP, 1)]
    m.primary_temp.clear()


def _upmap_set(m: OSDMap) -> None:
    m.pg_upmap[(REP, 0)] = [8, 7, 6]
    m.pg_upmap[(EC, 3)] = [8, 7, 6, 5, 4, 3, 2, 1, 0]


def _upmap_rm(m: OSDMap) -> None:
    m.pg_upmap.clear()


def _affinity(m: OSDMap) -> None:
    for o in m.pg_to_up_osds(REP, 3)[:1] + m.pg_to_up_osds(REP, 0)[:1]:
        m.osds[o].primary_affinity = 0.25


#: the monitor's kinds of write, each as it makes it: a plain field
#: write (or mark_*) on the working map, then a commit
STEPS = [
    ("osd_down", lambda m: m.mark_down(3)),
    ("osd_out", lambda m: m.mark_out(3)),
    ("osd_back_up", _osd_back),
    ("weight", lambda m: setattr(m.osds[1], "weight", 0.3)),
    ("upmap_set", _upmap_set),
    ("upmap_removed", _upmap_rm),
    ("temp_set", _temp_set),
    ("temp_cleared", _temp_clear),
    ("primary_affinity", _affinity),
    ("pg_num_split", lambda m: setattr(m.pools[EC], "pg_num", 8)),
    ("pool_added", lambda m: m.add_pool(PoolSpec(3, "q", size=2,
                                                 pg_num=2))),
    ("pool_removed", lambda m: m.pools.pop(REP)),
]
STEP_IDS = [name for name, _ in STEPS]


class _Commits:
    """The monitor's side of map distribution (``_commit_map`` and
    ``_publish_map``): mutate the working map in place, bump the epoch,
    push the incremental against the last committed copy."""

    def __init__(self):
        self.cur = _working_map()
        self.cur.epoch = 1
        self.prev = self.cur.deepcopy()

    def full(self) -> MMapPush:
        return MMapPush(self.cur.epoch, self.cur.encode_bytes())

    def commit(self, write) -> MMapPush:
        write(self.cur)
        self.cur.epoch += 1
        inc_b = self.cur.diff_from(self.prev).encode_bytes()
        base, self.prev = self.prev.epoch, self.cur.deepcopy()
        return MMapPush(self.cur.epoch, inc_bytes=inc_b, base_epoch=base)


def _all_up(m: OSDMap) -> dict:
    return {(pool_id, seed, ignore): m.pg_to_up_osds(pool_id, seed, ignore)
            for pool_id, pool in m.pools.items()
            for seed in range(pool.pg_num)
            for ignore in (False, True)}


def _fresh(m: OSDMap) -> OSDMap:
    fresh = OSDMap.decode_bytes(m.encode_bytes())
    assert not fresh.sealed
    return fresh


def _held_after(n_steps: int, perf=None):
    """(commits, the receiver's map) after the full push and the first
    ``n_steps`` incrementals, every placement asked on the way (so each
    map a copy is made from holds a full memo)."""
    mon = _Commits()
    held, request = apply_map_push(None, mon.full(), perf)
    assert request is None and held.sealed
    for _name, write in STEPS[:n_steps]:
        _all_up(held)
        held, request = apply_map_push(held, mon.commit(write), perf)
        assert request is None and held.sealed
    return mon, held


# -- (a) the sealed map answers as a fresh, unsealed decode does ---------
@pytest.mark.parametrize("n_steps", range(len(STEPS) + 1),
                         ids=["full_push"] + STEP_IDS)
def test_sealed_map_equals_fresh_decode(n_steps):
    mon, held = _held_after(n_steps)
    assert held.epoch == mon.cur.epoch
    want = _all_up(_fresh(mon.cur))
    assert want, "nothing compared"
    assert _all_up(held) == want      # computed
    assert _all_up(held) == want      # from the memo
    assert len(held._up_memo) == len(want)
    # the EC pool keeps its holes in place
    if n_steps and STEP_IDS[n_steps - 1] == "osd_down":
        assert all(None in up for (p, _s, _i), up in want.items()
                   if p == EC)


# -- (b) the copy made for the next incremental shares no memo -----------
@pytest.mark.parametrize("step", range(len(STEPS)), ids=STEP_IDS)
def test_incremental_copy_starts_without_memo(step):
    perf = PerfCounters("memo-test")
    perf.add_many(PLACEMENT_COUNTERS)
    mon, old = _held_after(step, perf)
    old_want = _all_up(_fresh(mon.cur))
    assert _all_up(old) == old_want   # the old map first: memo full
    copy = old.deepcopy()
    assert not copy.sealed and copy._up_memo is None \
        and copy._pm is None and copy._perf is None
    new, _ = apply_map_push(old, mon.commit(STEPS[step][1]), perf)
    assert new is not old and new.sealed
    assert new._up_memo == {} and new._up_memo is not old._up_memo
    assert new._pm is None or new._pm is not old._pm
    before = perf.get("placement_compute")
    want = _all_up(_fresh(mon.cur))
    assert _all_up(new) == want
    assert perf.get("placement_compute") - before == len(want)
    # and the old map still answers for ITS epoch
    assert _all_up(old) == old_want


# -- (c) the encoding does not see the memo ------------------------------
@pytest.mark.parametrize("n_steps", [0, 1, 5, 7, len(STEPS)])
def test_encoding_is_the_same_after_memoising(n_steps):
    mon, held = _held_after(n_steps)
    before = held.encode_bytes()
    assert before == mon.cur.encode_bytes()
    _all_up(held)
    assert held._up_memo and held._pm is not None
    assert held.encode_bytes() == before
    assert _fresh(held).encode_bytes() == before


# -- (d) the caller owns the list ----------------------------------------
@pytest.mark.parametrize("pool_id", [REP, EC])
def test_returned_list_is_the_callers(pool_id):
    _mon, held = _held_after(1)       # osd.3 down: the EC pool has holes
    for seed in range(held.pools[pool_id].pg_num):
        first = held.pg_to_up_osds(pool_id, seed)
        kept = list(first)
        first.reverse()
        first.append(99)
        first[0] = None
        again = held.pg_to_up_osds(pool_id, seed)
        assert again == kept and again is not first
        assert isinstance(again, list)


def test_unsealed_map_computes_every_time():
    m = _working_map()
    assert not m.sealed and m.placement() is not m.placement()
    m.pg_to_up_osds(EC, 0)
    assert m._up_memo is None
    held = _fresh(m).seal()
    assert held.placement() is held.placement()


def _inc_osd_down(m: OSDMap) -> None:
    nxt = m.deepcopy()
    nxt.mark_down(0)
    nxt.epoch += 1
    m.apply_incremental(nxt.diff_from(m))


# -- (f) a mutator called on a sealed map drops the seal -----------------
@pytest.mark.parametrize("mutate", [
    lambda m: m.add_osd(9, "host9"),
    lambda m: m.mark_up(0, addr="elsewhere"),
    lambda m: m.mark_down(0),
    lambda m: m.mark_out(0),
    lambda m: m.add_pool(PoolSpec(3, "q", size=2, pg_num=2)),
    _inc_osd_down,
], ids=["add_osd", "mark_up", "mark_down", "mark_out", "add_pool",
        "apply_incremental"])
def test_mutator_on_a_sealed_map_drops_the_seal(mutate):
    held = _fresh(_working_map()).seal()
    _all_up(held)
    assert held.sealed and held._up_memo and held._pm is not None
    mutate(held)                      # nobody does this to a held map
    assert not held.sealed and held._pm is None and held._perf is None
    # and it answers for what it now is, never from the old memo
    assert _all_up(held) == _all_up(_fresh(held))
    assert held._up_memo is None


def test_counters_count_hits_and_computes():
    perf = PerfCounters("memo-test")
    perf.add_many(PLACEMENT_COUNTERS)
    _mon, held = _held_after(0, perf)
    n = len(_all_up(held))
    assert (perf.get("placement_compute"), perf.get("placement_hit")) \
        == (n, 0)
    _all_up(held)
    assert (perf.get("placement_compute"), perf.get("placement_hit")) \
        == (n, n)
    with pytest.raises(KeyError):
        held.pg_to_up_osds(77, 0)     # no such pool: as unsealed, no entry
    assert len(held._up_memo) == n


def test_threads_that_miss_together_agree():
    """No lock on the lookup: threads that miss on one key each compute
    it, and every answer is the fresh decode's."""
    perf = PerfCounters("memo-test")
    perf.add_many(PLACEMENT_COUNTERS)
    mon, held = _held_after(1, perf)
    want = _all_up(_fresh(mon.cur))
    before = {n: perf.get(n) for n in PLACEMENT_COUNTERS}
    got, n_threads = [], 16
    go = threading.Barrier(n_threads)

    def ask():
        go.wait(10)
        got.append(_all_up(held))

    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        threads = [threading.Thread(target=ask) for _ in range(n_threads)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(30)
    finally:
        sys.setswitchinterval(old)
    assert not any(t.is_alive() for t in threads)
    assert got == [want] * n_threads
    assert len(held._up_memo) == len(want)
    computes = perf.get("placement_compute") - before["placement_compute"]
    hits = perf.get("placement_hit") - before["placement_hit"]
    assert len(want) <= computes <= n_threads * len(want)
    assert computes + hits == n_threads * len(want)


# -- (e) the monitor's working map is never sealed -----------------------
@pytest.fixture(scope="module")
def mon_cluster():
    c = MiniCluster(n_osds=6, cfg=make_cfg()).start()
    try:
        client = c.client()
        client.create_pool("rep", size=3, pg_num=4)
        client.create_pool("ec", kind="ec", size=5, pg_num=4,
                           ec_profile={"k": "3", "m": "2"})
        yield c, client
    finally:
        c.stop()


def _rep_up(c, seed):
    pool_id = next(p.pool_id for p in c.mon.osdmap.pools.values()
                   if p.name == "rep")
    return pool_id, c.mon.osdmap.pg_to_up_osds(pool_id, seed)


def _cmd_upmap(c, client):
    pool_id, up = _rep_up(c, 0)
    spare = next(o for o in range(6) if o not in up)
    client.mon_command({"prefix": "osd pg-upmap", "pool": pool_id,
                        "seed": 0, "osds": [spare] + up[1:]})
    yield
    client.mon_command({"prefix": "osd rm-pg-upmap", "pool": pool_id,
                        "seed": 0})


def _cmd_temp(c, client):
    pool_id, up = _rep_up(c, 1)
    client.mon_command({"prefix": "osd pg-temp", "pool": pool_id,
                        "seed": 1, "osds": list(reversed(up))})
    yield
    client.mon_command({"prefix": "osd primary-temp", "pool": pool_id,
                        "seed": 1, "id": up[1]})
    yield
    client.mon_command({"prefix": "osd primary-temp", "pool": pool_id,
                        "seed": 1})
    yield
    client.mon_command({"prefix": "osd pg-temp", "pool": pool_id,
                        "seed": 1, "osds": []})


def _cmd_affinity(c, client):
    _pool_id, up = _rep_up(c, 2)
    client.mon_command({"prefix": "osd primary-affinity", "id": up[0],
                        "weight": 0.1})
    yield
    client.mon_command({"prefix": "osd primary-affinity", "id": up[0],
                        "weight": 1.0})


def _cmd_down_out(c, client):
    # the daemon is alive: it boots again (mark_up on the working map)
    client.mon_command({"prefix": "osd down", "id": 4})
    yield
    deadline = time.time() + 10
    while time.time() < deadline and not c.mon.osdmap.osds[4].up:
        time.sleep(0.02)
    assert c.mon.osdmap.osds[4].up
    yield
    client.mon_command({"prefix": "osd out", "id": 5})
    yield
    with c.mon._lock:                 # no "osd in" command: as a boot does
        c.mon.osdmap.osds[5].in_cluster = True
        c.mon._commit_map("osd.5 in (test)")


def _cmd_split(c, client):
    client.mon_command({"prefix": "osd pool set-pg-num", "pool": "ec",
                        "pg_num": 8})
    yield
    client.create_pool("late", size=2, pg_num=2)
    yield
    client.mon_command({"prefix": "balancer optimize"})


@pytest.mark.parametrize("commands", [_cmd_upmap, _cmd_temp, _cmd_affinity,
                                      _cmd_down_out, _cmd_split],
                         ids=lambda f: f.__name__[5:])
def test_monitor_working_map_is_never_sealed(mon_cluster, commands):
    c, client = mon_cluster

    def check():
        with c.mon._lock:             # no commit between the two readings
            assert not c.mon.osdmap.sealed
            assert not c.mon._prev_map.sealed
            assert _all_up(c.mon.osdmap) == _all_up(_fresh(c.mon.osdmap))

    epoch = c.mon.osdmap.epoch
    for _ in commands(c, client):     # after each write, and the last
        check()
    check()
    assert c.mon.osdmap.epoch > epoch


# -- the OSD: "my PGs" once per map, nothing computed on a tick ----------
def _computes(c) -> int:
    return sum(o.perf.get("placement_compute") for o in c.osds.values())


def _hits(c) -> int:
    return sum(o.perf.get("placement_hit") for o in c.osds.values())


def _uncached_walk(osdmap, osd_id):
    fresh = _fresh(osdmap)
    return [(pool_id, seed, up)
            for pool_id, pool in fresh.pools.items()
            for seed in range(pool.pg_num)
            if osd_id in (up := fresh.pg_to_up_osds(pool_id, seed))]


def _mine(osd):
    """(the map, _pools_pgs_for_me() on it): asked again if a push
    landed in between."""
    while True:
        held = osd.osdmap
        mine = list(osd._pools_pgs_for_me())
        if osd.osdmap is held:
            return held, mine


def _settle(c) -> int:
    """Every OSD on the monitor's epoch; returns that epoch."""
    deadline = time.time() + 15
    while time.time() < deadline:
        epoch = c.mon.osdmap.epoch
        if all(o.osdmap is not None and o.osdmap.epoch == epoch
               for o in c.osds.values()):
            return epoch
        time.sleep(0.01)
    raise TimeoutError("maps never settled")


def test_ticks_at_one_epoch_compute_no_placement():
    # slow heartbeats: the ticks that count are the ones driven here
    c = MiniCluster(n_osds=5, cfg=make_cfg(osd_heartbeat_interval=0.2,
                                           osd_heartbeat_grace=5.0)).start()
    try:
        client = c.client()
        client.create_pool("rep", size=3, pg_num=8)
        client.create_pool("ec", kind="ec", size=5, pg_num=8,
                           ec_profile={"k": "3", "m": "2"})
        client.write_full("ec", "o", b"x" * 5000)
        client.write_full("rep", "o", b"y" * 500)
        epoch = _settle(c)
        n_pgs = sum(p.pg_num for p in c.mon.osdmap.pools.values())
        for osd in c.osds.values():   # the first tick on this map
            osd._scrub_tick(time.time())
            osd._report_stats()
            held, mine = _mine(osd)
            assert mine == _uncached_walk(held, osd.osd_id)
        assert client.read("ec", "o") == b"x" * 5000
        computes, hits = _computes(c), _hits(c)
        assert computes > 0
        for _ in range(20):
            for osd in c.osds.values():
                held = osd._my_pgs
                assert held[0] is osd.osdmap
                osd._scrub_tick(time.time())
                osd._report_stats()
                assert osd.admin_command("status")["num_pgs"] \
                    == len(held[1])
                assert osd._my_pgs is held
        assert _settle(c) == epoch, "the map moved under the ticks"
        assert _computes(c) == computes, \
            "a tick at an unchanged epoch computed a placement"
        assert _hits(c) >= hits
        # an operation looks its PG up and finds it
        client.write_full("ec", "o", b"z" * 5000)
        assert _computes(c) == computes and _hits(c) > hits
        # a map change: every PG is computed again, once for each map
        # in the chain and value of ignore_temp, and no more
        computes -= c.osds[4].perf.get("placement_compute")
        c.kill_osd(4)                 # and its counters leave the sums
        c.wait_for_epoch(epoch + 1)
        _settle(c)
        for osd in c.osds.values():
            osd._scrub_tick(time.time())
        grown = _computes(c) - computes   # before the epoch it is held to
        new_epoch = _settle(c)
        assert 0 < grown <= 2 * n_pgs * len(c.osds) * (new_epoch - epoch)
        for osd in c.osds.values():
            held, mine = _mine(osd)
            assert mine == _uncached_walk(held, osd.osd_id)
            assert all(4 not in up for _p, _s, up in mine)
    finally:
        c.stop()
