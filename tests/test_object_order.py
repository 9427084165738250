"""Operations on one object are served in one order on its primary: the
object's lock is a read/write lock in arrival order (osd/daemon.py,
``_obj_lock``), a
primary's EC read takes it shared, and a read that meets a write in
flight waits for the write's acknowledgement instead of meeting its
shards half applied.

The reference is plain and imports nothing of the program: a register
per object with the history rule of ``benchmark/verify.py`` written out
again here, and ``benchmark/reference.py``'s numpy encoding for what
the stores have to hold.  Small sizes on the CPU, seeded payloads;
nothing here is a measurement.
"""

import threading
import time

import numpy as np
import pytest

from benchmark import reference
from ceph_tpu.msg.messages import MPGQuery, MSubWrite, PgId
from ceph_tpu.osd.objectstore import CollectionId, ObjectId
from ceph_tpu.tools.vstart import MiniCluster
from tests.test_cluster import make_cfg

K, M, UNIT = 4, 2, 4096
SIZE = 1024
INF = float("inf")


# ------------------------------------------------------------ the reference
def payload(seed: int, key: int, version: int) -> bytes:
    """1 KiB from (seed, key, version), its first 16 bytes saying which."""
    body = np.random.PCG64([seed, key, version]).random_raw(SIZE // 8)
    head = np.array([key, version], "<u8").tobytes()
    return head + body.tobytes()[len(head):]


def tag(data: bytes) -> tuple[int, int]:
    k, v = np.frombuffer(bytes(data[:16]), "<u8")
    return int(k), int(v)


class Register:
    """One object's writes: version -> (sent, acknowledged).  A read
    sent at ``s`` and answered at ``a`` may return version v when v was
    sent before ``a`` and no other write was sent after v's
    acknowledgement and acknowledged before ``s``."""

    def __init__(self):
        self.writes = {0: (-INF, -INF)}

    def may_see(self, version: int, sent: float, answered: float) -> bool:
        w = self.writes.get(version)
        if w is None or w[0] >= answered:
            return False
        bar = max((s for v, (s, a) in self.writes.items()
                   if a < sent and v != version), default=-INF)
        return w[1] >= bar


# ---------------------------------------------------------------- plumbing
def _cluster(**cfg):
    return MiniCluster(n_osds=7, cfg=make_cfg(**cfg)).start()


def _pool(client, backend: str) -> int:
    return client.create_pool(
        "p", kind="ec", pg_num=4,
        ec_profile={"plugin": "tpu", "k": str(K), "m": str(M),
                    "backend": backend})


def _place(client, pool_id: int, oid: str):
    seed = client.osdmap.object_to_pg(pool_id, oid)
    up = list(client.osdmap.pg_to_up_osds(pool_id, seed))
    return PgId(pool_id, seed), up


def _stored(c, pgid: PgId, up: list, oid: str) -> list[bytes]:
    cid = CollectionId(pgid.pool, pgid.seed)
    return [c.osds[o].store.read(cid, ObjectId(oid, shard=s)).to_bytes()
            for s, o in enumerate(up)]


def _count(c, name: str) -> int:
    return sum(o.perf.get(name) for o in c.osds.values())


class Wire:
    """The test's hand on the network: counts the inventory rounds
    (``MPGQuery``) it carries, and holds back the sub-writes it is told
    to until ``release``."""

    def __init__(self, network):
        self.network = network
        self.real = network.deliver
        self.queries = 0
        self.hold = lambda dst, msg: False
        self.held: list[tuple] = []
        self._lock = threading.Lock()
        network.deliver = self.deliver

    def deliver(self, src, dst, msg):
        if isinstance(msg, MPGQuery):
            with self._lock:
                self.queries += 1
        if isinstance(msg, MSubWrite) and self.hold(dst, msg):
            with self._lock:
                self.held.append((src, dst, msg))
            return True
        return self.real(src, dst, msg)

    def release(self) -> None:
        self.hold = lambda dst, msg: False
        with self._lock:
            held, self.held = self.held, []
        for src, dst, msg in held:
            self.real(src, dst, msg)

    def close(self) -> None:
        self.release()
        del self.network.deliver


# ------------------------------------------- (a) many callers on four keys
@pytest.mark.parametrize("backend", ["numpy", "jax"])
@pytest.mark.parametrize("threads", [16, 32])
def test_callers_on_four_keys_see_one_order(threads, backend):
    seed = 7700 + threads
    keys = [f"rec{i}" for i in range(4)]
    c = _cluster()
    wire = Wire(c.network)
    try:
        client = c.client()
        pool_id = _pool(client, backend)
        regs = {k: Register() for k in keys}
        for i, k in enumerate(keys):
            client.write_full("p", k, payload(seed, i, 0))
        c.settle(0.3)
        epoch = client.osdmap.epoch
        before = {n: _count(c, n)
                  for n in ("pg_requery", "ec_read_torn",
                            "op_obj_lock_wait")}
        queries0 = wire.queries
        vlock = threading.Lock()
        versions = dict.fromkeys(range(len(keys)), 0)
        reads: list[tuple] = []
        errors: list[str] = []
        per_thread = 12

        def caller(n: int) -> None:
            rng = np.random.default_rng([seed, n])
            for _ in range(per_thread):
                i = int(rng.integers(len(keys)))
                write = bool(rng.integers(2))
                try:
                    if write:
                        with vlock:
                            versions[i] += 1
                            v = versions[i]
                            # the register knows the write before it is
                            # sent: a read may see it from then on
                            regs[keys[i]].writes[v] = (time.perf_counter(),
                                                       INF)
                        client.write_full("p", keys[i], payload(seed, i, v))
                        done = time.perf_counter()
                        with vlock:
                            sent = regs[keys[i]].writes[v][0]
                            regs[keys[i]].writes[v] = (sent, done)
                    else:
                        sent = time.perf_counter()
                        got = bytes(client.read("p", keys[i]))
                        with vlock:
                            reads.append((i, got, sent,
                                          time.perf_counter()))
                except Exception as e:  # noqa: BLE001 - a failed operation
                    errors.append(f"{'write' if write else 'read'} "
                                  f"{keys[i]}: {e!r}")

        ts = [threading.Thread(target=caller, args=(n,), daemon=True)
              for n in range(threads)]
        for t in ts:
            t.start()
        for t in ts:
            t.join(timeout=120)
        assert not any(t.is_alive() for t in ts)
        # no operation fails
        assert errors == []
        assert len(reads) > threads
        # every read returns whole a version it may see
        for i, got, sent, answered in reads:
            key, v = tag(got)
            assert key == i and got == payload(seed, i, v)
            assert regs[keys[i]].may_see(v, sent, answered), \
                (keys[i], v, sent, answered, regs[keys[i]].writes)
        # the stores of every key hold the reference's encoding of a
        # version that may be the last one
        for i, k in enumerate(keys):
            last = bytes(client.read("p", k))
            _key, v = tag(last)
            assert last == payload(seed, i, v)
            assert regs[k].may_see(v, INF, INF), (k, v, regs[k].writes)
            pgid, up = _place(client, pool_id, k)
            want = reference.encode(last, K, M, UNIT)
            assert _stored(c, pgid, up, k) == [bytes(w) for w in want]
        # callers did queue on the records' locks, and at a constant
        # epoch nobody asked for an inventory round or met a torn stripe
        assert client.osdmap.epoch == epoch
        assert _count(c, "op_obj_lock_wait") > before["op_obj_lock_wait"]
        assert _count(c, "pg_requery") == before["pg_requery"]
        assert _count(c, "ec_read_torn") == before["ec_read_torn"]
        assert wire.queries == queries0
    finally:
        wire.close()
        c.stop()


# ------------------------------------------------------ (b) the lock alone
class _Lock:
    """``_obj_lock`` / ``_obj_unlock`` of an OSD that serves nothing."""

    def __init__(self, osd, pool_id: int):
        self.osd = osd
        self.pool_id = pool_id
        self.key = (PgId(pool_id, 0), "obj")
        self.ran: list[str] = []
        self.holds: dict[str, object] = {}

    def read(self, name: str, fail: bool = False) -> None:
        def thunk(hold):
            self.ran.append(name)
            self.holds[name] = hold
            if fail:
                raise RuntimeError(name)
            return True                 # kept, until ``done``
        self.osd._obj_lock(self.key, thunk, shared=True)

    def write(self, name: str, fail: bool = False) -> None:
        def thunk():
            self.ran.append(name)
            if fail:
                raise RuntimeError(name)
        self.osd._obj_lock(self.key, thunk)

    def done(self, name: str) -> None:
        """A reader gives back its hold; a writer unlocks by key."""
        self.osd._obj_unlock(self.key, self.holds.get(name))

    def idle(self) -> bool:
        return self.key not in self.osd._obj_locks


def _readers_share(lk):
    lk.read("r1")
    lk.read("r2")
    assert lk.ran == ["r1", "r2"]
    lk.done("r1")
    lk.done("r1")                   # given back twice: nothing happens
    assert not lk.idle()
    lk.done("r2")


def _writer_waits_for_readers(lk):
    lk.read("r1")
    lk.read("r2")
    lk.write("w")
    assert lk.ran == ["r1", "r2"]
    lk.done("w")                    # not the writer's yet: nothing
    lk.done("r2")
    assert lk.ran == ["r1", "r2"]
    lk.done("r1")
    assert lk.ran == ["r1", "r2", "w"]
    lk.done("w")


def _waiters_are_served_in_arrival_order(lk):
    lk.write("w1")
    lk.read("r1")                   # behind the writer that runs: waits
    lk.write("w2")
    lk.read("r2")                   # behind w2: does not pass it
    lk.read("r3")
    assert lk.ran == ["w1"]
    lk.done("w1")
    # acknowledged: the reader that waited for it sees it, and no longer
    assert lk.ran == ["w1", "r1"]
    lk.done("r1")
    assert lk.ran == ["w1", "r1", "w2"]
    lk.done("w2")
    assert lk.ran == ["w1", "r1", "w2", "r2", "r3"]     # together
    lk.done("r3")
    lk.done("r2")


def _no_reader_passes_a_writer_that_waits_for_readers(lk):
    lk.read("r1")
    lk.write("w1")                  # waits for r1
    lk.read("r2")                   # must not keep w1 waiting: queues
    lk.write("w2")
    assert lk.ran == ["r1"]
    lk.done("r1")
    assert lk.ran == ["r1", "w1"]
    lk.done("w1")
    assert lk.ran == ["r1", "w1", "r2"]
    lk.done("r2")
    assert lk.ran == ["r1", "w1", "r2", "w2"]   # writers in arrival order
    lk.done("w2")


def _a_thunk_that_raises_frees_it(lk):
    with pytest.raises(RuntimeError):
        lk.write("w1", fail=True)
    assert lk.idle()
    with pytest.raises(RuntimeError):
        lk.read("r1", fail=True)
    assert lk.idle()
    lk.write("w2")
    lk.read("r2", fail=True)        # queued: raises where it is started
    lk.read("r3")
    with pytest.raises(RuntimeError):
        lk.done("w2")
    assert lk.ran == ["w1", "r1", "w2", "r2", "r3"]     # r3 still ran
    lk.done("r3")


def _the_sweep_frees_a_dead_reader(lk):
    """A read whose sub-reads never come back is ended by the sweep,
    and the writer behind it starts."""
    from ceph_tpu.osd.daemon import _PendingRead
    osd = lk.osd

    def thunk(hold):
        lk.ran.append("r")
        osd._pending_reads[10 ** 9] = _PendingRead(
            None, 0, lk.pool_id, "obj", total_shards=3, obj_hold=hold,
            on_done=lambda pr: lk.ran.append("swept"))
        return True                     # the pending read has it
    osd._obj_lock(lk.key, thunk, shared=True)
    lk.write("w")
    assert lk.ran == ["r"]
    osd._sweep_pending(time.time() + 10 ** 6)
    assert lk.ran == ["r", "swept", "w"]
    lk.done("w")


@pytest.fixture(scope="module")
def idle_cluster():
    """(an OSD that serves nothing, the id of an EC pool it knows)"""
    c = MiniCluster(n_osds=3, cfg=make_cfg()).start()
    pool_id = c.client().create_pool(
        "lk", kind="ec", pg_num=1,
        ec_profile={"plugin": "tpu", "k": "2", "m": "1",
                    "backend": "numpy"})
    c.settle(0.3)
    yield next(iter(c.osds.values())), pool_id
    c.stop()


@pytest.mark.parametrize("case", [
    _readers_share, _writer_waits_for_readers,
    _waiters_are_served_in_arrival_order,
    _no_reader_passes_a_writer_that_waits_for_readers,
    _a_thunk_that_raises_frees_it, _the_sweep_frees_a_dead_reader],
    ids=lambda f: f.__name__.strip("_"))
def test_the_lock_alone(case, idle_cluster):
    lk = _Lock(*idle_cluster)
    case(lk)
    assert lk.idle()


def test_the_lock_under_many_threads(idle_cluster):
    """Readers and writers from more threads than cores, given back by
    other threads as the ack drains do, the switch interval cut short:
    a writer never holds the object beside anybody, and every thunk
    runs once."""
    import queue
    import sys
    osd, pool_id = idle_cluster
    key = (PgId(pool_id, 0), "other")
    state = {"readers": 0, "writers": 0, "ran": 0}
    bad: list[str] = []
    guard = threading.Lock()
    running: queue.Queue = queue.Queue()
    workers, each = 24, 40

    def enter(hold=None) -> None:
        shared = hold is not None
        with guard:
            if state["writers"] or (not shared and state["readers"]):
                bad.append(f"{'reader' if shared else 'writer'} beside "
                           f"{dict(state)}")
            state["readers" if shared else "writers"] += 1
            state["ran"] += 1
        running.put(hold)
        return True                     # a drain thread gives it back

    def worker(n: int) -> None:
        for i in range(each):
            if (n + i) % 3:
                osd._obj_lock(key, enter, shared=True)
            else:
                osd._obj_lock(key, enter)

    def drain() -> None:
        while True:
            hold = running.get()
            if hold is drain:
                return
            with guard:
                state["writers" if hold is None else "readers"] -= 1
            osd._obj_unlock(key, hold)

    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        ts = [threading.Thread(target=worker, args=(n,), daemon=True)
              for n in range(workers)]
        ds = [threading.Thread(target=drain, daemon=True)
              for _ in range(4)]
        for t in ts + ds:
            t.start()
        for t in ts:
            t.join(timeout=60)
        deadline = time.monotonic() + 60
        while state["ran"] < workers * each \
                and time.monotonic() < deadline:
            time.sleep(0.01)
        for _ in ds:
            running.put(drain)
        for t in ds:
            t.join(timeout=60)
    finally:
        sys.setswitchinterval(old)
    assert not any(t.is_alive() for t in ts + ds)
    assert bad == []
    assert state == {"readers": 0, "writers": 0, "ran": workers * each}
    assert key not in osd._obj_locks


# ------------------------------------------ (c) a read on an idle object
def test_read_on_an_idle_object_never_waits():
    c = _cluster()
    try:
        client = c.client()
        pool_id = _pool(client, "numpy")
        data = payload(1, 0, 0)
        client.write_full("p", "obj", data)
        pgid, up = _place(client, pool_id, "obj")
        prim = c.osds[up[0]]
        waits0 = _count(c, "op_obj_lock_wait")
        for cached in (True, False):
            if not cached:
                prim._ec_cache.invalidate(pgid, "obj")   # it fans out
            assert client.read("p", "obj") == data
            read = next(d for d in reversed(
                prim.admin_command("dump_historic_ops"))
                if d["description"] == "read obj")
            marks = [e["event"] for e in read["events"]]
            assert "waiting_for_obj_lock" not in marks
            assert "started" in marks
        assert _count(c, "op_obj_lock_wait") == waits0
        # a read books no obj_lock time at all; its phases still add up
        d = prim.perf.dump()
        assert d["op_phase_obj_lock"]["count"] == d["op_timeline"]["count"]
        assert (pgid, "obj") not in prim._obj_locks
    finally:
        c.stop()


# ------------------------- (d) a read between a write's shard applies
def test_read_between_the_shard_applies_of_a_write():
    """Three of a ``write_full``'s five remote sub-writes are held back
    in the network: three shards hold the new version, three the old,
    and no k=4 of them agree.  A read sent then is answered with the
    old or the new record, whole, and starts no inventory round."""
    c = _cluster()
    wire = Wire(c.network)
    try:
        client = c.client()
        pool_id = _pool(client, "numpy")
        old, new = payload(2, 0, 0), payload(2, 0, 1)
        client.write_full("p", "obj", old)
        c.settle(0.3)
        pgid, up = _place(client, pool_id, "obj")
        prim = c.osds[up[0]]
        late = {f"osd.{o}" for o in up[3:]}
        wire.hold = lambda dst, msg: msg.oid == "obj" and dst in late
        queries0 = wire.queries
        before = {n: _count(c, n) for n in ("pg_requery", "ec_read_torn")}
        waits0 = _count(c, "op_obj_lock_wait")
        out: dict = {}

        def call(name, fn, *a):
            try:
                out[name] = fn(*a)
            except Exception as e:  # noqa: BLE001 - what the caller saw
                out[name] = e

        w = threading.Thread(target=call, daemon=True,
                             args=("write", client.write_full, "p", "obj",
                                   new))
        w.start()
        deadline = time.monotonic() + 10
        while len(wire.held) < len(late) and time.monotonic() < deadline:
            time.sleep(0.01)
        assert len(wire.held) == len(late)
        c.settle(0.2)       # the sub-writes that were let through apply
        vers = [int(c.osds[o].store.getattrs(
            CollectionId(pgid.pool, pgid.seed),
            ObjectId("obj", shard=s))["v"]) for s, o in enumerate(up)]
        assert len(set(vers[:3])) == 1 and len(set(vers[3:])) == 1
        assert vers[0] > vers[3]                 # three new, three old
        r = threading.Thread(target=call, daemon=True,
                             args=("read", client.read, "p", "obj"))
        r.start()
        r.join(timeout=0.5)
        wire.release()
        w.join(timeout=20)
        r.join(timeout=20)
        assert not w.is_alive() and not r.is_alive()
        assert not isinstance(out["write"], Exception), out["write"]
        assert bytes(out["read"]) in (old, new), out["read"]
        # it was sent after the write reached the primary: ordered
        # behind it, it waited for the acknowledgement and saw the new
        assert bytes(out["read"]) == new
        c.settle(0.3)
        assert wire.queries == queries0          # no inventory round
        assert _count(c, "pg_requery") == before["pg_requery"]
        assert _count(c, "ec_read_torn") == before["ec_read_torn"]
        assert _count(c, "op_obj_lock_wait") == waits0 + 1
        read = next(d for d in reversed(
            prim.admin_command("dump_historic_ops"))
            if d["description"] == "read obj")
        marks = [e["event"] for e in read["events"]]
        assert marks.index("waiting_for_obj_lock") \
            < marks.index("started")
        want = reference.encode(new, K, M, UNIT)
        assert _stored(c, pgid, up, "obj") == [bytes(x) for x in want]
    finally:
        wire.close()
        c.stop()


# --------------- (e) a lease and its revoke are taken in the order sent
def test_client_takes_a_lease_and_its_revoke_in_order():
    """With many callers the primary sends a read's reply (with a
    lease) and then the revoke of the write that was queued behind it.
    The client's dispatcher has handled both before the reader wakes
    up: the reader must not cache the bytes whose lease is gone."""
    from ceph_tpu.msg.messages import MOSDOpReply, MWatchNotify
    c = MiniCluster(n_osds=1, cfg=make_cfg()).start()
    try:
        client = c.client()
        tid = 10 ** 9
        client._lease_reads[tid] = (7, "obj", 0, 0)
        client._waiters[tid] = threading.Event()

        class Conn:
            def send(self, msg):
                return True

        reply = MOSDOpReply(tid, 0, data=b"old bytes", epoch=1, lease=5.0)
        assert client.ms_dispatch(Conn(), reply)
        assert client._lease_get(7, "obj", 0, 0) == b"old bytes"
        assert client.ms_dispatch(Conn(), MWatchNotify(0, 7, "obj",
                                                       "_lease"))
        # ... and only now does the reader run
        assert client._rpc_wait(client._waiters[tid], "osd.0", tid) \
            is reply
        assert client._lease_get(7, "obj", 0, 0) is None
        assert tid not in client._lease_reads
    finally:
        c.stop()
