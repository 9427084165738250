"""Sharded messenger dispatch workers (AsyncMessenger Worker role,
ref src/msg/async/Stack.h:259: ms_async_op_threads event loops with
connections pinned to one loop)."""

import threading
import time

from ceph_tpu.msg.messenger import Dispatcher, LocalNetwork, Messenger
from ceph_tpu.tools.vstart import MiniCluster
from tests.test_cluster import make_cfg


class _Recorder(Dispatcher):
    def __init__(self):
        self.seen = []
        self.lock = threading.Lock()
        self.block = None  # src name whose dispatch blocks on .gate
        self.gate = threading.Event()
        self.blocked = threading.Event()

    def ms_dispatch(self, conn, msg) -> bool:
        if conn.peer == self.block:
            self.blocked.set()
            assert self.gate.wait(10), "test gate never opened"
        with self.lock:
            self.seen.append((conn.peer, msg))
        return True


def _two_srcs_on_distinct_workers(m: Messenger) -> tuple[str, str]:
    srcs = [f"client.{i}" for i in range(64)]
    a = srcs[0]
    b = next(s for s in srcs if m.shard_of(s) != m.shard_of(a))
    return a, b


def test_dispatch_overlaps_across_connections():
    """THE acceptance property: with one peer's dispatch wedged, a
    different peer's messages still dispatch on the same daemon —
    impossible with the old single dispatch thread."""
    net = LocalNetwork()
    m = Messenger(net, "srv", workers=3)
    rec = _Recorder()
    m.add_dispatcher(rec)
    m.start()
    try:
        a, b = _two_srcs_on_distinct_workers(m)
        rec.block = a
        assert net.deliver(a, "srv", "slow-op")
        assert rec.blocked.wait(5)      # a's worker is now wedged
        assert net.deliver(b, "srv", "fast-op")
        deadline = time.time() + 5
        while time.time() < deadline:
            with rec.lock:
                if (b, "fast-op") in rec.seen:
                    break
            time.sleep(0.01)
        with rec.lock:
            assert (b, "fast-op") in rec.seen, \
                "b's dispatch queued behind a's wedged worker"
            assert (a, "slow-op") not in rec.seen  # still blocked
        rec.gate.set()
        deadline = time.time() + 5
        while time.time() < deadline:
            with rec.lock:
                if (a, "slow-op") in rec.seen:
                    break
            time.sleep(0.01)
        with rec.lock:
            assert (a, "slow-op") in rec.seen
    finally:
        rec.gate.set()
        m.shutdown()


def test_per_peer_ordering_preserved():
    """Sharding must never reorder one peer's stream: a peer's
    messages all ride one worker."""
    net = LocalNetwork()
    m = Messenger(net, "srv", workers=4)
    rec = _Recorder()
    m.add_dispatcher(rec)
    m.start()
    try:
        for i in range(200):
            assert net.deliver("client.x", "srv", i)
        deadline = time.time() + 10
        while time.time() < deadline:
            with rec.lock:
                if len(rec.seen) == 200:
                    break
            time.sleep(0.01)
        with rec.lock:
            assert [msg for _s, msg in rec.seen] == list(range(200))
    finally:
        m.shutdown()


def test_worker_counters_spread():
    """Perf evidence: many peers spread across every worker loop."""
    net = LocalNetwork()
    m = Messenger(net, "srv", workers=3)
    rec = _Recorder()
    m.add_dispatcher(rec)
    m.start()
    try:
        for i in range(60):
            assert net.deliver(f"client.{i}", "srv", i)
        # poll the COUNTERS (incremented after dispatch returns), not
        # rec.seen — the last counter bump can lag the handler append
        deadline = time.time() + 10
        while time.time() < deadline:
            if sum(m.worker_dispatched) == 60:
                break
            time.sleep(0.01)
        assert sum(m.worker_dispatched) == 60
        assert all(c > 0 for c in m.worker_dispatched), \
            m.worker_dispatched
    finally:
        m.shutdown()


def test_messenger_perf_dispatch_metrics():
    """The messenger perf registry (tentpole schema): every dispatched
    message lands in msg_dispatched AND the msg_dispatch_us pow2
    histogram, and the queue-depth gauge drains back to zero."""
    net = LocalNetwork()
    m = Messenger(net, "perf-srv", workers=2)
    rec = _Recorder()
    m.add_dispatcher(rec)
    m.start()
    try:
        for i in range(20):
            assert net.deliver(f"client.{i}", "perf-srv", i)
        deadline = time.time() + 10
        while time.time() < deadline:
            if m.perf.get("msg_dispatched") == 20:
                break
            time.sleep(0.01)
        d = m.perf.dump()
        assert d["msg_dispatched"] == 20
        assert d["msg_dispatch_us"]["count"] == 20
        assert d["msg_dispatch_us"]["sum"] > 0
        assert d["msg_queue_depth"] == 0  # enqueued == dispatched
        assert m.queue_depths() == [0, 0]
        st = m.dump_state()
        assert st["workers"] == 2 and sum(st["dispatched"]) == 20
        assert d["msg_drop_wire"] == 0
        assert d["msg_drop_backpressure"] == 0
    finally:
        m.shutdown()


def test_cluster_peers_pass_the_client_message_cap():
    """A server's cap throttles CLIENT messages: an osd or mon peer's
    messages are neither counted against it nor dropped."""
    from ceph_tpu.msg.messenger import Policy

    net = LocalNetwork()
    srv = Messenger(net, "cap-srv", Policy.stateless_server(cap=1),
                    workers=2)
    rec = _Recorder()
    rec.block = "client.a"
    srv.add_dispatcher(rec)
    srv.start()
    try:
        assert net.deliver("client.a", "cap-srv", "wedge")
        assert rec.blocked.wait(5)  # holds the one throttle unit
        assert net.deliver("client.b", "cap-srv", "dropped")
        for src in ("osd.3", "mon.a"):
            assert net.deliver(src, "cap-srv", "kept")
        assert net.dropped_backpressure == 1
        assert srv.perf.get("msg_drop_backpressure") == 1
    finally:
        rec.gate.set()
        srv.shutdown()


def test_drop_counters_split_by_cause():
    """The conflated-drop satellite: a lossy-WIRE drop and a
    receive-side BACKPRESSURE drop account separately (network totals
    and per-messenger perf), while network.dropped stays the sum."""
    from ceph_tpu.msg.messenger import Policy

    net = LocalNetwork()
    # backpressure: a lossy server capped at 1 message whose dispatch
    # is wedged — the 2nd..nth deliveries drop at the throttle
    srv = Messenger(net, "bp-srv", Policy.stateless_server(cap=1),
                    workers=1)
    rec = _Recorder()
    rec.block = "client.a"
    srv.add_dispatcher(rec)
    srv.start()
    try:
        assert net.deliver("client.a", "bp-srv", "wedge")
        assert rec.blocked.wait(5)
        # the throttle unit is held by the wedged message: these drop
        for i in range(3):
            assert net.deliver("client.a", "bp-srv", f"over-{i}")
        assert net.dropped_backpressure == 3
        assert srv.perf.get("msg_drop_backpressure") == 3
        assert net.dropped_wire == 0
        # wire drops: fault injection takes every delivery
        net.drop_rate = 1.0
        for i in range(4):
            assert net.deliver("client.b", "bp-srv", f"wire-{i}")
        net.drop_rate = 0.0
        assert net.dropped_wire == 4
        assert srv.perf.get("msg_drop_wire") == 4
        # the legacy conflated total is still the sum
        assert net.dropped == 7
    finally:
        rec.gate.set()
        srv.shutdown()


def test_throttle_wait_time_accounted():
    """A LOSSLESS peer past the message cap blocks in the throttle —
    the wait lands in msg_throttle_wait_time (seconds + samples)."""
    from ceph_tpu.msg.messenger import Policy

    net = LocalNetwork()
    srv = Messenger(net, "tw-srv",
                    Policy(lossy=False, throttler_cap=1), workers=1)
    rec = _Recorder()
    rec.block = "client.a"
    srv.add_dispatcher(rec)
    srv.start()
    try:
        assert net.deliver("client.a", "tw-srv", "wedge")
        assert rec.blocked.wait(5)

        def late_open():
            time.sleep(0.1)
            rec.gate.set()  # dispatch finishes -> throttle unit freed

        t = threading.Thread(target=late_open)
        t.start()
        # blocks in _enqueue until the wedged dispatch completes
        assert net.deliver("client.a", "tw-srv", "queued")
        t.join()
        tw = srv.perf.dump()["msg_throttle_wait_time"]
        assert tw["count"] == 1
        assert tw["sum_seconds"] >= 0.05
    finally:
        rec.gate.set()
        srv.shutdown()


def test_cluster_daemons_run_sharded_messengers():
    cfg = make_cfg(ms_dispatch_workers=2)
    c = MiniCluster(n_osds=3, cfg=cfg).start()
    try:
        client = c.client()
        client.create_pool("p", size=2, pg_num=4)
        for i in range(10):
            client.write_full("p", f"o{i}", b"x" * 1000)
        for i in range(10):
            assert client.read("p", f"o{i}") == b"x" * 1000
        for osd in c.osds.values():
            assert osd.messenger.workers == 2
        assert c.mon.messenger.workers == 2
    finally:
        c.stop()
