"""The system under test, as the benchmark drives it: an in-process
cluster (``MiniCluster``) on the jax back-end at default settings, its
pool, its client, and the counters the per-layer readers take.  This is
the only file of the benchmark that touches the program; the mechanics
are those ``chip_smoke.phase_cluster`` proved on the chip.
"""

from __future__ import annotations

import threading
import time
from concurrent.futures import ThreadPoolExecutor

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
LOSSY_CLASSES = ("client", "recovery", "scrub")


class NotADeviceRun(Exception):
    """The run is not a measurement of the device path."""


class CompileWatch:
    """Counts every XLA backend compile of the process through
    ``jax.monitoring`` (jitted programs and eager operations alike)."""

    def __init__(self):
        self._lock = threading.Lock()
        self.durations: list[float] = []
        from jax import monitoring
        monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, name: str, seconds: float, **_kw) -> None:
        if name == COMPILE_EVENT:
            with self._lock:
                self.durations.append(float(seconds))

    def count(self) -> int:
        with self._lock:
            return len(self.durations)


class Deployment:
    """One configuration file, booted."""

    def __init__(self, config: dict):
        from ceph_tpu.tools.vstart import MiniCluster
        from ceph_tpu.utils.config import default_config

        self.config = config
        pool = config["pool"]
        self.pool = pool["name"]
        self.k = int(pool["profile"]["k"])
        self.m = int(pool["profile"]["m"])
        self.stripe_unit = int(config["stripe_unit"])
        cfg = default_config()
        cfg.apply_dict(dict(config["settings"]))
        if int(cfg["osd_ec_stripe_unit"]) != self.stripe_unit:
            raise ValueError("the configuration's stripe_unit is not the "
                             "program's osd_ec_stripe_unit")
        self.stopped: list[int] = []
        self.cluster = MiniCluster(n_osds=int(config["osds"]),
                                   cfg=cfg).start()
        try:
            self.client = self.cluster.client()
            self.pool_id = self.client.create_pool(
                self.pool, kind=pool["kind"], pg_num=int(pool["pg_num"]),
                ec_profile=dict(pool["profile"]))
        except BaseException:
            self.cluster.stop()
            raise

    def close(self) -> None:
        self.cluster.stop()

    # ---------------------------------------------------------- client ops
    def write(self, name: str, payload: bytes) -> None:
        self.client.write_full(self.pool, name, payload)

    def read(self, name: str) -> bytes:
        return bytes(self.client.read(self.pool, name))

    def write_many(self, items, inflight: int) -> None:
        """Set-up writes, ``inflight`` at a time; any failure raises."""
        with ThreadPoolExecutor(inflight,
                                thread_name_prefix="bench-setup") as ex:
            for f in [ex.submit(self.write, n, p) for n, p in items]:
                f.result(timeout=600)

    def read_many(self, names, inflight: int) -> list[bytes]:
        with ThreadPoolExecutor(inflight,
                                thread_name_prefix="bench-setup") as ex:
            return [f.result(timeout=600)
                    for f in [ex.submit(self.read, n) for n in names]]

    # -------------------------------------------------------------- set-up
    def warm(self, items, inflight: int) -> None:
        """Warm-up through the client, as a deployment warms: the first
        write of a length bucket makes the OSDs' batcher compile the
        bucket's folded programs in the background; wait for that, then
        write and read a round at the cell's own size."""
        from ceph_tpu.ec.batcher import ECBatcher
        items = list(items)
        self.write_many(items[:1], 1)
        if not ECBatcher.warm_wait(timeout=900):
            raise NotADeviceRun("the batcher's program warm-up did not "
                                "finish in 900 s")
        self.write_many(items, inflight)
        got = self.read_many([n for n, _p in items], inflight)
        for (name, want), have in zip(items, got):
            if have != want:
                raise NotADeviceRun(f"warm-up read of {name} differs "
                                    f"from what was written")

    def stop_osds(self, count: int) -> list[int]:
        """Stop ``count`` OSDs from the middle of the id range (as the
        smoke does), tell the monitor, and wait until every OSD and the
        client hold the new map."""
        ids = sorted(self.cluster.osds)
        mid = len(ids) // 2
        victims = ids[mid:mid + count]
        for v in victims:
            self.cluster.kill_osd(v)
            self.stopped.append(v)
        epoch = self.cluster.mon.osdmap.epoch
        self.cluster.wait_for_epoch(epoch, timeout=30)
        deadline = time.monotonic() + 30
        while time.monotonic() < deadline:
            if self.client.osdmap.epoch >= epoch and all(
                    o.osdmap.epoch >= epoch
                    for o in self.cluster.osds.values()):
                break
            time.sleep(0.05)
        else:
            raise NotADeviceRun("the new map did not reach every OSD")
        self.cluster.settle(1.0)
        return victims

    # --------------------------------------------------- what was stored
    def _pg(self, name: str) -> tuple[int, list]:
        """The object's PG seed and the OSD id per shard position (None:
        a hole)."""
        om = self.client.osdmap
        seed = om.object_to_pg(self.pool_id, name)
        return seed, list(om.pg_to_up_osds(self.pool_id, seed))

    def placement(self, name: str) -> list:
        return self._pg(name)[1]

    def data_holes(self, name: str) -> int:
        return sum(1 for u in self.placement(name)[:self.k] if u is None)

    def stored_shards(self, name: str) -> dict[int, bytes]:
        """shard position -> the bytes that OSD's store holds for it."""
        from ceph_tpu.osd.objectstore import (CollectionId, NoSuchObject,
                                              ObjectId)
        seed, up = self._pg(name)
        cid = CollectionId(self.pool_id, seed)
        out = {}
        for shard, osd_id in enumerate(up):
            osd = self.cluster.osds.get(osd_id)
            if osd is None:
                continue
            try:
                out[shard] = osd.store.read(
                    cid, ObjectId(name, shard=shard)).to_bytes()
            except NoSuchObject:
                out[shard] = b""
        return out

    # ------------------------------------------------------------ counters
    def counters(self) -> dict[str, float]:
        """Every counter of the process under a flat name: the perf
        registries (``osd.N`` summed under ``osd``; a histogram as
        ``.sum`` and ``.count``), the batchers' own statistics, the
        messenger's and the schedulers' drops."""
        from ceph_tpu.utils import staging
        from ceph_tpu.utils.perf import global_perf
        staging.stage_perf()
        out: dict[str, float] = {}

        def add(key, v):
            out[key] = out.get(key, 0.0) + float(v)

        for reg, dump in global_perf().dump().items():
            prefix = "osd" if reg.startswith("osd.") else reg
            for name, v in dump.items():
                if isinstance(v, dict):
                    for part in ("sum", "count", "sum_seconds"):
                        if part in v:
                            add(f"{prefix}.{name}.{part}", v[part])
                elif isinstance(v, (int, float)):
                    add(f"{prefix}.{name}", v)
        for osd in self.cluster.osds.values():
            for key, v in osd._ec_batcher.stats.items():
                if isinstance(v, (int, float)):
                    add(f"batcher.{key}", v)
            for klass, n in osd.scheduler.dropped.items():
                add(f"sched.dropped.{klass}", n)
        for klass in LOSSY_CLASSES + ("system",):
            out.setdefault(f"sched.dropped.{klass}", 0.0)
        out["msg.dropped_backpressure"] = float(
            self.cluster.network.dropped_backpressure)
        return out

    def health(self) -> dict[str, float]:
        """What makes a run no measurement of the device path: each has
        to read 0."""
        from ceph_tpu.utils import staging
        bad = {n: float(v)
               for n, v in staging.fallthrough_counts().items()}
        bad["system_messages_dropped"] = float(sum(
            o.scheduler.dropped.get("system", 0)
            for o in self.cluster.osds.values()))
        bad["osds_marked_down"] = float(sum(
            1 for ev in self.cluster.mon.cluster_log.dump()["events"]
            if "marked down" in ev["message"]
            and "reporters" in ev["message"]))
        return bad
