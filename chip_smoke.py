#!/usr/bin/env python3
"""Bring-up smoke of the served erasure-code path on one TPU chip.

Drives the system's main path once through the entry points a user
calls, in ONE process (which owns the chip): the four kernel
realizations at deployment width, the upstream-compatible
``ec_benchmark`` entry point, and a 12-OSD in-process cluster with an EC
pool ``plugin=tpu k=8 m=3`` that writes, reads back and reads degraded
64 objects of 4 MiB and deep-scrubs them, a planted fault included —
once with a batch window that must fold, once at default batcher
settings.  Every byte is checked against the
native/numpy oracle or the digest of what was written.

Each phase prints one JSON line; the script exits non-zero at the first
failed phase.  The seconds it prints are BRING-UP seconds (compile and
first launches included), not a benchmark.  The last line of a run that
passed is exactly

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}

``--four-chips`` runs only the cluster phase with ``ec_shard=4`` and the
same objects with ``ec_shard=off`` on a four-chip host.

No JAX platform is set or defaulted here: without an accelerator the
device phase fails and nothing after it runs.
"""

from __future__ import annotations

import argparse
import collections
import functools
import hashlib
import json
import sys
import threading
import time

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
#: no compile on the served path (ec_benchmark and cluster phases) may
#: take longer than this
COMPILE_LIMIT_S = 15.0
#: the FIRST cluster run's only departure from default settings, stated
#: on its line: ops of one PG run one at a time, so two ops meet in one
#: OSD's batcher only where it leads two PGs, and the adaptive window
#: (50-4000 us, tuned on CPU latencies) lets them pass each other.  A
#: fixed 20 ms window makes "the batcher folds" a property of the run,
#: not of its timing.  Heartbeat, recovery and time-out settings are
#: the defaults.  The SECOND cluster run is at default batcher settings
#: (adaptive window) and does not require a fold.
FOLDING_SETTINGS = {"ec_batch_window_us": 20000.0,
                    "ec_batch_adaptive": "off"}


class PhaseFailed(Exception):
    pass


def emit(obj: dict) -> None:
    print(json.dumps(obj), flush=True)


class CompileWatch:
    """Counts every XLA backend compile in the process (jitted programs
    and eagerly dispatched operations alike) through jax.monitoring."""

    _instance = None

    def __init__(self):
        self._lock = threading.Lock()
        self.durations: list[float] = []
        from jax import monitoring
        monitoring.register_event_duration_secs_listener(self._on)

    @classmethod
    def get(cls) -> "CompileWatch":
        if cls._instance is None:
            cls._instance = cls()
        return cls._instance

    def _on(self, name: str, seconds: float, **_kw) -> None:
        if name == COMPILE_EVENT:
            with self._lock:
                self.durations.append(float(seconds))

    def count(self) -> int:
        with self._lock:
            return len(self.durations)

    def since(self, mark: int) -> list[float]:
        with self._lock:
            return list(self.durations[mark:])


def _profiler_compiles() -> dict:
    """compile seconds per signature, from the KernelProfiler."""
    from ceph_tpu.utils.perf import kernel_profiler
    return {sig: {"compiles": agg["compile"],
                  "compile_seconds": agg["compile_seconds"],
                  "compile_max_seconds": agg["compile_max_seconds"],
                  "launches": agg["device"] + agg["compile"]}
            for sig, agg in kernel_profiler().dump()["signatures"].items()
            if agg["compile"] or agg["device"]}


def _check_compile_limit(phase: str, before: dict) -> dict:
    now = _profiler_compiles()
    slow = {s: v["compile_max_seconds"] for s, v in now.items()
            if v["compile_max_seconds"] > COMPILE_LIMIT_S
            and v != before.get(s)}
    if slow:
        raise PhaseFailed(f"{phase}: compiles over {COMPILE_LIMIT_S}s: "
                          f"{slow}")
    return now


# ------------------------------------------------------------------ device
def phase_device(require_tpu: bool = True) -> dict:
    import jax
    devs = jax.devices()
    info = {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}
    if require_tpu and info["platform"] != "tpu":
        raise PhaseFailed(f"no TPU: jax.devices()[0].platform is "
                          f"{info['platform']!r}")
    return info


# ----------------------------------------------------------------- kernels
def phase_kernels(n_obj: int = 64, obj_bytes: int = 4 << 20, k: int = 8,
                  m: int = 3, seed: int = 0, interpret: bool = False
                  ) -> dict:
    """Each realization in KERNELS launched once, directly, on one fold
    of ``n_obj`` objects (lanes in, lanes out), against the oracle."""
    import jax
    import numpy as np

    from ceph_tpu.ops import ec_kernels, gf256, native

    M = gf256.vandermonde_matrix(k, m)
    n4 = n_obj * (obj_bytes // k) // 4
    rng = np.random.default_rng(seed)
    x32 = rng.integers(0, 1 << 32, (k, n4), dtype=np.uint32)
    oracle_fn = (native.encode_region if native.available()
                 else gf256.encode_region)
    t0 = time.perf_counter()
    want = oracle_fn(M, x32.view(np.uint8))
    oracle_s = time.perf_counter() - t0
    xdev = jax.device_put(x32)
    xdev.block_until_ready()
    out = {"fold_lanes": [k, n4], "in_bytes": x32.nbytes,
           "out_bytes": want.nbytes,
           "oracle": "native" if native.available() else "numpy",
           "oracle_seconds": oracle_s, "kernels": {}}
    # the static realizations, then the program every decode runs: the
    # same product with the matrix as a runtime operand
    for name in ec_kernels.KERNELS + ("generic",):
        t0 = time.perf_counter()
        if name == "generic":
            op = None
            compiled = ec_kernels.generic_lanes.lower(
                ec_kernels.coef_table(M), xdev).compile()
            compiled = functools.partial(compiled,
                                         ec_kernels.coef_table(M))
        else:
            op = ec_kernels.RegionMatmul(M, kernel=name,
                                         interpret=interpret)
            compiled = op.lanes_fn(n4).lower(xdev).compile()
        compile_s = time.perf_counter() - t0
        t0 = time.perf_counter()
        y = compiled(xdev).block_until_ready()
        first_s = time.perf_counter() - t0
        t0 = time.perf_counter()
        y = compiled(xdev).block_until_ready()
        second_s = time.perf_counter() - t0
        got = np.asarray(y).view(np.uint8)
        ok = bool(np.array_equal(got, want))
        mem = getattr(compiled, "func", compiled).memory_analysis()
        out["kernels"][name] = {
            "ok": ok, "compile_seconds": compile_s,
            "first_launch_seconds": first_s,
            "second_launch_seconds": second_s,
            "temp_bytes": int(getattr(mem, "temp_size_in_bytes", -1))}
        if op is not None:
            out["kernels"][name].update(pallas=bool(op._use_pallas),
                                        block=op.block)
        del y, compiled
        if not ok:
            raise PhaseFailed(f"kernel {name}: bytes differ from the "
                              f"{out['oracle']} oracle")
    return out


# ------------------------------------------------------------ ec_benchmark
EC_BENCH_CASES = (
    # (plugin, profile, two erasures) — the last is the bit-matrix
    # technique whose packet rows ride ScheduledXor
    ("tpu", {"k": "8", "m": "3"}, (1, 9)),
    ("jerasure", {"k": "8", "m": "3", "technique": "cauchy_good"}, (1, 9)),
    ("jerasure", {"k": "8", "m": "2", "technique": "liber8tion"}, (0, 8)),
)


def phase_ec_benchmark(size: int = 4 << 20, seed: int = 0,
                       cases=EC_BENCH_CASES) -> dict:
    """The upstream-compatible entry point on the jax back-end (encode
    and two-erasure decode), and the same codecs' bytes against the
    numpy back-end."""
    import contextlib
    import io

    import numpy as np

    from ceph_tpu import ec
    from ceph_tpu.tools import ec_benchmark

    before = _profiler_compiles()
    rng = np.random.default_rng(seed)
    data = rng.integers(0, 256, size, dtype=np.uint8)
    out = {"size": size, "cases": []}
    for plugin, profile, erased in cases:
        pj = dict(profile, backend="jax")
        cj = ec.factory(plugin, dict(pj))
        cn = ec.factory(plugin, dict(profile, backend="numpy"))
        t0 = time.perf_counter()
        enc_j, enc_n = cj.encode(data), cn.encode(data)
        same = all(np.array_equal(enc_j[i], enc_n[i]) for i in enc_n)
        avail = {i: c for i, c in enc_j.items() if i not in erased}
        dec = cj.decode(list(erased), avail)
        same = same and all(np.array_equal(dec[i], enc_n[i])
                            for i in erased)
        bytes_s = time.perf_counter() - t0
        cli = []
        for argv in (["--workload", "encode"],
                     ["--workload", "decode", "--erasures", "2"]
                     + [a for e in erased for a in ("--erased", str(e))]):
            argv = (["--plugin", plugin, "--size", str(size),
                     "--iterations", "2"] + argv
                    + [a for kv in pj.items()
                       for a in ("--parameter", f"{kv[0]}={kv[1]}")])
            buf = io.StringIO()
            with contextlib.redirect_stdout(buf):
                rc = ec_benchmark.main(argv)
            cli.append({"argv": argv[6:8], "rc": rc,
                        "printed": buf.getvalue().strip()})
            same = same and rc == 0
        out["cases"].append({"plugin": plugin, "profile": pj,
                             "erased": list(erased), "ok": bool(same),
                             "bring_up_seconds": bytes_s, "cli": cli})
        if not same:
            raise PhaseFailed(f"ec_benchmark {plugin} {profile}: bytes "
                              f"differ from the numpy back-end")
    out["compiles"] = _check_compile_limit("ec_benchmark", before)
    from ceph_tpu.utils import staging
    out["fallthroughs"] = staging.fallthrough_counts()
    if any(out["fallthroughs"].values()):
        raise PhaseFailed(f"ec_benchmark: host fall-throughs "
                          f"{out['fallthroughs']}")
    return out


# ----------------------------------------------------------------- cluster
def _digest(b: bytes) -> str:
    return hashlib.blake2b(b, digest_size=16).hexdigest()


def _write_all(client, pool: str, objs: dict, inflight: int) -> None:
    from concurrent.futures import ThreadPoolExecutor
    with ThreadPoolExecutor(inflight, thread_name_prefix="smoke-w") as ex:
        for f in [ex.submit(client.write_full, pool, oid, data)
                  for oid, data in objs.items()]:
            f.result(timeout=600)


def _read_all(client, pool: str, digests: dict, inflight: int,
              what: str) -> None:
    from concurrent.futures import ThreadPoolExecutor
    with ThreadPoolExecutor(inflight, thread_name_prefix="smoke-r") as ex:
        futs = {oid: ex.submit(client.read, pool, oid) for oid in digests}
        for oid, f in futs.items():
            if _digest(bytes(f.result(timeout=600))) != digests[oid]:
                raise PhaseFailed(f"{what}: {oid} differs from what was "
                                  f"written")


def _marked_down(mon) -> int:
    """OSDs the monitor marked down on failure reports (the operator's
    own ``osd down`` for the stopped OSD is not one)."""
    return sum(1 for ev in mon.cluster_log.dump()["events"]
               if "marked down" in ev["message"]
               and "reporters" in ev["message"])


def _stage_counts() -> dict:
    from ceph_tpu.utils import staging
    pc = staging.stage_perf()
    return {n: int(pc.get(n)) for n in staging.COUNTERS}


def _scrub_step(c, client, pool: str, stored: int, payload: bytes,
                shard: int) -> dict:
    """Deep scrub through the operator's verb (osd/scrub.py): the
    healthy pool reports nothing and, where the digests are a device
    program's, every stored byte went through it; then a byte flipped
    in one stored shard of an object nothing reads again is found as
    that shard's ``digest_mismatch`` and nothing else."""
    from ceph_tpu.ec.verify import verifier
    from ceph_tpu.msg.messages import PgId

    def verified() -> int:
        return sum(o.perf.get("scrub_verified_bytes")
                   for o in c.osds.values())

    on_device = verifier(str(c.cfg["osd_scrub_fold"])).on_device
    v0 = verified()
    found = client.scrub_pool(pool, deep=True)
    if found:
        raise PhaseFailed(f"cluster: a deep scrub of a healthy pool "
                          f"reports {found[:3]}")
    out = {"on_device": on_device, "verified_bytes": verified() - v0,
           "stored_bytes": stored}
    if on_device and out["verified_bytes"] != stored:
        raise PhaseFailed(f"cluster: the deep scrub put "
                          f"{out['verified_bytes']} B through the device "
                          f"program, the stores hold {stored}")
    client.write_full(pool, "planted", payload)
    pool_id = client._pool_id(pool)
    seed = client.osdmap.object_to_pg(pool_id, "planted")
    up = client.osdmap.pg_to_up_osds(pool_id, seed)
    osd = c.osds[up[shard]]
    if not osd.inject.corrupt_object(osd.store, PgId(pool_id, seed),
                                     "planted", shard=shard,
                                     offset=len(payload) // 16 + 77):
        raise PhaseFailed("cluster: nothing stored to plant a fault in")
    got = [(f["object"], f["shard"], f["kind"])
           for f in client.scrub_pg(pool, seed, deep=True).inconsistencies]
    out["planted"] = got
    if got != [("planted", shard, "digest_mismatch")]:
        raise PhaseFailed(f"cluster: a flipped byte in shard {shard} of "
                          f"'planted' was reported as {got}")
    return out


def phase_cluster(n_osds: int = 12, n_obj: int = 64,
                  obj_bytes: int = 4 << 20, inflight: int = 16,
                  seed: int = 0, k: int = 8, m: int = 3,
                  ec_shard: str | None = None, pg_num: int = 8,
                  require_fold: bool = True,
                  cfg_overrides: dict | None = None) -> dict:
    """MiniCluster in this process on the jax back-end with default
    heartbeat settings: warm-up, then ``n_obj`` seeded objects written
    ``inflight`` at a time, read back, deep-scrubbed (``_scrub_step``)
    and read again with one OSD stopped — every object compared by
    digest."""
    import numpy as np

    from ceph_tpu.ec.batcher import ECBatcher
    from ceph_tpu.tools.vstart import MiniCluster
    from ceph_tpu.utils import staging
    from ceph_tpu.utils.config import default_config

    watch = CompileWatch.get()
    before = _profiler_compiles()
    cfg = default_config()
    cfg.apply_dict({"ec_backend": "jax"})
    if ec_shard is not None:
        cfg.apply_dict({"ec_shard": ec_shard})
    settings = dict(FOLDING_SETTINGS if cfg_overrides is None
                    else cfg_overrides)
    cfg.apply_dict(settings)
    rng = np.random.default_rng(seed)
    objs = {f"obj{i:03d}": rng.integers(0, 256, obj_bytes,
                                        dtype=np.uint8).tobytes()
            for i in range(n_obj)}
    digests = {oid: _digest(b) for oid, b in objs.items()}
    out: dict = {"n_osds": n_osds, "n_obj": n_obj, "obj_bytes": obj_bytes,
                 "inflight": inflight, "ec_shard": cfg["ec_shard"],
                 "settings": dict(settings, ec_backend="jax"),
                 "csum": "host sweep", "seconds_are": "bring-up"}
    stage0 = _stage_counts()
    t0 = time.perf_counter()
    c = MiniCluster(n_osds=n_osds, cfg=cfg).start()
    try:
        client = c.client()
        client.create_pool("smoke", kind="ec", pg_num=pg_num,
                           ec_profile={"plugin": "tpu", "k": str(k),
                                       "m": str(m)})
        out["boot_seconds"] = time.perf_counter() - t0

        # warm-up (set-up), all through the client: the first write of
        # this length bucket makes the OSDs' batcher compile the
        # bucket's folded programs in the background (ec/batcher.py,
        # "Warm-up"), as it does in a deployment; wait for that, then
        # write and read warm objects
        t0 = time.perf_counter()
        warm = {f"warm{i:02d}": rng.integers(
            0, 256, obj_bytes, dtype=np.uint8).tobytes()
            for i in range(2 * inflight)}
        _write_all(client, "smoke", dict(list(warm.items())[:1]), 1)
        if not ECBatcher.warm_wait(timeout=600):
            raise PhaseFailed("cluster: the batcher's program warm-up "
                              "did not finish in 600 s")
        out["fold_warm_seconds"] = time.perf_counter() - t0
        _write_all(client, "smoke", warm, inflight)
        _read_all(client, "smoke", {o: _digest(b) for o, b in warm.items()},
                  inflight, "warm-up read")
        if ec_shard is not None:
            out["fold_result_devices"] = _fold_result_devices(c, obj_bytes)
        out["warmup_seconds"] = time.perf_counter() - t0
        out["warmup_compiles"] = watch.count()
        mark = watch.count()
        launches0 = _launches(c)

        t0 = time.perf_counter()
        _write_all(client, "smoke", objs, inflight)
        out["write_seconds"] = time.perf_counter() - t0
        t0 = time.perf_counter()
        _read_all(client, "smoke", digests, inflight, "read back")
        out["read_seconds"] = time.perf_counter() - t0
        out["marked_down_before_stop"] = _marked_down(c.mon)
        t0 = time.perf_counter()
        out["scrub"] = _scrub_step(
            c, client, "smoke",
            (len(objs) + len(warm)) * (k + m) * (obj_bytes // k),
            next(iter(warm.values())), shard=k + 1)
        out["scrub_seconds"] = time.perf_counter() - t0

        victim = sorted(c.osds)[n_osds // 2]
        # the stopped OSD leaves c.osds: keep what its batcher counted
        gone = _launches(c, only=victim)
        c.kill_osd(victim)
        c.wait_for_epoch(c.mon.osdmap.epoch, timeout=30)
        c.settle(1.0)  # every OSD and the client take the new map
        t0 = time.perf_counter()
        _read_all(client, "smoke", digests, inflight, "degraded read")
        out["degraded_read_seconds"] = time.perf_counter() - t0
        out["stopped_osd"] = victim
        out["marked_down"] = _marked_down(c.mon)

        after = watch.since(mark)
        out["compiles_after_warmup"] = len(after)
        out["compile_seconds_after_warmup"] = after
        la = _launches(c) + gone
        la.subtract(launches0)
        out["launches"], out["ops"] = la["launches"], la["ops"]
        out["ops_per_launch"] = (out["ops"] / out["launches"]
                                 if out["launches"] else 0.0)
        # keys 1, 2, 3..: buckets of the batcher's pow-2 histogram of
        # ops per launch (1: one op, 2: two or three, 3: four to seven)
        out["ops_per_launch_pow2"] = {
            b: n for b, n in sorted((b, n) for b, n in la.items()
                                    if isinstance(b, int)) if n}
        out["folded_launches"] = sum(
            n for b, n in out["ops_per_launch_pow2"].items() if b > 1)
        out["sharded_launches"] = la["sharded"]
        stage1 = _stage_counts()
        out["staging"] = {n: stage1[n] - stage0[n] for n in stage1}
        out["fallthroughs"] = staging.fallthrough_counts()
        out["dropped"] = _drops(c)
    finally:
        c.stop()
    out["compiles"] = _check_compile_limit("cluster", before)
    device_launches = sum(
        v["launches"] - before.get(s, {"launches": 0})["launches"]
        for s, v in out["compiles"].items() if s.startswith("matmul/"))
    out["device_launches"] = device_launches
    bad = {n: v for n, v in out["fallthroughs"].items() if v}
    if bad:
        raise PhaseFailed(f"cluster: host fall-throughs {bad}")
    if out["dropped"]["scheduler"].get("system"):
        raise PhaseFailed(f"cluster: system-class messages dropped "
                          f"{out['dropped']}")
    if out["marked_down"]:
        raise PhaseFailed(f"cluster: {out['marked_down']} OSDs marked "
                          f"down on failure reports")
    # the host fold of the CPU platform is not warmed (ec/batcher.py)
    if out["compiles_after_warmup"] and not staging.backend_is_cpu():
        raise PhaseFailed(f"cluster: {out['compiles_after_warmup']} "
                          f"compiles after warm-up")
    if not device_launches:
        raise PhaseFailed("cluster: no launch ran on the device")
    if require_fold and not out["folded_launches"]:
        raise PhaseFailed("cluster: no launch folded more than one op")
    return out


def _launches(c, only: int | None = None) -> collections.Counter:
    """The OSDs' batcher counts, summed: launches, ops, sharded launches,
    and under integer keys the ``ec_batch_ops_per_launch`` histogram."""
    tot: collections.Counter = collections.Counter()
    for osd_id, osd in c.osds.items():
        if only is not None and osd_id != only:
            continue
        st = osd._ec_batcher.stats
        tot.update(launches=st["launches"], ops=st["ops"],
                   sharded=st["sharded_launches"])
        hist = osd.perf.dump()["ec_batch_ops_per_launch"]["buckets_pow2"]
        tot.update({int(b): n for b, n in hist.items()})
    return tot


def _drops(c) -> dict:
    """Messages the OSDs dropped on purpose (lossy backpressure): the
    messenger's client cap and the op scheduler's per-class queue cap.
    ``client``, ``recovery`` and ``scrub`` senders re-send and their
    drops are printed; the ``system`` class (maps, peering, sub-writes,
    replies) has no retry path, is never dropped by the scheduler
    (MClockScheduler.LOSSY) and the phase fails if it was."""
    sched: dict = {}
    for osd in c.osds.values():
        for klass, n in osd.scheduler.dropped.items():
            sched[klass] = sched.get(klass, 0) + n
    return {"messenger_backpressure": c.network.dropped_backpressure,
            "scheduler": sched}


def _fold_result_devices(c, obj_bytes: int) -> list[str]:
    """Where a folded launch's result lives on this host: one fold of
    zeros at the pool's fan-out through an OSD's own codec (a sharded
    fold must come back spread over the devices, not on the first)."""
    import numpy as np

    from ceph_tpu.ec.batcher import shard_pad
    osd = next(iter(c.osds.values()))
    codec = osd._pool_codec(next(iter(osd.osdmap.pools)))
    ns, n_str = shard_pad(4, codec.shard_devices())
    fold = np.zeros((codec.k, n_str * (obj_bytes // codec.k)), np.uint8)
    dev = codec._matmul_device(codec.matrix, fold, n_shard=ns)
    return sorted(str(d) for d in dev.devices())


# -------------------------------------------------------------------- main
def run_phase(name: str, fn, *args, **kw) -> dict:
    t0 = time.perf_counter()
    try:
        res = fn(*args, **kw)
    except Exception as e:  # noqa: BLE001 - any failure fails the phase
        import traceback
        traceback.print_exc()
        emit({"phase": name, "ok": False, "error": repr(e),
              "bring_up_seconds": time.perf_counter() - t0})
        raise SystemExit(1)
    emit({"phase": name, "ok": True,
          "bring_up_seconds": time.perf_counter() - t0, **res})
    return res


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the cluster phase, sharded over four "
                         "chips and unsharded, and compare")
    args = ap.parse_args(argv)

    from ceph_tpu.utils import jaxenv
    cache = jaxenv.enable_compile_cache()
    CompileWatch.get()
    dev = run_phase("device", phase_device)
    emit({"compile_cache": cache})
    if args.four_chips:
        if dev["count"] != 4:
            emit({"phase": "device", "ok": False,
                  "error": f"--four-chips wants 4 devices, found "
                           f"{dev['count']}"})
            return 1
        run_phase("cluster/ec_shard=4", phase_cluster, seed=args.seed,
                  ec_shard="4")
        run_phase("cluster/ec_shard=off", phase_cluster, seed=args.seed,
                  ec_shard="off")
    else:
        run_phase("kernels", phase_kernels, seed=args.seed)
        run_phase("ec_benchmark", phase_ec_benchmark, seed=args.seed)
        run_phase("cluster", phase_cluster, seed=args.seed)
        run_phase("cluster/default_batcher", phase_cluster, seed=args.seed,
                  cfg_overrides={}, require_fold=False)
    print(json.dumps({"ok": True, "device": dev}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
