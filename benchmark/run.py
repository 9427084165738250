"""The benchmark's command:

    python -m benchmark.run --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process that owns the chip(s).  It refuses to start without a TPU
or with another number of devices than the cell asks for; boots the
cell's deployment at default settings on the jax back-end; warms up
through the client at the cell's own size; runs the cell's set-up
steps; measures for ``--seconds``; compares what the timed path stored
and returned with the plain reference, outside the window; prints one
JSON object as the last line of standard output.

Everything that belongs to one configuration, one traffic mix or one
per-layer metric is a file of its own, found by the name
``BENCHMARK.json`` gives (``cells.py``).
"""

from __future__ import annotations

import argparse
import contextlib
import importlib
import json
import math
import os
import shutil
import sys
import tempfile
import threading
import time

from . import cells, trace_reduce, verify, work

GRACE_S = 60.0


def log(*a) -> None:
    print(*a, file=sys.stderr, flush=True)


def device_info() -> dict:
    import jax
    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def memory_peak_bytes() -> int:
    """Peak bytes in use on the fullest chip."""
    import jax
    peak = 0
    for d in jax.devices():
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return peak


def process_age_s() -> float:
    """Seconds since this process was started, by the kernel's record
    of it (the interpreter's own start-up is part of set-up)."""
    with open("/proc/self/stat") as f:
        ticks = int(f.read().rsplit(")", 1)[1].split()[19])
    with open("/proc/uptime") as f:
        up = float(f.read().split()[0])
    return up - ticks / os.sysconf("SC_CLK_TCK")


def percentile(values: list[float], q: float) -> float:
    """Nearest rank."""
    s = sorted(values)
    return s[min(len(s), max(1, math.ceil(q * len(s)))) - 1]


def completed(ops, t1: float) -> list:
    """The operations acknowledged inside the window."""
    return [op for op in ops if op.ok and op.t_done <= t1]


def end_to_end(ops, t0: float, t1: float, setup_s: float,
               object_bytes: int) -> tuple[dict, dict]:
    """The end-to-end metrics over all the work and all the time of the
    window, and a few numbers printed beside them."""
    seconds = t1 - t0
    done = completed(ops, t1)
    slowest = t1 + GRACE_S
    lat = [((op.t_done if op.ok else slowest) - op.t_submit) * 1e3
           for op in ops]
    metrics = {
        "client_MBps": len(done) * object_bytes / seconds / 1e6,
        "client_ops_per_s": len(done) / seconds,
        "op_p90_ms": percentile(lat, 0.90) if lat else None,
        "setup_s": setup_s,
    }
    by_kind = {}
    for kind in ("read", "write"):
        kl = [v for v, op in zip(lat, ops) if op.kind == kind]
        if kl:
            by_kind[kind] = {"n": len(kl), **{
                f"p{int(q * 100)}": round(percentile(kl, q), 1)
                for q in (0.5, 0.9, 0.95, 0.99, 1.0)}}
    seen: set[int] = set()
    repeats = 0
    for op in ops:
        repeats += op.key in seen
        seen.add(op.key)
    extra = {"samples": len(lat),
             "op_p50_ms": percentile(lat, 0.50) if lat else None,
             "op_p95_ms": percentile(lat, 0.95) if lat else None,
             "latency_ms": by_kind, "repeat_keys": repeats,
             "completed_in_window": len(done),
             "finished_after_close": sum(
                 1 for op in ops if op.t_done is not None
                 and op.t_done > t1)}
    return metrics, extra


@contextlib.contextmanager
def traced_window(on: bool):
    """The profiler around the measured window, which is annotated for
    the reduction; yields the directory the trace goes to."""
    if not on:
        yield None
        return
    import jax.profiler
    trace_dir = tempfile.mkdtemp(prefix="bench-trace-")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(trace_dir, profiler_options=opts)
    try:
        with jax.profiler.TraceAnnotation(trace_reduce.WINDOW):
            yield trace_dir
    finally:
        jax.profiler.stop_trace()


def run_setup_steps(dep, plan, traffic: dict) -> set[int]:
    """The cell's set-up steps, by name; returns the objects that hold
    version 0."""
    populated: set[int] = set()
    for step in traffic.get("setup", []):
        if step["step"] == "populate":
            keys = range(plan.objects)
            dep.write_many(((plan.name(k), plan.payload(k, 0))
                            for k in keys), plan.inflight)
            populated.update(keys)
        elif step["step"] == "stop_osds":
            victims = dep.stop_osds(int(step["count"]))
            log(f"stopped OSDs {victims}")
        else:
            raise ValueError(f"unknown set-up step {step['step']!r}")
    return populated


def read_metric(spec: dict, ctx: dict):
    reader = importlib.import_module(
        f"benchmark.readers.{spec['reader']}")
    return reader.read(ctx, **spec.get("args", {}))


def execute(cell: dict, seed: int, seconds: float, trace: bool,
            require_chips: bool = True, control: bool = False) -> dict:
    """One run of one cell.  ``require_chips=False`` is the rehearsal
    of the benchmark's own tests (tiny sizes on the CPU); the command
    never passes it."""
    from ceph_tpu.utils import jaxenv
    if require_chips:
        jaxenv.enable_compile_cache()
    device = device_info()
    if require_chips and (device["platform"] != "tpu"
                          or device["count"] != cell["chips"]):
        raise SystemExit(
            f"{cell['name']} wants {cell['chips']} TPU chip(s); found "
            f"{device['count']} device(s) of platform "
            f"{device['platform']!r}")
    peaks = cells.peaks_for(device["kind"]) if require_chips else None

    from .cluster import CompileWatch, Deployment
    config, traffic = cell["config"], cell["traffic"]
    generator = importlib.import_module(
        f"benchmark.generators.{traffic['generator']}")
    watch = CompileWatch()
    parts = {"to_devices": process_age_s()}
    mark = time.perf_counter()

    def lap(name: str) -> None:
        nonlocal mark
        now = time.perf_counter()
        parts[name] = now - mark
        mark = now

    plan = generator.make_plan(traffic, seed)
    lap("plan")
    dep = Deployment(config)
    lap("boot")
    try:
        warm = [(f"warm{i:03d}", plan.payload(i, 0))
                for i in range(2 * plan.inflight)]
        dep.warm(warm, plan.inflight)
        lap("warm")
        populated = run_setup_steps(dep, plan, traffic)
        lap("steps")
        holes = {k: dep.data_holes(plan.name(k))
                 for k in range(plan.objects)}
        compiles_before = watch.count()
        before = dep.counters()
        with traced_window(trace) as trace_dir:
            setup_s = process_age_s()
            ops, t0, t1 = generator.run(plan, dep, seconds, annotate=trace,
                                        grace=GRACE_S)
        after = dep.counters()
        compiles = watch.count() - compiles_before
        device["memory_peak_bytes"] = memory_peak_bytes()
        health = dep.health()

        metrics, extra = end_to_end(ops, t0, t1, setup_s,
                                    plan.object_bytes)
        t_v = time.perf_counter()
        compared = verify.compare(
            plan, ops, dep, dep.k, dep.m, dep.stripe_unit, populated,
            int(traffic["verify"]["objects"]))
        extra["verify_s"] = time.perf_counter() - t_v
        extra["setup_parts_s"] = {n: round(v, 2) for n, v in parts.items()}
        extra["compiles_in_setup"] = compiles_before
        extra["drops"] = {n: after[n] - before.get(n, 0.0) for n in after
                          if "dropped" in n and after[n] != before.get(n)}
        compared_control = None
        if control:   # benchmark/control.py only, never the command
            from .control import compare_control
            compared_control = compare_control(
                traffic["verify"]["control"], plan, ops, dep, populated,
                int(traffic["verify"]["objects"]))
    finally:
        dep.close()

    failed = sum(1 for op in ops if not op.ok)
    for op in [o for o in ops if o.error is not None][:5]:
        log(f"failed: {op.kind} {plan.name(op.key)}: {op.error}")
    log("beside the metrics:", json.dumps(extra))
    bad = {n: v for n, v in health.items() if v}
    if bad:
        log(f"not a measurement of the device path: {bad}")
        raise SystemExit(3)

    if trace:
        done = completed(ops, t1)
        reduced = trace_reduce.reduce(
            trace_reduce.load(trace_reduce.find_xplane(trace_dir)),
            chips=cell["chips"])
        shutil.rmtree(trace_dir, ignore_errors=True)
        if reduced["busy_s"] <= 0:
            log("no operation ran on the device in the traced window")
            raise SystemExit(3)
        ctx = {
            "counters": {n: after[n] - before.get(n, 0.0) for n in after},
            "client_ops": len(done),
            "user_bytes": len(done) * plan.object_bytes,
            "needed_bytes": work.needed_bytes(
                ((op.kind, plan.object_bytes, holes[op.key])
                 for op in done), dep.k, dep.m),
            "trace": reduced, "peaks": peaks, "chips": cell["chips"],
            "compiles_in_window": compiles,
        }
        ctx["counters"]["client.ops"] = float(ctx["client_ops"])
        ctx["counters"]["client.user_bytes"] = float(ctx["user_bytes"])
        out_metrics = {}
        for spec in cell["per_layer"]:
            value = read_metric(spec, ctx)
            if value is not None:
                out_metrics[spec["name"]] = {"value": value,
                                             "unit": spec["unit"]}
        device["busy_s"] = reduced["busy_s"]
        device["window_s"] = reduced["window_s"]
    else:
        units = {e["name"]: e["unit"] for e in cell["end_to_end"]}
        out_metrics = {n: {"value": metrics[n], "unit": units[n]}
                       for n in units if metrics.get(n) is not None}

    result = {"correct": verify.is_correct(compared),
              "attempted": len(ops), "failed": failed,
              "metrics": out_metrics, "device": device}
    if trace:
        result["breakdown"] = {"device_ops": reduced["device_ops"],
                               "idle_gaps": reduced["idle_gaps"]}
    if compared_control is not None:
        result["control"] = compared_control
    result["compared"] = compared
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    cell = cells.load_cell(args.workload)
    result = execute(cell, args.seed, args.seconds, bool(args.trace))
    for name, c in result["compared"].items():
        log(f"compared {name}: {c['value']} (limit {c['limit']})")
    print(json.dumps(result), flush=True)
    # a thread of the program that outlives its cluster must not hold
    # the chip past the result
    bail = threading.Timer(30.0, os._exit, (0,))
    bail.daemon = True
    bail.start()
    return 0


if __name__ == "__main__":
    sys.exit(main())
