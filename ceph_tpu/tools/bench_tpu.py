"""Device-side EC bench worker: run the batched encode pipeline on the
default JAX backend and print one JSON line.

Run as a subprocess by bench.py: one process per chip, so the parent
stays off JAX and can time this worker out.

Methodology.  Every timed repetition fetches a 4-byte digest computed
from the full parity output, which forces the execution to complete
while moving almost nothing off the device; the digest is checked
against the CPU oracle, so a kernel that did not really run (or ran
wrong) cannot produce a timing at all.  Reported numbers:

- kernel_gbps: device-resident lanes in HBM -> parity in HBM.  A single
  encode at any HBM-fittable batch finishes inside one dispatch round
  trip, so one-dispatch-per-rep timing is unresolvable; instead each
  timed dispatch runs ITERS encodes in a rolled lax.fori_loop,
  iteration i encoding (lanes ^ i) and folding an XOR-digest of the
  parity into the loop carry, so N*kernel time dominates the one round
  trip (subtracted).  The digest still proves every loop ran real math:
  GF encode is XOR-linear, the per-iteration constant region contributes
  0 to an XOR-digest over an even lane count, so with ITERS odd the
  expected accumulator equals the XOR-digest of the base buffer's CPU
  parity — checked per rep, over DISTINCT input buffers.
- staging_gbps: host -> device transfer rate (device_put, waited for
  with block_until_ready).
- e2e_gbps: host bytes in -> full parity bytes back on host, one shot
  (BASELINE.md's staging-included rule).
- rtt_s: median trivial-fetch round trip, subtracted from kernel reps.

GB/s counts source data bytes (iterations x size / elapsed / 2^30),
matching the reference tool's convention
(ceph_erasure_code_benchmark.cc:193).
"""

from __future__ import annotations

import argparse
import json
import statistics
import sys
import time

import numpy as np


def main() -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--k", type=int, default=8)
    p.add_argument("--m", type=int, default=3)
    p.add_argument("--stripe-bytes", type=int, default=1024 * 1024)
    p.add_argument("--batch", type=int, default=64)
    p.add_argument("--reps", type=int, default=4)
    p.add_argument("--technique", default="reed_sol_van")
    p.add_argument("--kernel", default="auto",
                   choices=["auto", "pallas", "xla"],
                   help="pallas = VPU bit-term Pallas kernel; xla = same "
                        "math as a fused XLA graph; auto = time both, "
                        "keep the faster")
    p.add_argument("--skip-e2e", action="store_true",
                   help="skip the full-parity-fetch end-to-end rep "
                        "(a whole-output copy per rep)")
    p.add_argument("--candidate-budget", type=float, default=150.0,
                   help="soft per-candidate wall-clock budget (s): the "
                        "iteration ladder stops escalating when the "
                        "projected timing cost exceeds it")
    p.add_argument("--workload", default="encode",
                   choices=["encode", "decode"],
                   help="decode = reconstruct m erased shards from k "
                        "survivors (the recovery hot path)")
    p.add_argument("--csum", action="store_true",
                   help="fuse per-chunk CRC32C into the encode pass "
                        "(Checksummer.h:13 north star) and time "
                        "encode+csum; the digest gate then also proves "
                        "the csums (std-crc is raw-linear over XOR, so "
                        "with an even batch the per-iteration constant "
                        "contributions cancel in the XOR accumulator)")
    p.add_argument("--force-cpu", action="store_true",
                   help="hermetic CPU run: pin jax_platforms to cpu "
                        "before backend init")
    args = p.parse_args()

    import jax

    from ceph_tpu.utils import jaxenv
    jaxenv.enable_compile_cache()
    if args.force_cpu:
        jaxenv.force_cpu()
    import jax.numpy as jnp

    backend = jax.default_backend()
    from ceph_tpu.ops import gf256, native
    from ceph_tpu.ops.ec_kernels import RegionMatmul, lane_quantum

    if args.technique == "reed_sol_van":
        M = gf256.vandermonde_matrix(args.k, args.m)
    elif args.technique == "cauchy_good":
        M = gf256.cauchy_good_matrix(args.k, args.m)
    else:
        M = gf256.cauchy_matrix(args.k, args.m)

    if args.workload == "decode":
        # reconstruction of the e erased data shards from k survivors
        # (worst case: e = m data shards lost; survivors = remaining
        # data + all parity).  The working matrix is the e×k block of
        # the inverted survivor rows — the exact matmul ECBackend's
        # decode performs (ceph_erasure_code_benchmark.cc:260-326
        # semantics); the harness below times/verifies it identically.
        e = min(args.m, args.k)
        avail = list(range(e, args.k)) + list(range(args.k, args.k + e))
        W = gf256.decode_matrix(M, args.k, avail)[:e]
    else:
        W = M

    k, r = args.k, int(W.shape[0])
    chunk = args.stripe_bytes // k
    cols = args.batch * chunk           # stripes fold into the column axis
    rm = RegionMatmul(W)
    # round up to whole kernel tiles/blocks (encode_lanes contract, same
    # quantum rule RegionMatmul applies); the buffers are generated at
    # this size, so no padding bytes exist
    cols += (-cols) % lane_quantum(cols, rm.block)
    n4 = cols // 4
    rng = np.random.default_rng(0)

    # ---- candidates: all take (k, n4) uint32 lanes, return (parity_lanes,
    # uint32-sum digest); the digest fetch is the forcing function --------
    def with_digest(core):
        def fn(x32):
            y32 = core(x32)
            return y32, jnp.sum(y32, dtype=jnp.uint32)
        return jax.jit(fn)

    from jax import lax

    def xordig(y32):
        return lax.reduce(y32, jnp.uint32(0), lax.bitwise_xor,
                          tuple(range(y32.ndim)))

    def with_loop(core, iters: int):
        """ITERS encodes per dispatch (see module docstring); returns
        only the 4-byte XOR-digest accumulator.  Fused (parity, csums)
        cores fold BOTH outputs — the identity holds because std crc is
        raw-linear over XOR and the even batch cancels the constant
        per-iteration contributions pairwise."""
        def fn(x32):
            def body(i, acc):
                out = core(jnp.bitwise_xor(x32, jnp.uint32(i)))
                if isinstance(out, tuple):
                    y32, cs = out
                    return jnp.bitwise_xor(
                        acc, jnp.bitwise_xor(xordig(y32), xordig(cs)))
                return jnp.bitwise_xor(acc, xordig(out))
            return lax.fori_loop(0, iters, body, jnp.uint32(0))
        return jax.jit(fn)

    candidates: dict[str, object] = {}
    candidates_core: dict[str, object] = {}

    crcfn = None
    if args.csum:
        if args.batch % 2:
            p.error("--csum needs an even --batch (digest identity)")
        if (n4 * 4) % chunk:
            p.error("--csum: kernel tile rounding broke the chunk "
                    "boundary; pick a power-of-two stripe size")
        from ceph_tpu.ops.checksum import CrcPlan
        chunk_words = chunk // 4
        crcfn = CrcPlan(chunk).device_fn()

        def with_csums(core):
            def fused(x32):
                y32 = core(x32)
                stack = jnp.concatenate([x32, y32], axis=0)
                words = stack.reshape(stack.shape[0], -1, chunk_words)
                return y32, crcfn(words)  # (rows, batch) uint32
            return fused

    def register(name, core):
        if crcfn is not None:
            fused = with_csums(core)
            candidates_core[name] = fused

            def fn(x32, _f=fused):
                y32, cs = _f(x32)
                return y32, (jnp.sum(y32, dtype=jnp.uint32)
                             + jnp.sum(cs, dtype=jnp.uint32))
            candidates[name] = jax.jit(fn)
            return
        candidates_core[name] = core
        candidates[name] = with_digest(core)

    if args.kernel in ("auto", "pallas") and (
            rm._use_pallas or args.kernel == "pallas"):
        # off-TPU, _lanes_op degenerates to the same jnp graph as "xla" —
        # skip it in auto mode; an explicit request gets the real Pallas
        # kernel in interpret mode (honest label, interpreter speed)
        if not rm._use_pallas:
            rm = RegionMatmul(W, interpret=True)
        register("pallas", rm._lanes_op(n4))
    if args.kernel in ("auto", "xla"):
        from ceph_tpu.ops.ec_kernels import _rows_op, _terms
        terms = _terms(W)
        register("xla", lambda x32: _rows_op(x32, terms))
    def progress(msg: str) -> None:
        print(f"bench_tpu: {msg}", file=sys.stderr, flush=True)

    # ---- RTT: trivial computation + 4-byte fetch, distinct inputs ------
    progress(f"backend={backend} measuring rtt")
    bump = jax.jit(lambda s: s + jnp.uint32(1))
    int(bump(jnp.uint32(0)))  # compile
    rtts = []
    for i in range(5):
        t0 = time.perf_counter()
        int(bump(jnp.uint32(i + 1)))
        rtts.append(time.perf_counter() - t0)
    rtt = statistics.median(rtts)

    # ---- staging: distinct host buffers -> device ----------------------
    # reps timed + 1 warm/verify; E2E_SHOTS extra host buffers are
    # reserved for the e2e leg and never staged here, so neither their
    # transfer nor their execution can be served from a memo.
    # Each transfer is timed INDIVIDUALLY and the MEDIAN rate reported:
    # summing one window let a single stall (page-fault storm, load
    # spike, GC) poison the whole number — BENCH_SWEEP_CPU round-4 rows
    # ranged 0.05-1.57 GB/s for the identical copy on this box.
    E2E_SHOTS = 0 if args.skip_e2e else 3
    progress(f"rtt {rtt:.4f}s; staging {args.reps + 1} buffers of "
             f"{k * n4 * 4 / 2**20:.0f} MiB")
    hosts = [rng.integers(0, 2**32, (k, n4), dtype=np.uint32)
             for _ in range(args.reps + 1 + E2E_SHOTS)]
    nbytes = hosts[0].nbytes
    # warm transfer + the per-shape gather executable on the first
    # buffer (untimed), then time the rest one by one.  The put+land
    # idiom lives in utils/staging.device_put_landed (shared with the
    # batcher's ingest plane — this file used to hand-copy it at
    # three sites); the bench still runs its own clock around the
    # helper, the recorded ec_stage_* telemetry is cumulative and
    # separate.
    from ceph_tpu.utils import staging as _staging
    bufs = [_staging.device_put_landed(hosts[0], record=False)]
    stage_dts = []
    for h in hosts[1:args.reps + 1]:
        t0 = time.perf_counter()
        bufs.append(_staging.device_put_landed(h))
        stage_dts.append(time.perf_counter() - t0 - rtt)
    stage_med = statistics.median(stage_dts)
    staging_gbps = (None if stage_med <= 0
                    else round(nbytes / stage_med / 2**30, 4))
    staging_spread = ([round(nbytes / dt / 2**30, 4) for dt in
                       sorted(stage_dts, reverse=True)]
                      if min(stage_dts) > 0 else None)

    # ---- per-buffer oracle digests (prove every timed execution) -------
    def oracle_parity(h):
        return (native.encode_region(W, h.view(np.uint8))
                if native.available()
                else gf256.encode_region(W, h.view(np.uint8)))

    def oracle_csums(h, par) -> np.ndarray:
        stack = np.concatenate([h.view(np.uint8), par], axis=0)
        blocks = stack.reshape(stack.shape[0], -1, chunk)
        return np.array(
            [[native.crc32c(blocks[r, b].tobytes())
              for b in range(blocks.shape[1])]
             for r in range(blocks.shape[0])], dtype=np.uint32)

    def sum_digest(par, cs=None) -> int:
        s = int(np.sum(par.view(np.uint32), dtype=np.uint32))
        if cs is not None:
            s = (s + int(np.sum(cs, dtype=np.uint32))) & 0xFFFFFFFF
        return s

    def xor_digest(par, cs=None) -> int:
        x = int(np.bitwise_xor.reduce(par.view(np.uint32), axis=None))
        if cs is not None:
            x ^= int(np.bitwise_xor.reduce(cs, axis=None))
        return x

    progress(f"staged ({staging_gbps} GB/s); computing oracle digests")
    oracle_hosts = hosts[:args.reps + 1]
    parities = [oracle_parity(h) for h in oracle_hosts]
    csums_l = ([oracle_csums(h, p) for h, p in zip(oracle_hosts, parities)]
               if args.csum else [None] * len(parities))
    wants_sum = [sum_digest(p, c) for p, c in zip(parities, csums_l)]
    wants_xor = [xor_digest(p, c) for p, c in zip(parities, csums_l)]
    # odd ITERS + even lane count make the loop accumulator equal the
    # base buffer's parity XOR-digest (module docstring)
    assert n4 % 2 == 0, "xor-digest identity needs an even lane count"
    ITER_LADDER = (255, 2047, 16383)

    # ---- per-candidate: verify single-shot, then time the looped form --
    results = {}
    for name, fn in candidates.items():
        progress(f"{name}: compile + single-shot verify")
        try:
            t0 = time.perf_counter()
            _, dig = fn(bufs[-1])
            got = int(dig)
            compile_s = time.perf_counter() - t0
        except Exception as e:  # compile/runtime failure: skip candidate
            print(f"bench_tpu: {name} failed: {e}", file=sys.stderr)
            continue
        if got != wants_sum[-1]:
            print(f"bench_tpu: {name} WRONG digest {got} != "
                  f"{wants_sum[-1]}", file=sys.stderr)
            continue
        entry = {"kernel_gbps": None, "compile_s": round(compile_s, 3)}
        spent = 0.0
        prev = None  # (iters, median) from the rung below
        for iters in ITER_LADDER:
            if prev is not None:
                projected = prev[1] * iters / prev[0] * (args.reps + 1)
                if spent + projected > args.candidate_budget:
                    print(f"bench_tpu: {name} stopping ladder at "
                          f"x{prev[0]} (x{iters} projected "
                          f"{projected:.0f}s over budget)",
                          file=sys.stderr)
                    break
            progress(f"{name}: loop x{iters} compile + warm")
            lfn = with_loop(candidates_core[name], iters)
            try:
                t0 = time.perf_counter()
                got = int(lfn(bufs[-1]))  # compile + warm verify
                warm_s = time.perf_counter() - t0
            except Exception as e:
                print(f"bench_tpu: {name} loop x{iters} failed: {e}",
                      file=sys.stderr)
                break
            spent += warm_s
            if got != wants_xor[-1]:
                # the digest gate comes FIRST: a wrong kernel must never
                # publish a number, not even the warm bound below
                print(f"bench_tpu: {name} loop x{iters} WRONG xor-digest "
                      f"{got} != {wants_xor[-1]}", file=sys.stderr)
                break
            if warm_s * args.reps > args.candidate_budget:
                # kernel too slow to time at even this rung: report the
                # warm run as a (pessimistic, compile-inclusive) bound
                print(f"bench_tpu: {name} x{iters} warm run took "
                      f"{warm_s:.0f}s — skipping timed reps",
                      file=sys.stderr)
                entry["warm_bound_gbps"] = round(
                    iters * nbytes / warm_s / 2**30, 4)
                entry["iters"] = iters
                break
            times, bad = [], False
            for i in range(args.reps):
                t0 = time.perf_counter()
                got = int(lfn(bufs[i]))
                times.append(time.perf_counter() - t0)
                if got != wants_xor[i]:
                    print(f"bench_tpu: {name} loop rep {i} WRONG "
                          f"xor-digest", file=sys.stderr)
                    bad = True
                    break
            if bad:
                break
            med = statistics.median(times)
            spent += sum(times)
            prev = (iters, med)
            entry["rep_times_s"] = [round(t, 6) for t in times]
            entry["iters"] = iters
            if med - rtt <= rtt:  # still RTT-dominated: climb the ladder
                print(f"bench_tpu: {name} x{iters} RTT-bound "
                      f"(median {med:.4f}s vs rtt {rtt:.4f}s), "
                      f"escalating", file=sys.stderr)
                continue
            entry["kernel_gbps"] = iters * nbytes / (med - rtt) / 2**30
            break
        results[name] = entry
    measurable = {n: v for n, v in results.items()
                  if v["kernel_gbps"] is not None}
    if not measurable:
        print("bench_tpu: no candidate produced a verified, measurable "
              "timing", file=sys.stderr)
        return 1

    best = max(measurable, key=lambda n: measurable[n]["kernel_gbps"])

    # ---- end-to-end: host bytes in -> full parity bytes out ------------
    # uses the reserved never-seen buffers: fresh transfers and fresh
    # executions, immune to memoization.  Each shot is
    # verified byte-exact against the CPU oracle and timed separately;
    # the MEDIAN is reported (same stall-robustness rationale as the
    # staging probe above).
    e2e_gbps = None
    e2e_spread = None
    if E2E_SHOTS:
        fn = candidates[best]  # already compiled by the verify pass
        e2e_dts = []
        for shot, h in enumerate(hosts[args.reps + 1:]):
            t0 = time.perf_counter()
            # landing not forced: the full parity fetch below is the
            # forcing function for the whole shot
            d = _staging.device_put_landed(h, force=False)
            y32, _ = fn(d)
            parity = np.asarray(y32)      # full fetch
            e2e_dts.append(time.perf_counter() - t0)
            if parity.view(np.uint8).tobytes() != \
                    oracle_parity(h).tobytes():
                print(f"bench_tpu: e2e shot {shot} WRONG parity bytes",
                      file=sys.stderr)
                e2e_dts = []
                break
        if e2e_dts:
            # warm-rep median: shot 0 pays one-time costs (the
            # per-shape transfer executable, allocator growth) — with
            # 3 shots the BENCH_SWEEP_CPU rows read e.g. [0.26, 0.25,
            # 0.13 cold] and folding the cold shot into the median
            # understates steady state.  The spread keeps every shot
            # (cold included, slowest-first) for honesty.
            warm = e2e_dts[1:] if len(e2e_dts) > 1 else e2e_dts
            e2e_gbps = nbytes / statistics.median(warm) / 2**30
            e2e_spread = [round(nbytes / dt / 2**30, 6)
                          for dt in sorted(e2e_dts, reverse=True)]

    print(json.dumps({
        "backend": backend,
        "kernel": best,
        "workload": args.workload + ("+csum" if args.csum else ""),
        "k": k, "m": r, "stripe_bytes": args.stripe_bytes,
        "batch": args.batch, "reps": args.reps,
        "bytes_per_rep": nbytes,
        "digest_verified": True,
        "rtt_s": round(rtt, 6),
        "staging_gbps": staging_gbps,
        "staging_spread_gbps": staging_spread,
        "kernel_gbps": round(measurable[best]["kernel_gbps"], 4),
        "e2e_gbps": None if e2e_gbps is None else round(e2e_gbps, 6),
        "e2e_spread_gbps": e2e_spread,
        "candidates": results,
    }))
    return 0


if __name__ == "__main__":
    sys.exit(main())
