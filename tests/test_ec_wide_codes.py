"""Wide/local codes through the batching seam (ISSUE 11).

- batcher signature hardening: codec identity/sub-chunk layout rides
  every flush signature, so two codecs sharing a matrix's bytes+shape
  can never coalesce into one fold;
- batched-vs-unbatched byte-identity for CLAY/LRC/SHEC encode + decode
  across the erasure grid (including the CLAY d != k+m-1 full-decode
  fallback and an LRC LAYERS-grammar profile), against the numpy-backend
  oracle;
- the folded CLAY MSR repair (ECBatcher.repair) and the narrow
  repair-equation decode folds (LRC locality group / SHEC shingle);
- e2e: degraded reads per plugin through the PR-5 read pipeline, and
  the narrow/sub-chunk RECOVERY fetch path (kill + fresh-store revive:
  rebuilds read one locality group / alpha/q sub-chunk ranges instead
  of k whole shards — counter-verified);
- the bench matrix: the tier-1-sized smoke leg runs here, the full
  {rs, clay, lrc, shec} x {healthy, degraded, storm} leg is `slow`.
"""

import itertools
import threading
import time

import numpy as np
import pytest

import bench
from ceph_tpu import ec
from ceph_tpu.ec.batcher import ECBatcher
from ceph_tpu.ec.interface import ErasureCodeError
from ceph_tpu.tools.vstart import MiniCluster
from ceph_tpu.utils.config import default_config

RNG = np.random.default_rng(29)

LAYERS_PROFILE = {
    # 4 data, 1 global RS parity over all data, 2 local XORs over the
    # halves (the reference's pyramid composition semantics)
    "mapping": "DD_DD__",
    "layers": ('[["DDcDD__", "plugin=jerasure technique=reed_sol_van"],'
               ' ["DD___c_", "plugin=xor"],'
               ' ["___DD_c", "plugin=xor"]]'),
}

WIDE_PROFILES = [
    ("clay", {"k": "4", "m": "2", "d": "5"}),       # MSR point (m == q)
    ("clay", {"k": "3", "m": "3", "d": "4"}),       # d != k+m-1 fallback
    ("lrc", {"k": "4", "m": "2", "l": "3"}),
    ("lrc", dict(LAYERS_PROFILE)),
    ("shec", {"k": "8", "m": "4", "c": "3"}),
]


def _mk(plugin, prof, backend):
    return ec.factory(plugin, dict(prof, backend=backend))


def _chunk_len(codec):
    # divisible by alpha for CLAY; exercise a non-pow2-friendly width
    return codec.get_sub_chunk_count() * 384


def _full_map(codec, data):
    parity = codec.encode_chunks(data)
    out = {i: data[i] for i in range(codec.k)}
    out.update({codec.k + j: parity[j] for j in range(codec.m)})
    return out


def _burst(fn, n, stagger=0.02):
    res = [None] * n
    errs = []

    def run(i):
        try:
            res[i] = fn(i)
        except Exception as e:  # noqa: BLE001 - surfaced by the test
            errs.append(e)

    threads = [threading.Thread(target=run, args=(i,)) for i in range(n)]
    threads[0].start()
    time.sleep(stagger)
    for t in threads[1:]:
        t.start()
    for t in threads:
        t.join()
    assert not errs, errs[:3]
    return res


# ------------------------------------------------- signature hardening
def test_fold_sig_prevents_cross_codec_coalescing():
    """Two codecs with IDENTICAL matrix bytes+shape but different fold
    identities must not share a fold (regression: the sig used to be
    matrix-derived only)."""
    rs = ec.factory("tpu", {"k": 4, "m": 2, "backend": "jax"})

    class Impostor(type(rs)):
        def fold_sig(self):
            return ("impostor",)

    imp = Impostor({"k": 4, "m": 2, "backend": "jax"})
    assert np.array_equal(imp.matrix, rs.matrix)
    assert imp.fold_sig() != rs.fold_sig()
    datas = [RNG.integers(0, 256, (4, 2048), dtype=np.uint8)
             for _ in range(6)]
    b = ECBatcher(window_us=5000)
    res = _burst(lambda i: b.encode(rs if i % 2 else imp, datas[i]), 6)
    # one window, two signatures: at least two launches (same-codec ops
    # still coalesce) and byte-correct parity everywhere
    assert b.stats["launches"] >= 2
    oracle = ec.factory("tpu", {"k": 4, "m": 2, "backend": "numpy"})
    for i, (p, _c) in enumerate(res):
        assert np.array_equal(np.asarray(p),
                              oracle.encode_chunks(datas[i]))


def test_fold_sig_distinguishes_wide_codecs():
    sigs = {("tpu", "k4m2"): ec.factory(
        "tpu", {"k": 4, "m": 2, "backend": "numpy"}).fold_sig()}
    for plugin, prof in WIDE_PROFILES:
        c = _mk(plugin, prof, "numpy")
        key = (plugin, tuple(sorted(prof.items())))
        sigs[key] = c.fold_sig()
    vals = list(sigs.values())
    assert len(set(map(repr, vals))) == len(vals), sigs


# --------------------------------------- batched-vs-oracle byte identity
@pytest.mark.parametrize("plugin,prof", WIDE_PROFILES)
def test_batched_encode_matches_oracle(plugin, prof):
    codec = _mk(plugin, prof, "jax")
    oracle = _mk(plugin, prof, "numpy")
    L = _chunk_len(codec)
    datas = [RNG.integers(0, 256, (codec.k, L), dtype=np.uint8)
             for _ in range(6)]
    # a window well over _burst's 20 ms stagger: the five that follow
    # the leader join its group however slowly their threads start
    b = ECBatcher(window_us=200_000)
    res = _burst(lambda i: b.encode(codec, datas[i]), 6)
    assert b.stats["launches"] < 6, "burst never coalesced"
    for i, (p, _c) in enumerate(res):
        assert np.array_equal(np.asarray(p),
                              oracle.encode_chunks(datas[i])), i


@pytest.mark.parametrize("plugin,prof", WIDE_PROFILES)
def test_batched_decode_matches_oracle_across_erasure_grid(plugin, prof):
    codec = _mk(plugin, prof, "jax")
    oracle = _mk(plugin, prof, "numpy")
    L = _chunk_len(codec)
    n = codec.chunk_count
    data = RNG.integers(0, 256, (codec.k, L), dtype=np.uint8)
    full = _full_map(oracle, data)
    b = ECBatcher(window_us=200)
    grid = [list(c) for r in (1, 2)
            for c in itertools.combinations(range(n), r)]
    tested = skipped = 0
    for erased in grid:
        avail = {i: c for i, c in full.items() if i not in erased}
        try:
            want_oracle = oracle.decode(list(erased), dict(avail))
        except ErasureCodeError:
            # non-MDS envelope (SHEC): the batched path must raise too
            with pytest.raises(ErasureCodeError):
                b.decode(codec, list(erased), dict(avail))
            skipped += 1
            continue
        out = b.decode(codec, list(erased), dict(avail))
        for i in erased:
            assert np.array_equal(np.asarray(out[i]),
                                  want_oracle[i]), (erased, i)
        tested += 1
    assert tested > 0
    if plugin == "shec":
        assert skipped > 0  # the envelope was actually exercised


def test_clay_full_decode_fallback_geometry():
    """d != k+m-1 (m != q): the sub-chunk repair path refuses, full
    decode (also batched) stays byte-exact."""
    codec = _mk("clay", {"k": "3", "m": "3", "d": "4"}, "jax")
    assert codec.q != codec.m
    with pytest.raises(ErasureCodeError, match="d = k\\+m-1"):
        codec.repair_chunk(0, {}, codec.alpha * 16)


def test_clay_repair_fold_matches_oracle():
    codec = _mk("clay", {"k": "4", "m": "2", "d": "5"}, "jax")
    oracle = _mk("clay", {"k": "4", "m": "2", "d": "5"}, "numpy")
    L = _chunk_len(codec)
    lost = 2
    planes = codec.repair_planes(lost)
    datas = [RNG.integers(0, 256, (4, L), dtype=np.uint8)
             for _ in range(5)]
    fulls = [_full_map(oracle, d) for d in datas]

    def subs(i):
        return {h: fulls[i][h].reshape(codec.alpha,
                                       L // codec.alpha)[planes]
                for h in range(6) if h != lost}

    b = ECBatcher(window_us=5000)
    res = _burst(lambda i: b.repair(codec, lost, subs(i), L), 5)
    assert b.stats["launches"] < 5
    for i, got in enumerate(res):
        assert np.array_equal(np.asarray(got), fulls[i][lost]), i
        # and the per-op oracle path agrees
        assert np.array_equal(oracle.repair_chunk(lost, subs(i), L),
                              fulls[i][lost])


def test_lrc_narrow_fold_uses_locality_group():
    """A single-failure LRC decode folds over the repair equation's
    participants — |group| rows, not k — and decodes from ONLY those
    chunks."""
    codec = _mk("lrc", {"k": "4", "m": "2", "l": "3"}, "jax")
    oracle = _mk("lrc", {"k": "4", "m": "2", "l": "3"}, "numpy")
    n = codec.chunk_count
    rows = codec.fold_rows([0], list(range(1, n)))
    assert rows is not None and len(rows) < codec.k, rows
    L = 2048
    data = RNG.integers(0, 256, (4, L), dtype=np.uint8)
    full = _full_map(oracle, data)
    b = ECBatcher(window_us=200)
    out = b.decode(codec, [0], {s: full[s] for s in rows})
    assert np.array_equal(np.asarray(out[0]), full[0])


def test_shec_narrow_fold_smaller_than_k():
    codec = _mk("shec", {"k": "8", "m": "4", "c": "3"}, "numpy")
    n = codec.chunk_count
    for lost in range(codec.k):
        rows = codec.fold_rows([lost],
                               [i for i in range(n) if i != lost])
        assert rows is not None and len(rows) <= codec.window < codec.k


# ------------------------------------------------------- e2e clusters
def _cfg(**over):
    cfg = default_config()
    cfg.apply_dict({"osd_heartbeat_interval": 0.05,
                    "osd_heartbeat_grace": 0.5,
                    "ec_backend": "native",
                    "osd_op_num_shards": 2,
                    "ms_dispatch_workers": 2,
                    "osd_recovery_max_active": 4, **over})
    return cfg


def _write_read_kill_read(c, cl, pool, n_obj=6, size=20_000):
    payloads = {}
    for i in range(n_obj):
        data = bytes(RNG.integers(0, 256, size, dtype=np.uint8))
        payloads[f"{pool}-o{i}"] = data
        cl.write_full(pool, f"{pool}-o{i}", data)
    for name, data in payloads.items():
        assert cl.read(pool, name) == data, f"healthy {name}"
    return payloads


def _assert_reads(c, cl, pool, payloads, what, retries=40):
    for name, data in payloads.items():
        got = None
        for _ in range(retries):
            try:
                got = cl.read(pool, name)
                break
            except Exception:  # noqa: BLE001 - transient EAGAIN
                time.sleep(0.1)
        assert got == data, f"{what}: {name}"


def _counters(c, prefix="recovery"):
    tot = {}
    for osd in c.osds.values():
        for k, v in osd.perf.dump().items():
            if k.startswith(prefix) and isinstance(v, (int, float)):
                tot[k] = tot.get(k, 0) + v
    return tot


@pytest.mark.parametrize("plugin,profile,n_osds", [
    ("clay", {"plugin": "clay", "k": "2", "m": "2", "d": "3"}, 4),
    ("lrc", {"plugin": "lrc", "k": "2", "m": "1", "l": "3"}, 4),
    ("shec", {"plugin": "shec", "k": "3", "m": "2", "c": "1"}, 5),
])
def test_e2e_degraded_read_per_plugin(plugin, profile, n_osds):
    """Degraded reads through the PR-5 read pipeline for each wide
    plugin: kill one OSD (no spares: the PG stays degraded) and every
    object must still read back byte-identical through the coalesced
    read path + batched decode."""
    cfg = _cfg(ec_read_coalesce="on", ec_read_cache_serve="off")
    c = MiniCluster(n_osds=n_osds, cfg=cfg).start()
    try:
        cl = c.client()
        cl.create_pool("w", kind="ec", pg_num=2,
                       ec_profile=dict(profile, backend="numpy"))
        payloads = _write_read_kill_read(c, cl, "w")
        c.kill_osd(n_osds - 1)
        c.settle(0.8)
        _assert_reads(c, cl, "w", payloads, f"{plugin} degraded")
    finally:
        c.stop()


def test_e2e_lrc_narrow_recovery_fetch():
    """Kill + FRESH-store revive on an LRC pool whose locality group
    (l=3) is narrower than k=4: every rebuilt shard must fetch its one
    locality group — counter-verified: narrow rebuilds happened, and
    the fleet-wide repair-bytes-per-lost-byte stays below k."""
    c = MiniCluster(n_osds=8, cfg=_cfg()).start()
    try:
        cl = c.client()
        cl.create_pool("lw", kind="ec", pg_num=2,
                       ec_profile={"plugin": "lrc", "k": "4", "m": "2",
                                   "l": "3", "backend": "numpy"})
        payloads = _write_read_kill_read(c, cl, "lw", n_obj=6)
        c.kill_osd(7)
        c.settle(0.5)
        c.revive_osd(7)  # fresh store: its shards all rebuild
        deadline = time.time() + 30
        while time.time() < deadline:
            tot = _counters(c)
            if tot.get("recovery_narrow_rebuilds", 0) > 0:
                break
            time.sleep(0.1)
        tot = _counters(c)
        assert tot.get("recovery_narrow_rebuilds", 0) > 0, tot
        assert tot.get("recovery_rebuilt_bytes", 0) > 0
        ratio = tot["recovery_fetch_bytes"] / tot["recovery_rebuilt_bytes"]
        assert ratio < 4, f"repair-bytes-per-lost-byte {ratio} >= k"
        c.settle(1.0)
        _assert_reads(c, cl, "lw", payloads, "post-recovery")
    finally:
        c.stop()


def test_e2e_clay_subchunk_recovery_fetch():
    """Kill + fresh revive on a CLAY pool at the MSR point (d=k+m-1):
    rebuilds fetch only alpha/q sub-chunk ranges per helper — the
    sub-chunk counter fires and the byte ratio lands near (n-1)/q,
    below the k whole chunks a plain decode would read."""
    c = MiniCluster(n_osds=4, cfg=_cfg()).start()
    try:
        cl = c.client()
        cl.create_pool("cw", kind="ec", pg_num=2,
                       ec_profile={"plugin": "clay", "k": "2", "m": "2",
                                   "d": "3", "backend": "numpy"})
        payloads = _write_read_kill_read(c, cl, "cw", n_obj=6)
        c.kill_osd(3)
        c.settle(0.5)
        c.revive_osd(3)
        deadline = time.time() + 30
        while time.time() < deadline:
            tot = _counters(c)
            if tot.get("recovery_subchunk_rebuilds", 0) > 0:
                break
            time.sleep(0.1)
        tot = _counters(c)
        assert tot.get("recovery_subchunk_rebuilds", 0) > 0, tot
        # (n-1)/q = 3/2 per sub-chunk rebuild, k=2 for a whole-chunk
        # decode: the blended fleet ratio must stay under k
        ratio = tot["recovery_fetch_bytes"] / tot["recovery_rebuilt_bytes"]
        assert ratio < 2, f"repair-bytes-per-lost-byte {ratio} >= k"
        # ISSUE 14 satellite: the repair-plane extents rode the
        # per-(peer, pg) aggregator in recovery-class lanes — and
        # coalescing means the helper-bound MESSAGE count stays at or
        # below the sub-read count (strictly below whenever a storm
        # window caught two rebuilds; >= 1 msgs proves the routing)
        agg = _counters(c, prefix="ec_read_repair")
        assert agg.get("ec_read_repair_subreads", 0) > 0, agg
        assert agg.get("ec_read_repair_msgs", 0) > 0
        assert agg["ec_read_repair_msgs"] <= \
            agg["ec_read_repair_subreads"]
        c.settle(1.0)
        _assert_reads(c, cl, "cw", payloads, "post-recovery")
    finally:
        c.stop()


def test_e2e_recovery_push_spans_linked(monkeypatch):
    """ROADMAP telemetry follow-on (b): with sampling forced on, a
    recovery storm's MPGPush carries the storm root's trace ctx and the
    receiving peer journals a recovery-push-apply child span."""
    cfg = _cfg(trace_sample_rate=1.0)
    c = MiniCluster(n_osds=4, cfg=cfg).start()
    try:
        cl = c.client()
        cl.create_pool("tp", kind="ec", pg_num=2,
                       ec_profile={"plugin": "jerasure", "k": "2",
                                   "m": "1", "backend": "numpy"})
        for i in range(8):
            cl.write_full("tp", f"o{i}", b"t" * 8192)
        c.kill_osd(3)
        c.settle(0.5)
        c.revive_osd(3)
        deadline = time.time() + 30
        found = None
        while time.time() < deadline and found is None:
            for osd in c.osds.values():
                spans = [s for s in osd.tracer.dump()
                         if s["name"] == "recovery-push-apply"]
                for s in spans:
                    if s.get("parent_id"):
                        found = s
                        break
            time.sleep(0.1)
        assert found is not None, "no linked recovery-push-apply span"
        # the parent must be some OTHER daemon's storm root, in the
        # SAME trace (the wire ctx carried both ids)
        roots = [s for o in c.osds.values() for s in o.tracer.dump()
                 if s["name"] == "recovery-storm"
                 and s["span_id"] == found["parent_id"]
                 and s["trace_id"] == found["trace_id"]]
        assert roots, "push span not parented under a storm root"
    finally:
        c.stop()


# ------------------------------------------------------- bench matrix
def test_wide_repair_matrix_smoke():
    """Tier-1-sized smoke leg of the bench matrix: every cell batched,
    byte-verified, and the repair-bandwidth ordering holds."""
    m = bench.wide_repair_matrix(full=False, chunk=4096)
    assert m["ok"], m
    r = m["repair_bytes_per_lost_byte"]
    assert r["clay"] < r["lrc"] < r["rs"] == float(m["k"])
    assert r["shec"] < r["rs"]


@pytest.mark.slow
def test_wide_repair_matrix_full():
    """The full {rs, clay, lrc, shec} x {healthy, degraded, storm}
    matrix at bench sizes — every cell byte-identical to the numpy
    oracle (the acceptance gate bench.py --ec-recovery enforces)."""
    m = bench.wide_repair_matrix(full=True)
    assert m["ok"], m
    for pname, cell in m["cells"].items():
        for leg, v in cell.items():
            assert v["ok"], (pname, leg, v)
