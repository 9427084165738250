"""chip_smoke.py's phases at a tiny size on the CPU mesh, and its refusal
to pass without a TPU."""

from __future__ import annotations

import json
import os

import pytest

import chip_smoke
from ceph_tpu.ec import verify
from ceph_tpu.utils import jaxenv, staging


def test_kernels_phase_tiny():
    res = chip_smoke.phase_kernels(n_obj=2, obj_bytes=64 << 10,
                                   interpret=True)
    assert set(res["kernels"]) == {"xla", "pallas", "generic"}
    assert all(k["ok"] for k in res["kernels"].values())
    assert res["kernels"]["pallas"]["pallas"]  # the kernel body itself


def test_ec_benchmark_phase_tiny():
    res = chip_smoke.phase_ec_benchmark(size=256 << 10)
    assert [c["ok"] for c in res["cases"]] == [True] * 3
    assert not any(res["fallthroughs"].values())
    # the bit-matrix technique really rode the scheduled-XOR kernel
    assert any(s.startswith("bitxor/") for s in res["compiles"])


@pytest.mark.parametrize("device_plane", [False, True])
def test_cluster_phase_tiny(device_plane, monkeypatch):
    """Written objects read back whole and degraded, all fall-through
    counters zero, no compile after warm-up — on the host fold (what
    the CPU platform runs) and with the device plane forced on (lane
    staging + fold-and-launch programs, what a TPU runs)."""
    if device_plane:
        monkeypatch.setattr(staging, "_CPU_BACKEND", False)
    # the scrub's verifier is chosen once a process, by the platform
    monkeypatch.setattr(verify, "_SINGLETONS", {})
    # the profile is the process's: earlier tests may have left folds
    before = chip_smoke._profiler_compiles()
    res = chip_smoke.phase_cluster(n_osds=12, n_obj=6,
                                   obj_bytes=256 << 10, inflight=4,
                                   require_fold=False)
    assert not any(res["fallthroughs"].values())
    assert res["marked_down"] == 0
    if device_plane:  # the CPU platform's host fold is not warmed
        assert res["compiles_after_warmup"] == 0
    assert res["device_launches"] > 0
    assert res["csum"] == "host sweep"
    # the deep scrub: nothing on the healthy pool, the planted fault
    # and nothing else; with the device plane every stored byte went
    # through the verify program, warmed with the bucket's encodes
    scrub = res["scrub"]
    assert scrub["planted"] == [("planted", 9, "digest_mismatch")]
    assert scrub["on_device"] == device_plane
    assert scrub["verified_bytes"] == \
        (scrub["stored_bytes"] if device_plane else 0)
    assert scrub["stored_bytes"] == (6 + 8) * 11 * (256 << 10) // 8
    assert res["staging"]["ec_stage_d2h_copies"] > 0
    assert not res["dropped"]["scheduler"].get("system")
    folds = [s for s in res["compiles"]
             if s.rsplit("/", 1)[-1].startswith("f")]
    assert bool(folds) or not device_plane
    # the host fold neither compiles nor launches a folded program
    assert device_plane or all(res["compiles"][s] == before.get(s)
                               for s in folds)
    if device_plane:
        # the OSDs' batcher warmed the bucket's folded programs itself,
        # on the first client write: encode and 1..m lost, widths 1..16
        assert len(folds) >= 2 * (1 + 3)


def test_main_refuses_without_a_tpu(capsys):
    with pytest.raises(SystemExit) as exc:
        chip_smoke.main([])
    assert exc.value.code not in (0, None)
    out = capsys.readouterr().out
    assert '"ok": true' not in out
    last = json.loads(out.strip().splitlines()[-1])
    assert last["phase"] == "device" and last["ok"] is False


def test_compile_cache_helper(monkeypatch, tmp_path):
    """With JAX_COMPILATION_CACHE_DIR set the helper sets nothing;
    unset, the cache is <checkout>/.jax_cache."""
    import jax
    checkout = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    was = jax.config.jax_compilation_cache_dir
    try:
        jax.config.update("jax_compilation_cache_dir", None)
        monkeypatch.setenv(jaxenv.CACHE_ENV, str(tmp_path))
        assert jaxenv.enable_compile_cache() == str(tmp_path)
        assert jax.config.jax_compilation_cache_dir is None  # untouched
        monkeypatch.delenv(jaxenv.CACHE_ENV)
        want = os.path.join(checkout, ".jax_cache")
        assert jaxenv.enable_compile_cache() == want
        assert jax.config.jax_compilation_cache_dir == want
    finally:
        jax.config.update("jax_compilation_cache_dir", was)
