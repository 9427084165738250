"""The rest of a run, without the look for a chip: every traffic mix at
a tiny size on the CPU platform through ``run.execute`` (the harness's
rehearsal; the command itself has no CPU path), the control in the
program's place, and the timed path broken underneath.  ``correct`` has
to come out true for the program and false for the control and for
each fault.  No number printed here is a measurement.
"""

import json

import pytest

from benchmark import cells, run, verify
from benchmark.generators import closed_loop

from .conftest import tiny

SEED = 3_000_000_019          # the driver's seeds pass 2**31
CELLS = ["rados_write_4m", "rados_degraded_read_4m", "ycsb_a_1k"]


def _execute(name, bench, seconds=1.5, control=False):
    cell = tiny(cells.load_cell(name, bench))
    return run.execute(cell, SEED, seconds, False, require_chips=False,
                       control=control)


def test_command_refuses_without_the_chips(bench):
    cell = cells.load_cell("rados_write_4m", bench)
    with pytest.raises(SystemExit) as e:
        run.execute(cell, SEED, 1.0, False)
    assert "TPU" in str(e.value)


def test_command_needs_the_program(tmp_path):
    """In a directory that holds only BENCHMARK.json and benchmark/ the
    command exits non-zero and prints no result."""
    import shutil
    import subprocess
    import sys
    shutil.copy(cells.MANIFEST, tmp_path / "BENCHMARK.json")
    shutil.copytree(cells.HERE, tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = subprocess.run(
        [sys.executable, "-m", "benchmark.run", "--workload",
         "rados_write_4m", "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=tmp_path, capture_output=True, text=True, timeout=120,
        env={"PATH": "/usr/bin:/bin", "JAX_PLATFORMS": "cpu"})
    assert p.returncode != 0
    assert p.stdout.strip() == ""


@pytest.mark.parametrize("name", CELLS)
def test_program_is_correct_and_control_is_not(name, bench):
    r = _execute(name, bench, control=True)
    assert r["failed"] == 0 and r["attempted"] > 0
    assert r["correct"] is True, r["compared"]
    assert list(r)[-1] == "compared"
    assert all(c["value"] == 0 for c in r["compared"].values()
               if c["limit"] == 0)
    assert r["compared"]["shards_compared"]["value"] > 0
    assert not verify.is_correct(r["control"]), r["control"]
    assert r["device"]["platform"] == "cpu"      # says where it ran
    units = {m["name"]: m["unit"] for m in bench["end_to_end"]}
    assert set(r["metrics"]) == {m["name"] for m in cells.load_cell(
        name, bench)["end_to_end"]}
    assert all(v["unit"] == units[n] for n, v in r["metrics"].items())
    json.dumps(r)


# ---------------------------------------------------------------- faults
def _with_fault(monkeypatch, install):
    """``install(dep)`` breaks the timed path just before the window
    opens; what it returns undoes it when the window has closed."""
    real = closed_loop.run

    def run_broken(plan, dep, seconds, **kw):
        undo = install(dep)
        try:
            return real(plan, dep, seconds, **kw)
        finally:
            if undo:
                undo()
    monkeypatch.setattr(closed_loop, "run", run_broken)


def _flip(data):
    b = bytearray(bytes(data))
    b[len(b) // 2] ^= 0x5A
    return bytes(b)


def test_fault_write_acknowledged_and_state_unchanged(monkeypatch, bench):
    def install(dep):
        stores = [o.store for o in dep.cluster.osds.values()]

        def dropped(tx, on_commit=None):
            if on_commit is not None:
                on_commit()
        for s in stores:
            s.queue_transaction = dropped

        def undo():
            for s in stores:
                del s.queue_transaction
        return undo
    _with_fault(monkeypatch, install)
    r = _execute("rados_write_4m", bench)
    assert r["correct"] is False
    # the read-back is served from the write-through cache and is
    # right: only the comparison with the stores shows the loss
    assert r["compared"]["shards_wrong"]["value"] > 0


def test_fault_parity_altered_where_it_is_produced(monkeypatch, bench):
    from ceph_tpu.osd.daemon import OSDDaemon
    real = OSDDaemon._ec_encode

    def bent(self, codec, streams, with_csums, m=None):
        parity, csums = real(self, codec, streams, with_csums, m)
        parity = list(parity)
        parity[0] = parity[0] ^ 1
        return parity, csums
    monkeypatch.setattr(OSDDaemon, "_ec_encode", bent)
    r = _execute("rados_write_4m", bench)
    assert r["correct"] is False
    # a healthy read never touches parity: only the stores show it
    assert r["compared"]["shards_wrong"]["value"] > 0


def test_fault_decode_output_altered(monkeypatch, bench):
    from ceph_tpu.osd.daemon import OSDDaemon
    real = OSDDaemon._ec_decode

    def bent(self, codec, want, chunks, span=None):
        out = real(self, codec, want, chunks, span)
        return {i: (v ^ 1) for i, v in out.items()}
    monkeypatch.setattr(OSDDaemon, "_ec_decode", bent)
    r = _execute("rados_degraded_read_4m", bench)
    assert r["correct"] is False
    assert r["compared"]["answers_wrong"]["value"] > 0


def test_fault_answer_altered_at_the_client(monkeypatch, bench):
    from ceph_tpu.client.rados import RadosClient
    real = RadosClient.read
    monkeypatch.setattr(RadosClient, "read",
                        lambda self, *a, **kw: _flip(real(self, *a, **kw)))
    # set-up's warm-up read already sees it: the run is no measurement
    with pytest.raises(Exception, match="warm-up read"):
        _execute("ycsb_a_1k", bench)


def test_fault_answer_altered_in_the_window_only(monkeypatch, bench):
    from ceph_tpu.client.rados import RadosClient
    real = RadosClient.read

    def install(dep):
        RadosClient.read = lambda self, *a, **kw: _flip(
            real(self, *a, **kw))

        def undo():
            RadosClient.read = real
        return undo
    _with_fault(monkeypatch, install)
    r = _execute("ycsb_a_1k", bench)
    assert r["correct"] is False
    assert r["compared"]["answers_wrong"]["value"] > 0
    assert r["compared"]["readbacks_wrong"]["value"] == 0
