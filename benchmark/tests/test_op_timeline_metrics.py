"""The fourteen per-layer metrics that read the program's op timelines
(``op_ms.*``, ``subop_ms.*``, ``flush_ms.*``, ``op_timeline_coverage``):
data files over ``counter_ratio``.  On the CPU, at a tiny size, every
one of them has something to read in every cell's deployment, and the
phases add up to the timeline.  No number here is a measurement.
"""

import pytest

from benchmark import cells, run
from benchmark.cluster import Deployment

from .conftest import tiny

NEW = ["op_ms.client", "op_ms.queue", "op_ms.obj_lock", "op_ms.prepare",
       "op_ms.subread_wait", "op_ms.batch_wait", "op_ms.flush",
       "op_ms.subwrite_wait", "subop_ms.queue", "subop_ms.apply",
       "flush_ms.stage_in", "flush_ms.launch", "flush_ms.fetch",
       "op_timeline_coverage"]
OP_PHASES = ["op_ms.queue", "op_ms.obj_lock", "op_ms.prepare",
             "op_ms.subread_wait", "op_ms.batch_wait", "op_ms.flush",
             "op_ms.subwrite_wait"]


def test_manifest_lists_them_in_every_cell(bench):
    names = [m["name"] for m in bench["per_layer"]]
    assert names[-len(NEW):] == NEW          # appended, in this order
    every = sorted(w["name"] for w in bench["workloads"])
    for m in bench["per_layer"][-len(NEW):]:
        assert sorted(m["workloads"]) == every
        assert m["moves"] == "op_p90_ms"
        assert m["source"] == "program_counter"


@pytest.mark.parametrize("name", ["rados_write_4m",
                                  "rados_degraded_read_4m", "ycsb_a_1k"])
def test_rehearsal_reads_all_fourteen(name, bench):
    cell = tiny(cells.load_cell(name, bench))
    specs = {m["name"]: m for m in cell["per_layer"]}
    assert set(NEW) <= set(specs)
    dep = Deployment(cell["config"])
    try:
        payload = bytes(range(256)) * 64            # 16 KiB
        names = [f"obj{i:02d}" for i in range(6)]
        dep.write_many(((n, payload) for n in names), 2)     # warm
        degraded = name == "rados_degraded_read_4m"
        if degraded:        # as the cell does: in set-up, not the window
            dep.stop_osds(1)
        before = dep.counters()
        if not degraded:
            dep.write_many(((n, payload) for n in names), 2)
        assert dep.read_many(names, 2) == [payload] * len(names)
        after = dep.counters()
    finally:
        dep.close()
    ctx = {"counters": {n: after[n] - before.get(n, 0.0) for n in after}}
    got = {n: run.read_metric(specs[n], ctx) for n in NEW}
    assert all(v is not None for v in got.values()), got
    assert all(v >= 0 for v in got.values()), got
    # the phases partition the timeline
    c = ctx["counters"]
    per_op = 1000.0 * c["osd.op_timeline.sum_seconds"] \
        / c["osd.op_timeline.count"]
    assert sum(got[n] for n in OP_PHASES) == pytest.approx(per_op,
                                                           rel=1e-3)
    assert c["osd.op_timeline.count"] >= len(names) * (1 if degraded else 2)
    assert 0 < got["op_timeline_coverage"] <= 102.0, got
    # a parent that has no such counter reports nothing and does not raise
    assert run.read_metric(specs["op_ms.queue"], {"counters": {}}) is None
