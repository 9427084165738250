"""trace_tool: waterfall rendering, per-stage self-time aggregation,
and the asok collector — the analysis half of the tracing story."""

import numpy as np
import pytest

from ceph_tpu.tools.trace_tool import (format_stage_table, merge_spans,
                                       self_times, stage_stats,
                                       waterfall)
from ceph_tpu.utils.tracer import Tracer


def _span(span_id, parent_id, name, start, end, trace_id=1, **tags):
    return {"trace_id": trace_id, "span_id": span_id,
            "parent_id": parent_id, "name": name, "service": "osd.0",
            "start": start, "end": end,
            "dur_ms": (end - start) * 1000, "tags": dict(tags)}


def _trace(t0=100.0):
    # op [0, 10ms] -> encode [2, 8ms] -> {wait [2, 5ms], flush [5, 8ms]}
    return [
        _span(1, 0, "osd-op write", t0, t0 + 0.010),
        _span(2, 1, "ec-encode", t0 + 0.002, t0 + 0.008),
        _span(3, 2, "ec-batch-wait", t0 + 0.002, t0 + 0.005,
              flush_span=4),
        _span(4, 3, "ec-flush", t0 + 0.005, t0 + 0.008, n_ops=2),
    ]


def test_merge_spans_dedups():
    spans = _trace()
    merged = merge_spans([spans, spans[:2]])
    assert len(merged) == len(spans)


def test_merge_spans_skew_normalizes_clocks():
    """The clock-skew satellite: a daemon whose wall clock runs fast
    has its spans shifted back onto the monitor's clock in the merge;
    services without an estimate (and in-flight end=0 sentinels) stay
    untouched, and the source dicts are never mutated."""
    spans = _trace()
    ahead = [dict(s, service="osd.9", span_id=s["span_id"] + 100,
                  start=s["start"] + 0.5,
                  end=(s["end"] + 0.5) if s["end"] else 0.0)
             for s in spans]
    ahead[3] = dict(ahead[3], end=0.0, in_flight=True)
    before = [dict(s) for s in ahead]
    merged = merge_spans([spans, ahead], skew={"osd.9": 0.5})
    by_id = {s["span_id"]: s for s in merged}
    for orig in spans:
        shifted = by_id[orig["span_id"] + 100]
        assert abs(shifted["start"] - orig["start"]) < 1e-9
        if shifted.get("in_flight"):
            assert shifted["end"] == 0.0  # sentinel survives the shift
        else:
            assert abs(shifted["end"] - orig["end"]) < 1e-9
        # the un-skewed service is untouched
        assert by_id[orig["span_id"]]["start"] == orig["start"]
    assert ahead == before  # sources copied, not mutated


def test_critical_path_partitions_root_wall_time():
    """The blocking chain: per-stage self-times along the path sum to
    the root's wall time, and a child leaking past its parent (the
    flush runs after its wait parent ends) is clamped out rather than
    double-counted."""
    from ceph_tpu.utils.critical_path import critical_path
    cp = critical_path(_trace())
    by_name = {e["name"]: e for e in cp}
    # op self = 10 - encode's 6; encode self = 6 - wait's 3; the wait's
    # flush child lies entirely past the wait's end -> wait owns its 3
    assert abs(by_name["osd-op write"]["self_ms"] - 4.0) < 1e-3
    assert abs(by_name["ec-encode"]["self_ms"] - 3.0) < 1e-3
    assert abs(by_name["ec-batch-wait"]["self_ms"] - 3.0) < 1e-3
    assert "ec-flush" not in by_name  # clamped off the chain
    assert abs(sum(e["self_ms"] for e in cp) - 10.0) < 1e-3
    # chronological order (start-time ties keep the deeper span first
    # — the sort is stable over the walk's child-first appends)
    assert cp[0]["name"] == "osd-op write"
    assert {e["name"] for e in cp[1:]} == {"ec-encode", "ec-batch-wait"}
    assert all(e["service"] == "osd.0" for e in cp)
    assert critical_path([]) == []


def test_critical_path_gap_blames_parent_not_sibling():
    """Two sequential children with a gap between them: the gap is the
    PARENT's critical-path self-time (it was the one not running
    anything), and a concurrent sibling overlapping the chain
    contributes nothing."""
    from ceph_tpu.utils.critical_path import blame, critical_path
    t0 = 100.0
    spans = [
        _span(1, 0, "osd-op write", t0, t0 + 0.010),
        _span(2, 1, "stage-a", t0 + 0.001, t0 + 0.004),
        _span(3, 1, "stage-b", t0 + 0.006, t0 + 0.010),
        # concurrent with stage-b, ends earlier: not blocking
        _span(4, 1, "shadow", t0 + 0.006, t0 + 0.008),
    ]
    by_name = {e["name"]: e for e in critical_path(spans)}
    # parent: [0,1) before stage-a + the (4,6) gap = 3ms
    assert abs(by_name["osd-op write"]["self_ms"] - 3.0) < 1e-3
    assert abs(by_name["stage-a"]["self_ms"] - 3.0) < 1e-3
    assert abs(by_name["stage-b"]["self_ms"] - 4.0) < 1e-3
    assert "shadow" not in by_name
    # blame aggregates shares over many traces
    table = blame([spans, _trace()])
    assert table["osd-op write"]["count"] == 2
    assert abs(table["osd-op write"]["self_total_ms"] - 7.0) < 1e-3
    grand = sum(s["self_total_ms"] for s in table.values())
    assert abs(sum(s["share"] for s in table.values()) - 1.0) < 0.01
    assert abs(grand - 20.0) < 1e-2  # both roots fully attributed


def test_critical_path_in_flight_span_owns_its_age():
    """A hung stage (end=0, dur_ms = its age at dump time) owns its
    elapsed time on the path instead of vanishing."""
    from ceph_tpu.utils.critical_path import critical_path
    t0 = 100.0
    spans = [
        _span(1, 0, "osd-op write", t0, t0 + 0.010),
        dict(_span(2, 1, "stuck-stage", t0 + 0.002, 0.0),
             end=0.0, in_flight=True, dur_ms=8.0),
    ]
    by_name = {e["name"]: e for e in critical_path(spans)}
    assert abs(by_name["stuck-stage"]["self_ms"] - 8.0) < 1e-3
    assert abs(by_name["osd-op write"]["self_ms"] - 2.0) < 1e-3


def test_format_blame_table_renders():
    from ceph_tpu.utils.critical_path import blame, format_blame_table
    out = format_blame_table(blame([_trace()]))
    lines = out.splitlines()
    assert "self_total" in lines[0] and "share" in lines[0]
    # biggest owner of blocked time leads
    assert lines[2].startswith("osd-op write")


def test_self_times_subtract_children():
    rows = {r["name"]: r for r in self_times(_trace())}
    assert abs(rows["osd-op write"]["dur_ms"] - 10.0) < 1e-3
    # op self = 10 - 6 (encode child)
    assert abs(rows["osd-op write"]["self_ms"] - 4.0) < 1e-3
    # encode self = 6 - 3 (wait child; the flush nests under the wait)
    assert abs(rows["ec-encode"]["self_ms"] - 3.0) < 1e-3
    # the wait span's time is all in its flush child
    assert abs(rows["ec-batch-wait"]["self_ms"] - 0.0) < 1e-3
    # leaves: self == dur
    assert abs(rows["ec-flush"]["self_ms"] - 3.0) < 1e-3


def test_stage_stats_percentiles():
    traces = []
    for i in range(100):
        t0 = 100.0 + i
        spans = [_span(10 * i + 1, 0, "osd-op write", t0,
                       t0 + 0.001 * (i + 1), trace_id=i + 1)]
        traces.append(spans)
    stats = stage_stats(traces)
    s = stats["osd-op write"]
    assert s["count"] == 100
    assert 45.0 <= s["p50_ms"] <= 56.0
    assert s["p99_ms"] >= 95.0
    assert s["self_p50_ms"] == s["p50_ms"]  # leaves: self == total
    table = format_stage_table(stats)
    assert "osd-op write" in table and "p99_ms" in table.splitlines()[0]


def test_waterfall_renders_tree_and_bars():
    out = waterfall(_trace())
    lines = out.splitlines()
    assert "4 spans" in lines[0]
    assert any("osd-op write" in ln and "#" in ln for ln in lines)
    # children indent under parents, in start order
    names = [ln.split("|")[0].rstrip() for ln in lines[1:]]
    assert names[0].startswith("osd-op")
    assert names[1].strip().startswith("ec-encode")
    assert names[1].startswith("  ")  # indented
    # the cross-trace fan-in tag surfaces
    assert "->flush:" in out


def test_waterfall_in_flight_span():
    spans = _trace()
    spans[3] = dict(spans[3], end=0.0, in_flight=True)
    out = waterfall(spans)
    assert "(in flight)" in out


def test_stage_stats_from_real_tracer():
    """End-to-end with real Tracer spans (the shapes bench --trace and
    the asok collector feed in)."""
    import time

    tracer = Tracer("bench")
    traces = []
    for i in range(5):
        root = tracer.start("ec-op")
        with tracer.start("stage-a", parent=root.ctx):
            time.sleep(0.001)
        root.finish()
        traces.append(tracer.spans_for(root.trace_id))
    stats = stage_stats(traces)
    assert stats["ec-op"]["count"] == 5
    assert stats["stage-a"]["p50_ms"] >= 1.0
    assert stats["ec-op"]["p50_ms"] >= stats["stage-a"]["p50_ms"]


def test_collect_from_asok(tmp_path):
    """The operator-facing collector: spans merged over real admin
    sockets, dead/mon sockets skipped."""
    from ceph_tpu.tools.trace_tool import collect_from_asok
    from ceph_tpu.utils.admin_socket import AdminSocketServer

    t_a, t_b = Tracer("osd.0"), Tracer("osd.1")
    root = t_a.start("osd-op write")
    child = t_b.start("sub-write", parent=root.ctx)
    child.finish()
    root.finish()

    servers = [
        AdminSocketServer(str(tmp_path / "osd.0.asok"),
                          lambda prefix, _t=t_a, **kw:
                          _t.dump(kw.get("trace_id"))),
        AdminSocketServer(str(tmp_path / "osd.1.asok"),
                          lambda prefix, _t=t_b, **kw:
                          _t.dump(kw.get("trace_id"))),
        # a verb-less daemon must not break the merge
        AdminSocketServer(str(tmp_path / "mon.0.asok"),
                          lambda prefix, **kw:
                          (_ for _ in ()).throw(ValueError(prefix))),
        # a mon command handler answers unknown verbs with an
        # (errno, detail) LIST — must not be mistaken for spans
        AdminSocketServer(str(tmp_path / "mon.1.asok"),
                          lambda prefix, **kw:
                          [-22, {"error": f"unknown {prefix!r}"}]),
    ]
    try:
        spans = collect_from_asok(str(tmp_path), root.trace_id)
    finally:
        for s in servers:
            s.stop()
    assert {s["name"] for s in spans} == {"osd-op write", "sub-write"}
    assert np.isclose(
        sum(1 for s in spans if s["service"] == "osd.1"), 1)


def test_xplane_report_reads_annotations_and_checks_the_clock(tmp_path):
    """--xplane: a profiler trace taken here on the CPU (no device
    plane, so all of the window is device-idle): the clock-sync
    annotations put now_ns() within a millisecond of the profiler's
    clock, annotation seconds are the union over threads, and the time
    no thread was annotated is what is left of the window."""
    import threading
    import time

    import jax

    from ceph_tpu.tools import trace_tool
    from ceph_tpu.utils import tracer

    def worker():
        with tracer.annotate("ceph:launch", n_ops=1):
            time.sleep(0.05)

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        with jax.profiler.TraceAnnotation(trace_tool.XPLANE_WINDOW):
            tracer.clock_sync()
            threads = [threading.Thread(target=worker) for _ in range(2)]
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=10)
            time.sleep(0.03)                 # nobody annotated
            with tracer.annotate("ceph:ec-flush"):
                with tracer.annotate("ceph:fetch"):
                    time.sleep(0.02)
            tracer.clock_sync()
    finally:
        jax.profiler.stop_trace()
    (path,) = tmp_path.glob("plugins/profile/*/*.xplane.pb")
    rep = trace_tool.xplane_report(str(path))
    clock = rep["clock_sync"]
    assert clock["samples"] == 2 and clock["aligned"]
    assert 0 <= clock["min_us"] <= clock["max_us"] < 1000.0
    ann = rep["annotations"]
    assert ann["ceph:launch"]["count"] == 2
    # two threads slept side by side: the union is one sleep, not two
    assert 0.045 <= ann["ceph:launch"]["seconds"] < 0.095
    assert ann["ceph:fetch"]["seconds"] <= ann["ceph:ec-flush"]["seconds"]
    assert ann["ceph:fetch"]["seconds"] >= 0.018
    # thread-seconds inside an annotation and in none nested in it
    assert ann["ceph:launch"]["self_thread_seconds"] >= 0.095
    assert ann["ceph:ec-flush"]["self_thread_seconds"] < 0.01
    assert rep["device_busy_s"] == 0.0
    assert all(r["idle_seconds"] == r["seconds"] for r in ann.values())
    assert rep["unannotated_s"] >= 0.028
    covered = rep["window_s"] - rep["unannotated_s"]
    assert covered == pytest.approx(
        ann["ceph:launch"]["seconds"] + ann["ceph:ec-flush"]["seconds"]
        + ann["ceph:clock-sync"]["seconds"], abs=2e-3)
    text = trace_tool.format_xplane(rep)
    assert "aligned" in text and "ceph:ec-flush" in text
    assert trace_tool.main(["--xplane", str(path), "--json"]) == 0
