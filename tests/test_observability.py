"""Observability tier: unix admin sockets + prometheus exporter.

The reference's AdminSocket (src/common/admin_socket.cc: `ceph daemon
<name> <cmd>`) and metrics path (mgr prometheus module /
src/exporter/): every daemon answers commands over a real unix socket,
and an HTTP /metrics endpoint serves cluster + per-daemon counters in
the prometheus text format.  Plus the batch-aware latency-decomposition
layer: trace-dump + kernel-profile verbs end-to-end, SLOW_OPS health
appearing and clearing, and a STRICT exposition-format parse (grouped
metrics, single HELP/TYPE, counters monotonic across scrapes).
"""

import http.client
import json
import subprocess
import sys
import time

import pytest

from ceph_tpu.tools.vstart import MiniCluster
from ceph_tpu.utils.admin_socket import admin_request
from tests.test_cluster import make_cfg


@pytest.fixture
def obs_cluster(tmp_path):
    c = MiniCluster(n_osds=4, cfg=make_cfg(),
                    admin_dir=str(tmp_path / "asok"),
                    metrics_port=0).start()
    yield c, tmp_path
    c.stop()


def test_admin_socket_serves_daemon_commands(obs_cluster):
    c, tmp_path = obs_cluster
    client = c.client()
    client.create_pool("p", size=2, pg_num=1)
    client.write_full("p", "o", b"x" * 1000)
    asok = str(tmp_path / "asok" / "osd.0.asok")
    perf = admin_request(asok, "perf dump")
    assert "op_w" in perf and "subop_w" in perf
    st = admin_request(asok, "status")
    assert st["osd"] == 0 and st["epoch"] >= 1
    q = admin_request(asok, "dump_op_queue")
    assert q["mode"] == "mclock"
    # config set over the socket takes effect
    admin_request(asok, "config set", name="osd_op_timeout", value=9.5)
    cfgd = admin_request(asok, "config show")
    assert cfgd["osd_op_timeout"] == 9.5
    # mon socket answers cluster-level verbs
    mon_asok = str(tmp_path / "asok" / "mon.0.asok")
    res, data = admin_request(mon_asok, "status")
    assert res == 0 and data["num_up"] == 4
    # errors come back as errors, not hangs
    with pytest.raises(RuntimeError):
        admin_request(asok, "no such verb")


def test_admin_socket_via_cli(obs_cluster):
    c, tmp_path = obs_cluster
    asok = str(tmp_path / "asok" / "osd.1.asok")
    out = subprocess.run(
        [sys.executable, "-m", "ceph_tpu.tools.cli", "daemon", asok,
         "perf", "dump"],
        capture_output=True, text=True, timeout=60)
    assert out.returncode == 0, out.stderr
    assert "op_w" in json.loads(out.stdout)


def test_observability_verbs_end_to_end(obs_cluster):
    """The full admin-socket observability surface against a live
    cluster: perf dump, op-tracker dumps, the trace dump of a real
    traced op, and the kernel profile — every verb answers with its
    documented shape over the real unix socket."""
    from ceph_tpu.utils.tracer import build_tree

    c, tmp_path = obs_cluster
    client = c.client()
    client.tracing = True
    client.create_pool("p", kind="ec", pg_num=1,
                       ec_profile={"plugin": "jerasure", "k": "2",
                                   "m": "1", "backend": "numpy"})
    client.write_full("p", "obj", b"traced" * 2048)
    root = next(s for s in client.tracer.dump()
                if s["name"] == "client-op write_full")
    asoks = [str(tmp_path / "asok" / f"osd.{i}.asok") for i in range(4)]
    # op-tracker verbs: lists everywhere, history on the primary
    assert all(isinstance(admin_request(a, "dump_ops_in_flight"), list)
               for a in asoks)
    hists = [admin_request(a, "dump_historic_ops") for a in asoks]
    served = [h for h in hists if h]
    assert served, "no OSD recorded the op in its history"
    assert any("write" in d["description"]
               for h in served for d in h)
    assert all(isinstance(admin_request(a, "dump_historic_slow_ops"),
                          list) for a in asoks)
    # trace dump: merging every daemon's ring for the trace id
    # reconstructs the op tree (the collector role over real sockets)
    merged = {s["span_id"]: s for s in
              client.tracer.spans_for(root["trace_id"])}
    for a in asoks:
        for s in admin_request(a, "dump_tracing",
                               trace_id=root["trace_id"]):
            merged[s["span_id"]] = s
    tree = build_tree(list(merged.values()))
    assert len(tree) == 1 and tree[0]["name"] == "client-op write_full"

    def find(nodes, name):
        out = []
        for n in nodes:
            if n["name"].startswith(name):
                out.append(n)
            out += find(n["children"], name)
        return out

    osd_ops = find(tree, "osd-op")
    assert osd_ops, "no osd-op span collected over the admin socket"
    # the encode stage is decomposed under the osd op (numpy backend:
    # per-op path, so the span exists without batcher children)
    assert find(osd_ops, "ec-encode"), "no ec-encode stage span"
    # kernel profile: stable document shape on every daemon (counts
    # are zero on the numpy backend — the schema is the contract)
    for a in asoks:
        prof = admin_request(a, "dump_kernel_profile")
        assert set(prof) == {"signatures", "recent_compiles"}
        assert isinstance(prof["signatures"], dict)
        assert isinstance(prof["recent_compiles"], list)


def test_slow_ops_health_warn_appears_and_clears(tmp_path):
    """An op blocked past osd_op_complaint_time surfaces as
    HEALTH_WARN SLOW_OPS with per-daemon detail in status() and as
    daemon_slow_ops in /metrics — and CLEARS once the op finishes."""
    cfg = make_cfg(osd_op_complaint_time=0.05)
    c = MiniCluster(n_osds=2, cfg=cfg,
                    admin_dir=str(tmp_path / "asok"),
                    metrics_port=0).start()
    try:
        client = c.client()

        def status():
            return client.status()

        assert status()["health"] == "HEALTH_OK"
        # wedge an op: a tracked op that outlives the complaint time
        # (the op-tracker feed is what the health mux consumes, so
        # driving it directly keeps the test deterministic)
        op = c.osds[0].op_tracker.create("write obj.wedged")
        deadline = time.time() + 10
        st = status()
        while time.time() < deadline:
            st = status()
            if st["health"] == "HEALTH_WARN" and "SLOW_OPS" in \
                    st.get("checks", {}):
                break
            time.sleep(0.05)
        assert st["health"] == "HEALTH_WARN", st
        slow = st["checks"]["SLOW_OPS"]
        assert "osd.0" in slow["detail"]
        assert slow["detail"]["osd.0"]["slow_ops"] == 1
        assert slow["detail"]["osd.0"]["worst"][0]["description"] == \
            "write obj.wedged"
        # the exporter face: daemon_slow_ops gauge
        conn = http.client.HTTPConnection("127.0.0.1", c.exporter.port,
                                          timeout=5)
        conn.request("GET", "/metrics")
        body = conn.getresponse().read().decode()
        conn.close()
        assert 'ceph_tpu_daemon_slow_ops{daemon="osd.0"} 1' in body
        # the blocked op's own verb agrees
        asok = str(tmp_path / "asok" / "osd.0.asok")
        assert any("obj.wedged" in d["description"]
                   for d in admin_request(asok, "dump_slow_ops"))
        # finish the op: the warning must clear on the next report
        op.finish()
        deadline = time.time() + 10
        while time.time() < deadline:
            st = status()
            if st["health"] == "HEALTH_OK":
                break
            time.sleep(0.05)
        assert st["health"] == "HEALTH_OK", st
        assert "SLOW_OPS" not in st.get("checks", {})
        # ...and the historic record remembers it
        assert any("obj.wedged" in d["description"] for d in
                   admin_request(asok, "dump_historic_slow_ops"))
    finally:
        c.stop()


def test_cluster_events_progress_and_messenger_metrics(tmp_path):
    """The cluster-narrative acceptance path: an OSD kill + fresh-store
    revive drives a recovery storm, and the operator can watch it
    WITHOUT replaying traces — (a) ordered PG state-transition events
    in dump_cluster_log, (b) a progress item that goes 0 -> 100 and
    clears, (c) nonzero messenger dispatch-latency histograms in one
    exporter scrape that still passes the strict text-format parser."""
    from ceph_tpu.mon.mgr import MgrDaemon
    from ceph_tpu.tools.event_tool import fetch_events, tail

    cfg = make_cfg(osd_recovery_sleep=0.005,
                   osd_recovery_progress_interval=0.0,
                   mgr_progress_linger=2.0)
    c = MiniCluster(n_osds=4, cfg=cfg,
                    admin_dir=str(tmp_path / "asok"),
                    metrics_port=0).start()
    mgr = None
    try:
        client = c.client()
        client.create_pool("p", kind="ec", pg_num=4,
                           ec_profile={"plugin": "jerasure", "k": "2",
                                       "m": "1", "backend": "numpy"})
        for i in range(24):
            client.write_full("p", f"o{i}", b"evt" * 1024)
        mgr = MgrDaemon(c.mon, modules=("status", "progress")).start()
        # victim: a member of some PG's up set, so its fresh-store
        # revive forces shard rebuilds (a non-holder would recover
        # nothing and the storm never happens)
        pool_id = next(pid for pid, p in c.mon.osdmap.pools.items()
                       if p.name == "p")
        members = {o for seed in range(4)
                   for o in c.mon.osdmap.pg_to_up_osds(pool_id, seed)
                   if o is not None}
        victim = max(members)
        c.kill_osd(victim)             # marked down -> map change
        c.settle(0.3)
        c.revive_osd(victim)           # FRESH store: rebuild its shards
        mon_asok = str(tmp_path / "asok" / "mon.0.asok")

        def cluster_log(**kw):
            res, data = admin_request(mon_asok, "dump_cluster_log",
                                      **kw)
            assert res == 0, data
            return data["events"]

        # --- (b) progress 0 -> 100, sampled while the storm runs ----
        percents: dict[str, list] = {}
        deadline = time.time() + 30
        storm_done = False
        while time.time() < deadline:
            for it in c.mon.progress.items():
                percents.setdefault(it["id"], []).append(it["percent"])
            evs = cluster_log(channel="recovery")
            if any((e["fields"].get("event") == "recovery_done")
                   for e in evs) and not c.mon.progress.active():
                storm_done = True
                break
            time.sleep(0.02)
        assert storm_done, "recovery storm never completed in the log"
        assert percents, "no progress item ever appeared"
        assert all(all(a <= b for a, b in zip(ps, ps[1:]))
                   for ps in percents.values()), percents
        assert any(ps[-1] == 100.0 for ps in percents.values()), \
            percents
        # the mgr digest carries the items (the `ceph status` face)
        digest = mgr.command("status", "status")
        assert "progress" in digest
        ls = mgr.command("progress", "ls")
        assert any(i["percent"] == 100.0 for i in ls["completed"])

        # --- (a) ordered PG state transitions in the cluster log ----
        evs = cluster_log(channel="pg")
        by_pg: dict[tuple, dict] = {}
        for e in evs:
            key = (e["daemon"], e["fields"].get("pg"))
            slot = by_pg.setdefault(key, {})
            if "peering start" in e["message"]:
                slot.setdefault("start", e["seq"])
            elif "peering done" in e["message"]:
                slot["done"] = e["seq"]
        ordered = [k for k, s in by_pg.items()
                   if "start" in s and "done" in s
                   and s["start"] < s["done"]]
        assert ordered, f"no ordered peering start->done pair: {by_pg}"
        # the mon's own channels narrate the flap too
        assert any(f"osd.{victim} marked down" in e["message"]
                   for e in cluster_log(channel="cluster"))
        assert any(e["fields"].get("epoch")
                   for e in cluster_log(channel="osdmap"))
        assert any("recovery start" in e["message"]
                   for e in cluster_log(channel="recovery"))

        # event_tool: the `ceph -W` face over the same socket — the
        # one-shot dump prints the ring, follow mode resumes the cursor
        lines: list[str] = []
        tail(mon_asok, channel="pg", out=lines.append)
        assert lines and any("peering" in ln for ln in lines)
        _evs, cursor = fetch_events(mon_asok)
        # follow contract: a since-cursor fetch returns ONLY events
        # sequenced after it (the cluster is live — stragglers may
        # still land between the two fetches, but never replays)
        newer, cursor2 = fetch_events(mon_asok, since=cursor)
        assert all(e["seq"] > cursor for e in newer)
        assert cursor2 >= cursor

        # per-daemon verbs: local journal + messenger introspection
        osd_id = next(iter(c.osds))
        asok = str(tmp_path / "asok" / f"osd.{osd_id}.asok")
        local = admin_request(asok, "dump_events")
        assert isinstance(local, list)
        msgr = admin_request(asok, "dump_messenger")
        assert msgr["data"]["perf"]["msg_dispatched"] > 0
        assert len(msgr["data"]["queue_depths"]) == \
            msgr["data"]["workers"]

        # --- (c) one strict scrape: msg histograms are NONZERO -------
        conn = http.client.HTTPConnection("127.0.0.1", c.exporter.port,
                                          timeout=5)
        conn.request("GET", "/metrics")
        body = conn.getresponse().read().decode()
        conn.close()
        parsed = _parse_exposition_strict(body)
        counts = parsed["ceph_tpu_daemon_msg_dispatch_us_count"]
        assert sum(counts["samples"].values()) > 0
        buckets = parsed["ceph_tpu_daemon_msg_dispatch_us_bucket"]
        assert any(v > 0 for v in buckets["samples"].values())
        assert parsed["ceph_tpu_daemon_msg_queue_depth"]["type"] == \
            "gauge"
        # the progress gauge is visible while items linger; a late
        # recovery wave may have opened a FRESH sub-100 item by now
        # (storms close whenever the in-flight count drains), so the
        # contract asserted is "a completed storm's gauge shows 100",
        # not "every gauge is 100"
        assert "ceph_tpu_progress_percent" in parsed
        assert any(v == 100.0 for v in
                   parsed["ceph_tpu_progress_percent"]
                   ["samples"].values())
        # ...and CLEARS once the linger expires
        deadline = time.time() + 15
        cleared = False
        while time.time() < deadline:
            if not c.mon.progress.percent_gauges():
                cleared = True
                break
            time.sleep(0.05)
        assert cleared, "progress gauge never cleared"
        conn = http.client.HTTPConnection("127.0.0.1", c.exporter.port,
                                          timeout=5)
        conn.request("GET", "/metrics")
        body2 = conn.getresponse().read().decode()
        conn.close()
        assert "ceph_tpu_progress_percent" not in body2
    finally:
        if mgr is not None:
            mgr.stop()
        c.stop()


def _parse_exposition_strict(body: str):
    """Strict prometheus text-format parse: returns
    {metric: {"type": t, "samples": {labelstr: value}}} and asserts the
    format invariants — single HELP/TYPE per metric, TYPE before the
    samples, ALL samples of a metric contiguous in one group."""
    metrics: dict[str, dict] = {}
    current = None
    closed: set[str] = set()
    for line in body.splitlines():
        if not line.strip():
            continue
        if line.startswith("# HELP "):
            name = line.split(" ", 3)[2]
            assert name not in metrics, f"duplicate HELP for {name}"
            if current is not None:
                closed.add(current)
            assert name not in closed, f"{name} group reopened"
            metrics[name] = {"type": None, "samples": {}}
            current = name
            continue
        if line.startswith("# TYPE "):
            parts = line.split(" ", 3)
            name, typ = parts[2], parts[3]
            assert name == current, \
                f"TYPE {name} outside its HELP group"
            assert metrics[name]["type"] is None, \
                f"duplicate TYPE for {name}"
            assert typ in ("counter", "gauge", "histogram", "summary")
            metrics[name]["type"] = typ
            continue
        assert not line.startswith("#"), f"unknown comment: {line}"
        sample, value = line.rsplit(" ", 1)
        name = sample.split("{", 1)[0]
        assert name == current, \
            f"sample {name} outside its group (current {current})"
        assert sample not in metrics[name]["samples"], \
            f"duplicate sample {sample}"
        metrics[name]["samples"][sample] = float(value)
    for name, m in metrics.items():
        assert m["type"] is not None, f"{name} has no TYPE"
        assert m["samples"], f"{name} has no samples"
    return metrics


def test_metrics_exposition_strict_format(obs_cluster):
    """The exposition-format contract a real prometheus scraper holds
    us to: grouped metrics (one HELP/TYPE, contiguous samples — the
    per-daemon interleaving bug), and counters monotonic across two
    scrapes with traffic in between."""
    c, _ = obs_cluster
    client = c.client()
    client.create_pool("p", size=2, pg_num=1)
    client.write_full("p", "o", b"z" * 2000)

    def scrape():
        conn = http.client.HTTPConnection("127.0.0.1",
                                          c.exporter.port, timeout=5)
        conn.request("GET", "/metrics")
        body = conn.getresponse().read().decode()
        conn.close()
        return _parse_exposition_strict(body)

    first = scrape()
    # multiple daemons must appear under ONE metric group
    op_w = first["ceph_tpu_daemon_op_w"]
    assert len(op_w["samples"]) >= 4  # one series per OSD
    assert op_w["type"] == "counter"
    assert first["ceph_tpu_daemon_ec_batch_window_us_now"]["type"] \
        == "gauge"
    for i in range(5):
        client.write_full("p", f"o{i}", b"w" * 1500)
    second = scrape()
    for name, m in first.items():
        if m["type"] != "counter":
            continue
        after = second.get(name)
        assert after is not None, f"counter {name} vanished"
        for sample, value in m["samples"].items():
            if sample in after["samples"]:
                assert after["samples"][sample] >= value, \
                    f"counter {sample} went backwards"
    # the op counters actually moved
    assert sum(second["ceph_tpu_daemon_op_w"]["samples"].values()) > \
        sum(first["ceph_tpu_daemon_op_w"]["samples"].values())


def test_prometheus_exporter_serves_metrics(obs_cluster):
    c, _ = obs_cluster
    client = c.client()
    client.create_pool("p", size=2, pg_num=1)
    for i in range(5):
        client.write_full("p", f"o{i}", b"y" * 500)
    conn = http.client.HTTPConnection("127.0.0.1", c.exporter.port,
                                      timeout=5)
    conn.request("GET", "/metrics")
    resp = conn.getresponse()
    assert resp.status == 200
    assert resp.getheader("Content-Type").startswith("text/plain")
    body = resp.read().decode()
    conn.close()
    # cluster gauges
    assert "ceph_tpu_osd_up 4" in body
    assert "ceph_tpu_osd_total 4" in body
    assert "ceph_tpu_pools 1" in body
    assert "ceph_tpu_mon_is_leader 1" in body
    # per-daemon counters with labels, prometheus-parsable lines
    assert 'ceph_tpu_daemon_op_w{daemon="osd.' in body
    for line in body.splitlines():
        if not line or line.startswith("#"):
            continue
        metric, value = line.rsplit(" ", 1)
        float(value)  # every sample parses
    # 404 for other paths
    conn = http.client.HTTPConnection("127.0.0.1", c.exporter.port,
                                      timeout=5)
    conn.request("GET", "/nope")
    assert conn.getresponse().status == 404
    conn.close()


def test_slow_op_flight_recorder_and_metrics_history(tmp_path):
    """ISSUE 9 acceptance, end to end on a live cluster with
    trace_sample_rate=1.0: an injected dispatch stall produces a
    historic slow-op entry whose ATTACHED cross-daemon trace spans at
    least two services, journals a slow_op cluster event, and the
    metrics history answers rate queries over two disjoint snapshot
    windows that agree exactly with raw counter deltas."""
    cfg = make_cfg(trace_sample_rate=1.0, osd_op_complaint_time=0.08,
                   metrics_history_interval_s=0.1)
    c = MiniCluster(n_osds=4, cfg=cfg,
                    admin_dir=str(tmp_path / "asok")).start()
    try:
        client = c.client()
        client.create_pool("p", kind="ec", pg_num=1,
                           ec_profile={"plugin": "jerasure", "k": "2",
                                       "m": "1", "backend": "numpy"})
        client.write_full("p", "obj", b"a" * 4096)
        pool_id = next(pid for pid, p in c.mon.osdmap.pools.items()
                       if p.name == "p")
        seed = c.mon.osdmap.object_to_pg(pool_id, "obj")
        primary = next(o for o in
                       c.mon.osdmap.pg_to_up_osds(pool_id, seed)
                       if o is not None)
        posd = c.osds[primary]

        # --- flight recorder: stall the primary's EC write dispatch
        orig = posd._ec_write

        def stalled(*a, **kw):
            time.sleep(0.2)
            return orig(*a, **kw)

        posd._ec_write = stalled
        try:
            client.write_full("p", "obj", b"b" * 8192)
        finally:
            posd._ec_write = orig
        asok = str(tmp_path / "asok" / f"osd.{primary}.asok")
        hist = admin_request(asok, "dump_historic_slow_ops")
        entries = [d for d in hist if "obj" in d["description"]]
        assert entries, f"no historic slow op recorded: {hist}"
        entry = entries[-1]
        assert entry.get("trace_id"), "slow op lost its trace id"
        trace = entry.get("trace") or []
        services = {s["service"] for s in trace}
        assert len(services) >= 2, \
            f"slow-op trace does not cross daemons: {services}"
        # the op's own span names are in the merged evidence
        assert any(s["name"].startswith("osd-op") for s in trace)
        # ...and the complaint is journaled as a slow_op cluster event
        mon_asok = str(tmp_path / "asok" / "mon.0.asok")
        deadline = time.time() + 10
        evs = []
        while time.time() < deadline:
            res, data = admin_request(mon_asok, "dump_cluster_log",
                                      channel="slow_op")
            assert res == 0, data
            evs = data["events"]
            if evs:
                break
            time.sleep(0.05)
        assert evs, "slow_op event never reached the cluster log"
        assert any(e["fields"].get("trace_id") == entry["trace_id"]
                   for e in evs)

        # --- metrics history: two disjoint windows vs raw deltas ----
        # Boundaries are driven by MERGE COVERAGE, not fixed sleeps:
        # the loaded CI box can starve the heartbeat sampler / stats
        # shipping for long stretches, so each phase ends only once
        # the mon's newest merged snapshot reflects the raw counters
        # taken at that boundary (samples merge seq-ordered, so a
        # newer sample covering the counter implies every earlier one
        # is in too).
        reg = f"osd.{primary}"

        def newest(counter):
            res, data = admin_request(mon_asok, "dump_metrics_history",
                                      registry=reg, max=1)
            assert res == 0, data
            rows = data["registries"].get(reg) or []
            return rows[-1]["counters"].get(counter) if rows else None

        def wait_merged(counter, want, timeout=20):
            deadline = time.time() + timeout
            while time.time() < deadline:
                got = newest(counter)
                if isinstance(got, dict):
                    got = got.get("count")
                if got == want:
                    return
                time.sleep(0.05)
            raise AssertionError(
                f"mon history never caught up: {counter} stuck at "
                f"{newest(counter)!r}, want {want!r}")

        def newest_ts():
            res, data = admin_request(mon_asok, "dump_metrics_history",
                                      registry=reg, max=1)
            assert res == 0, data
            rows = data["registries"].get(reg) or []
            return float(rows[-1]["ts"]) if rows else 0.0

        w0 = posd.perf.get("op_w")
        q0 = posd.perf.dump()["mclock_qwait_us_client"]["count"]
        wait_merged("op_w", w0)
        t0 = time.time()
        # window 1 (quiet) closes only once a sample taken INSIDE it
        # has merged — the window query needs an in-window row
        deadline = time.time() + 20
        while newest_ts() <= t0 + 0.2:
            assert time.time() < deadline, "sampler stalled mid-quiet"
            time.sleep(0.05)
        t1 = time.time()
        w1 = posd.perf.get("op_w")
        q1 = posd.perf.dump()["mclock_qwait_us_client"]["count"]
        eb1 = posd.perf.get("ec_batch_coalesced_ops")
        for i in range(6):                    # window 2: traffic
            client.write_full("p", f"w{i}", b"c" * 2048)
        posd.perf.inc("ec_batch_coalesced_ops", 9)  # ec_batch_* probe
        w2 = posd.perf.get("op_w")
        q2 = posd.perf.dump()["mclock_qwait_us_client"]["count"]
        # wait until snapshots covering ALL the burst's counters merge
        wait_merged("op_w", w2)
        wait_merged("ec_batch_coalesced_ops", eb1 + 9)
        wait_merged("mclock_qwait_us_client", q2)
        t2 = time.time()
        now = time.time()

        def mon_query(counter, lo, hi):
            # ABSOLUTE window edges: relative since/until re-anchor to
            # the server clock at execution, and serial admin round
            # trips on a loaded box drift the edges across the burst
            # boundary (observed flake)
            res, data = admin_request(mon_asok, "metrics_query",
                                      registry=reg, counter=counter,
                                      start_ts=lo, end_ts=hi)
            assert res == 0, data
            return data

        quiet = mon_query("op_w", t0, t1)
        busy = mon_query("op_w", t1, t2)
        assert quiet["samples"] >= 2 and busy["samples"] >= 2
        assert quiet["delta"] == w1 - w0 == 0
        assert busy["delta"] == w2 - w1 == 6
        # span_s is rounded for the wire; the rate agrees to within
        # that rounding
        assert abs(busy["rate_per_s"]
                   - busy["delta"] / busy["span_s"]) < 1e-3
        # ec_batch_* rides the same surface
        eb = mon_query("ec_batch_coalesced_ops", t1, t2)
        assert eb["delta"] == 9
        # mclock_qwait histogram: count delta matches the raw registry
        # and the window quantiles are well-formed
        qq = mon_query("mclock_qwait_us_client", t1, t2)
        assert qq["count_delta"] == q2 - q1 > 0
        assert 0.0 <= qq["p50"] <= qq["p99"]
        qquiet = mon_query("mclock_qwait_us_client", t0, t1)
        assert qquiet["count_delta"] == q1 - q0 == 0
        # the local daemon verb serves the same ring
        local = admin_request(asok, "metrics_query", registry=reg,
                              counter="op_w", start_ts=t1, end_ts=t2)
        assert local["delta"] == 6
        # perf_history CLI helpers read the same surfaces
        from ceph_tpu.tools.perf_history import ls, show
        regs = ls(mon_asok)
        assert reg in regs and "op_w" in regs[reg]
        text = show(mon_asok, reg, "op_w", since_s=now - t0)
        assert "rate" in text
    finally:
        c.stop()


def test_sampling_off_zero_tracer_cost(tmp_path):
    """The zero-cost-when-off half of the acceptance: with
    trace_sample_rate at its 0 default, a burst of real client IO
    allocates NOTHING in any tracer — no spans, no unsampled ring
    entries, no counter movement."""
    c = MiniCluster(n_osds=3, cfg=make_cfg(),
                    admin_dir=str(tmp_path / "asok")).start()
    try:
        client = c.client()
        client.create_pool("p", size=2, pg_num=1)
        for i in range(8):
            client.write_full("p", f"o{i}", b"q" * 1024)
            client.read("p", f"o{i}")
        assert client.tracer.dump() == []
        assert len(client.tracer._unsampled) == 0
        for osd in c.osds.values():
            assert osd.tracer.dump() == []
            assert len(osd.tracer._unsampled) == 0
            assert osd.perf.get("trace_sampled") == 0
            assert osd.perf.get("trace_dropped") == 0
    finally:
        c.stop()
    # stop() retires the daemons' registries from the global
    # collection, so a later same-process cluster (the next test)
    # starts from zeroed counters instead of inheriting these
    from ceph_tpu.utils.perf import global_perf
    live = global_perf().registries()
    assert not any(n in live for n in ("osd.0", "osd.1", "osd.2"))


def test_counter_schema_lint_one_strict_scrape(obs_cluster):
    """The counter-schema lint: EVERY counter of every live registry
    (daemons, messengers, stores, the kernel profiler) renders in ONE
    strict scrape with its documented exporter faces — zeroed schema
    included (the exporter emits a histogram's +Inf bucket and
    sum/count at zero samples).  A counter registered but dropped by
    the renderer — or renamed on one side only — fails here, not on a
    dashboard weeks later."""
    from ceph_tpu.mon.exporter import _sanitize
    from ceph_tpu.utils.perf import global_perf

    c, _ = obs_cluster
    # enumerate BEFORE the scrape: anything registered by then must
    # render (late registrants after this snapshot are out of scope)
    expected = {daemon: reg.dump()
                for daemon, reg in global_perf().registries().items()}
    assert expected, "no live registries to lint"
    conn = http.client.HTTPConnection("127.0.0.1", c.exporter.port,
                                      timeout=5)
    conn.request("GET", "/metrics")
    body = conn.getresponse().read().decode()
    conn.close()
    parsed = _parse_exposition_strict(body)

    def assert_series(family: str, daemon: str, cname: str,
                      extra: str = ""):
        fam = parsed.get(family)
        assert fam is not None, \
            f"{daemon}:{cname}: family {family} missing from the scrape"
        assert any(f'daemon="{daemon}"' in s and extra in s
                   for s in fam["samples"]), \
            f"{daemon}:{cname}: no {family}{{{extra}}} series"

    checked = 0
    for daemon, counters in expected.items():
        for cname, val in counters.items():
            base = f"ceph_tpu_daemon_{_sanitize(cname)}"
            if isinstance(val, dict):
                for sub in ("sum", "count", "sum_seconds"):
                    if sub in val:
                        assert_series(f"{base}_{sub}", daemon, cname)
                if "buckets_pow2" in val:
                    # the zeroed-schema contract: +Inf exists even for
                    # an empty histogram
                    assert_series(f"{base}_bucket", daemon, cname,
                                  extra='le="+Inf"')
            else:
                assert_series(base, daemon, cname)
            checked += 1
    # the lint actually covered the fleet: four OSDs' worth of
    # registries plus messenger/kernel planes
    assert checked > 100, f"suspiciously few counters linted: {checked}"
    assert len(expected) >= 5, sorted(expected)


def test_perf_query_scrape_series_bounded_under_tenant_churn():
    """Counter-schema lint for the perf-query scrape face: a standing
    query fed 500 distinct HOSTILE tenant names still renders exactly
    four aggregate families labeled only by query id — no tenant-named
    series, no label-breaking characters, series count bounded by the
    number of standing queries (never by key cardinality; churn past
    top-N lands in the overflow fold, and totals stay conserved)."""
    import threading

    from ceph_tpu.mon.exporter import render_metrics
    from ceph_tpu.mon.maps import OSDMap
    from ceph_tpu.telemetry.perf_query import (PerfQuerySet,
                                               PerfQuerySpec,
                                               PerfQueryStore)

    class StubMon:
        def __init__(self, pq_store):
            self._lock = threading.Lock()
            self.osdmap = OSDMap()
            self.is_leader = True
            self._osd_stats = {}
            self.progress = None
            self.metrics_history = None
            self.perf_queries = pq_store

    pq = PerfQuerySet()
    pq.set_queries({1: PerfQuerySpec(qid=1, key_by=("tenant",),
                                     top_n=8),
                    2: PerfQuerySpec(qid=2, key_by=("pool",))})
    for i in range(500):
        hostile = f'ten{{ant}}"\n{"x" * (i % 90)}-{i}'
        pq.observe(hostile, 0, (1, i % 4), "write", f"obj-{i}",
                   4096, 0, 100.0)
    store = PerfQueryStore()
    assert store.merge("osd.0", pq.snapshot())
    body = render_metrics(StubMon(store))
    parsed = _parse_exposition_strict(body)
    fams = {n: m for n, m in parsed.items() if "perf_query" in n}
    assert set(fams) == {"ceph_tpu_perf_query_ops_total",
                         "ceph_tpu_perf_query_bytes_total",
                         "ceph_tpu_perf_query_keys",
                         "ceph_tpu_perf_query_overflow_ops"}
    # exactly one series per (family, standing query) — 500 tenants in,
    # 8 series out
    for name, fam in fams.items():
        assert sorted(fam["samples"]) == [f'{name}{{query="1"}}',
                                          f'{name}{{query="2"}}']
    samples = parsed["ceph_tpu_perf_query_ops_total"]["samples"]
    assert samples['ceph_tpu_perf_query_ops_total{query="1"}'] == 500.0
    keys = parsed["ceph_tpu_perf_query_keys"]["samples"]
    assert keys['ceph_tpu_perf_query_keys{query="1"}'] <= 8.0
    assert keys['ceph_tpu_perf_query_keys{query="2"}'] == 1.0
    overflow = parsed["ceph_tpu_perf_query_overflow_ops"]["samples"]
    assert overflow['ceph_tpu_perf_query_overflow_ops{query="1"}'] \
        == 500.0 - keys['ceph_tpu_perf_query_keys{query="1"}']
    # no tenant fragment leaks into any perf-query metric line: every
    # sample is exactly name{query="N"} value
    import re as _re
    pq_lines = [ln for ln in body.splitlines()
                if "perf_query" in ln and not ln.startswith("#")]
    assert pq_lines
    assert all(_re.fullmatch(
        r'ceph_tpu_perf_query_\w+\{query="\d+"\} [\d.e+-]+', ln)
        for ln in pq_lines), pq_lines


def test_exemplar_blame_slo_burn_end_to_end(tmp_path, capsys):
    """ISSUE 18 acceptance, end to end on a live cluster: an injected
    stall's op lands an exemplar in its latency bucket; ``metrics_query``
    on the mon surfaces the trace_id; ``trace_tool --exemplar`` resolves
    it to a merged skew-aligned waterfall whose critical path blames the
    stalled stage; the SLO mgr module raises ``SLO_BURN`` carrying that
    trace_id in the health detail and journals the transition; the
    check clears on its own once the stall stops and the fast window
    drains."""
    from ceph_tpu.mon.mgr import MgrDaemon
    from ceph_tpu.tools import trace_tool
    from ceph_tpu.utils.critical_path import critical_path

    cfg = make_cfg(trace_sample_rate=1.0, osd_op_complaint_time=0.08,
                   metrics_history_interval_s=0.1,
                   slo_objectives="client_op_p99<=20ms@99%",
                   slo_fast_window_s=5.0, slo_slow_window_s=30.0,
                   slo_burn_threshold=2.0)
    c = MiniCluster(n_osds=4, cfg=cfg,
                    admin_dir=str(tmp_path / "asok")).start()
    mgr = None
    try:
        client = c.client()
        client.create_pool("p", kind="ec", pg_num=1,
                           ec_profile={"plugin": "jerasure", "k": "2",
                                       "m": "1", "backend": "numpy"})
        client.write_full("p", "obj", b"a" * 4096)
        pool_id = next(pid for pid, p in c.mon.osdmap.pools.items()
                       if p.name == "p")
        seed = c.mon.osdmap.object_to_pg(pool_id, "obj")
        primary = next(o for o in
                       c.mon.osdmap.pg_to_up_osds(pool_id, seed)
                       if o is not None)
        posd = c.osds[primary]
        orig = posd._ec_write

        def stalled(*a, **kw):
            time.sleep(0.2)  # >> the 20ms objective threshold
            return orig(*a, **kw)

        posd._ec_write = stalled
        try:
            client.write_full("p", "obj", b"b" * 8192)
        finally:
            posd._ec_write = orig
        asok_dir = str(tmp_path / "asok")
        mon_asok = str(tmp_path / "asok" / "mon.0.asok")
        reg = f"osd.{primary}"

        # 1) the stalled op's bucket exemplar via the mon metrics_query
        # (bucket hi > 100ms: only the injected stall lives up there)
        tid = None
        deadline = time.time() + 25
        while time.time() < deadline and tid is None:
            res, data = admin_request(mon_asok, "metrics_query",
                                      registry=reg,
                                      counter="op_lat_us", since_s=60.0)
            assert res == 0, data
            for b, ring in sorted(
                    (data.get("exemplars") or {}).items(),
                    key=lambda kv: -int(kv[0])):
                if 2.0 ** int(b) > 100_000.0 and ring:
                    tid = int(ring[0]["trace_id"])
                    break
            if tid is None:
                time.sleep(0.05)
        assert tid is not None, "stall exemplar never reached the mon"

        # 2) trace_tool --exemplar: the trace_id resolves to a merged,
        # skew-aligned waterfall crossing daemons
        skew = trace_tool.collect_skew(asok_dir)
        assert reg in skew  # the mon has a skew estimate per reporter
        spans = trace_tool.collect_from_asok(asok_dir, tid, skew=skew)
        assert spans, "exemplar trace_id resolved to no spans"
        assert any(s["name"].startswith("osd-op") for s in spans)
        assert reg in {s["service"] for s in spans}
        assert trace_tool.main(
            ["--asok-dir", asok_dir, "--exemplar", str(tid)]) == 0
        out = capsys.readouterr().out
        assert "critical path" in out and "osd-op" in out

        # 3) the critical path blames the stalled stage: the injected
        # sleep is the osd-op span's own (un-childed) time
        cp = critical_path(spans)
        top = max(cp, key=lambda e: e["self_ms"])
        assert top["name"].startswith("osd-op"), cp
        assert top["service"] == reg
        assert top["self_ms"] >= 150.0, cp
        assert trace_tool.main(
            ["--asok-dir", asok_dir, "--blame", "--json"]) == 0
        doc = json.loads(capsys.readouterr().out)
        assert doc["traces"] >= 1
        # the stage owning the most blocked time cluster-wide is the
        # stalled op dispatch
        assert next(iter(doc["blame"])).startswith("osd-op")

        # 4) SLO_BURN raises with the exemplar trace_id in the detail
        mgr = MgrDaemon(c.mon, modules=("slo",))
        slo = mgr.module("slo")
        check = None
        deadline = time.time() + 25
        while time.time() < deadline:
            slo.tick()
            checks = client.status().get("checks", {})
            if "SLO_BURN" in checks:
                check = checks["SLO_BURN"]
                break
            time.sleep(0.1)
        assert check, "SLO_BURN never raised"
        assert check["severity"] == "HEALTH_WARN"
        detail = "\n".join(check["detail"])
        assert "client_op_p99<=20ms@99%" in detail
        assert str(tid) in detail, \
            f"exemplar trace {tid} not in detail: {detail}"
        # ...and the raise is journaled on the slo channel with the
        # exemplar trace ids
        res, data = admin_request(mon_asok, "dump_cluster_log",
                                  channel="slo")
        assert res == 0
        raised = [e for e in data["events"]
                  if "SLO_BURN raised" in e["message"]]
        assert raised
        assert str(tid) in raised[-1]["fields"]["exemplar_trace_ids"]

        # 5) the stall is over: good traffic refills the fast window,
        # the burn drops, the check clears and journals the clear
        cleared = False
        deadline = time.time() + 30
        i = 0
        while time.time() < deadline:
            client.write_full("p", f"g{i}", b"c" * 1024)
            i += 1
            slo.tick()
            if "SLO_BURN" not in client.status().get("checks", {}):
                cleared = True
                break
            time.sleep(0.2)
        assert cleared, "SLO_BURN never cleared after the stall"
        res, data = admin_request(mon_asok, "dump_cluster_log",
                                  channel="slo")
        assert res == 0
        assert any("SLO_BURN cleared" in e["message"]
                   for e in data["events"])
    finally:
        if mgr is not None:
            mgr.stop()
        c.stop()


def test_batch_thrash_health_warn_appears_and_clears(tmp_path):
    """The config-gated BATCH_THRASH promotion: repeated batch-channel
    events (adaptive-window resizes / fused-csum fall-throughs) from
    one daemon cross the threshold -> HEALTH_WARN with per-daemon
    detail; the window draining clears it without intervention."""
    cfg = make_cfg(mon_batch_thrash_warn_count=3,
                   mon_batch_thrash_warn_window_s=1.5)
    c = MiniCluster(n_osds=2, cfg=cfg,
                    admin_dir=str(tmp_path / "asok")).start()
    try:
        client = c.client()
        assert client.status()["health"] == "HEALTH_OK"
        # journal a resize storm on osd.0 (the batcher's emission
        # shape); it rides the next stats reports to the mon
        for i in range(4):
            c.osds[0].events.emit(
                "batch", f"ec batch window resized to {100 + i}us",
                window_us=100.0 + i, prev_us=50.0, ops_ewma=1.0)
        deadline = time.time() + 10
        st = client.status()
        while time.time() < deadline:
            st = client.status()
            if "BATCH_THRASH" in st.get("checks", {}):
                break
            time.sleep(0.05)
        check = st.get("checks", {}).get("BATCH_THRASH")
        assert check, f"BATCH_THRASH never raised: {st}"
        assert check["detail"] == {"osd.0": 4}
        assert "osd.0" in check["summary"]
        # ...and the transition is narrated on the health channel
        res, data = admin_request(
            str(tmp_path / "asok" / "mon.0.asok"),
            "dump_cluster_log", channel="health")
        assert res == 0
        assert any(e["fields"].get("check") == "BATCH_THRASH"
                   for e in data["events"])
        # the sliding window drains -> the warning clears on its own
        deadline = time.time() + 15
        while time.time() < deadline:
            st = client.status()
            if "BATCH_THRASH" not in st.get("checks", {}):
                break
            time.sleep(0.1)
        assert "BATCH_THRASH" not in st.get("checks", {}), st
    finally:
        c.stop()
