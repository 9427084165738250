"""Recording-rule generation (tools/prom_rules.py): the generated
p50/p99 histogram_quantile rules must reference ONLY metric names the
exporter actually emits — a renamed histogram must fail here, not
silently strand a dashboard on a dead series."""

import re

from ceph_tpu.mon.exporter import render_metrics
from ceph_tpu.msg.messenger import LocalNetwork, Messenger
from ceph_tpu.tools.prom_rules import (recording_rules, referenced_metrics,
                                       render)
from ceph_tpu.utils.perf import kernel_profiler


def _emitted_metric_names(body: str) -> set[str]:
    names = set()
    for line in body.splitlines():
        if not line or line.startswith("#"):
            continue
        names.add(line.rsplit(" ", 1)[0].split("{", 1)[0])
    return names


class _StubMon:
    """The minimal monitor surface render_metrics()'s mon branch
    touches — enough to materialize the mon-side staleness gauge
    without booting a cluster."""

    def __init__(self, store):
        import threading

        from ceph_tpu.mon.maps import OSDMap
        self._lock = threading.Lock()
        self.osdmap = OSDMap()
        self.is_leader = True
        self._osd_stats = {}
        self.progress = None
        self.metrics_history = store


def test_rules_reference_only_emitted_metrics():
    # materialize the registries the rules read: the kernel profiler
    # (ec_kernels: kernel_*_us), one messenger (msg_dispatch_us), the
    # scheduler's per-class QoS counters (mclock_qwait_us_*), a tracer
    # (trace_sampled/trace_dropped) and a mon-side metrics-history
    # store with one merged sample (the staleness gauge) — the
    # exporter emits every histogram's +Inf bucket even at zero
    # samples, so the schema exists without traffic
    from ceph_tpu.osd.scheduler import (ClassParams,
                                        register_qos_counters,
                                        register_tenant_counters)
    from ceph_tpu.utils.metrics_history import MetricsHistoryStore
    from ceph_tpu.utils.perf import global_perf
    from ceph_tpu.utils.tracer import Tracer
    kernel_profiler()
    net = LocalNetwork()
    m = Messenger(net, "prom-rules-probe")
    qos_probe = global_perf().create("qos_probe")
    register_qos_counters(qos_probe, {
        "client": ClassParams(0, 1, 0),
        "recovery": ClassParams(0, 1, 0),
        "scrub": ClassParams(0, 1, 0)})
    # the per-tenant family's always-present anchor (the scheduler
    # registers it at construction — same zeroed-schema contract)
    register_tenant_counters(qos_probe, ("default",))
    # the store commit pipeline's schema (store_commit_us /
    # store_queue_us p50/p99 rules)
    from ceph_tpu.osd.objectstore import register_store_counters
    register_store_counters(qos_probe)
    # the KV metadata tier's maintenance schema (kv_flush_us /
    # kv_compact_us / kv_stall_us / kv_wal_compact_us p50/p99 rules +
    # flush/compact/cache rate rules)
    from ceph_tpu.osd.kvstore import register_kv_counters
    register_kv_counters(qos_probe)
    # the read scale-out schema (balanced_read_* / read_lease_* /
    # ec_read_tier_* rate rules — registered zeroed at OSD boot)
    from ceph_tpu.osd.extent_cache import register_read_scaleout_counters
    register_read_scaleout_counters(qos_probe)
    # the exemplar-era op-path histograms (op_lat_us from the
    # OpTracker bind, ec_batch_{wait,flush}_us from the batcher) —
    # registered zeroed at daemon/batcher construction
    from ceph_tpu.utils.perf import CounterType
    for h in ("op_lat_us", "ec_batch_wait_us", "ec_batch_flush_us"):
        qos_probe.add(h, CounterType.HISTOGRAM)
    # the background-scrub + inline-compression counter families
    # (registered zeroed at OSD boot; schema pinned by the lint below)
    from ceph_tpu.tools.prom_rules import (COMPRESS_COUNTERS,
                                           SCRUB_COUNTERS)
    qos_probe.add_many(SCRUB_COUNTERS + COMPRESS_COUNTERS)
    Tracer("qos_probe", perf=qos_probe)  # trace_* counter schema
    import time as _time
    store = MetricsHistoryStore()
    # a FRESH sample: the store expires silent daemons out of the
    # staleness gauge, so an ancient ts would render nothing
    store.merge("osd.0", {"osd.0": [
        {"ts": _time.time(), "seq": 1, "counters": {"op_w": 0}}]})
    try:
        body = render_metrics(_StubMon(store))
    finally:
        m.shutdown()
        global_perf().remove("qos_probe")
    emitted = _emitted_metric_names(body)
    rules = recording_rules()
    refs = referenced_metrics(rules)
    assert refs, "rules reference no metrics at all"
    missing = refs - emitted
    assert not missing, \
        f"rules reference metrics the exporter never emits: {missing}"


def test_rules_shape_and_rendering():
    rules = recording_rules()
    # one rule per (histogram, quantile) + one rate rule per tracer /
    # messenger-copy / kv-maintenance / read-scale-out counter + the
    # SLO bad-fraction ratio + the staleness max, records namespaced
    assert len(rules) == 79
    assert all(r["record"].startswith("ceph_tpu:") for r in rules)
    hist = [r for r in rules if "histogram_quantile(" in r["expr"]]
    assert len(hist) == 34
    assert all("by (daemon, le)" in r["expr"] for r in hist)
    quantiles = {r["record"].rsplit(":", 1)[1] for r in hist}
    assert quantiles == {"p50", "p99"}
    # the KV tier's maintenance walls + write-stall time quantiles
    hist_recs = {r["record"] for r in hist}
    for kvh in ("kv_flush_us", "kv_compact_us", "kv_stall_us",
                "kv_wal_compact_us"):
        assert f"ceph_tpu:daemon_{kvh}:p99" in hist_recs
    rates = [r for r in rules if ":rate" in r["record"]]
    assert {r["record"] for r in rates} == {
        "ceph_tpu:daemon_trace_sampled:rate5m",
        "ceph_tpu:daemon_trace_dropped:rate5m",
        "ceph_tpu:daemon_msg_tx_flatten_bytes:rate5m",
        "ceph_tpu:daemon_msg_tx_flatten_copies:rate5m",
        "ceph_tpu:daemon_msg_rx_copy_bytes:rate5m",
        "ceph_tpu:daemon_msg_rx_copy_copies:rate5m",
        "ceph_tpu:daemon_msg_syscalls_tx:rate5m",
        "ceph_tpu:daemon_msg_syscalls_rx:rate5m",
        "ceph_tpu:daemon_msg_uring_sqe_batch:rate5m",
        "ceph_tpu:daemon_msg_uring_reg_buf_recycled:rate5m",
        "ceph_tpu:daemon_kv_flush:rate5m",
        "ceph_tpu:daemon_kv_compact:rate5m",
        "ceph_tpu:daemon_kv_cache_hit:rate5m",
        "ceph_tpu:daemon_kv_cache_miss:rate5m",
        "ceph_tpu:daemon_balanced_read_serve:rate5m",
        "ceph_tpu:daemon_balanced_read_bounce:rate5m",
        "ceph_tpu:daemon_read_lease_grant:rate5m",
        "ceph_tpu:daemon_read_lease_ride:rate5m",
        "ceph_tpu:daemon_read_lease_revoke:rate5m",
        "ceph_tpu:daemon_ec_read_tier_hit:rate5m",
        "ceph_tpu:daemon_ec_read_tier_miss:rate5m",
        "ceph_tpu:daemon_ec_read_tier_admit:rate5m",
        "ceph_tpu:daemon_ec_read_tier_evict:rate5m",
        "ceph_tpu:daemon_scrubs:rate5m",
        "ceph_tpu:daemon_scrub_errors:rate5m",
        "ceph_tpu:daemon_scrub_verified_bytes:rate5m",
        "ceph_tpu:daemon_scrub_verify_launches:rate5m",
        "ceph_tpu:daemon_scrub_mismatches:rate5m",
        "ceph_tpu:daemon_scrub_digest_missing:rate5m",
        "ceph_tpu:daemon_scrub_auto_chunks:rate5m",
        *(f"ceph_tpu:daemon_scrub_finding_{k}:rate5m"
          for k in ("read_error", "digest_missing", "digest_mismatch",
                    "missing_shard", "stale_version", "missing_copy",
                    "size_mismatch", "replica_digest_mismatch")),
        "ceph_tpu:daemon_compress_blobs:rate5m",
        "ceph_tpu:daemon_compress_rejected:rate5m",
        "ceph_tpu:daemon_compress_decompress:rate5m",
        "ceph_tpu:daemon_bluestore_compressed_original:rate5m",
        "ceph_tpu:daemon_bluestore_compressed_allocated:rate5m"}
    assert all("rate(" in r["expr"] and "by (daemon)" in r["expr"]
               for r in rates)
    stale = [r for r in rules
             if r["record"] == "ceph_tpu:metrics_history_staleness_s:max"]
    assert len(stale) == 1
    assert stale[0]["expr"] == "max(ceph_tpu_metrics_history_staleness_s)"
    # the SLO_BURN-aligned bad-fraction ratio: observations over the
    # bucket bound as a fraction of all (slo/objectives.py's
    # bad_fraction in PromQL; burn = ratio / (1 - target))
    slo = [r for r in rules if r["record"].startswith("ceph_tpu:slo_")]
    assert len(slo) == 1
    assert slo[0]["record"] == "ceph_tpu:slo_client_op_bad:ratio_rate5m"
    assert 'le="16384"' in slo[0]["expr"] \
        and 'le="+Inf"' in slo[0]["expr"] \
        and "ceph_tpu_daemon_op_lat_us_bucket" in slo[0]["expr"]
    text = render(rules)
    assert text.startswith("groups:\n- name: ceph_tpu_latency\n")
    assert text.count("  - record: ") == 79
    assert text.count("    expr: ") == 79
    # per-tenant family: the default anchor is standing, and named
    # tenants generate the same rule shape via tenant_histograms
    from ceph_tpu.tools.prom_rules import tenant_histograms
    named = recording_rules(
        histograms=tenant_histograms(("gold", "Bul-k!")))
    recs = {r["record"] for r in named
            if "histogram_quantile(" in r["expr"]}
    assert ("ceph_tpu:daemon_mclock_qwait_us_tenant_gold:p99"
            in recs)
    # names sanitize exactly like the scheduler's counter stems
    assert ("ceph_tpu:daemon_mclock_qwait_us_tenant_bul_k_:p50"
            in recs)


def test_scrub_compress_counter_schema_lint():
    """The scrub_*/compress_* families stay in lockstep between the
    daemon's zeroed registration and the standing rate rules: a
    counter added to one side without the other fails the lint."""
    from ceph_tpu.osd.compression import COUNTERS as COMPRESS_DAEMON
    from ceph_tpu.tools.prom_rules import (COMPRESS_COUNTERS,
                                           SCRUB_COUNTERS,
                                           lint_counter_schema)
    # the exact names the OSD registers zeroed at boot (daemon.py
    # perf.add_many + compression.COUNTERS)
    from ceph_tpu.osd import scrub
    daemon_registered = ("scrubs", "scrub_errors") + scrub.COUNTERS \
        + COMPRESS_DAEMON
    assert set(SCRUB_COUNTERS) == {"scrubs", "scrub_errors",
                                   *scrub.COUNTERS}
    assert lint_counter_schema(daemon_registered) == []
    assert set(COMPRESS_COUNTERS) == set(COMPRESS_DAEMON)
    # drift in either direction is a loud, named failure
    missing = lint_counter_schema(daemon_registered[:-1])
    assert len(missing) == 1 and "missing counter" in missing[0]
    stray = lint_counter_schema(
        daemon_registered + ("scrub_new_thing",))
    assert len(stray) == 1 and "unruled counter" in stray[0]
    # every family member has a standing rate rule
    recs = {r["record"] for r in recording_rules()}
    for c in SCRUB_COUNTERS + COMPRESS_COUNTERS:
        assert f"ceph_tpu:daemon_{c}:rate5m" in recs
    # and the LIVE daemon registration passes the lint end-to-end
    from ceph_tpu.tools.vstart import MiniCluster
    from tests.test_cluster import make_cfg
    c = MiniCluster(n_osds=1, cfg=make_cfg()).start()
    try:
        osd = next(iter(c.osds.values()))
        names = list(osd.perf.dump())
        assert lint_counter_schema(names) == []
    finally:
        c.stop()


def test_dashboard_pinned_to_emitted_rule_names():
    """The generated Grafana dashboard may reference ONLY series that
    exist: recorded rule names from recording_rules() plus the
    exporter's bounded perf-query aggregates (PERF_QUERY_METRICS) — a
    rule rename must break generation here, not strand a live panel
    on a dead series."""
    import json

    import pytest

    from ceph_tpu.tools.prom_rules import (PERF_QUERY_METRICS, dashboard,
                                           main)
    dash = json.loads(json.dumps(dashboard()))   # valid JSON document
    assert dash["uid"] == "ceph-tpu-overview"
    assert dash["panels"], "dashboard has no panels"
    records = {r["record"] for r in recording_rules()}
    raw_ok = {f"ceph_tpu_{m}" for m in PERF_QUERY_METRICS}
    seen_raw = set()
    ids = [p["id"] for p in dash["panels"]]
    assert len(ids) == len(set(ids))
    for p in dash["panels"]:
        assert p["datasource"]["uid"] == "${DS_PROMETHEUS}"
        refids = [t["refId"] for t in p["targets"]]
        assert len(refids) == len(set(refids))
        for t in p["targets"]:
            for token in re.findall(r"ceph_tpu[A-Za-z0-9_:]*",
                                    t["expr"]):
                assert token in records or token in raw_ok, \
                    f"panel {p['title']!r} references unknown " \
                    f"series {token!r}"
                if token in raw_ok:
                    seen_raw.add(token)
    # the attribution panel really reads the perf-query aggregates
    assert f"ceph_tpu_perf_query_ops_total" in seen_raw
    # the exemplar-linked target: client op p99 resolves trace dots
    p99 = [t for p in dash["panels"] for t in p["targets"]
           if t["expr"].endswith("op_lat_us:p99")]
    assert p99 and p99[0].get("exemplar") is True
    # a panel referencing a rule that was renamed away fails LOUDLY
    with pytest.raises(KeyError):
        dashboard(rules=[])
    # the CLI face emits the same parseable document
    import contextlib
    import io
    buf = io.StringIO()
    with contextlib.redirect_stdout(buf):
        main(["--dashboard"])
    assert json.loads(buf.getvalue())["uid"] == "ceph-tpu-overview"


def test_exporter_histogram_buckets_are_cumulative_le():
    """The rule expressions only work over CUMULATIVE le-labeled
    buckets — pin the exporter's rendering contract."""
    from ceph_tpu.utils.perf import global_perf
    pc = global_perf().create("bucket_probe")
    from ceph_tpu.utils.perf import CounterType
    pc.add("lat_us", CounterType.HISTOGRAM)
    for v in (3, 3, 10, 300):
        pc.hinc("lat_us", v)
    try:
        body = render_metrics(None)
    finally:
        global_perf().remove("bucket_probe")
    rows = {}
    for line in body.splitlines():
        m = re.match(r'ceph_tpu_daemon_lat_us_bucket\{daemon="'
                     r'bucket_probe",le="([^"]+)"\} (\d+)', line)
        if m:
            rows[m.group(1)] = int(m.group(2))
    # 3 -> bucket 2 (le 4), 10 -> bucket 4 (le 16), 300 -> bucket 9
    # (le 512); counts accumulate and +Inf carries the total
    assert rows == {"4": 2, "16": 3, "512": 4, "+Inf": 4}
    assert 'ceph_tpu_daemon_lat_us_count{daemon="bucket_probe"} 4' \
        in body
