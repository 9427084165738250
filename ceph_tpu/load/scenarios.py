"""Scenario runner: saturation legs + thrash-while-loaded + QoS sweep.

Composes the load generator into the scenarios the ROADMAP's "heavy
traffic" frontier names, against a real multi-OSD ``MiniCluster`` over
TCP with the mclock scheduler as the experiment variable:

- **ramp** — open-loop offered-rate steps on the healthy cluster; the
  saturation knee is the last step that still achieves >= KNEE_RATIO of
  its offered rate.
- **steady** — closed-loop saturation at full client concurrency.
- **thrash** — same load while an OSD is killed and revived with a
  FRESH store mid-leg: a full rebuild storm competes with client
  traffic, scored by the mon's progress/event stack (recovery ETA,
  completion) and the SLOW_OPS health tripwire.

A sweep runs >= 3 mclock recovery-reservation/limit settings and gates
on STRUCTURAL invariants, not absolute throughput (the CI box is a
2-core high-variance machine): no deadlock (every worker exits, every
leg makes progress), no unbounded queue growth (scheduler depths drain
to zero; drops are accounted), recovery completes, and QoS ordering
holds — raising the recovery reservation must speed recovery up and
must not worsen client p99 beyond the sweep's monotone envelope.
"""

from __future__ import annotations

import tempfile
import threading
import time
from dataclasses import dataclass, field

from .generator import LoadGenerator
from .profiles import LegSpec

#: a ramp step "keeps up" while achieved/offered stays above this
KNEE_RATIO = 0.85
#: envelope tolerances (generous: 2-core CI-box variance) — recovery
#: rates must be non-decreasing in reservation order within REC_SLACK
#: (monotone_within); client p99 across the sweep must stay inside a
#: bounded spread, max <= min * P99_SLACK (bounded_spread): raising
#: the recovery reservation may cost clients, but not beyond the
#: envelope — and a low-reservation point starving clients an order
#: of magnitude worse than the high ones trips it too.  The p99 slack
#: is wide because every point's thrash p99 carries the kill
#: transient (rpc timeout + map propagation) on top of the QoS
#: competition being gated.
REC_SLACK = 1.6
P99_SLACK = 8.0


@dataclass
class ScenarioConfig:
    """One saturation point: cluster shape + legs + mclock setting."""

    point_id: str = "default"
    profile: str = "small_mixed"
    procs: int = 2
    clients: int = 16            # cluster-wide closed-loop concurrency
    n_osds: int = 4
    objects: int = 48
    obj_bytes: int = 8192
    pg_num: int = 8
    ramp_rates: tuple = (50.0, 150.0, 450.0)  # cluster ops/s steps
    ramp_leg_s: float = 1.5
    steady_s: float = 4.0
    thrash_s: float = 8.0
    kill_after_s: float = 1.0    # offset into the thrash leg
    thrash: bool = True
    recovery_deadline_s: float = 45.0
    #: fixed measurement window after the kill for the sweep's
    #: recovery-rate comparison: robust to recovery WAVES (concurrent
    #: writes re-opening storms) and to slow points catching up later —
    #: served-ops-in-window is what the reservation/limit knob shapes
    qos_window_s: float = 3.0
    #: force one background deep-scrub cycle per OSD at the head of
    #: the steady leg (the scrub-while-loaded leg: the cycle runs
    #: under the scrub mclock class and the client envelope must hold)
    scrub: bool = True
    mclock: dict = field(default_factory=dict)  # osd_mclock_* overrides
    seed: int = 0
    #: "rados" = librados directly; "rgw" = the RgwGateway PUT/GET
    #: object path (ROADMAP saturation follow-on (b): the load model
    #: is front-end agnostic — same legs, histograms and invariants)
    frontend: str = "rados"

    def legs(self) -> list[LegSpec]:
        out = [LegSpec(name=f"ramp{i}", profile=self.profile,
                       duration_s=self.ramp_leg_s, mode="open",
                       rate=r, concurrency=self.clients)
               for i, r in enumerate(self.ramp_rates)]
        out.append(LegSpec(name="steady", profile=self.profile,
                           duration_s=self.steady_s, mode="closed",
                           concurrency=self.clients))
        if self.thrash:
            out.append(LegSpec(name="thrash", profile=self.profile,
                               duration_s=self.thrash_s, mode="closed",
                               concurrency=self.clients))
        return out


def _build_cluster(cfg: ScenarioConfig, admin_dir: str):
    from ..tools.vstart import MiniCluster
    from ..utils.config import default_config
    conf = default_config()
    conf.apply_dict({
        "osd_heartbeat_interval": 0.05,
        "osd_heartbeat_grace": 0.5,
        "ec_backend": "native",
        "ms_dispatch_workers": 2,
        "osd_op_num_shards": 2,
        # SLOW_OPS as a live tripwire at bench timescales (default 30s
        # would never fire inside a seconds-long leg)
        "osd_op_complaint_time": 2.0,
        # recovery pacing off: the mclock reservation/limit must be the
        # binding constraint the sweep turns, not the sleep throttle
        "osd_recovery_sleep": 0.0,
        "osd_recovery_max_active": 8,
        "osd_recovery_progress_interval": 0.0,
        "mgr_progress_linger": 1.0,
        **cfg.mclock})
    c = MiniCluster(n_osds=cfg.n_osds, cfg=conf, transport="tcp",
                    admin_dir=admin_dir).start()
    cl = c.client()
    cl.create_pool("sat", kind="ec", pg_num=cfg.pg_num,
                   ec_profile={"plugin": "jerasure", "k": "2",
                               "m": "1", "backend": "numpy"})
    payload = b"\xa5" * cfg.obj_bytes
    if getattr(cfg, "frontend", "rados") == "rgw":
        # S3 front-end leg: seed bucket + objects THROUGH the gateway
        # so the workers' GETs find gateway-laid-out objects
        from ..services.rgw import RgwGateway
        gw = RgwGateway(cl, "sat", listen=False)  # store path only
        gw.create_bucket("sat")
        for i in range(cfg.objects):
            gw.put_object("sat", f"o{i:04d}", payload)
    else:
        for i in range(cfg.objects):
            cl.write_full("sat", f"o{i:04d}", payload)
    return c


def _pcts(hist) -> dict:
    p50 = hist.quantile(0.50)
    p99 = hist.quantile(0.99)
    return {"p50_ms": round(p50 / 1e3, 3) if p50 is not None else None,
            "p99_ms": round(p99 / 1e3, 3) if p99 is not None else None,
            "ops": hist.count}


def _leg_row(leg_res, duration: float) -> dict:
    wall = leg_res.wall_s or duration
    return {"offered_per_s": round(leg_res.offered / wall, 1),
            "achieved_per_s": round(leg_res.achieved / wall, 1),
            "errors": leg_res.errors,
            **{k: _pcts(h) for k, h in sorted(leg_res.hists.items())}}


def _cluster_counters(c) -> dict:
    """The counter snapshot the per-point deltas come from."""
    out = {"msg_dispatched": 0, "recovery_served": 0,
           "client_served": 0, "dropped": {}}
    # list(): the thrash thread kills/revives OSDs while samplers read
    for osd in list(c.osds.values()):
        out["msg_dispatched"] += osd.messenger.perf.get("msg_dispatched")
        out["recovery_served"] += osd.scheduler.served.get("recovery", 0)
        out["client_served"] += osd.scheduler.served.get("client", 0)
        for k, v in osd.scheduler.dropped.items():
            out["dropped"][k] = out["dropped"].get(k, 0) + v
    return out


def _slow_ops_trips(c) -> int:
    """SLOW_OPS raise transitions from the mon's merged cluster log,
    fetched over the SHARED admin-socket resolver (the operator path a
    real deployment scrapes, not a private attribute)."""
    try:
        log = c.admin("mon.0", "dump_cluster_log", channel="health")
    except (OSError, RuntimeError):
        return 0
    return sum(1 for ev in log.get("events", [])
               if (ev.get("fields") or {}).get("check") == "SLOW_OPS"
               and (ev.get("fields") or {}).get("status")
               == "HEALTH_WARN")


def run_point(cfg: ScenarioConfig) -> dict:
    """One saturation point: build the cluster, drive the legs, thrash
    mid-traffic, score invariants.  Returns the per-point row."""
    with tempfile.TemporaryDirectory(prefix="sat-asok-") as admin_dir:
        c = _build_cluster(cfg, admin_dir)
        try:
            return _run_point_on(c, cfg)
        finally:
            c.stop()


def _run_point_on(c, cfg: ScenarioConfig) -> dict:
    gen = LoadGenerator(
        c.network.addr_of("mon.0"), "sat", cfg.objects, cfg.legs(),
        procs=cfg.procs, seed=cfg.seed, client_timeout=3.0,
        frontend=getattr(cfg, "frontend", "rados"))
    base = _cluster_counters(c)
    gen.launch()
    times = gen.leg_times()

    depth_samples: list[int] = []
    stop_sampling = threading.Event()
    # progress must be sampled WHILE the storm runs: completed items
    # linger only mgr_progress_linger seconds, so a post-hoc poll after
    # the workers drain would find an empty tracker and call a finished
    # recovery "never happened"
    mon_state = {"seen": {},          # item id -> max percent
                 "eta_max": 0.0,
                 "drain_t": None,     # first instant the storm drained
                 "served_at": (0, 0.0),
                 "kill_t": None,      # set by the thrash thread
                 "kill_served": 0,
                 "window_served": None}

    def rec_busy() -> bool:
        # the storm is live while ANY stage still holds work: the
        # primaries' reservation/initiation queues, recovery-class
        # items queued in ANY mclock shard (the stage the sweep's
        # limit knob actually paces — progress items complete at the
        # primary while pushes still sit here), or in-flight ops
        for o in list(c.osds.values()):
            if o._recovery_inflight > 0 or len(o._recovery_q) > 0:
                return True
            if o.scheduler.queue_depth("recovery") > 0:
                return True
        return False

    def monitor() -> None:
        while not stop_sampling.is_set():
            depth_samples.append(sum(o.scheduler.queue_depth()
                                     for o in list(c.osds.values())))
            items = c.mon.progress.items()
            for it in items:
                iid = it.get("id", "?")
                mon_state["seen"][iid] = max(
                    mon_state["seen"].get(iid, 0.0),
                    float(it.get("percent") or 0.0))
                if it.get("eta_seconds"):
                    mon_state["eta_max"] = max(
                        mon_state["eta_max"],
                        float(it["eta_seconds"]))
            served = sum(o.scheduler.served.get("recovery", 0)
                         for o in list(c.osds.values()))
            if served != mon_state["served_at"][0]:
                mon_state["served_at"] = (served, time.time())
            if mon_state["kill_t"] is not None \
                    and mon_state["window_served"] is None \
                    and time.time() >= mon_state["kill_t"] \
                    + cfg.qos_window_s:
                mon_state["window_served"] = served
            quiesced = time.time() - mon_state["served_at"][1] > 0.3
            if mon_state["seen"] and not c.mon.progress.active() \
                    and not rec_busy() and quiesced:
                if mon_state["drain_t"] is None:
                    mon_state["drain_t"] = mon_state["served_at"][1]
            else:
                mon_state["drain_t"] = None  # a fresh wave re-opened
            stop_sampling.wait(0.05)

    sampler = threading.Thread(target=monitor, daemon=True)
    sampler.start()

    scrub_info = {"forced": False, "cycles": 0, "verified_bytes": 0}
    if getattr(cfg, "scrub", True):
        # scrub-while-loaded: an operator deep-scrubs the pool at the
        # head of the steady leg (the verb every pass starts by) —
        # chunks queue under the scrub mclock class while client load
        # saturates, and the point's client invariants must hold
        # regardless
        s_start, _s_end = times["steady"]
        if (d := s_start + 0.2 - time.time()) > 0:
            time.sleep(d)

        def operator() -> None:
            try:
                c.client().scrub_pool("sat", deep=True)
            except Exception as e:  # noqa: BLE001 - the row says so
                scrub_info["error"] = repr(e)

        scrubber = threading.Thread(target=operator, daemon=True,
                                    name="load-scrub-operator")
        scrubber.start()
        scrub_info["forced"] = True

    thrash_info = {"killed": False, "revived": False,
                   "kill_t": None, "victim": None}
    pre_thrash = None
    if cfg.thrash:
        t_start, _t_end = times["thrash"]
        kill_at = t_start + cfg.kill_after_s
        if (d := kill_at - time.time()) > 0:
            time.sleep(d)
        victim = max(c.osds)  # deterministic: the highest-id OSD
        pre_thrash = _cluster_counters(c)
        # the kill destroys the victim's messenger registry and its
        # scheduler's served dicts (revive starts both at zero), so
        # post-thrash sums would silently lose its pre-kill counts —
        # snapshot them now and fold them back into every later delta
        thrash_info["lost"] = {
            "msg_dispatched":
                c.osds[victim].messenger.perf.get("msg_dispatched"),
            "recovery_served":
                c.osds[victim].scheduler.served.get("recovery", 0),
        }
        c.kill_osd(victim)
        thrash_info.update(killed=True, kill_t=time.time(),
                           victim=victim)
        mon_state["kill_served"] = pre_thrash["recovery_served"] \
            - thrash_info["lost"]["recovery_served"]
        mon_state["kill_t"] = thrash_info["kill_t"]
        time.sleep(0.3)
        c.revive_osd(victim)  # FRESH store: every shard rebuilds
        thrash_info["revived"] = True

    merged = gen.collect(grace=60.0)

    # recovery score: the mgr progress stack must see the storm reach
    # 100% and CLEAR (the PR-4 acceptance face, now under client load)
    recovery = {"completed": not cfg.thrash, "eta_s": None,
                "wall_s": None, "served_per_s": None}
    if cfg.thrash and thrash_info["killed"]:
        deadline = thrash_info["kill_t"] + cfg.recovery_deadline_s
        while time.time() < deadline:
            if mon_state["drain_t"] is not None \
                    and time.time() - mon_state["drain_t"] > 0.5:
                break  # drained and STAYED drained (no fresh wave)
            time.sleep(0.05)
        drained_at = mon_state["drain_t"]
        seen = dict(mon_state["seen"])
        recovery["completed"] = bool(seen) and drained_at is not None
        recovery["items"] = len(seen)
        recovery["wall_s"] = round(
            (drained_at or time.time()) - thrash_info["kill_t"], 2)
        recovery["eta_s"] = round(mon_state["eta_max"], 2) \
            if mon_state["eta_max"] else None
        after = _cluster_counters(c)
        rec_ops = after["recovery_served"] \
            - (pre_thrash["recovery_served"]
               - thrash_info["lost"]["recovery_served"])
        recovery["served_ops"] = rec_ops
        recovery["served_per_s"] = round(
            rec_ops / max(1e-3, (drained_at or time.time())
                          - thrash_info["kill_t"]), 1)
        win = mon_state["window_served"]
        recovery["window_s"] = cfg.qos_window_s
        recovery["window_ops"] = (win - mon_state["kill_served"]
                                  if win is not None else rec_ops)
        recovery["window_rate_per_s"] = round(
            recovery["window_ops"] / cfg.qos_window_s, 1)

    # queue drain: depths must return to zero once load + storm stop
    drained = False
    drain_deadline = time.time() + 10.0
    while time.time() < drain_deadline:
        if sum(o.scheduler.queue_depth()
               for o in list(c.osds.values())) == 0:
            drained = True
            break
        time.sleep(0.1)
    stop_sampling.set()
    sampler.join(timeout=2.0)

    after = _cluster_counters(c)
    legs = merged["legs"]
    achieved_total = sum(r.achieved for r in legs.values())
    lost_msgs = (thrash_info.get("lost") or {}).get("msg_dispatched", 0)
    msgs_per_op = round(
        (after["msg_dispatched"] + lost_msgs - base["msg_dispatched"])
        / max(1, achieved_total), 2)
    dropped = {k: after["dropped"].get(k, 0) - base["dropped"].get(k, 0)
               for k in after["dropped"]}

    ramp = {"rates_per_s": list(cfg.ramp_rates), "achieved_ratio": []}
    for i, r in enumerate(cfg.ramp_rates):
        leg = legs[f"ramp{i}"]
        ramp["achieved_ratio"].append(
            round(leg.achieved / max(1, leg.offered), 3))
    knee = None
    for r, ratio in zip(cfg.ramp_rates, ramp["achieved_ratio"]):
        if ratio >= KNEE_RATIO:
            knee = r
    ramp["saturation_knee_per_s"] = knee

    # only CLOSED legs gate progress: an open-loop ramp step offered
    # far past the knee may legitimately achieve ~nothing inside its
    # bounded window — that is the saturation signal, not a deadlock
    closed_progressed = all(
        legs[l.name].achieved > 0 for l in cfg.legs()
        if l.mode == "closed")
    if scrub_info["forced"]:
        # the operator's passes must have ended (the drain loop above
        # already waited out the scrub-class queue); count them from
        # the OSDs still alive — the thrash victim restarts at zero
        scrubber.join(10.0)
        live = list(c.osds.values())
        scrub_info["cycles"] = sum(o.perf.get("scrubs") for o in live)
        scrub_info["verified_bytes"] = sum(
            o.perf.get("scrub_verified_bytes") for o in live)

    invariants = {
        "no_deadlock": merged["ok"] and closed_progressed,
        "queues_bounded": drained,
        "recovery_completes": recovery["completed"],
    }
    if scrub_info["forced"]:
        invariants["scrub_completes"] = scrub_info["cycles"] > 0
    row = {
        "id": cfg.point_id,
        "mclock": dict(cfg.mclock),
        "ramp": ramp,
        "steady": _leg_row(legs["steady"], cfg.steady_s),
        "max_queue_depth": max(depth_samples, default=0),
        "sched_dropped": dropped,
        "msgs_per_op": msgs_per_op,
        "slow_ops_trips": _slow_ops_trips(c),
        "recovery": recovery,
        "scrub": scrub_info,
        "invariants": invariants,
        "worker_errors": merged["worker_errors"],
    }
    if cfg.thrash:
        row["thrash"] = _leg_row(legs["thrash"], cfg.thrash_s)
    return row


def monotone_within(seq: list[float], slack: float) -> bool:
    """Non-decreasing up to a slack factor: for i<j,
    seq[j] * slack >= seq[i].  The recovery-rate ordering check —
    strict monotonicity is unfalsifiable on a 2-core box."""
    vals = [v for v in seq if v is not None]
    return all(vals[j] * slack >= vals[i]
               for i in range(len(vals)) for j in range(i + 1,
                                                        len(vals)))


def bounded_spread(seq: list[float], slack: float) -> bool:
    """max <= min * slack over the non-None values: the client-p99
    envelope.  Two-sided by construction — raising the recovery
    reservation must not WORSEN client p99 beyond the envelope, and a
    low-reservation point must not sit an order of magnitude above the
    high ones either (the starvation inversion)."""
    vals = [v for v in seq if v is not None]
    if not vals:
        return True
    return max(vals) <= min(vals) * slack


def default_sweep_points() -> list[dict]:
    """>= 3 recovery reservation/limit settings, ascending: the limit
    doubles the reservation so the low point is crisply shaped (well
    under the storm's natural drain rate) and the top point runs
    recovery unthrottled.  Limits apply PER scheduler shard — a 4-OSD,
    2-shard cluster's aggregate ceiling is 8x the per-shard number."""
    return [
        {"id": "rec_res4", "osd_mclock_recovery_res": 4.0,
         "osd_mclock_recovery_lim": 8.0},
        {"id": "rec_res16", "osd_mclock_recovery_res": 16.0,
         "osd_mclock_recovery_lim": 32.0},
        {"id": "rec_res128", "osd_mclock_recovery_res": 128.0,
         "osd_mclock_recovery_lim": 0.0},
    ]


def run_sweep(points: list[dict] | None = None,
              base: ScenarioConfig | None = None) -> dict:
    """The `bench.py --saturate` engine: one point per mclock setting,
    then the cross-point QoS ordering checks.  Returns the full JSON
    row; ``row["ok"]`` is the exit-code gate."""
    base = base or ScenarioConfig()
    points = points if points is not None else default_sweep_points()
    rows = []
    for i, pt in enumerate(points):
        cfg = ScenarioConfig(**{
            **{k: v for k, v in vars(base).items()},
            "point_id": pt.get("id", f"pt{i}"),
            "mclock": {k: v for k, v in pt.items() if k != "id"},
            "seed": base.seed + i,
        })
        row = run_point(cfg)
        if not all(row["invariants"].values()):
            # one fresh-cluster retry: a mid-write kill occasionally
            # lands the cluster in a slow reconcile churn (a
            # convergence pathology of the data plane, not of the QoS
            # setting under test) — a GATE must not false-alarm on it,
            # and two consecutive failures remain a real trip
            cfg.seed += 1000
            row = run_point(cfg)
            row["retried"] = True
        rows.append(row)

    # the gated recovery metric is the WINDOWED rate (served recovery
    # ops in the fixed post-kill window): shaped directly by the knob,
    # robust to recovery waves and to slow points catching up later
    rec_rates = [r["recovery"].get("window_rate_per_s") for r in rows]
    p99s = []
    for r in rows:
        leg = r.get("thrash") or r["steady"]
        cls = leg.get("write") or leg.get("read") or {}
        p99s.append(cls.get("p99_ms"))
    qos = {
        "recovery_window_rate_per_s": rec_rates,
        "client_p99_ms": p99s,
        "recovery_monotone": monotone_within(
            [v for v in rec_rates if v is not None], REC_SLACK),
        "p99_envelope_holds": bounded_spread(p99s, P99_SLACK),
        "tradeoff_direction_ok": True,
    }
    real_rates = [v for v in rec_rates if v is not None]
    if len(real_rates) >= 2 and base.thrash:
        # the sweep must actually MOVE recovery: the unthrottled top
        # point beats the tightly-limited bottom one
        qos["tradeoff_direction_ok"] = \
            real_rates[-1] >= real_rates[0] * 1.1
    qos["ordering_holds"] = (qos["recovery_monotone"]
                             and qos["p99_envelope_holds"]
                             and qos["tradeoff_direction_ok"])

    invariants_ok = all(all(r["invariants"].values()) for r in rows) \
        and (qos["ordering_holds"] if len(rows) >= 2 else True)
    return {"points": rows, "qos": qos, "ok": invariants_ok}


# ---------------------------------------------------------------------------
# Multi-tenant QoS suite (the --saturate --tenants engine)
# ---------------------------------------------------------------------------

#: the named tenant population the suite commits via `osd qos
#: set-profile` (qos/profiles.py grammar): one reserved tenant whose
#: p99 envelope must survive a flood, two weight-only tenants whose
#: 2:1 split is gated, and the best-effort flooder
TENANT_PROFILES = {
    "gold":   {"res": 60.0, "wgt": 8.0, "lim": 0.0},
    "silver": {"res": 0.0,  "wgt": 4.0, "lim": 0.0},
    "bronze": {"res": 0.0,  "wgt": 1.0, "lim": 0.0},
    "bulk":   {"res": 0.0,  "wgt": 1.0, "lim": 0.0},
}


@dataclass
class TenantScenarioConfig:
    """One multi-tenant point: four aligned per-tenant load streams
    (solo -> flood -> weights -> thrash legs) against one cluster."""

    n_osds: int = 4
    objects: int = 32
    obj_bytes: int = 8192
    pg_num: int = 8
    solo_s: float = 2.0        # gold alone: the p99 envelope baseline
    flood_s: float = 3.0       # bulk floods; gold must hold its envelope
    settle_s: float = 1.2      # flood backlog drains before the split
    weights_s: float = 3.0     # silver vs bronze saturate: 2:1 split
    thrash_s: float = 5.0      # kill/revive storm; controller retunes
    kill_after_s: float = 1.0
    solo_rate: float = 32.0    # frontline offered in the baseline leg
    flood_rate: float = 128.0  # frontline offered in the flood leg
    thrash_rate: float = 40.0  # frontline offered through the storm
    recovery_deadline_s: float = 40.0
    seed: int = 0
    controller: bool = True    # qos_controller=on for the thrash leg
    #: isolation gates (generous: 2-core CI-box variance).  The
    #: envelope is judged on the SERVER-side per-tenant queue-wait p99
    #: (mclock_qwait_us_tenant_gold via mon metrics_query windows with
    #: absolute edges — the quantity the scheduler owns): flood-window
    #: p99 within slack x the solo-window baseline, OR under an
    #: absolute floor (a microsecond-fast solo baseline must not make
    #: any flood p99 a failure).  Client-observed p99s are REPORTED
    #: alongside but not gated — on a 2-core box they fold in worker-
    #: process CPU starvation and rpc-timeout retry spirals the QoS
    #: layer cannot control.  A throughput floor keeps the claim
    #: end-to-end honest: a flooded gold must still achieve a real
    #: fraction of its solo rate.
    envelope_slack: float = 6.0
    envelope_floor_ms: float = 80.0
    #: goodput floor: gold's achieved/offered ratio under flood must
    #: hold this fraction of its baseline-leg ratio (both tenants
    #: share one worker process, so CPU starvation cancels out of the
    #: comparison), plus an absolute achieved-ops/s anti-starvation
    #: floor
    throughput_floor_frac: float = 0.4
    throughput_floor_abs: float = 3.0
    #: per-tenant offered rate for the weights leg — deliberately
    #: WELL past the box's knee: the proportional split only binds
    #: while both tenants hold queued backlog (an under-the-knee rate
    #: serves everyone their arrival and the ratio reads 1.0)
    weights_rate: float = 160.0
    weights_width: int = 14          # per-tenant executor width
    #: the weight gate: under identical offered overload, the
    #: heavier-weighted tenant's server-side queue-wait p50 must sit
    #: WELL below the lighter one's (the proportional share decides
    #: who queues; measured ratios run 10-30x at 4:1 weights), and
    #: the favored tenant's served count must never trail far behind
    weight_wait_min: float = 2.0
    weight_served_floor: float = 0.7  # silver >= this x bronze served

    def durations(self) -> dict[str, float]:
        return {"solo": self.solo_s, "flood": self.flood_s,
                "settle": self.settle_s, "weights": self.weights_s,
                "thrash": self.thrash_s}

    #: frontline stream client mix: 1 gold client per GOLD_EVERY
    #: clients, the rest bulk — open-loop arrivals round-robin the
    #: clients, so gold's offered share is 1/GOLD_EVERY of the
    #: stream's rate at EVERY leg intensity
    GOLD_EVERY = 4

    def stream_legs(self) -> dict[str, dict]:
        """stream -> {"tenants": [...], "legs": [...]} — aligned leg
        names + durations in every stream, one shared go instant.

        Two streams, each mixing its competing tenants inside ONE
        worker process: when the 2-core box starves a worker of CPU it
        starves BOTH competitors equally, so the per-tenant split
        stays a SCHEDULER measurement instead of an OS-scheduling one.

        - ``frontline``: gold (reserved) + bulk at 3:1 client mix.
          The solo leg offers a low rate (the envelope baseline); the
          flood leg multiplies the SAME mix's rate several-fold —
          gold's qwait must hold its envelope while bulk's offered
          load explodes around it.
        - ``weight``: silver vs bronze, idle until the weights leg,
          then open-loop well past the knee with a wide executor (the
          split only binds while BOTH tenants hold queued backlog;
          closed loops self-limit to in-flight counts the box's
          process scheduler would end up deciding).
        """
        d = self.durations()

        def leg(name, mode="open", rate=0.5, conc=2,
                profile="small_mixed"):
            return LegSpec(name=name, profile=profile,
                           duration_s=d[name], mode=mode, rate=rate,
                           concurrency=conc)

        ge = self.GOLD_EVERY
        return {
            "frontline": {
                "tenants": ["gold"] + ["bulk"] * (ge - 1),
                "legs": [
                    leg("solo", rate=self.solo_rate, conc=8),
                    leg("flood", rate=self.flood_rate, conc=16),
                    leg("settle", rate=2.0, conc=4),
                    leg("weights", rate=2.0, conc=4),
                    leg("thrash", rate=self.thrash_rate, conc=8),
                ]},
            "weight": {
                "tenants": ["silver", "bronze"],
                "legs": [
                    leg("solo"), leg("flood"), leg("settle"),
                    # stream totals: the 2-tenant round-robin halves
                    # them back to the per-tenant figures
                    leg("weights", rate=self.weights_rate * 2,
                        conc=self.weights_width * 2),
                    leg("thrash"),
                ]},
        }


def _tenant_cluster(cfg: TenantScenarioConfig, admin_dir: str):
    from ..tools.vstart import MiniCluster
    from ..utils.config import default_config
    conf = default_config()
    conf.apply_dict({
        "osd_heartbeat_interval": 0.05,
        "osd_heartbeat_grace": 0.5,
        "ec_backend": "native",
        "ms_dispatch_workers": 2,
        # ONE scheduler shard per OSD: the isolation invariants need
        # tenants COMPETING inside a queue — spreading a small box's
        # shallow in-flight window over N shards leaves most picks
        # uncontended and the measurement noise-bound
        "osd_op_num_shards": 1,
        "osd_op_complaint_time": 2.0,
        "osd_recovery_sleep": 0.0,
        "osd_recovery_max_active": 8,
        "osd_recovery_progress_interval": 0.0,
        "mgr_progress_linger": 1.0,
        # the controller senses through the metrics history: sample
        # fast enough that a seconds-long storm yields p99 windows
        "metrics_history_interval_s": 0.25,
        "qos_controller_window_s": 1.5,
        "qos_controller_hold_ticks": 1,
        "qos_controller_cooldown_ticks": 1,
        "qos_controller_step": 16.0,
        # start recovery at the hand-tuned sweep's LOW point: the
        # controller must climb out of it on its own
        "osd_mclock_recovery_res": 4.0,
        "osd_mclock_recovery_lim": 8.0,
        # cap aggregate client IOPS per OSD (the operator's fleet-
        # protection knob): the class limit — not the box's noisy CPU
        # capacity — becomes the pacing point, so the weights leg's
        # overload deterministically backs up in the tenant sub-queues
        # where the proportional split is decided
        "osd_mclock_client_lim": 60.0,
    })
    c = MiniCluster(n_osds=cfg.n_osds, cfg=conf, transport="tcp",
                    admin_dir=admin_dir).start()
    cl = c.client()
    cl.create_pool("sat", kind="ec", pg_num=cfg.pg_num,
                   ec_profile={"plugin": "jerasure", "k": "2",
                               "m": "1", "backend": "numpy"})
    for name, prof in TENANT_PROFILES.items():
        cl.mon_command({"prefix": "osd qos set-profile",
                        "name": name, **prof})
    payload = b"\xa5" * cfg.obj_bytes
    for i in range(cfg.objects):
        cl.write_full("sat", f"o{i:04d}", payload)
    # profiles ride the map: wait until every OSD's scheduler holds
    # the committed book before any tenant traffic arrives
    deadline = time.time() + 10.0
    while time.time() < deadline:
        if all("gold" in o.scheduler.shards[0]._tparams
               for o in c.osds.values()):
            break
        time.sleep(0.02)
    else:
        c.stop()  # no leaked cluster behind the raise
        raise TimeoutError("qos profiles never reached the OSDs")
    return c, conf


def _tenant_served(c) -> dict[str, int]:
    out: dict[str, int] = {}
    for o in list(c.osds.values()):
        for t, n in o.scheduler.tenant_served.items():
            out[t] = out.get(t, 0) + n
    return out


def run_tenant_point(cfg: TenantScenarioConfig | None = None) -> dict:
    """The --saturate --tenants engine: commit tenant profiles, run
    four aligned per-tenant load streams, thrash mid-run with the
    adaptive controller live, and gate the three isolation
    invariants."""
    cfg = cfg or TenantScenarioConfig()
    with tempfile.TemporaryDirectory(prefix="sat-tenant-") as admin_dir:
        c, conf = _tenant_cluster(cfg, admin_dir)
        mgr = None
        try:
            from ..mon.mgr import MgrDaemon
            mgr = MgrDaemon(c.mon, modules=("qos",), tick=0.25)
            qos_mod = mgr.module("qos")
            qos_mod.TICK_EVERY = 0.5

            def apply_retune(res, lim):
                conf.set("osd_mclock_recovery_res", res)
                conf.set("osd_mclock_recovery_lim", lim)
                for o in list(c.osds.values()):
                    try:
                        o.admin_command("reset_mclock")
                    except Exception:  # noqa: BLE001 - mid-kill races
                        pass

            qos_mod.bind(apply_retune,
                         res0=conf["osd_mclock_recovery_res"])
            if cfg.controller:
                conf.set("qos_controller", "on")
            mgr.start()
            return _run_tenant_point_on(c, conf, cfg, qos_mod)
        finally:
            if mgr is not None:
                mgr.stop()
            c.stop()


def _run_tenant_point_on(c, conf, cfg: TenantScenarioConfig,
                         qos_mod) -> dict:
    mon_addr = c.network.addr_of("mon.0")
    streams = {
        name: LoadGenerator(mon_addr, "sat", cfg.objects,
                            spec["legs"], procs=1, seed=cfg.seed + i,
                            client_timeout=2.5,
                            tenants=spec["tenants"])
        for i, (name, spec) in enumerate(cfg.stream_legs().items())
    }
    # spawn ALL streams first, then go() them onto one shared instant:
    # the per-leg phases (solo/flood/weights/thrash) line up across
    # tenants by construction
    spawn_errors = []

    def spawn_one(gen):
        try:
            gen.spawn()
        except Exception as e:  # noqa: BLE001
            spawn_errors.append(repr(e))

    spawners = [threading.Thread(target=spawn_one, args=(g,),
                                 daemon=True)
                for g in streams.values()]
    for t in spawners:
        t.start()
    for t in spawners:
        t.join(timeout=90.0)
    if spawn_errors:
        for g in streams.values():
            g.abort()
        raise RuntimeError(f"tenant stream spawn failed: "
                           f"{spawn_errors}")
    start_at = time.time() + 0.5
    for g in streams.values():
        g.go(start_at)
    times = next(iter(streams.values())).leg_times()

    # weight-split window: the silver:bronze SERVED ratio inside the
    # weights leg, measured server-side (scheduler tenant counters —
    # what the weights actually shape), sampled just inside the edges
    w_start, w_end = times["weights"]
    weight_snap = {}

    def weight_sampler():
        if (d := w_start + 0.3 - time.time()) > 0:
            time.sleep(d)
        weight_snap["t0"] = _tenant_served(c)
        if (d := w_end - 0.1 - time.time()) > 0:
            time.sleep(d)
        weight_snap["t1"] = _tenant_served(c)

    wthread = threading.Thread(target=weight_sampler, daemon=True)
    wthread.start()

    # thrash: kill + fresh-store revive mid-leg; the controller climbs
    # the recovery reservation out of the hand-tuned low point
    t_start, _t_end = times["thrash"]
    kill_at = t_start + cfg.kill_after_s
    if (d := kill_at - time.time()) > 0:
        time.sleep(d)
    victim = max(c.osds)
    c.kill_osd(victim)
    kill_t = time.time()
    time.sleep(0.3)
    c.revive_osd(victim)

    merged: dict[str, dict] = {}
    results: dict[str, dict] = {}

    def collect_one(tenant, gen):
        try:
            results[tenant] = gen.collect(grace=45.0)
        except Exception as e:  # noqa: BLE001
            results[tenant] = {"legs": {}, "ok": False,
                               "worker_errors": [repr(e)]}

    collectors = [threading.Thread(target=collect_one, args=(t, g),
                                   daemon=True)
                  for t, g in streams.items()]
    for t in collectors:
        t.start()
    for t in collectors:
        t.join(timeout=120.0)
    ok_all = True
    errors: list[str] = []
    for tenant in streams:
        res = results.get(tenant) or {"legs": {}, "ok": False,
                                      "worker_errors": ["no result"]}
        merged[tenant] = res["legs"]
        ok_all = ok_all and res["ok"]
        errors.extend(f"{tenant}: {e}" for e in res["worker_errors"])

    # recovery drain (post-collect: the workers already stopped)
    def rec_busy() -> bool:
        for o in list(c.osds.values()):
            if o._recovery_inflight > 0 or len(o._recovery_q) > 0 \
                    or o.scheduler.queue_depth("recovery") > 0:
                return True
        return False

    recovered = False
    deadline = kill_t + cfg.recovery_deadline_s
    while time.time() < deadline:
        if not rec_busy() and not c.mon.progress.active():
            recovered = True
            break
        time.sleep(0.1)
    wthread.join(timeout=5.0)

    from .profiles import LegResult

    def leg_of(stream, name):
        return merged.get(stream, {}).get(name) or LegResult()

    def tenant_hists(stream, name, tenant):
        leg = leg_of(stream, name)
        return {k: h for k, h in leg.hists.items()
                if k.startswith(f"{tenant}:")}

    def tenant_count(stream, name, tenant):
        return sum(h.count
                   for h in tenant_hists(stream, name,
                                         tenant).values())

    def tenant_p99_us(stream, name, tenant):
        from .profiles import Pow2Histogram
        h = Pow2Histogram()
        for hh in tenant_hists(stream, name, tenant).values():
            h.merge(hh)
        return h.quantile(0.99)

    # ---- invariant 1: the reserved tenant's p99 envelope ----
    # server-side: a tenant's queue-wait quantile over a leg's
    # ABSOLUTE window, answered by the mon's merged metrics history
    # (the same per-tenant histograms the exporter scrapes), bucket
    # deltas aggregated across every OSD registry
    def qwait_quantile(tenant: str, t0: float, t1: float,
                       quant: float) -> float | None:
        from ..utils.metrics_history import pow2_quantile
        store = c.mon.metrics_history
        buckets: dict[int, int] = {}
        for reg in store.registries():
            if not reg.startswith("osd."):
                continue
            qq = store.query(reg,
                             f"mclock_qwait_us_tenant_{tenant}",
                             start_ts=t0, end_ts=t1)
            for b, n in (qq.get("buckets_delta") or {}).items():
                buckets[int(b)] = buckets.get(int(b), 0) + int(n)
        return pow2_quantile(buckets, quant) if buckets else None

    def qwait_p99(tenant: str, t0: float, t1: float) -> float | None:
        return qwait_quantile(tenant, t0, t1, 0.99)

    solo_t = times["solo"]
    flood_t = times["flood"]
    solo_p99 = qwait_p99("gold", *solo_t)
    flood_p99 = qwait_p99("gold", *flood_t)
    isolation_ratio = (round(flood_p99 / solo_p99, 2)
                       if solo_p99 and flood_p99 else None)
    # goodput: gold's achieved/offered ratio per leg — offered splits
    # by the frontline client mix (1/GOLD_EVERY of the stream), and
    # both tenants share ONE worker process, so a CPU-starved run
    # shrinks offered and achieved TOGETHER instead of faking a drop
    ge = cfg.GOLD_EVERY
    solo_leg = leg_of("frontline", "solo")
    flood_leg = leg_of("frontline", "flood")
    gold_solo_ach = tenant_count("frontline", "solo", "gold")
    gold_flood_ach = tenant_count("frontline", "flood", "gold")
    gold_solo_off = max(1.0, solo_leg.offered / ge)
    gold_flood_off = max(1.0, flood_leg.offered / ge)
    solo_goodput = gold_solo_ach / gold_solo_off
    flood_goodput = gold_flood_ach / gold_flood_off
    flood_rate_achieved = gold_flood_ach / max(1e-3,
                                               flood_leg.wall_s
                                               or cfg.flood_s)
    solo_rate = gold_solo_ach / max(1e-3, solo_leg.wall_s
                                    or cfg.solo_s)
    envelope_ok = (
        flood_p99 is not None and solo_p99 is not None
        and (flood_p99 <= solo_p99 * cfg.envelope_slack
             or flood_p99 <= cfg.envelope_floor_ms * 1e3)
        and gold_flood_ach >= cfg.throughput_floor_abs * cfg.flood_s
        and flood_goodput >= cfg.throughput_floor_frac
        * max(0.1, solo_goodput))

    # ---- invariant 2: proportional weight split ----
    # under identical offered overload from ONE worker process, the
    # weights decide WHO QUEUES: the heavier tenant's queue-wait p50
    # stays far below the lighter one's, and its served count never
    # trails far behind (served-count ratios stay arrival-coupled on
    # a shared executor, so the wait ratio is the gated signal)
    t0, t1 = weight_snap.get("t0", {}), weight_snap.get("t1", {})
    silver_ops = t1.get("silver", 0) - t0.get("silver", 0)
    bronze_ops = t1.get("bronze", 0) - t0.get("bronze", 0)
    split_ratio = (round(silver_ops / bronze_ops, 2)
                   if bronze_ops > 0 else None)
    weights_t = times["weights"]
    silver_wait = qwait_quantile("silver", *weights_t, 0.50)
    bronze_wait = qwait_quantile("bronze", *weights_t, 0.50)
    wait_ratio = (round(bronze_wait / silver_wait, 2)
                  if silver_wait and bronze_wait else None)
    split_ok = (wait_ratio is not None
                and wait_ratio >= cfg.weight_wait_min
                and silver_ops >= cfg.weight_served_floor
                * max(1, bronze_ops))

    # ---- invariant 3: the controller converged between the sweep points
    status = qos_mod.command("status")
    ctl = status.get("controller") or {}
    res_min = conf["qos_recovery_res_min"]
    res_max = conf["qos_recovery_res_max"]
    retunes = int(ctl.get("retunes", 0))
    final_res = float(ctl.get("res", 0.0))
    controller_ok = (not cfg.controller) or (
        retunes >= 1 and res_min < final_res <= res_max)
    qos_events = len((c.mon.cluster_log.dump(channel="qos")
                      or {}).get("events", []))

    served = _tenant_served(c)
    invariants = {
        "no_deadlock": ok_all,
        "reserved_p99_envelope": envelope_ok,
        "weight_split_proportional": split_ok,
        "controller_converges": controller_ok,
        "recovery_completes": recovered,
    }

    def _tenant_row(stream, leg, tenant):
        p99 = tenant_p99_us(stream, leg, tenant)
        return {"achieved": tenant_count(stream, leg, tenant),
                "client_p99_ms": (round(p99 / 1e3, 3)
                                  if p99 is not None else None)}

    row = {
        "tenants": dict(TENANT_PROFILES),
        "frontline": {
            leg: _leg_row(leg_of("frontline", leg),
                          cfg.durations()[leg])
            for leg in ("solo", "flood", "thrash")},
        "gold": {leg: _tenant_row("frontline", leg, "gold")
                 for leg in ("solo", "flood", "thrash")},
        "bulk": {leg: _tenant_row("frontline", leg, "bulk")
                 for leg in ("solo", "flood")},
        "weights": {"silver": _tenant_row("weight", "weights",
                                          "silver"),
                    "bronze": _tenant_row("weight", "weights",
                                          "bronze")},
        "tenant_isolation_ratio": isolation_ratio,
        "gold_solo_qwait_p99_ms": (round(solo_p99 / 1e3, 3)
                                   if solo_p99 else None),
        "gold_flood_qwait_p99_ms": (round(flood_p99 / 1e3, 3)
                                    if flood_p99 else None),
        "gold_solo_goodput": round(solo_goodput, 3),
        "gold_flood_goodput": round(flood_goodput, 3),
        "gold_flood_achieved_per_s": round(flood_rate_achieved, 1),
        "gold_solo_achieved_per_s": round(solo_rate, 1),
        "weight_split_ratio": split_ratio,
        "weight_wait_ratio": wait_ratio,
        "weight_wait_p50_ms": {
            "silver": (round(silver_wait / 1e3, 3)
                       if silver_wait else None),
            "bronze": (round(bronze_wait / 1e3, 3)
                       if bronze_wait else None)},
        "weight_served": {"silver": silver_ops, "bronze": bronze_ops},
        "tenant_served_total": served,
        "controller_retunes": retunes,
        "controller_final_res": final_res,
        "controller_convergence_error":
            float(ctl.get("convergence_error", 0.0)),
        "controller_trajectory": [h.get("res")
                                  for h in ctl.get("history", [])],
        "qos_events": qos_events,
        "invariants": invariants,
        "worker_errors": errors,
        "ok": all(invariants.values()),
    }
    return row
