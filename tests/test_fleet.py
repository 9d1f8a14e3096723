"""Fleet orchestration (repro.fleet, DESIGN.md §13): heartbeat/lease
failure discovery, capability-aware scheduling, and the structured
metrics stream — unit level plus end to end through the edge simulator.

The lease edge cases pinned here:

  * silent stall → exactly one ``WorkerLeft(discovered=True)`` at the
    last heartbeat arrival + TTL, and a barrier fleet *unblocks*;
  * a healthy worker on a congested link whose heartbeat delivery
    overshoots the TTL flaps — the tracker models the false positive
    faithfully instead of forbidding it;
  * recover *before* expiry is invisible (no discovered events at all);
  * recover *after* expiry is a discovered rejoin with a state catch-up
    and the offline span excluded from the active-time accounting;
  * lease expiry inside an Alg. 1 probe window restarts the
    SearchSession (the silent stall alone, with no lease layer, does
    not — that contrast is the regression);
  * a scripted leave racing a missed lease dedupes to ONE WorkerLeft in
    either order (discovery first, or administrative notice first).
"""

import importlib.util
import json
import math
import pathlib

import numpy as np
import pytest

from repro.cluster import ChurnSchedule, churn, make_policy
from repro.control.theory import WorkerProfile
from repro.edgesim import SimConfig, Simulator
from repro.edgesim.profiles import fleet_profiles, ratio_profiles
from repro.edgesim.tasks import svm_task
from repro.fleet import (
    AssignRecord,
    CapabilityRecord,
    ChurnRecord,
    CommitRecord,
    DriftRecord,
    EvalRecord,
    FleetConfig,
    JsonlSink,
    LeaseConfig,
    LeaseRecord,
    LeaseTracker,
    MetricsLog,
    PullRecord,
    SearchRecord,
    ServeRecord,
    SpanRecord,
    from_dict,
    get_scheduler,
    load_jsonl,
    record_kinds,
    scheduler_names,
    to_dict,
)

# ttl=6, period=2 with a zero-delay link: a worker stalling at t has its
# last heartbeat arrive at floor(t/2)*2 and its lease expire ttl later.
LEASE = LeaseConfig(ttl=6.0, heartbeat_period=2.0)


def _fleet_sim(actions, *, policy=None, fleet=None, metrics=None,
               n_shards=1, profiles=None):
    profiles = profiles or ratio_profiles((1.0, 1.0, 1.0), base_v=1.0, o=0.2)
    cfg = SimConfig(gamma=20.0, epoch_seconds=200.0, base_batch=32,
                    max_seconds=4000.0, local_lr=0.05)
    return Simulator(svm_task(len(profiles)), profiles,
                     policy or make_policy("bsp"), cfg,
                     churn=ChurnSchedule(actions) if actions else None,
                     n_shards=n_shards, fleet=fleet, metrics=metrics)


# ---------------------------------------------------------------------------
# Lease life cycle through the simulator
# ---------------------------------------------------------------------------


def test_lease_expiry_discovers_silent_stall():
    """A silent stall produces no WorkerLeft by itself; the lease layer
    synthesizes exactly one discovered departure at last-heartbeat + TTL,
    and the BSP barrier (blocked on the dead worker) releases."""
    log = MetricsLog()
    sim = _fleet_sim([churn.stall(10.0, worker=1)],
                     fleet=FleetConfig(lease=LEASE), metrics=log)
    sim.run(40.0)
    granted = [r for r in log.of("lease") if r.event == "granted"]
    assert sorted(r.worker for r in granted) == [0, 1, 2]
    stalled = [r for r in log.of("lease") if r.event == "stalled"]
    assert [(r.worker, r.t) for r in stalled] == [(1, 10.0)]
    expired = [r for r in log.of("lease") if r.event == "expired"]
    # last heartbeat sent at the stall instant t=10 still delivers
    assert [(r.worker, r.t) for r in expired] == [(1, 16.0)]
    disc = [r for r in log.of("churn") if r.discovered]
    assert [(r.event, r.worker, r.t) for r in disc] == [("leave", 1, 16.0)]
    assert sim.num_workers == 2
    # the survivors kept training past the barrier the dead worker held
    assert all(w.steps > 0 for w in sim.workers)


def test_congested_link_flaps_like_a_death():
    """False positive: a perfectly healthy worker whose link delay pushes
    every heartbeat past the TTL is indistinguishable from a death — the
    lease layer evicts it (the documented TTL-misconfiguration mode)."""
    profiles = [WorkerProfile(v=1.0, o=0.2), WorkerProfile(v=1.0, o=0.2),
                WorkerProfile(v=1.0, o=0.2, latency=7.0)]  # delay 7 > ttl 6
    log = MetricsLog()
    sim = _fleet_sim([], profiles=profiles,
                     fleet=FleetConfig(lease=LEASE), metrics=log)
    sim.run(20.0)
    disc = [r for r in log.of("churn") if r.discovered and r.event == "leave"]
    assert [r.worker for r in disc] == [2]
    # its first renewal could never land inside the grant TTL
    expired = [r for r in log.of("lease") if r.event == "expired"]
    assert [(r.worker, r.t) for r in expired] == [(2, 6.0)]


def test_heartbeat_delayed_just_past_ttl_false_positive_tracker_level():
    cfg = LeaseConfig(ttl=5.0, heartbeat_period=2.0)
    tr = LeaseTracker()
    tr.grant(0, 0.0, cfg, delay=0.5)  # renewals at 2.5, 4.5, ... < ttl
    assert tr.next_expiry() == math.inf
    tr.grant(1, 0.0, cfg, delay=3.5)  # first renewal at 5.5 > ttl=5
    assert tr.next_expiry() == pytest.approx(5.0)
    assert tr.pop_expired(5.0) == [1]
    assert 0 in tr and 1 not in tr
    assert tr.next_expiry() == math.inf


def test_recover_before_expiry_is_invisible():
    """A stall that resumes inside the TTL never surfaces: no expiry, no
    rejoin, no discovered churn — the control plane simply never knew."""
    log = MetricsLog()
    sim = _fleet_sim([churn.stall(10.0, worker=1),
                      churn.recover(12.0, worker=1)],
                     fleet=FleetConfig(lease=LEASE), metrics=log)
    sim.run(30.0)
    assert not [r for r in log.of("lease") if r.event in ("expired", "rejoined")]
    assert not [r for r in log.of("churn") if r.discovered]
    assert sim.num_workers == 3
    assert sim._dead_time == 0.0
    w = sim.worker_by_id(1)
    assert w.status != "stalled" and w.steps > 0


def test_rejoin_after_expiry_catches_up():
    """Recovery after the lease expired is a discovered rejoin: a
    WorkerJoined(discovered=True), a state catch-up over the partial
    shard-pull path, and the offline span excluded from active time."""
    log = MetricsLog()
    sim = _fleet_sim([churn.stall(10.0, worker=1),
                      churn.recover(30.0, worker=1)],
                     fleet=FleetConfig(lease=LEASE), metrics=log, n_shards=4)
    sim.run(60.0)
    assert [(r.worker, r.t) for r in log.of("lease")
            if r.event == "expired"] == [(1, 16.0)]
    assert [(r.worker, r.t) for r in log.of("lease")
            if r.event == "rejoined"] == [(1, 30.0)]
    disc = [r for r in log.of("churn") if r.discovered]
    assert [(r.event, r.worker) for r in disc] == [("leave", 1), ("join", 1)]
    assert sim.num_workers == 3
    # dead from discovery (16) to rejoin (30): not counted as active
    assert sim._dead_time == pytest.approx(14.0)
    w = sim.worker_by_id(1)
    assert w.status != "catching_up" and w.steps > 0


def test_lease_expiry_mid_probe_restarts_search():
    """A lease expiry inside an Alg. 1 probe window is fleet churn: the
    window is discarded and the climb restarts — but ONLY because the
    lease layer turned the silent stall into a WorkerLeft. The same stall
    without a fleet monitor is invisible and nothing restarts."""
    def run(fleet):
        policy = make_policy("adsp", gamma=20.0, search=True,
                             probe_seconds=30.0, max_probes=4)
        profiles = ratio_profiles((1, 1, 3), base_v=1.0, o=0.2)
        cfg = SimConfig(gamma=20.0, epoch_seconds=200.0, base_batch=32,
                        max_seconds=4000.0, local_lr=0.05)
        sim = Simulator(svm_task(3), profiles, policy, cfg,
                        churn=ChurnSchedule([churn.stall(10.0, worker=2)]),
                        fleet=fleet)
        sim.engine.epoch_end()  # expiry at t=16 lands in the first window
        return sim, policy

    sim, policy = run(FleetConfig(lease=LEASE))
    assert len(policy.traces) == 1
    tr = policy.traces[0]
    assert tr.restarts >= 1
    assert tr.chosen in tr.candidates
    assert all(np.isfinite(r) for r in tr.rewards)
    assert sim.num_workers == 2
    assert policy.c_target == tr.chosen
    sim.run(50.0)
    assert all(w.steps > 0 for w in sim.workers)

    _, blind = run(None)  # no lease layer: the stall stays silent
    assert blind.traces[0].restarts == 0


def test_discovered_failure_triggers_drift_search():
    """on_worker_lost feeds the drift detector *bypassing* the TV
    threshold: with a threshold no ordinary churn could reach (0.9), the
    discovery alone re-searches, at the discovery instant."""
    policy = make_policy("adsp", gamma=20.0, search=True, search_mode="drift",
                         drift_threshold=0.9, drift_cooldown=1.0,
                         probe_seconds=10.0, max_probes=3)
    cfg = SimConfig(gamma=20.0, epoch_seconds=1e9, base_batch=32,
                    max_seconds=4000.0, local_lr=0.05)
    profiles = ratio_profiles((1.0, 1.0, 1.0), base_v=1.0, o=0.2)
    sim = Simulator(svm_task(3), profiles, policy, cfg,
                    churn=ChurnSchedule([churn.stall(10.0, worker=1)]),
                    fleet=FleetConfig(lease=LEASE))
    sim.run(100.0)
    assert len(policy.traces) >= 1
    assert policy.traces[0].t_start == pytest.approx(16.0)


# ---------------------------------------------------------------------------
# Scripted-vs-discovered departure dedupe (regression)
# ---------------------------------------------------------------------------


def test_scripted_leave_racing_missed_lease_dedupes():
    """Discovery first (t=16), administrative notice second (t=20): the
    scripted leave must consume the parked discovery, not raise on the
    already-removed worker — exactly one WorkerLeft total. Without the
    ``_lease_gone`` guard in ``_apply_churn`` this run dies with a
    KeyError at t=20."""
    log = MetricsLog()
    sim = _fleet_sim([churn.stall(10.0, worker=1),
                      churn.leave(20.0, worker=1)],
                     fleet=FleetConfig(lease=LEASE), metrics=log)
    sim.run(40.0)
    leaves = [r for r in log.of("churn") if r.event == "leave"]
    assert len(leaves) == 1 and leaves[0].worker == 1 and leaves[0].discovered
    assert 1 not in sim._lease_gone  # parking consumed: no ghost rejoin
    assert sim.num_workers == 2


def test_scripted_leave_before_expiry_cancels_discovery():
    """Notice first (t=12), lease deadline later (t=16): forgetting the
    lease must guarantee the expiry never also fires — one WorkerLeft,
    and it is the administrative (non-discovered) one."""
    log = MetricsLog()
    sim = _fleet_sim([churn.stall(10.0, worker=1),
                      churn.leave(12.0, worker=1)],
                     fleet=FleetConfig(lease=LEASE), metrics=log)
    sim.run(40.0)
    leaves = [r for r in log.of("churn") if r.event == "leave"]
    assert len(leaves) == 1 and not leaves[0].discovered
    assert not [r for r in log.of("lease") if r.event == "expired"]


# ---------------------------------------------------------------------------
# Device scheduler
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("name", scheduler_names())
def test_scheduler_fractions_normalized(name):
    table = {0: 1.0, 1: 4.0, 2: 0.5, 7: 2.0}
    asg = get_scheduler(name).assign(table)
    assert set(asg.fractions) == set(table)
    assert sum(asg.fractions.values()) == pytest.approx(1.0)
    assert sum(asg.data_shares.values()) == pytest.approx(1.0)
    assert all(f > 0 for f in asg.fractions.values())


@pytest.mark.parametrize("name", scheduler_names())
def test_scheduler_degenerate_capability_table_falls_back_uniform(name):
    asg = get_scheduler(name).assign({0: 0.0, 1: 0.0})
    assert asg.fractions == pytest.approx({0: 0.5, 1: 0.5})


def test_proportional_floor_guarantee():
    sched = get_scheduler("proportional", floor=0.25)
    asg = sched.assign({0: 100.0, 1: 1.0, 2: 1.0})
    assert all(f >= 0.25 / 3 - 1e-12 for f in asg.fractions.values())
    assert asg.fractions[0] > 0.7  # the fast device still dominates


def test_sqrt_sits_between_uniform_and_proportional():
    table = {0: 1.0, 1: 4.0}
    prop = get_scheduler("proportional", floor=0.0).assign(table).fractions
    sq = get_scheduler("sqrt").assign(table).fractions
    assert 0.5 < sq[1] < prop[1]  # flattens toward uniform, keeps order


def test_unknown_scheduler_names_the_known_ones():
    with pytest.raises(KeyError, match="proportional"):
        get_scheduler("nope")


def test_capability_report_lags_to_next_heartbeat():
    """set_speed changes ground truth at t=3, but the scheduler only sees
    it when the next heartbeat (sent at t=4, period 2) arrives — until
    then assignments run on the stale report."""
    log = MetricsLog()
    sim = _fleet_sim([churn.speed(3.0, worker=0, v=5.0)],
                     fleet=FleetConfig(lease=LEASE, scheduler="proportional"),
                     metrics=log)
    sim.run(10.0)
    caps = [r for r in log.of("capability") if r.worker == 0 and r.v == 5.0]
    assert caps and caps[0].t == pytest.approx(4.0)
    asg0 = [r for r in log.of("assign") if r.worker == 0 and r.t == 0.0]
    assert asg0 and asg0[0].fraction == pytest.approx(1 / 3)  # equal fleet
    asg4 = [r for r in log.of("assign")
            if r.worker == 0 and r.t == pytest.approx(4.0)]
    assert asg4 and asg4[0].fraction > 0.5  # v=5 vs 1,1 after the report


def test_scheduled_run_trains_end_to_end():
    log = MetricsLog()
    profiles = fleet_profiles(4, spread=4.0, seed=1, o=0.2)
    sim = _fleet_sim([], profiles=profiles, metrics=log,
                     fleet=FleetConfig(lease=LEASE, scheduler="sqrt"))
    sim.run(30.0)
    assert all(w.steps > 0 for w in sim.workers)
    assert len(log.of("assign")) >= len(profiles)  # at least the join pass
    assert len(log.of("commit")) > 0 and len(log.of("eval")) > 0


# ---------------------------------------------------------------------------
# Lease tracker scale behaviour (the no-per-period-timers contract)
# ---------------------------------------------------------------------------


def test_lease_tracker_batch_expiry_at_scale():
    cfg = LeaseConfig(ttl=30.0, heartbeat_period=10.0)
    tr = LeaseTracker()
    for wid in range(2000):
        tr.grant(wid, 0.0, cfg, delay=0.0)
    # a healthy fleet schedules ZERO pending expiries, whatever its size
    assert tr.next_expiry() == math.inf
    for wid in range(100):
        tr.stall(wid, 100.0)
    for wid in range(0, 100, 2):
        assert tr.recover(wid, 105.0)  # resumed inside the TTL
    deadline = tr.next_expiry()
    assert math.isfinite(deadline)
    gone = tr.pop_expired(deadline + cfg.ttl)  # one batch drain
    assert sorted(gone) == list(range(1, 100, 2))
    assert tr.next_expiry() == math.inf
    assert len(tr) == 2000 - 50


def test_lease_tracker_recover_at_deadline_still_expires():
    """Recovering exactly AT the deadline loses the race: the expiry
    stands and the caller must take the rejoin path (returns False)."""
    cfg = LeaseConfig(ttl=6.0, heartbeat_period=2.0)
    tr = LeaseTracker()
    tr.grant(0, 0.0, cfg, delay=0.0)
    tr.stall(0, 10.0)
    assert not tr.recover(0, 16.0)  # tie goes to the expiry
    assert tr.pop_expired(16.0) == [0]


# ---------------------------------------------------------------------------
# Metrics registry + sinks
# ---------------------------------------------------------------------------

SAMPLE_RECORDS = [
    CommitRecord(t=1.5, worker=3, latency=0.7, push_bytes=1e6,
                 pull_bytes=2e6, stale_shards=2, n_shards=8),
    EvalRecord(t=2.0, loss=0.123),
    SearchRecord(t=3.0, chosen=4, windows=5, restarts=1, aborted=False),
    DriftRecord(t=4.0, cause="worker_left"),
    LeaseRecord(t=5.0, worker=1, event="expired"),
    ChurnRecord(t=6.0, worker=1, event="leave", discovered=True),
    CapabilityRecord(t=7.0, worker=2, v=3.5),
    AssignRecord(t=8.0, worker=2, fraction=0.4, data_share=0.4),
    ServeRecord(t=9.0, req=5, queue=0.01, prefill=0.004, decode=0.05,
                total=0.064, tokens=9, slo=0.8, slo_ok=True, version=3),
    PullRecord(t=10.0, stale_shards=2, n_shards=4, nbytes=2048.0),
    SpanRecord(t=10.0, name="serve.step", start_ns=5_000, end_ns=9_000, id=7,
               parent=3, key=12, counts={"action": "decode", "slots": 4}),
]


def test_sample_records_cover_every_registered_kind():
    assert {r.kind for r in SAMPLE_RECORDS} == set(record_kinds())


@pytest.mark.parametrize("rec", SAMPLE_RECORDS, ids=lambda r: r.kind)
def test_record_roundtrips_through_json(rec):
    assert from_dict(json.loads(json.dumps(to_dict(rec)))) == rec


def test_from_dict_unknown_kind_names_known_kinds():
    with pytest.raises(KeyError, match="lease"):
        from_dict({"kind": "bogus", "t": 0.0})


def test_metrics_log_roundtrips_through_jsonl(tmp_path):
    log = MetricsLog.from_records(SAMPLE_RECORDS)
    assert len(log) == len(SAMPLE_RECORDS)
    assert log.of("lease") == [SAMPLE_RECORDS[4]]
    path = tmp_path / "stream.jsonl"
    log.to_jsonl(path)
    assert load_jsonl(path) == SAMPLE_RECORDS


def test_jsonl_sink_streams_as_emitted(tmp_path):
    path = tmp_path / "live.jsonl"
    with JsonlSink(path) as sink:
        sink.record(SAMPLE_RECORDS[0])
        # flushed per record: a crashed run keeps its prefix
        assert load_jsonl(path) == SAMPLE_RECORDS[:1]
        sink.record(SAMPLE_RECORDS[1])
    assert load_jsonl(path) == SAMPLE_RECORDS[:2]


# ---------------------------------------------------------------------------
# tools/fleet_report.py
# ---------------------------------------------------------------------------


def _fleet_report_module():
    root = pathlib.Path(__file__).resolve().parent.parent
    spec = importlib.util.spec_from_file_location(
        "fleet_report", root / "tools" / "fleet_report.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_fleet_report_summarize_and_format():
    fr = _fleet_report_module()
    s = fr.summarize(SAMPLE_RECORDS)
    assert s["t_end"] == 10.0
    assert s["searches"] == 1 and s["drift_triggers"] == 1
    assert s["serve"]["requests"] == 1 and s["serve"]["slo_ok"] == 1
    assert s["pulls"]["polls"] == 1 and s["pulls"]["nbytes"] == 2048.0
    assert s["lease"]["expired"] == 1
    assert s["churn"]["leave"] == 1 and s["discovered"] == 1
    assert s["assigns"] == 1 and s["capability_reports"] == 1
    assert s["per_worker"][3]["commits"] == 1
    assert s["per_worker"][3]["stale_shards"] == 2
    out = fr.format_report(s)
    assert "fleet report" in out and "stale_ratio" in out
    assert "drift triggers: 1" in out
    assert "serving: 1 requests" in out and "SLO attainment 100.0%" in out


def test_fleet_report_on_a_real_stream(tmp_path):
    fr = _fleet_report_module()
    log = MetricsLog()
    sim = _fleet_sim([churn.stall(10.0, worker=1)],
                     fleet=FleetConfig(lease=LEASE, scheduler="proportional"),
                     metrics=log)
    sim.run(40.0)
    path = tmp_path / "run.jsonl"
    log.to_jsonl(path)
    s = fr.summarize(load_jsonl(path))
    assert s["lease"]["granted"] == 3 and s["lease"]["expired"] == 1
    assert s["discovered"] == 1
    assert len(s["per_worker"]) >= 2
    assert "lease:" in fr.format_report(s)
