"""Actuation plane (ISSUE 12): planner hysteresis/cooldown units, actuator
drills under the fleet-mutation lock, traffic record/replay, and the two
acceptance drills:

- **Replay**: the same seeded bursty trace through an in-process fleet
  with the autoscaler off and on — the actions the planner took, the whole
  trace served both times, and perf_compare exit 0 on a row against its
  copy, 1 on a synthetically degraded copy.
- **Remediation**: a chaos-forced TPOT storm on one replica yields exactly
  ONE drain action — journaled with its triggering signal snapshot in
  causal order (signal -> planned -> executed), visible at /actions,
  incident-bundled with ``injected_fault`` attribution — while the
  chaos-free control run takes zero actions.
"""

from __future__ import annotations

import json
import os
import threading
import time
import urllib.error
import urllib.request
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

import pytest

from ditl_tpu.config import AutoscaleConfig, GatewayConfig
from ditl_tpu.gateway import (
    Action,
    ActionPlanner,
    Actuator,
    Fleet,
    FleetSignals,
    FleetSupervisor,
    GatewayMetrics,
    InProcessReplica,
    ReplicaSecondsSampler,
    ReplicaView,
    TrafficRecorder,
    load_trace,
    make_gateway,
)

pytestmark = [pytest.mark.autoscale, pytest.mark.gateway]

TRACES_DIR = os.path.join(os.path.dirname(__file__), "fixtures", "traces")


# ---------------------------------------------------------------------------
# Planner units (pure host logic over fabricated signals)
# ---------------------------------------------------------------------------


def _view(rid, *, outstanding=0, queue_depth=0, active_slots=0, capacity=2,
          tpot=None, recent_hit=(0, 0), cold=None):
    return ReplicaView(
        id=rid, address=("h", 1), outstanding=outstanding,
        queue_depth=queue_depth, active_slots=active_slots,
        capacity=capacity, live=True, draining=False,
        recent_cache_hit_tokens=recent_hit[0],
        recent_cache_miss_tokens=recent_hit[1],
        tpot_p95_s=tpot, cold_start_s=cold,
    )


def _signals(views, *, now, active=None, parked=(), quarantined=(),
             slo_alerting=False):
    views = tuple(views)
    n = len(views)
    return FleetSignals(
        now=now,
        views=views,
        active=tuple(active if active is not None
                     else [v.id for v in views]),
        parked=tuple(parked),
        quarantined=tuple(quarantined),
        pressure=(sum(v.slot_pressure for v in views) / n) if n else 0.0,
        queue_per_replica=(
            sum(v.queue_depth + v.outstanding for v in views) / n
        ) if n else 0.0,
        slo_alerting=slo_alerting,
    )


def test_planner_scale_up_hysteresis_and_cooldown():
    cfg = AutoscaleConfig(enabled=True, up_hysteresis_polls=2,
                          hysteresis_polls=2, cooldown_s=100.0)
    p = ActionPlanner(cfg)
    hot = [_view("r0", active_slots=2), _view("r1", active_slots=2)]
    # First hot poll: hysteresis holds the action back.
    assert p.plan(_signals(hot, now=0.0, active=["r0", "r1"],
                           parked=["r2"])) == []
    # Second consecutive hot poll: scale_up planned, lowest parked id.
    (a,) = p.plan(_signals(hot, now=1.0, active=["r0", "r1"],
                           parked=["r2"]))
    assert (a.kind, a.target) == ("scale_up", "r2")
    assert a.signal["pressure"] == pytest.approx(1.0)
    # Executed -> cooldown: a fresh hot streak inside the window is held.
    p.note_executed(a, now=1.0)
    assert p.plan(_signals(hot, now=2.0, active=["r0", "r1", "r2"],
                           parked=["r3"])) == []
    assert p.plan(_signals(hot, now=3.0, active=["r0", "r1", "r2"],
                           parked=["r3"])) == []
    # Past the cooldown the still-held signal acts again (the streak
    # accumulated through the cooled polls — the signal never dropped).
    (a2,) = p.plan(_signals(hot, now=102.0, active=["r0", "r1", "r2"],
                            parked=["r3"]))
    assert (a2.kind, a2.target) == ("scale_up", "r3")


def test_planner_flapping_load_never_oscillates_the_fleet():
    """The flapping guard: a load oscillating faster than the hysteresis
    window must plan NOTHING in either direction."""
    cfg = AutoscaleConfig(enabled=True, up_hysteresis_polls=2,
                          hysteresis_polls=3, cooldown_s=0.0)
    p = ActionPlanner(cfg)
    hot = [_view("r0", active_slots=2), _view("r1", active_slots=2)]
    idle = [_view("r0"), _view("r1")]
    for i in range(20):
        views = hot if i % 2 else idle
        assert p.plan(_signals(views, now=float(i), active=["r0", "r1"],
                               parked=["r2"])) == []


def test_planner_scale_down_floor_slo_pin_and_target_choice():
    cfg = AutoscaleConfig(enabled=True, hysteresis_polls=2, cooldown_s=0.0,
                          min_replicas=1)
    p = ActionPlanner(cfg)
    # r0 is actively reusing prefixes, r1 and r2 are not; among the
    # no-reuse pair the HIGHEST id parks (low ids stay stable).
    idle = [_view("r0", recent_hit=(90, 10)), _view("r1"), _view("r2")]
    assert p.plan(_signals(idle, now=0.0)) == []
    (a,) = p.plan(_signals(idle, now=1.0))
    assert (a.kind, a.target) == ("scale_down", "r2")
    assert a.allow_zero is False
    # A burning SLO pins the fleet size regardless of pressure.
    p2 = ActionPlanner(cfg)
    p2.plan(_signals(idle, now=0.0, slo_alerting=True))
    assert p2.plan(_signals(idle, now=1.0, slo_alerting=True)) == []
    # The min_replicas floor refuses at plan time.
    p3 = ActionPlanner(cfg)
    one = [_view("r0")]
    p3.plan(_signals(one, now=0.0))
    assert p3.plan(_signals(one, now=1.0)) == []


def test_planner_scale_to_zero_and_wake():
    cfg = AutoscaleConfig(enabled=True, hysteresis_polls=2, cooldown_s=0.0,
                          min_replicas=1, scale_to_zero=True,
                          idle_to_zero_s=5.0)
    p = ActionPlanner(cfg)
    one = [_view("r0")]
    p.plan(_signals(one, now=0.0))
    p.plan(_signals(one, now=1.0))  # floor blocks ordinary scale_down
    # Idle long enough: the zero path fires with allow_zero.
    (a,) = p.plan(_signals(one, now=6.0))
    assert (a.kind, a.target, a.allow_zero) == ("scale_down", "r0", True)
    p.note_executed(a, now=6.0)
    # Demand against the empty fleet: wake bypasses hysteresis+cooldown.
    p.note_demand()
    (w,) = p.plan(_signals([], now=6.1, active=[], parked=["r0"]))
    assert (w.kind, w.target, w.allow_zero) == ("scale_up", "r0", True)


def test_planner_drain_culprit_once_per_cooldown():
    cfg = AutoscaleConfig(enabled=True, tpot_storm_factor=4.0,
                          tpot_storm_min_s=0.1, remedy_cooldown_s=300.0)
    p = ActionPlanner(cfg)
    views = [_view("r0", tpot=0.02), _view("r1", tpot=0.5),
             _view("r2", tpot=0.03)]
    (a,) = p.plan(_signals(views, now=0.0))
    assert (a.kind, a.target) == ("drain", "r1")
    assert a.signal["tpot_p95_s"]["r1"] == pytest.approx(0.5)
    p.note_executed(a, now=0.0)
    # The storm persists (lifetime p95 is sticky) but the per-replica
    # remedy cooldown makes it ONE drain, not one per poll.
    assert p.plan(_signals(views, now=1.0)) == []
    # An even fleet-wide slowdown has no culprit: nothing to drain.
    even = [_view("r0", tpot=0.5), _view("r1", tpot=0.5),
            _view("r2", tpot=0.5)]
    assert ActionPlanner(cfg).plan(_signals(even, now=0.0)) == []
    # Below the absolute floor, peer ratios alone never read as a storm.
    tiny = [_view("r0", tpot=0.001), _view("r1", tpot=0.02)]
    assert ActionPlanner(cfg).plan(_signals(tiny, now=0.0)) == []


def test_planner_quarantine_after_death_storm():
    # min_replicas == fleet size: idle fabricated views must not ALSO
    # plan demand scale-downs in this quarantine-focused unit.
    cfg = AutoscaleConfig(enabled=True, quarantine_deaths=3,
                          quarantine_window_s=60.0, min_replicas=2)
    p = ActionPlanner(cfg)
    views = [_view("r0"), _view("r1")]
    p.note_death("r1", now=0.0)
    p.note_death("r1", now=1.0)
    assert all(a.kind != "quarantine"
               for a in p.plan(_signals(views, now=2.0)))
    p.note_death("r1", now=3.0)
    acts = p.plan(_signals(views, now=4.0))
    assert [(a.kind, a.target) for a in acts] == [("quarantine", "r1")]
    p.note_executed(acts[0], now=4.0)
    # Quarantined replicas are not re-planned.
    p.note_death("r1", now=5.0)
    assert p.plan(_signals(views, now=6.0,
                           quarantined=["r1"])) == []
    # Deaths outside the window never accumulate into a storm.
    p2 = ActionPlanner(cfg)
    for t in (0.0, 100.0, 200.0):
        p2.note_death("r0", now=t)
    assert p2.plan(_signals(views, now=201.0)) == []


# ---------------------------------------------------------------------------
# Stub-replica layer
# ---------------------------------------------------------------------------


class _StubServer(ThreadingHTTPServer):
    daemon_threads = True
    allow_reuse_address = True
    label = "stub"
    health_extra: dict = {}

    def close(self, drain=True, timeout=30.0):
        self.shutdown()
        self.server_close()

    def kill(self):
        self.close()


class _StubHandler(BaseHTTPRequestHandler):
    def log_message(self, *args):
        pass

    def _json(self, status, payload):
        body = json.dumps(payload).encode()
        self.send_response(status)
        self.send_header("Content-Type", "application/json")
        self.send_header("Content-Length", str(len(body)))
        self.end_headers()
        self.wfile.write(body)

    def do_GET(self):
        self._json(200, {"status": "ok", "draining": False,
                         "queue_depth": 0, "active_slots": 0, "n_slots": 2,
                         **self.server.health_extra})

    def do_POST(self):
        self.rfile.read(int(self.headers.get("Content-Length", 0)))
        self._json(200, {
            "object": "text_completion",
            "choices": [{"index": 0, "text": self.server.label,
                         "finish_reason": "stop"}],
            "usage": {"prompt_tokens": 1, "completion_tokens": 1,
                      "total_tokens": 2},
        })


def _stub(rid, health_extra=None):
    extra = dict(health_extra or {})

    def factory():
        server = _StubServer(("127.0.0.1", 0), _StubHandler)
        server.label = rid
        server.health_extra = extra
        return server

    return InProcessReplica(rid, factory)


def _fleet(*handles):
    fleet = Fleet(list(handles))
    fleet.start_all()
    for rid in fleet.ids:
        assert fleet.probe(rid, timeout=5.0)
    return fleet


def _actuator(fleet, cfg, **kw):
    supervisor = FleetSupervisor(fleet, interval_s=0.05,
                                 restart_timeout_s=10.0)
    act = Actuator(fleet, supervisor, cfg, **kw)
    supervisor.autoscaler = act
    return supervisor, act


def _post(port, body, path="/v1/completions", headers=None, timeout=30):
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}{path}", data=json.dumps(body).encode(),
        headers={"Content-Type": "application/json", **(headers or {})},
        method="POST",
    )
    try:
        with urllib.request.urlopen(req, timeout=timeout) as resp:
            return resp.status, dict(resp.headers), json.loads(resp.read())
    except urllib.error.HTTPError as e:
        return e.code, dict(e.headers or {}), json.loads(e.read())


def _get(port, path):
    with urllib.request.urlopen(
        f"http://127.0.0.1:{port}{path}", timeout=10
    ) as resp:
        return resp.status, json.loads(resp.read())


def _scrape(port):
    with urllib.request.urlopen(
        f"http://127.0.0.1:{port}/metrics", timeout=10
    ) as resp:
        return resp.read().decode()


# ---------------------------------------------------------------------------
# Actuator drills over stub fleets
# ---------------------------------------------------------------------------


def test_actuator_scale_roundtrip_journal_causal_order_and_endpoints(
        tmp_path):
    """Idle fleet parks one replica; demand brings it back. The journal
    carries the causal chain signal -> planned -> executed (the cooldown
    contract is keyed on EXECUTED, pinned here), /actions lists both
    actions with their signal snapshots, /metrics carries the
    per-kind/outcome counters and the active/quarantined gauges, and the
    flight ring holds the same story."""
    from ditl_tpu.telemetry.flight import ACTION_RING, FlightRecorder
    from ditl_tpu.telemetry.journal import EventJournal, read_journal

    journal_path = str(tmp_path / "events-gateway.jsonl")
    journal = EventJournal(journal_path, source="gateway")
    flight = FlightRecorder(64)
    fleet = _fleet(_stub("r0"), _stub("r1"), _stub("r2"))
    cfg = AutoscaleConfig(enabled=True, min_replicas=2,
                          up_hysteresis_polls=1, hysteresis_polls=2,
                          cooldown_s=0.0, drain_wait_s=1.0,
                          scale_up_queue=1.0)
    gw_metrics = GatewayMetrics()
    supervisor, act = _actuator(fleet, cfg, journal=journal,
                                metrics=gw_metrics, flight=flight)
    server = make_gateway(fleet, config=GatewayConfig(router="round_robin"),
                          metrics=gw_metrics, port=0, actuator=act)
    threading.Thread(target=server.serve_forever, daemon=True).start()
    port = server.server_address[1]
    try:
        # Two idle polls -> scale_down r2 (highest id, no reuse anywhere).
        assert act.poll() == []
        entries = act.poll()
        assert [(e["kind"], e["target"], e["outcome"]) for e in entries] \
            == [("scale_down", "r2", "executed")]
        assert fleet.parked_ids() == ["r2"]
        assert sorted(v.id for v in fleet.routable()) == ["r0", "r1"]
        # The gateway still serves from the remaining pair.
        status, _, out = _post(port, {"prompt": "hi", "max_tokens": 1})
        assert status == 200 and out["choices"][0]["text"] in ("r0", "r1")

        # Demand: stub healths report queued work -> scale_up brings r2
        # back (a NEW stub server on a fresh port, probed healthy).
        for rid in ("r0", "r1"):
            fleet._state(rid).handle._server.health_extra.update(
                {"queue_depth": 3, "active_slots": 2})
            assert fleet.probe(rid, timeout=5.0)
        entries = act.poll()
        assert [(e["kind"], e["target"], e["outcome"]) for e in entries] \
            == [("scale_up", "r2", "executed")]
        assert fleet.parked_ids() == []
        assert sorted(v.id for v in fleet.routable()) == ["r0", "r1", "r2"]

        # Journal causal order per action: signal <= planned <= executed
        # (seq within one source file is the total order).
        rows = read_journal(journal_path)
        by_event = {}
        for r in rows:
            by_event.setdefault(r["event"], []).append(r["seq"])
        assert by_event["action.signal"][0] \
            <= by_event["action.planned"][0] \
            <= by_event["action.executed"][0]
        planned = [r for r in rows if r["event"] == "action.planned"]
        assert all("signal" in r and "pressure" in r["signal"]
                   for r in planned)
        down_sig = [r for r in rows if r["event"] == "action.signal"
                    and r.get("signal_name") == "pressure_low"]
        up_sig = [r for r in rows if r["event"] == "action.signal"
                  and r.get("signal_name") == "pressure_high"]
        assert down_sig and up_sig

        # /actions: both entries, signal snapshots inline.
        status, body = _get(port, "/actions")
        assert status == 200 and body["count"] == 2
        kinds = [(a["kind"], a["outcome"]) for a in body["actions"]]
        assert kinds == [("scale_down", "executed"),
                         ("scale_up", "executed")]
        assert all("signal" in a for a in body["actions"])

        # /metrics: per-kind/outcome counters + pool gauges.
        text = _scrape(port)
        assert "ditl_gateway_action_scale_down_planned_total 1" in text
        assert "ditl_gateway_action_scale_down_executed_total 1" in text
        assert "ditl_gateway_action_scale_up_executed_total 1" in text
        assert "ditl_gateway_replicas_active 3" in text
        assert "ditl_gateway_replicas_quarantined 0" in text

        # Flight ring: the same story, bounded in memory.
        ring_rows = flight.ring(ACTION_RING).dump()
        assert [r["event"] for r in ring_rows].count("executed") == 2
    finally:
        server.shutdown()
        server.server_close()
        fleet.stop_all(drain=False)
        journal.close()


def test_actuator_dry_run_plans_but_never_touches_the_fleet(tmp_path):
    from ditl_tpu.telemetry.journal import EventJournal, read_journal

    journal_path = str(tmp_path / "events-gateway.jsonl")
    journal = EventJournal(journal_path, source="gateway")
    fleet = _fleet(_stub("r0"), _stub("r1"))
    cfg = AutoscaleConfig(enabled=True, min_replicas=1,
                          hysteresis_polls=1, cooldown_s=60.0,
                          dry_run=True)
    gw_metrics = GatewayMetrics()
    _, act = _actuator(fleet, cfg, journal=journal, metrics=gw_metrics)
    try:
        (entry,) = act.poll()  # idle -> scale_down planned
        assert (entry["kind"], entry["outcome"]) == ("scale_down", "dry_run")
        # Nothing moved.
        assert fleet.parked_ids() == []
        assert fleet.live_count() == 2
        # Dry-run previews the real cadence: the cooldown stamps on the
        # dry outcome too, so the identical plan is NOT re-logged every
        # supervisor pass against the fleet state it cannot change.
        assert act.poll() == []
        rows = read_journal(journal_path)
        events = [r["event"] for r in rows]
        assert "action.planned" in events
        assert "action.executed" not in events
        assert gw_metrics.action_counter("scale_down", "planned").value == 1
        assert gw_metrics.action_counter("scale_down", "dry_run").value == 1
        assert gw_metrics.action_counter("scale_down", "executed").value == 0
    finally:
        fleet.stop_all(drain=False)
        journal.close()


def test_scale_to_zero_wake_admission_uses_measured_cold_start():
    """Scale-to-zero parks the last replica; demand answers 429 with a
    Retry-After derived from the MEASURED cold start the replica stamped
    on /health (not a constant), and the next planner pass wakes it."""
    fleet = _fleet(_stub("r0", health_extra={"cold_start_s": 2.2}))
    cfg = AutoscaleConfig(enabled=True, min_replicas=1,
                          hysteresis_polls=1, cooldown_s=0.0,
                          scale_to_zero=True, idle_to_zero_s=0.0,
                          wake_budget_factor=2.0,
                          default_cold_start_s=999.0)
    gw_metrics = GatewayMetrics()
    supervisor, act = _actuator(fleet, cfg, metrics=gw_metrics)
    server = make_gateway(fleet, config=GatewayConfig(router="round_robin"),
                          metrics=gw_metrics, port=0, actuator=act)
    threading.Thread(target=server.serve_forever, daemon=True).start()
    port = server.server_address[1]
    try:
        # While anything is routable, demand is NOT a wake: the fast 503/
        # retry path stays (a wake promise the planner would drop).
        assert act.note_demand() is None
        (entry,) = act.poll()  # idle fleet of 1 + scale_to_zero -> park it
        assert (entry["kind"], entry["outcome"]) == ("scale_down", "executed")
        assert entry["detail"] == "parked r0"
        assert fleet.live_count() == 0
        # Measured (2.2s) x factor (2.0) = 4.4 -> ceil 5; the 999s default
        # must NOT be the budget once a measurement exists.
        assert act.wake_budget_s() == pytest.approx(4.4)
        status, headers, out = _post(port, {"prompt": "hi",
                                            "max_tokens": 1})
        assert status == 429
        assert int(headers["Retry-After"]) == 5
        assert "waking" in out["error"]["message"]
        assert "ditl_gateway_cold_start_429_total 1" in _scrape(port)
        # The wake lands on the next planner pass, bypassing cooldown.
        (wake,) = act.poll()
        assert (wake["kind"], wake["outcome"]) == ("scale_up", "executed")
        deadline = time.monotonic() + 5
        while fleet.live_count() == 0 and time.monotonic() < deadline:
            fleet.probe("r0", timeout=2.0)
        status, _, out = _post(port, {"prompt": "hi", "max_tokens": 1})
        assert status == 200 and out["choices"][0]["text"] == "r0"
    finally:
        server.shutdown()
        server.server_close()
        fleet.stop_all(drain=False)


def test_actuator_refuses_when_world_moved_and_fails_on_injected_error():
    """Execute-time re-validation under the lock (refused outcomes) and
    the supervisor.action chaos seam's error path (failed outcome, fleet
    untouched)."""
    from ditl_tpu.chaos import FaultPlane, arm, disarm

    fleet = _fleet(_stub("r0"), _stub("r1"))
    cfg = AutoscaleConfig(enabled=True, min_replicas=1, cooldown_s=0.0)
    gw_metrics = GatewayMetrics()
    _, act = _actuator(fleet, cfg, metrics=gw_metrics)
    try:
        # Floor re-check: a stale plan naming the only remaining active
        # replica refuses instead of emptying the fleet.
        e = act.apply(Action("scale_down", "r1", "test"))
        assert e["outcome"] == "executed"
        e = act.apply(Action("scale_down", "r0", "test"))
        assert e["outcome"] == "refused" and "floor" in e["detail"]
        e = act.apply(Action("scale_up", "zzz", "test"))
        # Unknown target resolves to any parked replica (r1).
        assert e["outcome"] == "executed" and "r1" in e["detail"]
        e = act.apply(Action("drain", "nope", "test"))
        assert e["outcome"] == "refused"
        # The floor binds on LIVE capacity: with r1 dead (crashed, not
        # parked) the roster still counts 2 active, but parking the only
        # LIVE replica would leave zero serving — refused.
        fleet.handle("r1").kill()
        fleet.note_failure("r1")
        e = act.apply(Action("scale_down", "r0", "test"))
        assert e["outcome"] == "refused" and "live" in e["detail"]
        fleet._state("r1").handle.start()
        fleet.probe("r1", timeout=5.0)
        # Injected actuation error -> failed, replica still active.
        arm(FaultPlane(seed=3, rules="supervisor.action:error@max=1"))
        try:
            e = act.apply(Action("scale_down", "r1", "test"))
        finally:
            disarm()
        assert e["outcome"] == "failed"
        assert "InjectedFault" in e["detail"]
        assert sorted(fleet.active_ids()) == ["r0", "r1"]
        assert gw_metrics.action_counter("scale_down", "failed").value == 1
    finally:
        fleet.stop_all(drain=False)


# ---------------------------------------------------------------------------
# Chaos-composed drills: scale events racing the supervisor
# ---------------------------------------------------------------------------


def test_scale_down_racing_kill_is_serialized_by_the_fleet_lock():
    """A scale-down and a kill -9 of the SAME replica race: the
    fleet-mutation lock serializes the actuator against the supervisor's
    crash recovery, and whichever order the lock resolves, the end state
    is consistent — the replica is parked, down, and NOT relaunched."""
    from ditl_tpu.chaos import FaultPlane, arm, disarm

    fleet = _fleet(_stub("r0"), _stub("r1"), _stub("r2"))
    cfg = AutoscaleConfig(enabled=True, min_replicas=1, cooldown_s=0.0,
                          drain_wait_s=0.5)
    supervisor, act = _actuator(fleet, cfg)
    # Widen the race window: the actuator sleeps INSIDE the lock, so the
    # supervisor's recovery of the killed replica must queue behind it.
    arm(FaultPlane(seed=7,
                   rules="supervisor.action:delay@delay=0.3,max=1"))
    try:
        entries = []
        t = threading.Thread(
            target=lambda: entries.append(
                act.apply(Action("scale_down", "r1", "race"))),
        )
        t.start()
        time.sleep(0.05)  # actuator is inside the lock's chaos delay now
        fleet.handle("r1").kill()
        # The supervisor notices the corpse and tries to recover it —
        # its _recover must queue on the lock, then observe "parked".
        for _ in range(10):
            supervisor.poll_once()
            time.sleep(0.05)
        t.join(timeout=10.0)
        assert not t.is_alive()
        for rec in list(supervisor._recoveries.values()):
            rec.join(timeout=10.0)
        assert entries and entries[0]["outcome"] == "executed"
        st = fleet._state("r1")
        assert st.deactivated and not st.live
        # A few more supervision passes must NOT resurrect it.
        for _ in range(5):
            supervisor.poll_once()
            time.sleep(0.02)
        assert not fleet._state("r1").live
        assert sorted(v.id for v in fleet.routable()) == ["r0", "r2"]
    finally:
        disarm()
        fleet.stop_all(drain=False)


def test_scale_up_during_rolling_restart_waits_its_turn():
    """A scale-up landing mid-rolling-restart serializes on the same
    lock: both complete, every replica (including the newly activated
    one) ends live and routable."""
    fleet = _fleet(_stub("r0"), _stub("r1"), _stub("r2"))
    cfg = AutoscaleConfig(enabled=True, min_replicas=1, cooldown_s=0.0,
                          drain_wait_s=0.5)
    supervisor, act = _actuator(fleet, cfg)
    try:
        e = act.apply(Action("scale_down", "r2", "setup"))
        assert e["outcome"] == "executed"
        errors = []

        def rolling():
            try:
                supervisor.rolling_restart(drain_timeout_s=2.0)
            except Exception as exc:  # noqa: BLE001 - surfaced below
                errors.append(exc)

        t = threading.Thread(target=rolling)
        t.start()
        entry = act.apply(Action("scale_up", "r2", "mid-rolling"))
        t.join(timeout=30.0)
        assert not t.is_alive() and not errors
        assert entry["outcome"] == "executed"
        for rid in fleet.ids:
            fleet.probe(rid, timeout=5.0)
        assert fleet.live_count() == 3
        assert sorted(v.id for v in fleet.routable()) == ["r0", "r1", "r2"]
    finally:
        fleet.stop_all(drain=False)


def test_quarantine_breaks_a_crash_loop():
    """Supervisor death notes feed the planner's per-replica window; past
    the threshold ONE quarantine executes, the supervisor stops feeding
    the loop, and the fleet serves on without it."""
    fleet = _fleet(_stub("r0"), _stub("r1"))
    cfg = AutoscaleConfig(enabled=True, quarantine_deaths=3,
                          quarantine_window_s=60.0, cooldown_s=0.0,
                          # Idle stubs must not also trigger demand scaling
                          # mid-drill: floor the fleet at its full size.
                          min_replicas=2)
    supervisor, act = _actuator(fleet, cfg)
    try:
        for _ in range(3):
            act.note_death("r1")
        entries = act.poll()
        assert [(e["kind"], e["target"], e["outcome"]) for e in entries] \
            == [("quarantine", "r1", "executed")]
        st = fleet._state("r1")
        assert st.quarantined and not st.live
        assert fleet.quarantined_ids() == ["r1"]
        # Supervision skips it: no recovery threads spawn for it.
        for _ in range(3):
            supervisor.poll_once()
        assert "r1" not in supervisor._recoveries or \
            not fleet._state("r1").live
        assert [v.id for v in fleet.routable()] == ["r0"]
        # One quarantine only, even as deaths keep being noted.
        act.note_death("r1")
        assert act.poll() == []
    finally:
        fleet.stop_all(drain=False)


# ---------------------------------------------------------------------------
# Traffic recorder + replay fixtures
# ---------------------------------------------------------------------------


def test_traffic_recorder_records_admitted_requests(tmp_path):
    trace_path = str(tmp_path / "trace.jsonl")
    recorder = TrafficRecorder(trace_path)
    fleet = _fleet(_stub("r0"))
    server = make_gateway(fleet, config=GatewayConfig(router="round_robin"),
                          port=0, recorder=recorder)
    threading.Thread(target=server.serve_forever, daemon=True).start()
    port = server.server_address[1]
    try:
        _post(port, {"prompt": "one two three", "max_tokens": 7},
              headers={"Authorization": "Bearer super-secret-key"})
        _post(port, {"prompt": "a b c d e", "max_tokens": 3,
                     "slo_class": "batch"})
        # Metadata routes are NOT traffic: tokenize never records.
        _post(port, {"text": "hi"}, path="/tokenize")
    finally:
        server.shutdown()
        server.server_close()
        fleet.stop_all(drain=False)
        recorder.close()
    rows = load_trace(trace_path)
    assert len(rows) == 2
    assert rows[0]["t"] == 0.0 and rows[1]["t"] >= 0.0
    assert rows[0]["prompt_tokens"] == 3 and rows[0]["max_new"] == 7
    assert rows[1]["slo_class"] == "batch"
    # The bearer token never reaches the trace — only the stable digest.
    assert rows[0]["tenant"].startswith("t_")
    assert "super-secret-key" not in json.dumps(rows)
    # A torn tail line (the kill case) is skipped, not an error.
    with open(trace_path, "a") as f:
        f.write('{"t": 9.1, "tenant": "t_x", "prompt')
    assert len(load_trace(trace_path)) == 2


def test_committed_trace_fixtures_are_replayable():
    for name, min_rows in (("burst.jsonl", 15), ("diurnal.jsonl", 15)):
        rows = load_trace(os.path.join(TRACES_DIR, name))
        assert len(rows) >= min_rows, name
        assert rows[0]["t"] == 0.0
        assert all(rows[i]["t"] <= rows[i + 1]["t"]
                   for i in range(len(rows) - 1)), name
        assert rows[-1]["t"] < 10.0, f"{name} too long for tier-1 replay"
        assert all(r.get("slo_class") in (None, "interactive", "batch",
                                          "best_effort") for r in rows)
        assert all(r["prompt_tokens"] > 0 and r["max_new"] > 0
                   for r in rows), name
    # The burst shape really is bursty: at least two inter-arrival gaps
    # long enough for a scale-down hysteresis window to drain.
    rows = load_trace(os.path.join(TRACES_DIR, "burst.jsonl"))
    gaps = [b["t"] - a["t"] for a, b in zip(rows, rows[1:])]
    assert sum(1 for g in gaps if g >= 1.5) >= 2


def test_replica_seconds_sampler_integrates_live_count():
    class _FakeFleet:
        def __init__(self):
            self.n = 3

        def live_count(self):
            return self.n

    fake = _FakeFleet()
    sampler = ReplicaSecondsSampler(fake, interval_s=0.01).start()
    time.sleep(0.25)
    fake.n = 1
    time.sleep(0.25)
    total = sampler.stop()
    # ~3x0.25 + 1x0.25 = 1.0, generous bounds for CI scheduling noise.
    assert 0.5 < total < 1.6


# ---------------------------------------------------------------------------
# Acceptance drill 1: trace replay — autoscaler on vs off, perf_compare-gated
# ---------------------------------------------------------------------------


def test_replay_ab_autoscaler_saves_replica_seconds_at_same_slo():
    """THE autoscaler replay drill: the same seeded bursty trace with the
    autoscaler off and on. Asserted is what the planner DID — no action
    off, at least one executed scale-down and no failed action on — and
    that both legs served the whole trace with the same tokens; the
    replica-seconds, TTFT and SLO-violation instruments are read for
    presence only (they are wall-clock integrals on shared CPU cores, so
    which leg's is smaller is not evidence here). perf_compare exits 0 on
    a row against its copy and 1 against a degraded copy with the
    autoscale key named."""
    from ditl_tpu.telemetry.perf_compare import compare_records
    from tests.gateway_drivers import run_trace_replay_bench

    trace = os.path.join(TRACES_DIR, "burst.jsonl")
    off = run_trace_replay_bench(trace, 3, autoscale=False)
    on = run_trace_replay_bench(
        trace, 3, autoscale=True, min_replicas=2,
        autoscale_overrides={"scale_up_queue": 0.75})

    # The off leg took zero actions; the on leg scaled down at least once
    # and every action it took executed (none failed).
    assert off["autoscale"]["actions"] == {}
    on_actions = on["autoscale"]["actions"]
    assert on_actions.get("scale_down_executed", 0) >= 1
    assert not any(k.endswith("_failed") for k in on_actions)
    # The cost and SLO instruments ran on both legs.
    for leg in (off, on):
        assert leg["autoscale"]["replica_seconds"] > 0.0
        assert leg["autoscale"]["ttft_slo_violation_rate"] is not None
        assert leg["serving"]["ttft_p95_s"] is not None
    assert on["requests"] == off["requests"] == 18
    assert on["generated_tokens"] == off["generated_tokens"]

    # perf_compare gates the row: its own copy passes, and a degraded
    # copy — the autoscaler burning MORE replica-seconds — fails with the
    # key named.
    code, report = compare_records(on, json.loads(json.dumps(on)), 0.25)
    assert code == 0, report
    degraded = json.loads(json.dumps(on))
    degraded["autoscale"]["replica_seconds"] = round(
        on["autoscale"]["replica_seconds"] * 3, 3)
    code, report = compare_records(on, degraded, 0.25)
    assert code == 1
    assert "replica_seconds" in report


# ---------------------------------------------------------------------------
# Acceptance drill 2: chaos-forced TPOT storm -> exactly one drain action
# ---------------------------------------------------------------------------


_TINY = dict(num_layers=1, hidden_size=64, intermediate_size=176,
             vocab_size=512, num_heads=2, num_kv_heads=2, head_dim=32,
             max_seq_len=256)


def _real_replica(rid, tmp_cfg):
    """One REAL continuous-engine replica (tiny model) whose measured
    TPOT lands on /health — the drain drill's culprit."""
    import jax

    from ditl_tpu.config import ModelConfig
    from ditl_tpu.data.tokenizer import ByteTokenizer
    from ditl_tpu.infer.continuous import ContinuousEngine, ThreadedEngine
    from ditl_tpu.infer.engine import Generator
    from ditl_tpu.infer.server import make_server
    from ditl_tpu.models import llama

    cfg = ModelConfig(name="drill-tiny", **tmp_cfg)
    params = llama.init_params(jax.random.key(0), cfg)
    tok = ByteTokenizer()
    engine = ThreadedEngine(ContinuousEngine(
        params, cfg, tok, n_slots=2, decode_chunk=1))
    gen = Generator(params, cfg, tok)

    def factory():
        return make_server(gen, port=0, threaded_engine=engine,
                           default_max_tokens=8, cold_start_s=0.9)

    return InProcessReplica(rid, factory), engine


def _run_storm_leg(tmp_path, *, chaos: bool):
    """One remediation leg: a real engine replica among healthy-stub
    peers; with chaos armed, every engine tick eats an injected delay and
    the replica's measured TPOT p95 storms."""
    from ditl_tpu.chaos import FaultPlane, arm, disarm
    from ditl_tpu.telemetry import (
        AnomalyPlane, FlightRecorder, IncidentManager,
    )
    from ditl_tpu.telemetry.journal import EventJournal, read_journal

    leg = "chaos" if chaos else "healthy"
    handle, engine = _real_replica("r0", _TINY)
    fleet = Fleet([
        handle,
        _stub("r1", health_extra={"tpot_p95_s": 0.02}),
        _stub("r2", health_extra={"tpot_p95_s": 0.03}),
    ])
    journal_path = str(tmp_path / f"events-{leg}.jsonl")
    journal = EventJournal(journal_path, source="gateway")
    flight = FlightRecorder(64)
    gw_metrics = GatewayMetrics()
    incidents = IncidentManager(
        str(tmp_path / f"incidents-{leg}"), flight=flight,
        metrics_render=gw_metrics.registry.render,
        journal_dir=str(tmp_path), registry=gw_metrics.registry,
        source="gateway",
    )
    plane = AnomalyPlane(incidents=incidents, journal=journal)
    cfg = AutoscaleConfig(
        enabled=True, min_replicas=3, cooldown_s=1000.0,
        tpot_storm_factor=4.0, tpot_storm_min_s=0.25,
        remedy_cooldown_s=1000.0, drain_wait_s=2.0,
    )
    if chaos:
        arm(FaultPlane(seed=11, rules="engine.tick:delay@delay=0.4"))
    try:
        fleet.start_all()
        for rid in fleet.ids:
            assert fleet.probe(rid, timeout=10.0)
        supervisor, act = _actuator(fleet, cfg, journal=journal,
                                    metrics=gw_metrics, flight=flight,
                                    plane=plane)
        server = make_gateway(
            fleet, config=GatewayConfig(router="round_robin"),
            metrics=gw_metrics, port=0, actuator=act)
        threading.Thread(target=server.serve_forever, daemon=True).start()
        port = server.server_address[1]
        try:
            # Generate measured decode latency on the REAL replica
            # (decode_chunk=1: one TPOT observation per token; under
            # chaos each tick absorbs the injected 0.4s delay).
            addr = handle.address
            for i in range(2):
                req = urllib.request.Request(
                    f"http://{addr[0]}:{addr[1]}/v1/completions",
                    data=json.dumps({"prompt": f"storm drill {i}",
                                     "max_tokens": 6}).encode(),
                    headers={"Content-Type": "application/json"},
                    method="POST",
                )
                with urllib.request.urlopen(req, timeout=120) as resp:
                    resp.read()
            # Several supervision passes: probes refresh the health-polled
            # TPOT p95s, then the planner reads them.
            entries = []
            for _ in range(4):
                supervisor.poll_once()
                entries += act.poll()
            status, body = _get(port, "/actions")
            assert status == 200
            return {
                "entries": entries,
                "actions_body": body,
                "journal": read_journal(journal_path),
                "incident_dir": incidents.directory,
                "replica_live": fleet._state("r0").live,
            }
        finally:
            server.shutdown()
            server.server_close()
            fleet.stop_all(drain=False)
            engine.close()
            journal.close()
    finally:
        if chaos:
            disarm()


@pytest.mark.chaos
def test_tpot_storm_drains_exactly_the_culprit_with_injected_attribution(
        tmp_path):
    """THE remediation drill (ISSUE 12 acceptance): chaos-forced TPOT
    storm on one replica -> exactly ONE drain action targeting it,
    journaled with the triggering signal snapshot, visible at /actions
    with its incident cross-link, and the bundle carries the
    ``injected_fault`` attribution — while the chaos-free control run
    takes zero actions and builds zero bundles."""
    from ditl_tpu.telemetry.incident import list_bundles

    out = _run_storm_leg(tmp_path, chaos=True)
    drains = [e for e in out["entries"]
              if (e["kind"], e["outcome"]) == ("drain", "executed")]
    assert len(drains) == 1, out["entries"]
    assert drains[0]["target"] == "r0"
    # The triggering signal snapshot rides the action end to end.
    assert drains[0]["signal"]["tpot_p95_s"]["r0"] >= 0.25
    # Causal order in the journal: tpot_storm signal -> planned ->
    # executed.
    seqs = {}
    for r in out["journal"]:
        if r["event"] in ("action.signal", "action.planned",
                          "action.executed") and r["event"] not in seqs:
            seqs[r["event"]] = r["seq"]
    assert seqs["action.signal"] <= seqs["action.planned"] \
        <= seqs["action.executed"]
    storm_signals = [r for r in out["journal"]
                     if r["event"] == "action.signal"
                     and r.get("signal_name") == "tpot_storm"]
    assert storm_signals
    # /actions carries the drain with its incident cross-link.
    acts = [a for a in out["actions_body"]["actions"]
            if a["kind"] == "drain"]
    assert len(acts) == 1 and acts[0]["outcome"] == "executed"
    assert acts[0]["incident"], "drain action not incident-bundled"
    # The bundle: trigger action.drain, chaos attribution, signal inline.
    bundles = list_bundles(out["incident_dir"])
    assert len(bundles) == 1
    m = bundles[0]
    assert m["trigger"] == "action.drain"
    assert m.get("injected_fault", {}).get("injected", {}).get(
        "engine.tick:delay"), m.get("injected_fault")
    assert m["detail"]["target"] == "r0"
    assert m["detail"]["signal"]["tpot_p95_s"]["r0"] >= 0.25
    # Drain-and-restart left the culprit serving again.
    assert out["replica_live"]

    # The chaos-free control: zero actions, zero bundles.
    control = _run_storm_leg(tmp_path, chaos=False)
    assert [e for e in control["entries"]
            if e["outcome"] != "refused"] == []
    assert control["actions_body"]["count"] == 0
    assert list_bundles(control["incident_dir"]) == []
