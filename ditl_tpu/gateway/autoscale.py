"""Actuation plane: demand-driven autoscaling + detector-triggered
remediation (ISSUE 12 tentpole).

Every observability layer before this one *watches* — metrics, traces,
flight rings, anomaly detectors, incident bundles all terminate at a
human. This module closes the loop from signals to actions: an
:class:`ActionPlanner` turns the signals the fleet already produces
(``ReplicaView.slot_pressure``, queue depth, windowed
``recent_cache_hit_ratio``, health-polled TPOT p95s, SLO burn state,
supervisor death notes) into typed :class:`Action` records, and an
:class:`Actuator` executes them through the existing FleetSupervisor
primitives (drain / stop / start / await-healthy) under the one
fleet-mutation lock that crash recovery and rolling restarts already
hold — a scale event can no longer race a relaunch.

The observability spine is the point, not a side effect. Every action —
planned, executed, refused, failed, or dry-run — is:

- **journaled** as ``action.*`` events carrying the triggering signal
  snapshot inline (``events-gateway.jsonl``; the flapping-guard drill pins
  the causal order ``action.signal -> action.planned -> action.executed``);
- **flight-recorded** into the ACTION ring (telemetry/flight.py), so an
  incident bundle dumps the last few hundred actions next to the routing
  decisions they reshaped;
- **span-traced** as ``gateway.action`` on the existing trace layer;
- **counted** per action-kind/outcome on the gateway's /metrics
  (``ditl_gateway_action_<kind>_<outcome>_total``);
- **listable** at the gateway's ``/actions`` endpoint (bounded in-memory
  log, each entry cross-linked to its incident bundle when one fired);
- **incident-bundled** for executed remediation and failed actions via the
  PR 10 IncidentManager — a bad remediation leaves the same forensic trail
  as an organic failure, chaos attribution included.

Action taxonomy:

- ``scale_up`` / ``scale_down`` — demand scaling between
  ``autoscale.min_replicas`` and the launched pool, with hysteresis
  (asymmetric: fast up, slow down) and a post-execute cooldown so an
  oscillating load cannot oscillate the fleet. Scale-down parks the
  replica (``deactivated``): drained, stopped, excluded from routing and
  from supervisor recovery; the affinity ring's consistent hashing
  guarantees only the parked replica's keys remap (router.py). Scale-to-
  zero is the same action below the floor, armed separately, and demand
  arriving against an empty fleet answers 429 with a wake-up budget
  derived from the MEASURED replica cold start (time-to-first-ready
  stamped on /health) while a wake is planned.
- ``drain`` — TPOT-storm remediation: the live replica whose health-polled
  TPOT p95 stands ``tpot_storm_factor`` x above its peers' median (and
  above the absolute ``tpot_storm_min_s`` floor) is drained, restarted,
  and re-admitted — the targeted version of a rolling-restart leg.
- ``quarantine`` — death-storm remediation: a replica that died
  ``quarantine_deaths`` times inside ``quarantine_window_s`` is stopped
  and excluded from supervision, breaking the crash loop the supervisor's
  relaunch budget would otherwise bleed out on.

Also here (ISSUE 12 satellites): the :class:`TrafficRecorder` the gateway
arms with ``--save-trace`` (one JSONL row per admitted request — arrival
offset, tenant digest, class, prompt/max_new token estimates) and
:func:`load_trace`, the reader a trace replay drives
(``tests/gateway_drivers.py``); and :class:`ReplicaSecondsSampler`, the
replica-seconds integral a replay reports.

Stdlib-only and jax-free like the rest of the gateway package.
"""

from __future__ import annotations

import collections
import dataclasses
import json
import os
import statistics
import threading
import time
from typing import Callable

from ditl_tpu.chaos import maybe_inject
from ditl_tpu.telemetry.anomaly import Anomaly
from ditl_tpu.telemetry.flight import ACTION_RING
from ditl_tpu.telemetry.tracing import NULL_TRACER
from ditl_tpu.utils.logging import get_logger

logger = get_logger(__name__)

__all__ = [
    "ACTION_KINDS",
    "Action",
    "ActionPlanner",
    "Actuator",
    "FleetSignals",
    "ReplicaSecondsSampler",
    "TrafficRecorder",
    "load_trace",
]

ACTION_KINDS = ("scale_up", "scale_down", "drain", "quarantine")
# Remediation kinds bundle on EXECUTE (a remediation is incident-worthy by
# definition); every kind bundles on FAILED.
REMEDIATION_KINDS = frozenset({"drain", "quarantine"})


@dataclasses.dataclass(frozen=True)
class Action:
    """One typed fleet action. ``signal`` is the triggering signal
    snapshot (host scalars only — journaled and bundled verbatim);
    ``allow_zero`` marks the scale paths exempt from the min_replicas
    floor (idle scale-to-zero) or from hysteresis/cooldown (wake)."""

    kind: str
    target: str
    reason: str
    signal: dict = dataclasses.field(default_factory=dict)
    ts: float = dataclasses.field(default_factory=time.time)
    allow_zero: bool = False


@dataclasses.dataclass(frozen=True)
class FleetSignals:
    """The planner's input: one coherent read of the signals the fleet
    already produces, taken once per supervision pass."""

    now: float
    views: tuple  # live, routable ReplicaViews (the pressure denominators)
    active: tuple  # replica ids participating in serving (may be down)
    parked: tuple  # scale-down-parked ids (the scale-up pool)
    quarantined: tuple
    pressure: float  # mean active_slots/capacity across live views
    queue_per_replica: float  # mean queued+outstanding per live view
    slo_alerting: bool = False
    cold_start_s: float = 0.0  # wake budget input (measured, or default)
    # Pending bulk-lane work items (ISSUE 19): a standing demand signal
    # the instantaneous pressure/queue reads cannot see — bulk dispatches
    # best_effort and is preempted first, so a busy fleet shows ZERO bulk
    # in its queues while hours of work wait in the lane.
    bulk_backlog: int = 0

    def snapshot(self) -> dict:
        """The journal/bundle form: small, flat-ish, host scalars only."""
        return {
            "pressure": round(self.pressure, 4),
            "queue_per_replica": round(self.queue_per_replica, 4),
            "live": len(self.views),
            "active": len(self.active),
            "parked": len(self.parked),
            "quarantined": len(self.quarantined),
            "slo_alerting": self.slo_alerting,
            "cold_start_s": round(self.cold_start_s, 3),
            "bulk_backlog": self.bulk_backlog,
            "tpot_p95_s": {
                v.id: round(v.tpot_p95_s, 6) for v in self.views
                if isinstance(v.tpot_p95_s, (int, float))
            },
        }


class ActionPlanner:
    """Signals -> typed actions, under hysteresis and cooldown guards.

    Pure host logic: ``plan()`` is called once per supervision pass with a
    fresh :class:`FleetSignals`; the planner keeps only the small state a
    control loop needs (streak counters, cooldown stamps, per-replica
    death windows). The ACTUATOR reports back via :meth:`note_executed` —
    cooldowns key on actions that actually happened, never on plans, so a
    refused plan cannot silently burn the window (the flapping-guard
    drill pins the journal order ``signal -> planned -> executed``).

    ``on_signal(name, snapshot)`` fires once when a hysteresis episode
    BEGINS (a pressure signal first crosses its threshold) — the causal
    head of the journal chain."""

    def __init__(self, config, *,
                 on_signal: Callable[[str, dict], None] | None = None):
        self.config = config
        self.on_signal = on_signal
        self._up_streak = 0
        self._down_streak = 0
        self._idle_since: float | None = None
        self._last_scale = float("-inf")
        self._remedy_last: dict[str, float] = {}
        # Death notes arrive on per-replica recovery threads and demand
        # notes on gateway request threads, while plan() iterates on the
        # supervisor thread — the cross-thread inputs take this lock (the
        # rest of the planner state is supervisor-thread-only).
        self._lock = threading.Lock()
        self._deaths: dict[str, collections.deque] = {}  # guarded-by: _lock
        self._wake_pending = False  # guarded-by: _lock

    # -- inputs from the supervisor/gateway ---------------------------------

    def note_death(self, replica_id: str, now: float | None = None) -> None:
        """One supervisor death note — the quarantine window's input.
        Called from recovery threads; never blocks on fleet state."""
        with self._lock:
            d = self._deaths.setdefault(
                replica_id, collections.deque(maxlen=64)
            )
            d.append(time.time() if now is None else now)

    def note_demand(self) -> None:
        """Demand arrived while nothing was routable: plan a wake on the
        next pass, bypassing hysteresis AND cooldown (answering demand
        must not wait out a scale-down's cooldown)."""
        with self._lock:
            self._wake_pending = True

    def note_executed(self, action: Action, now: float | None = None,
                      dry_run: bool = False) -> None:
        """The actuator executed ``action`` (or dry-ran it): stamp the
        cooldowns — dry-run must preview the real cadence, one action per
        window. Detection STATE is only consumed by real execution: a
        dry-run quarantine leaves the death history intact, so flipping
        dry_run off does not restart the crash-loop breaker's count from
        zero."""
        now = time.time() if now is None else now
        if action.kind in ("scale_up", "scale_down"):
            self._last_scale = now
            self._up_streak = self._down_streak = 0
            self._idle_since = None
        if action.kind in REMEDIATION_KINDS:
            self._remedy_last[action.target] = now
            if action.kind == "quarantine" and not dry_run:
                with self._lock:
                    self._deaths.pop(action.target, None)

    def note_replayed(self, kind: str, target: str, ts: float) -> None:
        """Journal-tail replay after a gateway crash (ISSUE 20): restamp
        the cooldown a previous incarnation's executed action started,
        WITHOUT re-executing anything. Only recency is rebuilt — streaks,
        death windows and wake state are detection state that the new
        incarnation re-observes live; a recovered planner that forgot
        its cooldowns would immediately re-plan an action whose window
        had not expired when the old gateway died. Stamps keep the max
        (the tail may replay out of order across rotated segments)."""
        if kind in ("scale_up", "scale_down"):
            self._last_scale = max(self._last_scale, ts)
        if kind in REMEDIATION_KINDS and target:
            prior = self._remedy_last.get(target, float("-inf"))
            self._remedy_last[target] = max(prior, ts)

    # -- planning -----------------------------------------------------------

    def _signal(self, name: str, signals: FleetSignals) -> None:
        if self.on_signal is not None:
            try:
                self.on_signal(name, signals.snapshot())
            except Exception:  # noqa: BLE001 - observer must not break plan
                logger.exception("autoscale: on_signal hook failed")

    def plan(self, signals: FleetSignals) -> list[Action]:
        cfg = self.config
        now = signals.now
        out: list[Action] = []
        out.extend(self._plan_quarantine(signals))
        out.extend(self._plan_drain(signals))
        # Wake (scale-to-zero admission): demand against an empty fleet
        # bypasses hysteresis and cooldown — the 429 the gateway answered
        # promised capacity within the wake budget.
        with self._lock:
            wake, self._wake_pending = self._wake_pending, False
        if wake:
            if not signals.views and signals.parked:
                self._signal("wake", signals)
                out.append(Action(
                    "scale_up", sorted(signals.parked)[0],
                    "wake: demand while scaled to zero",
                    signals.snapshot(), now, allow_zero=True,
                ))
                return out
        if not signals.views:
            # Nothing live to read pressure from (crash storm or scaled to
            # zero): demand scaling needs a denominator; remediation above
            # already did its work.
            self._up_streak = self._down_streak = 0
            self._idle_since = None
            return out
        cooled = now - self._last_scale >= cfg.cooldown_s
        # Bulk-lane coupling (armed only when bulk_scale_up_backlog > 0):
        # a deep offline backlog is demand even when every queue reads
        # empty — bulk is preempted first, so it never shows up there.
        bulk_coupled = cfg.bulk_scale_up_backlog > 0
        bulk_hot = (bulk_coupled
                    and signals.bulk_backlog >= cfg.bulk_scale_up_backlog)
        bulk_pending = bulk_coupled and signals.bulk_backlog > 0
        # -- scale up -------------------------------------------------------
        hot = (signals.pressure >= cfg.scale_up_pressure
               or signals.queue_per_replica >= cfg.scale_up_queue
               or bulk_hot)
        if hot:
            if self._up_streak == 0:
                self._signal("pressure_high", signals)
            self._up_streak += 1
        else:
            self._up_streak = 0
        if (hot and self._up_streak >= cfg.up_hysteresis_polls
                and signals.parked and cooled):
            out.append(Action(
                "scale_up", sorted(signals.parked)[0],
                f"pressure {signals.pressure:.2f} / queue "
                f"{signals.queue_per_replica:.2f} / bulk backlog "
                f"{signals.bulk_backlog} over "
                f"{self._up_streak} poll(s)",
                signals.snapshot(), now,
            ))
            return out
        # -- scale down -----------------------------------------------------
        # A pending bulk backlog vetoes parking: the lane exists to soak
        # exactly the capacity a scale-down would remove. Drain the
        # backlog first; THEN the fleet may shrink.
        idle = (signals.pressure <= cfg.scale_down_pressure
                and signals.queue_per_replica == 0
                and not bulk_pending)
        all_idle = signals.pressure == 0 and signals.queue_per_replica == 0 \
            and not bulk_pending \
            and all(v.outstanding == 0 for v in signals.views)
        if idle:
            if self._down_streak == 0:
                self._signal("pressure_low", signals)
            self._down_streak += 1
        else:
            self._down_streak = 0
        self._idle_since = (
            (self._idle_since or now) if all_idle else None
        )
        if not idle or signals.slo_alerting or not cooled:
            # A burning SLO pins the fleet size no matter how quiet the
            # instantaneous pressure looks.
            return out
        n_active = len(signals.active)
        floor = cfg.min_replicas
        # The floor binds on LIVE capacity, not the active roster: an
        # active-but-dead replica (mid-recovery, or given up on) serves
        # nothing, so parking a live one while dead peers pad the count
        # would take the fleet below its real floor.
        if self._down_streak >= cfg.hysteresis_polls and n_active > floor \
                and len(signals.views) > floor:
            out.append(Action(
                "scale_down", self._down_target(signals),
                f"pressure {signals.pressure:.2f} idle over "
                f"{self._down_streak} poll(s)",
                signals.snapshot(), now,
            ))
        elif (cfg.scale_to_zero and n_active > 0
              and self._idle_since is not None
              and now - self._idle_since >= cfg.idle_to_zero_s):
            out.append(Action(
                "scale_down", self._down_target(signals),
                f"idle {now - self._idle_since:.1f}s: scale to zero",
                signals.snapshot(), now, allow_zero=True,
            ))
        return out

    @staticmethod
    def _down_target(signals: FleetSignals) -> str:
        """Park the LEAST valuable replica: lowest windowed prefix-cache
        hit ratio first (its cache is the cheapest to lose — only its own
        ring keys remap), highest id among ties (low ids stay stable)."""
        return max(
            signals.views,
            key=lambda v: (-(round(v.recent_cache_hit_ratio or 0.0, 4)),
                           v.id),
        ).id

    def _plan_drain(self, signals: FleetSignals) -> list[Action]:
        """TPOT-storm remediation: one live replica far above its peers'
        median is the culprit (an even fleet-wide slowdown is load, not a
        culprit — nothing to drain)."""
        cfg = self.config
        rated = [v for v in signals.views
                 if isinstance(v.tpot_p95_s, (int, float))]
        if len(rated) < 2:
            return []
        worst = max(rated, key=lambda v: v.tpot_p95_s)
        peers = [v.tpot_p95_s for v in rated if v.id != worst.id]
        bar = max(cfg.tpot_storm_min_s,
                  cfg.tpot_storm_factor * statistics.median(peers))
        if worst.tpot_p95_s <= bar:
            return []
        last = self._remedy_last.get(worst.id, float("-inf"))
        if signals.now - last < cfg.remedy_cooldown_s:
            return []
        self._signal("tpot_storm", signals)
        return [Action(
            "drain", worst.id,
            f"tpot p95 {worst.tpot_p95_s:.3f}s > {bar:.3f}s "
            f"(peers' median x {cfg.tpot_storm_factor:g})",
            signals.snapshot(), signals.now,
        )]

    def _plan_quarantine(self, signals: FleetSignals) -> list[Action]:
        cfg = self.config
        out: list[Action] = []
        with self._lock:
            # Snapshot: recovery threads append death notes concurrently.
            deaths_by_rid = {rid: list(d)
                             for rid, d in self._deaths.items()}
        for rid, deaths in deaths_by_rid.items():
            if rid in signals.quarantined:
                continue
            recent = [t for t in deaths
                      if signals.now - t <= cfg.quarantine_window_s]
            if len(recent) < cfg.quarantine_deaths:
                continue
            last = self._remedy_last.get(rid, float("-inf"))
            if signals.now - last < cfg.remedy_cooldown_s:
                continue
            self._signal("death_storm", signals)
            out.append(Action(
                "quarantine", rid,
                f"{len(recent)} death(s) in {cfg.quarantine_window_s:g}s",
                signals.snapshot(), signals.now,
            ))
        return out


class Actuator:
    """Executes planned actions through FleetSupervisor primitives, under
    the supervisor's fleet-mutation lock, with the full observability
    spine (journal / flight ring / span / counters / incident bundle) on
    every outcome. ``dry_run`` plans-but-logs: the action journals and
    counts as planned, then records outcome ``dry_run`` without touching
    the fleet."""

    def __init__(
        self,
        fleet,
        supervisor,
        config,
        *,
        planner: ActionPlanner | None = None,
        journal=None,
        tracer=None,
        metrics=None,
        flight=None,
        plane=None,
        slo=None,
        bulk=None,
    ):
        """``journal``: EventJournal for ``action.*`` events; ``metrics``:
        GatewayMetrics (per-kind/outcome counters); ``flight``:
        FlightRecorder (ACTION ring); ``plane``: AnomalyPlane — executed
        remediation and failed actions become incident bundles through it;
        ``slo``: BurnRateMonitor whose ``any_alerting()`` pins the fleet
        size while burning; ``bulk``: BulkJobManager whose ``backlog()``
        feeds the bulk demand signal (ISSUE 19) — None reads as zero."""
        self.fleet = fleet
        self.supervisor = supervisor
        self.config = config
        self.planner = planner if planner is not None else ActionPlanner(
            config, on_signal=self._on_signal
        )
        if planner is not None and planner.on_signal is None:
            planner.on_signal = self._on_signal
        self.journal = journal
        self.tracer = tracer if tracer is not None else NULL_TRACER
        self.metrics = metrics
        self.flight = flight
        self.plane = plane
        self.slo = slo
        self.bulk = bulk
        # THE fleet-mutation lock — the same Lock object the supervisor's
        # crash recovery and rolling restarts hold (replica.py); sharing
        # the object is what serializes a scale event against a relaunch.
        self.fleet_lock = supervisor.fleet_lock
        self._executing = ""  # guarded-by: fleet_lock
        self._log_lock = threading.Lock()
        self._log: collections.deque = collections.deque(
            maxlen=max(1, getattr(config, "action_log", 256))
        )  # guarded-by: _log_lock
        # Written by signals() on the supervisor thread, read by gateway
        # request threads (/actions wake budget, note_demand).
        self._cold_lock = threading.Lock()
        self._cold_starts: dict[str, float] = {}  # guarded-by: _cold_lock

    # -- signal plumbing ----------------------------------------------------

    def _on_signal(self, name: str, snapshot: dict) -> None:
        """Hysteresis-episode head: the causal anchor the planned/executed
        events chain after in the journal."""
        self._journal_event("action.signal", signal_name=name,
                            signal=snapshot)
        if self.flight is not None:
            self.flight.ring(ACTION_RING).record(
                event="signal", signal_name=name, **snapshot
            )

    def _journal_event(self, event: str, **attrs) -> None:
        if self.journal is not None:
            try:
                self.journal.event(event, **attrs)
            except Exception:  # noqa: BLE001 - journal loss must not stop us
                logger.exception("autoscale: journal write failed")

    def note_death(self, replica_id: str) -> None:
        """Supervisor death-branch hook (never raises)."""
        try:
            self.planner.note_death(replica_id)
        except Exception:  # noqa: BLE001 - never break replica recovery
            logger.exception("autoscale: death note failed")

    def note_demand(self) -> int | None:
        """The gateway found nothing routable: if the fleet is genuinely
        asleep (NO routable replica anywhere, parked capacity available),
        request a wake and return the Retry-After the 429 should carry
        (the measured wake-up budget); None otherwise — a request that
        merely exhausted its retries against live-but-erroring replicas
        must get the fast 503, not a wake promise the planner (which
        wakes only an empty fleet) would silently drop."""
        try:
            if self.fleet.routable() or not self.fleet.parked_ids():
                return None
            self.planner.note_demand()
            return max(1, int(self.wake_budget_s() + 0.999))
        except Exception:  # noqa: BLE001 - admission must not crash
            logger.exception("autoscale: demand note failed")
            return None

    def wake_budget_s(self) -> float:
        """``wake_budget_factor`` x the largest MEASURED cold start any
        replica ever reported on /health (compile cache included);
        ``default_cold_start_s`` only bootstraps a fleet that has never
        reported one."""
        with self._cold_lock:
            measured = max(self._cold_starts.values(), default=0.0)
        base = measured if measured > 0 else self.config.default_cold_start_s
        return self.config.wake_budget_factor * base

    # -- the control loop ---------------------------------------------------

    def signals(self, now: float | None = None) -> FleetSignals:
        now = time.time() if now is None else now
        views = self.fleet.routable()
        with self._cold_lock:
            for v in views:
                if isinstance(v.cold_start_s, (int, float)):
                    self._cold_starts[v.id] = float(v.cold_start_s)
        n = len(views)
        pressure = (
            sum(v.slot_pressure for v in views) / n if n else 0.0
        )
        queue = (
            sum(v.queue_depth + v.outstanding for v in views) / n
            if n else 0.0
        )
        alerting = False
        if self.slo is not None:
            try:
                alerting = bool(self.slo.any_alerting())
            except Exception:  # noqa: BLE001 - a broken monitor reads calm
                alerting = False
        bulk_backlog = 0
        if self.bulk is not None:
            try:
                bulk_backlog = int(self.bulk.backlog())
            except Exception:  # noqa: BLE001 - a broken lane reads empty
                bulk_backlog = 0
        return FleetSignals(
            now=now,
            views=tuple(views),
            active=tuple(self.fleet.active_ids()),
            parked=tuple(self.fleet.parked_ids()),
            quarantined=tuple(self.fleet.quarantined_ids()),
            pressure=pressure,
            queue_per_replica=queue,
            slo_alerting=alerting,
            cold_start_s=self.wake_budget_s() / self.config.wake_budget_factor,
            bulk_backlog=bulk_backlog,
        )

    def poll(self) -> list[dict]:
        """One planner pass + actuation; rides the supervisor loop. Never
        raises — the supervisor thread it rides IS the fleet's crash
        recovery, and a broken actuation pass must not take that down.
        Returns the log entries this pass produced (tests)."""
        try:
            actions = self.planner.plan(self.signals())
            return [self.apply(a) for a in actions]
        except Exception:  # noqa: BLE001 - never break the health loop
            logger.exception("autoscale: actuation pass failed")
            return []

    # -- actuation ----------------------------------------------------------

    def apply(self, action: Action) -> dict:
        """Execute one action with the full observability spine. Returns
        the /actions log entry."""
        m = self.metrics
        dry = bool(self.config.dry_run)
        self._journal_event("action.planned", kind=action.kind,
                            target=action.target, reason=action.reason,
                            dry_run=dry, signal=action.signal)
        if self.flight is not None:
            self.flight.ring(ACTION_RING).record(
                event="planned", kind=action.kind, target=action.target,
                reason=action.reason, dry_run=dry,
            )
        if m is not None:
            m.action_counter(action.kind, "planned").inc()
        span = self.tracer.start_span(
            "gateway.action", kind=action.kind, target=action.target,
            reason=action.reason, dry_run=dry,
        )
        outcome, detail = "refused", ""
        try:
            if dry:
                outcome = "dry_run"
            else:
                # BOUNDED wait for the fleet-mutation lock: apply() runs
                # on the supervisor's run-loop thread, and a recovery leg
                # can hold the lock up to restart_timeout_s — blocking
                # here unboundedly would stall health probing of the
                # whole rest of the fleet behind one wedged relaunch. A
                # timed-out action refuses (cooldown un-stamped), so the
                # planner simply re-plans it on a later pass.
                lock_wait = max(5.0, 2 * self.config.drain_wait_s)
                if not self.fleet_lock.acquire(timeout=lock_wait):
                    detail = (f"fleet-mutation lock busy after "
                              f"{lock_wait:.0f}s (recovery or rolling "
                              "restart in progress); will replan")
                else:
                    try:
                        outcome, detail = self._apply_holding_locked(action)
                    finally:
                        self.fleet_lock.release()
        except Exception as e:  # noqa: BLE001 - incl. InjectedFault
            outcome, detail = "failed", f"{type(e).__name__}: {e}"
            logger.exception("autoscale: %s %s failed",
                             action.kind, action.target)
        if outcome in ("executed", "dry_run"):
            # Dry-run stamps the cooldowns too: plan-but-log must PREVIEW
            # the real cadence (one action per cooldown window), not
            # re-plan the identical action every supervisor pass — the
            # fleet state a real execute would change cannot change here,
            # so the cooldown is the only thing bounding repetition.
            self.planner.note_executed(action, dry_run=(outcome == "dry_run"))
        if outcome != "dry_run":
            self._journal_event(f"action.{outcome}", kind=action.kind,
                                target=action.target, detail=detail,
                                signal=action.signal)
        if self.flight is not None:
            self.flight.ring(ACTION_RING).record(
                event=outcome, kind=action.kind, target=action.target,
                detail=detail,
            )
        if m is not None:
            m.action_counter(action.kind, outcome).inc()
        try:
            # The span write lands in the journal file; a full disk must
            # cost the trace record, never the action log entry below (or
            # the supervisor thread this runs on).
            span.end(outcome=outcome)
        except Exception:  # noqa: BLE001 - observability loss only
            logger.exception("autoscale: action span write failed")
        incident = None
        if self.plane is not None and (
            outcome == "failed"
            or (outcome == "executed" and action.kind in REMEDIATION_KINDS)
        ):
            # Remediation leaves the same forensic trail as the failure it
            # chased: ring dumps (incl. the ACTION ring), metrics, journal
            # tail, trace slice, chaos attribution — one bundle.
            incident = self.plane.trigger(Anomaly(
                f"action.{action.kind}",
                severity="warning",
                detail={"fingerprint_key": action.target,
                        "target": action.target,
                        "outcome": outcome,
                        "reason": action.reason,
                        "action_detail": detail,
                        "signal": action.signal},
            ))
        entry = {
            "ts": action.ts,
            "kind": action.kind,
            "target": action.target,
            "reason": action.reason,
            "outcome": outcome,
            "detail": detail,
            "dry_run": dry,
            "signal": action.signal,
            "incident": incident,
        }
        with self._log_lock:
            self._log.append(entry)
        return entry

    def recent(self) -> list[dict]:
        """The bounded action log, oldest first (the /actions body)."""
        with self._log_lock:
            return list(self._log)

    # -- executors (caller holds fleet_lock) --------------------------------

    def _apply_holding_locked(self, action: Action) -> tuple[str, str]:
        """The under-lock half of :meth:`apply`; caller holds (and
        releases) ``fleet_lock`` via the timed acquire above."""
        self._executing = f"{action.kind}:{action.target}"
        try:
            # Chaos seam (ISSUE 12 satellite): inside the lock on purpose
            # — a delay here WIDENS the window a racing kill/rolling-
            # restart must serialize against; error = a failed actuation.
            maybe_inject("supervisor.action")
            return self._execute_locked(action)
        finally:
            self._executing = ""

    def _execute_locked(self, action: Action) -> tuple[str, str]:
        if action.kind == "scale_up":
            return self._scale_up_locked(action)
        if action.kind == "scale_down":
            return self._scale_down_locked(action)
        if action.kind == "drain":
            return self._drain_locked(action)
        if action.kind == "quarantine":
            return self._quarantine_locked(action)
        return "refused", f"unknown action kind {action.kind!r}"

    def _scale_up_locked(self, action: Action) -> tuple[str, str]:
        # Re-validate under the lock: the world may have moved since the
        # plan (another actor already woke it, an operator removed it).
        parked = self.fleet.parked_ids()
        rid = action.target if action.target in parked else (
            sorted(parked)[0] if parked else ""
        )
        if not rid:
            return "refused", "no parked replica to activate"
        st = self.fleet._state(rid)
        self.fleet.set_deactivated(rid, False)
        st.handle.start()
        if self.supervisor._await_healthy(rid):
            st.fails = 0
            self.fleet.mark_draining(rid, False)
            return "executed", f"activated {rid}"
        # Revert: a replica that cannot come up must not sit half-active
        # soaking supervisor recovery attempts against a broken image.
        st.handle.stop(drain=False, timeout=0.0)
        st.live = False
        self.fleet.set_deactivated(rid, True)
        return "failed", f"{rid} did not become healthy"

    def _scale_down_locked(self, action: Action) -> tuple[str, str]:
        rid = action.target
        active = self.fleet.active_ids()
        if rid not in active:
            return "refused", f"{rid} is not active"
        floor = 0 if action.allow_zero else self.config.min_replicas
        if len(active) - 1 < floor:
            return "refused", (
                f"would leave {len(active) - 1} active < floor {floor}"
            )
        # The floor binds on LIVE capacity too: active-but-dead replicas
        # (mid-recovery or given up on) pad the roster without serving,
        # and parking a live one behind that padding would leave fewer
        # than `floor` replicas actually answering requests.
        live = [r for r in active if self.fleet._state(r).live]
        if rid in live and len(live) - 1 < floor:
            return "refused", (
                f"would leave {len(live) - 1} live < floor {floor}"
            )
        st = self.fleet._state(rid)
        # Park FIRST: routing stops, the supervisor's poll skips it, and a
        # concurrent death of this very replica resolves to "down on
        # purpose" instead of a relaunch (the scale-down-racing-kill
        # drill).
        self.fleet.set_deactivated(rid, True)
        self.fleet.mark_draining(rid, True)
        self.supervisor.drain_stop_locked(rid, st, self.config.drain_wait_s)
        self.fleet.mark_draining(rid, False)
        return "executed", f"parked {rid}"

    def _drain_locked(self, action: Action) -> tuple[str, str]:
        rid = action.target
        if rid not in self.fleet.active_ids():
            return "refused", f"{rid} is not active"
        st = self.fleet._state(rid)
        self.fleet.mark_draining(rid, True)
        self.supervisor.drain_stop_locked(rid, st, self.config.drain_wait_s)
        st.handle.start()
        if self.supervisor._await_healthy(rid):
            st.fails = 0
            self.fleet.mark_draining(rid, False)
            return "executed", f"drained and restarted {rid}"
        # Leave it draining-and-dead: it is NOT parked, so the supervisor's
        # ordinary recovery keeps trying after the lock releases — but
        # ONLY if the failure count reads dead. Pin it to the threshold
        # (the _recover_cycle_locked rule): a replica that turns healthy
        # just after our await timed out would otherwise probe fails=0,
        # live=True with draining stuck True — permanently unroutable.
        st.fails = max(st.fails, self.supervisor.fail_threshold)
        return "failed", f"{rid} did not come back after drain"

    def _quarantine_locked(self, action: Action) -> tuple[str, str]:
        rid = action.target
        st = self.fleet._state(rid)
        if st.quarantined:
            return "refused", f"{rid} already quarantined"
        self.fleet.set_quarantined(rid, True)
        self.fleet.mark_draining(rid, True)
        # Hard stop: a crash-looping replica has nothing worth draining.
        st.handle.stop(drain=False, timeout=0.0)
        st.live = False
        self.fleet.mark_draining(rid, False)
        return "executed", f"quarantined {rid}"


class ReplicaSecondsSampler:
    """Integral of live replica count over wall time — the resource-cost
    number a trace replay reports for the autoscaler (perf_compare gates it
    downward).
    Sampling, not transition-tracking: the supervisor mutates liveness
    from several threads and a 50 ms Riemann sum is honest enough for
    runs measured in seconds-to-hours."""

    def __init__(self, fleet, interval_s: float = 0.05):
        self.fleet = fleet
        self.interval_s = interval_s
        self._total = 0.0
        self._stop = threading.Event()
        self._thread: threading.Thread | None = None

    def start(self) -> "ReplicaSecondsSampler":
        self._stop.clear()
        self._thread = threading.Thread(
            target=self._run, daemon=True, name="replica-seconds"
        )
        self._thread.start()
        return self

    def _run(self) -> None:
        last = time.monotonic()
        while not self._stop.wait(self.interval_s):
            now = time.monotonic()
            self._total += self.fleet.live_count() * (now - last)
            last = now

    def stop(self) -> float:
        self._stop.set()
        if self._thread is not None:
            self._thread.join(timeout=5.0)
            self._thread = None
        return self.total

    @property
    def total(self) -> float:
        return self._total


class TrafficRecorder:
    """``--save-trace``: one JSONL row per ADMITTED request — arrival
    offset from the first admitted request, tenant digest (the
    credential-safe label, never the bearer token), SLO class, and the
    gateway's tokenizer-free prompt/max_new estimates. The shape
    :func:`load_trace` reads back for a replay with preserved inter-arrival
    times. Line-buffered appends: a killed gateway loses at most the row
    it never wrote (the journal contract)."""

    def __init__(self, path: str):
        if not path:
            raise ValueError("TrafficRecorder needs a path")
        self.path = path
        os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
        self._f = open(path, "a", buffering=1)
        self._lock = threading.Lock()
        self._t0: float | None = None  # guarded-by: _lock
        self.rows = 0

    def note(self, *, tenant: str = "", slo_class: str | None = None,
             prompt_tokens: int = 0, max_new: int = 0,
             stream: bool = False, now: float | None = None) -> None:
        now = time.time() if now is None else now
        with self._lock:
            if self._t0 is None:
                self._t0 = now
            row = {
                "t": round(now - self._t0, 4),
                "tenant": tenant,
                "slo_class": slo_class,
                "prompt_tokens": int(prompt_tokens),
                "max_new": int(max_new),
                "stream": bool(stream),
            }
            try:
                self._f.write(json.dumps(row, sort_keys=True) + "\n")
                self.rows += 1
            except OSError:
                logger.exception("traffic recorder: write failed")

    def close(self) -> None:
        with self._lock:
            try:
                self._f.close()
            except OSError:
                pass


def load_trace(path: str) -> list[dict]:
    """Read a recorded traffic trace, oldest first. Corrupt lines (the
    torn tail a kill leaves) are skipped, never an error; offsets are
    re-zeroed to the first row so replays always start at t=0."""
    rows: list[dict] = []
    with open(path) as f:
        for line in f:
            line = line.strip()
            if not line:
                continue
            try:
                row = json.loads(line)
            except ValueError:
                continue
            if not isinstance(row, dict):
                continue
            t = row.get("t")
            if not isinstance(t, (int, float)) or t < 0:
                continue
            rows.append(row)
    rows.sort(key=lambda r: r["t"])
    if rows:
        t0 = rows[0]["t"]
        for r in rows:
            r["t"] = round(r["t"] - t0, 4)
    return rows
