"""Flight recorder + anomaly plane + incident bundles (ISSUE 10).

- jax-free units: ring bounds/snapshots, training/serving/gateway
  detectors, fingerprint dedupe + cooldown, bundle GC caps, torn-bundle
  hygiene (chaos kill at the ``incident.dump`` seam), the SLO
  alert-transition hook, perf_compare's incident gating, and the CLI.
- THE acceptance drills (tier-1): a chaos-forced deadline storm on a real
  serving engine and an injected non-finite loss on a real training run
  each produce exactly ONE fingerprint-deduped bundle whose contents
  verify (tick ring parseable, metrics snapshot carries the triggering
  family, trace slice is valid Chrome-trace JSON, ``injected_fault``
  present for the chaos case) — while identical healthy runs produce
  ZERO bundles, and flight recording adds no blocking device transfers
  and no ring iteration on the /metrics scrape path.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import threading
import time
import urllib.request

import pytest

from ditl_tpu.telemetry.anomaly import (
    Anomaly,
    AnomalyPlane,
    GatewayDetector,
    NonFiniteMetricError,
    ServingAnomalyMonitor,
    ServingDetector,
    TrainingDetector,
)
from ditl_tpu.telemetry.flight import (
    STEP_RING,
    TICK_RING,
    FlightRecorder,
    FlightRing,
)
from ditl_tpu.telemetry.incident import (
    IncidentManager,
    incidents_total,
    list_bundles,
    read_bundle,
)

pytestmark = pytest.mark.incident

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ---------------------------------------------------------------------------
# flight rings
# ---------------------------------------------------------------------------


def test_flight_ring_bounded_and_ordered():
    ring = FlightRing("t", capacity=4)
    for i in range(10):
        ring.record(i=i)
    rows = ring.dump()
    assert [r["i"] for r in rows] == [6, 7, 8, 9]  # newest 4, oldest first
    assert len(ring) == 4 and ring.recorded == 10
    assert all("ts" in r for r in rows)


def test_flight_recorder_get_or_create_and_dump_all():
    rec = FlightRecorder(capacity=8)
    assert rec.ring("a") is rec.ring("a")
    rec.ring("a").record(x=1)
    rec.ring("empty")  # never recorded: excluded from dumps
    dumped = rec.dump_all()
    assert list(dumped) == ["a"] and dumped["a"][0]["x"] == 1


# ---------------------------------------------------------------------------
# detectors
# ---------------------------------------------------------------------------


def test_training_detector_nonfinite_and_spike():
    det = TrainingDetector(window=16, min_history=4, loss_spike_factor=3.0,
                           grad_explosion_factor=5.0)
    for step in range(6):
        assert det.observe_step(step, 2.0, 1.0) == []
    spike = det.observe_step(6, 7.0)  # 3.5x the rolling median of 2.0
    assert [a.kind for a in spike] == ["train.loss_spike"]
    boom = det.observe_step(7, 2.0, 6.0)
    assert [a.kind for a in boom] == ["train.grad_explosion"]
    fatal = det.observe_step(8, float("nan"), float("inf"))
    assert sorted(a.kind for a in fatal) == [
        "train.grad_nonfinite", "train.loss_nonfinite"]
    assert all(a.severity == "fatal" for a in fatal)


def test_serving_detector_storms_and_queue_growth():
    from ditl_tpu.telemetry.serving import ServingMetrics

    m = ServingMetrics()
    det = ServingDetector(storm_threshold=5, queue_depth_limit=10)
    assert det.observe({"queue_depth": 0}, m) == []
    m.deadline_expired.inc(6)
    m.queue_full.inc(5)
    kinds = sorted(a.kind for a in det.observe({"queue_depth": 0}, m))
    assert kinds == ["serving.429_storm", "serving.deadline_storm"]
    # same cumulative values next window: deltas are zero, nothing fires
    assert det.observe({"queue_depth": 0}, m) == []
    # deep AND growing queue fires; deep-but-stable does not
    out = det.observe({"queue_depth": 15}, m)
    assert [a.kind for a in out] == ["serving.queue_growth"]
    assert det.observe({"queue_depth": 15}, m) == []


def test_serving_detector_latency_jump_vs_rolling_baseline():
    from ditl_tpu.telemetry.serving import ServingMetrics

    m = ServingMetrics()
    det = ServingDetector(latency_factor=3.0, min_samples=8)
    for _ in range(20):
        m.ttft.observe(0.01)
    assert det.observe({"queue_depth": 0}, m) == []  # first window: baseline
    for _ in range(20):
        m.ttft.observe(0.01)
    assert det.observe({"queue_depth": 0}, m) == []  # steady
    for _ in range(20):
        m.ttft.observe(2.0)  # 200x jump
    out = det.observe({"queue_depth": 0}, m)
    assert [a.kind for a in out] == ["serving.ttft_jump"]
    assert out[0].detail["window_p95_s"] > out[0].detail["baseline_p95_s"]


def test_gateway_detector_death_rate_and_spill_storm():
    from ditl_tpu.gateway.gateway import GatewayMetrics

    det = GatewayDetector(storm_threshold=4, death_threshold=2,
                          death_window_s=60.0)
    assert det.note_death("r0") == []
    out = det.note_death("r1")
    assert [a.kind for a in out] == ["gateway.replica_death_storm"]
    g = GatewayMetrics()
    assert det.observe(g) == []
    g.saturated.inc(3)
    g.no_replica.inc(2)
    assert [a.kind for a in det.observe(g)] == ["gateway.spill_storm"]


# ---------------------------------------------------------------------------
# incident manager: dedupe, cooldown, retention, hygiene
# ---------------------------------------------------------------------------


def test_incident_dedupe_cooldown_and_counters(tmp_path):
    from ditl_tpu.telemetry.registry import MetricsRegistry

    r = MetricsRegistry()
    flight = FlightRecorder()
    flight.ring(TICK_RING).record(tick=1)
    man = IncidentManager(str(tmp_path), flight=flight, registry=r,
                          cooldown_s=3600.0,
                          metrics_render=lambda: "ditl_x_total 1")
    a = Anomaly("serving.deadline_storm", detail={"window_count": 9})
    path = man.trigger(a)
    assert path is not None and os.path.isdir(path)
    # same fingerprint within cooldown: suppressed, counted, no bundle
    assert man.trigger(Anomaly("serving.deadline_storm")) is None
    assert man.trigger(Anomaly("serving.deadline_storm")) is None
    # a DIFFERENT kind is a different fingerprint: new bundle
    other = man.trigger(Anomaly("serving.429_storm"))
    assert other is not None
    bundles = list_bundles(str(tmp_path))
    assert len(bundles) == 2
    first = bundles[0]
    assert first["trigger"] == "serving.deadline_storm"
    assert first["detail"]["window_count"] == 9
    assert first["git_rev"] and first["schema"] == 1
    assert "metrics.prom" in first["files"]
    assert os.path.join("flight", "engine_tick.jsonl") in first["files"]
    samples = r.render()
    assert "ditl_incidents_total 2" in samples
    assert "ditl_incidents_suppressed_total 2" in samples
    assert "ditl_incidents_trigger_serving_deadline_storm_total 1" in samples
    assert incidents_total() >= 2  # process-wide count a run's row embeds


def test_failed_assembly_does_not_burn_cooldown(tmp_path, monkeypatch):
    """A transient dump failure (ENOSPC, unreadable journal) must not
    suppress the NEXT trigger for the same fingerprint — the cooldown
    stamp is rolled back so a real incident still gets its bundle."""
    man = IncidentManager(str(tmp_path), cooldown_s=3600.0)
    orig = man._assemble
    calls = {"n": 0}

    def flaky(*args, **kwargs):
        calls["n"] += 1
        if calls["n"] == 1:
            raise OSError("injected: disk full")
        return orig(*args, **kwargs)

    monkeypatch.setattr(man, "_assemble", flaky)
    assert man.trigger(Anomaly("serving.deadline_storm")) is None
    path = man.trigger(Anomaly("serving.deadline_storm"))
    assert path is not None and len(list_bundles(str(tmp_path))) == 1
    # a failed dump is not "suppressed" — that counter stays honest
    assert man.suppressed_total == 0
    assert man.trigger(Anomaly("serving.deadline_storm")) is None  # cooldown
    assert man.suppressed_total == 1  # lifetime, endpoint-read, never reset


def test_incident_gc_count_and_size_caps(tmp_path):
    man = IncidentManager(str(tmp_path), cooldown_s=0.0, max_bundles=3,
                          max_total_mb=64.0)
    for i in range(6):
        assert man.trigger(Anomaly(f"kind.{i}")) is not None
    names = sorted(n for n in os.listdir(str(tmp_path))
                   if n.startswith("incident-"))
    assert len(names) == 3
    assert all(f"-00{i}-" not in n for n in names for i in (1, 2, 3))
    # size cap: bundles with a fat payload GC oldest-first below the cap
    man2 = IncidentManager(str(tmp_path / "sz"), cooldown_s=0.0,
                           max_bundles=100, max_total_mb=0.002,  # ~2 KB
                           metrics_render=lambda: "x" * 1500)
    man2.trigger(Anomaly("a"))
    man2.trigger(Anomaly("b"))
    kept = list_bundles(str(tmp_path / "sz"))
    assert len(kept) == 1 and kept[0]["trigger"] == "b"  # newest survives


def test_torn_bundle_is_invisible_and_swept(tmp_path):
    """A kill mid-dump (chaos `incident.dump:kill`) leaves only a hidden
    tmp dir: --list skips it, and the next manager sweeps it."""
    d = str(tmp_path / "inc")
    code = (
        "import sys\n"
        "from ditl_tpu.chaos import arm, plane\n"
        "from ditl_tpu.telemetry.anomaly import Anomaly\n"
        "from ditl_tpu.telemetry.incident import IncidentManager\n"
        "arm(plane.FaultPlane(rules='incident.dump:kill@max=1'))\n"
        "man = IncidentManager(sys.argv[1])\n"
        "man.trigger(Anomaly('serving.deadline_storm'))\n"
        "print('NOT REACHED')\n"
    )
    out = subprocess.run([sys.executable, "-c", code, d],
                         capture_output=True, text=True, cwd=REPO_ROOT,
                         timeout=120)
    assert out.returncode == -9, (out.returncode, out.stderr)  # SIGKILLed
    assert "NOT REACHED" not in out.stdout
    torn = [n for n in os.listdir(d) if n.startswith(".tmp-")]
    assert len(torn) == 1, os.listdir(d)
    # the torn dir holds a complete-looking manifest, yet --list skips it
    assert list_bundles(d) == []
    cli = subprocess.run(
        [sys.executable, "-m", "ditl_tpu.telemetry.incident", "--dir", d],
        capture_output=True, text=True, cwd=REPO_ROOT, timeout=120)
    assert cli.returncode == 0 and "no incident bundles" in cli.stdout
    # next manager construction sweeps the torn dir
    IncidentManager(d)
    assert [n for n in os.listdir(d) if n.startswith(".tmp-")] == []


def test_incident_cli_list_and_show(tmp_path):
    man = IncidentManager(str(tmp_path), cooldown_s=0.0)
    path = man.trigger(Anomaly("elastic.worker_death",
                               detail={"worker": 1}))
    name = os.path.basename(path)
    cli = subprocess.run(
        [sys.executable, "-m", "ditl_tpu.telemetry.incident",
         "--dir", str(tmp_path)],
        capture_output=True, text=True, cwd=REPO_ROOT, timeout=120)
    assert cli.returncode == 0
    assert name in cli.stdout and "elastic.worker_death" in cli.stdout
    show = subprocess.run(
        [sys.executable, "-m", "ditl_tpu.telemetry.incident",
         "--dir", str(tmp_path), "--show", name],
        capture_output=True, text=True, cwd=REPO_ROOT, timeout=120)
    assert show.returncode == 0
    manifest = json.loads(show.stdout)
    assert manifest["trigger"] == "elastic.worker_death"
    assert manifest["detail"]["worker"] == 1


# ---------------------------------------------------------------------------
# SLO alert transition (satellite): journaled + plane-fired, headlessly
# ---------------------------------------------------------------------------


def test_slo_alert_transition_journals_and_triggers(tmp_path):
    from ditl_tpu.telemetry.journal import EventJournal, read_journal
    from ditl_tpu.telemetry.slo import BurnRateMonitor, Objective

    state = {"good": 100.0, "total": 100.0}
    journal = EventJournal(str(tmp_path / "events-x.jsonl"), source="x")
    plane = AnomalyPlane(
        incidents=IncidentManager(str(tmp_path / "inc"), cooldown_s=3600.0),
        journal=journal,
    )
    mon = BurnRateMonitor(
        [Objective(name="avail", target=0.9,
                   good_total=lambda: (state["good"], state["total"]))],
        windows=(10.0, 60.0), journal=journal,
        on_alert=plane.on_slo_alert,
    )
    t0 = time.time()
    mon.report(now=t0)
    state["total"] += 50  # 50 new requests, ALL bad: burn >> 1
    rep = mon.report(now=t0 + 61.0)
    assert rep["objectives"]["avail"]["alerting"]
    # sustained burn: no re-fire while alerting stays true
    state["total"] += 50
    mon.report(now=t0 + 122.0)
    events = [r["event"] for r in read_journal(journal.path)]
    assert events.count("slo.alert") == 1
    assert events.count("anomaly.detected") == 1
    bundles = list_bundles(str(tmp_path / "inc"))
    assert len(bundles) == 1 and bundles[0]["trigger"] == "slo.burn_alert"
    assert bundles[0]["detail"]["objective"] == "avail"


# ---------------------------------------------------------------------------
# perf_compare gating (satellite)
# ---------------------------------------------------------------------------


def test_perf_compare_gates_new_incidents():
    from ditl_tpu.telemetry.perf_compare import compare_records

    clean = {"metric": "tok/s", "value": 100.0, "incidents": 0}
    stormy = {"metric": "tok/s", "value": 120.0, "incidents": 3}
    code, report = compare_records(clean, stormy, 0.05)
    assert code == 1 and "incidents: 0 -> 3" in report  # faster AND stormy: fails
    # both sides stormy: reported, not gated
    code, report = compare_records(
        {**clean, "incidents": 2}, stormy, 0.05)
    assert code == 0 and "not gated" in report
    # incidents cleared: never a regression
    code, _ = compare_records(stormy, clean, 0.30)
    assert code == 0


# ---------------------------------------------------------------------------
# THE acceptance drills
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def tiny_model():
    import jax

    from ditl_tpu.config import ModelConfig
    from ditl_tpu.data.tokenizer import ByteTokenizer
    from ditl_tpu.models import llama

    cfg = ModelConfig(
        vocab_size=512, hidden_size=64, intermediate_size=128, num_layers=2,
        num_heads=4, num_kv_heads=2, head_dim=16, max_seq_len=128,
        dtype="float32", param_dtype="float32",
    )
    params = llama.init_params(jax.random.key(0), cfg)
    return params, cfg, ByteTokenizer()


def _serving_run(tmp_path, tiny_model, tag: str, chaos_rules: str):
    """One serving leg: warm the engine, submit one live request plus a
    herd with deadlines, run to completion. With ``chaos_rules`` injecting
    per-tick delays the deadlines blow (a chaos-FORCED storm); without
    them the identical run completes healthily."""
    from ditl_tpu import chaos
    from ditl_tpu.infer.continuous import ContinuousEngine
    from ditl_tpu.infer.engine import GenerateConfig
    from ditl_tpu.telemetry.journal import EventJournal
    from ditl_tpu.telemetry.serving import ServingMetrics
    from ditl_tpu.telemetry.tracing import Tracer

    params, cfg, tok = tiny_model
    inc_dir = str(tmp_path / f"incidents-{tag}")
    journal_dir = str(tmp_path / f"journal-{tag}")
    journal = EventJournal(
        os.path.join(journal_dir, f"events-server-{tag}.jsonl"),
        source=f"server-{tag}")
    metrics = ServingMetrics()
    flight = FlightRecorder()
    incidents = IncidentManager(
        inc_dir, flight=flight, metrics_render=metrics.render,
        journal_dir=journal_dir, registry=metrics.registry,
        cooldown_s=3600.0, trace_window_s=120.0, source=f"server-{tag}")
    monitor = ServingAnomalyMonitor(
        AnomalyPlane(incidents=incidents, journal=journal),
        ServingDetector(storm_threshold=8),
        check_every=2,
    )
    eng = ContinuousEngine(
        params, cfg, tok, n_slots=1, decode_chunk=4,
        gen=GenerateConfig(max_new_tokens=4),
        metrics=metrics, tracer=Tracer(journal), flight=flight,
        anomaly=monitor,
    )
    prompt = [tok.bos_id] + tok.encode("hello")
    eng.submit(list(prompt))  # warm: compile happens on an undeadlined run
    eng.run()
    if chaos_rules:
        chaos.arm(chaos.FaultPlane(rules=chaos_rules, journal=journal))
    try:
        # The live request holds the single slot for ~16 ticks; behind the
        # injected per-tick stalls the queued herd's deadlines blow before
        # any of them can be admitted.
        eng.submit(list(prompt), max_new_tokens=64)
        for i in range(10):
            eng.submit([tok.bos_id] + tok.encode(f"doomed-{i}"),
                       deadline_s=2.0)
        eng.run()
    finally:
        chaos.disarm()
    journal.close()
    return eng, metrics, inc_dir


@pytest.mark.chaos
def test_acceptance_chaos_deadline_storm_yields_one_attributed_bundle(
    tmp_path, tiny_model
):
    """THE serving acceptance drill: a chaos rule stalls scheduler ticks
    until a herd of deadlined requests expires en masse; the storm yields
    exactly ONE bundle whose contents verify, carrying the
    injected_fault attribution — and the identical run WITHOUT the chaos
    rule produces ZERO bundles."""
    eng, metrics, inc_dir = _serving_run(
        tmp_path, tiny_model, "storm",
        # 0.35 s injected stall per tick, 8 times: ~2.8 s of scheduler
        # stall against 2 s deadlines — the deadlines expire BECAUSE of
        # the injected fault.
        "engine.tick:delay@delay=0.35,max=8",
    )
    assert metrics.deadline_expired.value >= 8
    bundles = list_bundles(inc_dir)
    assert len(bundles) == 1, [b["trigger"] for b in bundles]
    m = bundles[0]
    assert m["trigger"] == "serving.deadline_storm"
    # chaos attribution: the bundle names the injected fault (fire count
    # is whatever had fired by assembly time — the storm was mid-flight)
    assert m["injected_fault"]["injected"]["engine.tick:delay"] >= 1
    assert m["injected_fault"]["rules"] == ["engine.tick:delay"]
    path = m["path"]
    # tick ring dump present and parseable, with the scheduler's story
    ring_path = os.path.join(path, "flight", "engine_tick.jsonl")
    rows = [json.loads(ln) for ln in open(ring_path)]
    assert rows and rows[-1]["tick"] >= rows[0]["tick"]
    assert any(r["deadline_expired"] >= 8 for r in rows)
    assert {"queue_depth", "queue_by_class", "slots_busy",
            "prefill_tokens"} <= rows[-1].keys()
    # metrics snapshot includes the triggering family
    prom = open(os.path.join(path, "metrics.prom")).read()
    assert "ditl_serving_deadline_expired_total" in prom
    # trace slice is valid Chrome-trace JSON over the affected window
    trace = json.load(open(os.path.join(path, "trace_slice.json")))
    assert isinstance(trace["traceEvents"], list) and trace["traceEvents"]
    # journal tail rode along
    assert "journal_tail.jsonl" in m["files"]
    # incident counters visible on the same registry /metrics renders
    assert "ditl_incidents_total 1" in metrics.render()

    # the identical healthy run: zero bundles, zero expiries
    eng2, metrics2, inc_dir2 = _serving_run(
        tmp_path, tiny_model, "healthy", "")
    assert metrics2.deadline_expired.value == 0
    assert list_bundles(inc_dir2) == []
    assert len(eng2.flight.ring(TICK_RING)) > 0  # always-on ring, no dumps


def _train_config(tmp_path, tag, **train_kw):
    from ditl_tpu.config import (
        Config, DataConfig, ModelConfig, TelemetryConfig, TrainConfig,
    )

    return Config(
        model=ModelConfig(
            vocab_size=512, hidden_size=64, intermediate_size=128,
            num_layers=2, num_heads=4, num_kv_heads=2, head_dim=16,
            max_seq_len=64,
        ),
        data=DataConfig(synthetic=True, synthetic_examples=64, batch_size=8,
                        seq_len=32, num_epochs=1),
        train=TrainConfig(**{
            "total_steps": 6, "warmup_steps": 1, "log_every": 2,
            "telemetry_dir": str(tmp_path / f"tele-{tag}"),
            **train_kw,
        }),
        telemetry=TelemetryConfig(
            incident_dir=str(tmp_path / f"incidents-{tag}")),
    )


def test_acceptance_nonfinite_loss_bundles_then_crashes(
    tmp_path, monkeypatch
):
    """THE training acceptance drill: an injected NaN loss produces
    exactly ONE bundle (step ring + metrics + trace slice) BEFORE the run
    crashes with NonFiniteMetricError; the identical healthy run produces
    ZERO bundles — and arming the whole plane adds ZERO blocking device
    transfers beyond the metrics path's existing log_every flushes."""
    import jax

    from ditl_tpu.train.trainer import train

    calls: list[int] = []
    real_device_get = jax.device_get

    def counting_device_get(x):
        calls.append(1)
        return real_device_get(x)

    monkeypatch.setattr(jax, "device_get", counting_device_get)

    # healthy run first: completes, zero bundles, the blocking-transfer
    # budget is EXACTLY the pre-ISSUE-10 count (4 metric flushes + 1
    # summary final_loss — pinned against test_telemetry's baseline).
    out = train(_train_config(tmp_path, "healthy"))
    assert out["steps"] == 6
    assert len(calls) == 5, f"flight/anomaly plane added syncs: {len(calls)}"
    assert out.get("incidents", 0) == 0 and "anomalies" not in out
    # per-worker subdirectory (SPMD workers must not race one directory)
    assert list_bundles(str(tmp_path / "incidents-healthy" / "worker-0")) \
        == []

    # nan-injected run: ONE bundle, then the crash
    with pytest.raises(NonFiniteMetricError, match="loss_nonfinite"):
        train(_train_config(tmp_path, "nan", fault_nan_step=4))
    bundles = list_bundles(str(tmp_path / "incidents-nan" / "worker-0"))
    assert len(bundles) == 1
    m = bundles[0]
    assert m["trigger"] == "train.loss_nonfinite"
    assert m["severity"] == "fatal"
    assert "injected_fault" not in m  # organic as far as the chaos plane knows
    assert m["config"]["train"]["fault_nan_step"] == 4  # config stamped
    ring_path = os.path.join(m["path"], "flight", STEP_RING + ".jsonl")
    rows = [json.loads(ln) for ln in open(ring_path)]
    # the step ring carries the run's loss history INCLUDING the poisoned
    # step (json NaN round-trips through python's reader)
    assert any(r["loss"] != r["loss"] for r in rows)
    assert any(r["loss"] == r["loss"] for r in rows)
    trace = json.load(open(os.path.join(m["path"], "trace_slice.json")))
    assert isinstance(trace["traceEvents"], list)


def test_tail_window_nonfinite_crashes_after_clean_teardown(tmp_path):
    """A NaN surfaced only by the teardown's catch-up flush (last window
    never hits a log_every boundary) must still bundle + crash — but
    AFTER teardown completes (journal closed with worker.exit, barrier
    passed), never from inside the finally block."""
    from ditl_tpu.telemetry.journal import read_journal, worker_journal_path
    from ditl_tpu.train.trainer import train

    # steps 0..5 at log_every=4 flush at 0 and 4; step 5 (state.step 6)
    # carries the NaN and is flushed only by metrics.close() in teardown.
    with pytest.raises(NonFiniteMetricError, match="loss_nonfinite"):
        train(_train_config(tmp_path, "tail", log_every=4,
                            fault_nan_step=6))
    bundles = list_bundles(str(tmp_path / "incidents-tail" / "worker-0"))
    assert len(bundles) == 1
    assert bundles[0]["trigger"] == "train.loss_nonfinite"
    # teardown ran to completion before the crash: worker.exit journaled
    events = [r["event"] for r in read_journal(
        worker_journal_path(str(tmp_path / "tele-tail"), 0))]
    assert events[-1] == "worker.exit"


# ---------------------------------------------------------------------------
# HTTP surfaces: /incidents + the scrape-path pin
# ---------------------------------------------------------------------------


def test_server_incidents_endpoint_and_scrape_touches_no_ring(
    tmp_path, monkeypatch
):
    from ditl_tpu.infer.server import make_server

    man = IncidentManager(str(tmp_path), cooldown_s=0.0)
    man.trigger(Anomaly("serving.tpot_jump"))
    server = make_server(None, port=0, incidents=man)
    threading.Thread(target=server.serve_forever, daemon=True).start()
    port = server.server_address[1]
    try:
        with urllib.request.urlopen(
                f"http://127.0.0.1:{port}/incidents", timeout=10) as resp:
            data = json.loads(resp.read())
        assert data["count"] == 1
        assert data["incidents"][0]["trigger"] == "serving.tpot_jump"
        # the /metrics scrape must never iterate a flight ring (ISSUE 10
        # acceptance: no new scrape latency) — pin by counting dump()s
        dumps: list[int] = []
        real_dump = FlightRing.dump
        monkeypatch.setattr(FlightRing, "dump",
                            lambda self: dumps.append(1) or real_dump(self))
        with urllib.request.urlopen(
                f"http://127.0.0.1:{port}/metrics", timeout=10) as resp:
            body = resp.read().decode()
        assert "ditl_serving_up 1" in body
        assert not dumps, "scrape path iterated a flight ring"
    finally:
        server.close(drain=False)


def test_gateway_incidents_aggregates_replicas(tmp_path):
    from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

    from ditl_tpu.config import GatewayConfig
    from ditl_tpu.gateway.gateway import make_gateway
    from ditl_tpu.gateway.replica import Fleet, InProcessReplica

    replica_listing = {"count": 1, "incidents": [
        {"name": "incident-x", "trigger": "serving.deadline_storm",
         "iso": "2026-01-01T00:00:00Z", "files": []},
    ]}

    class _Stub(BaseHTTPRequestHandler):
        def log_message(self, *args):
            pass

        def do_GET(self):
            payload = (replica_listing if self.path == "/incidents"
                       else {"status": "ok", "model": "stub",
                             "draining": False})
            body = json.dumps(payload).encode()
            self.send_response(200)
            self.send_header("Content-Type", "application/json")
            self.send_header("Content-Length", str(len(body)))
            self.end_headers()
            self.wfile.write(body)

    class _StubServer(ThreadingHTTPServer):
        daemon_threads = True
        allow_reuse_address = True

        def close(self, drain=True, timeout=30.0):
            self.shutdown()
            self.server_close()

        def kill(self):
            self.close()

    fleet = Fleet([InProcessReplica(
        "r0", lambda: _StubServer(("127.0.0.1", 0), _Stub))])
    fleet.start_all()
    assert fleet.probe("r0", timeout=5.0)
    man = IncidentManager(str(tmp_path), cooldown_s=0.0)
    man.trigger(Anomaly("gateway.spill_storm"))
    gw = make_gateway(fleet, config=GatewayConfig(), port=0, incidents=man)
    threading.Thread(target=gw.serve_forever, daemon=True).start()
    port = gw.server_address[1]
    try:
        with urllib.request.urlopen(
                f"http://127.0.0.1:{port}/incidents", timeout=10) as resp:
            data = json.loads(resp.read())
        assert data["count"] == 2
        assert data["gateway"][0]["trigger"] == "gateway.spill_storm"
        assert data["replicas"]["r0"][0]["trigger"] == \
            "serving.deadline_storm"
    finally:
        gw.shutdown()
        gw.server_close()
        fleet.stop_all()


# ---------------------------------------------------------------------------
# elastic controller: worker death -> liveness-ring bundle
# ---------------------------------------------------------------------------


@pytest.mark.multiproc
def test_pod_controller_worker_death_assembles_bundle(tmp_path):
    from ditl_tpu.runtime.elastic import PodController
    from ditl_tpu.telemetry.flight import LIVENESS_RING

    d = str(tmp_path)
    flag = tmp_path / "gen0-ran"
    code = (
        "import os, sys\n"
        "flag = sys.argv[1]\n"
        "if os.path.exists(flag):\n"
        "    sys.exit(0)\n"
        "open(flag, 'w').close()\n"
        "os.kill(os.getpid(), 9)\n"
    )
    ctl = PodController(
        1,
        lambda i, n, port, a: [sys.executable, "-c", code, str(flag)],
        max_pod_restarts=1, poll_s=0.05, journal_dir=d,
        incident_dir=os.path.join(d, "incidents"),
        incident_kwargs={"cooldown_s": 3600.0},
    )
    result = ctl.run(timeout_s=60)
    assert result.ok, result.transitions
    bundles = list_bundles(os.path.join(d, "incidents"))
    assert len(bundles) == 1
    m = bundles[0]
    assert m["trigger"] == "elastic.worker_death"
    assert m["detail"]["cause"] == "signal SIGKILL"
    ring_path = os.path.join(m["path"], "flight", LIVENESS_RING + ".jsonl")
    events = [json.loads(ln)["event"] for ln in open(ring_path)]
    assert "pod.spawn" in events and "pod.worker_died" in events
    # the anomaly landed in the pod timeline too
    from ditl_tpu.telemetry.journal import read_journal

    timeline = read_journal(os.path.join(d, "pod_timeline.jsonl"))
    kinds = [r.get("kind") for r in timeline
             if r["event"] == "anomaly.detected"]
    assert kinds == ["elastic.worker_death"]
