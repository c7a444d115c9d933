"""Per-tenant usage metering, cost attribution & noisy-neighbor forensics
(ISSUE 15, telemetry/usage.py).

- Meter/ledger units: bounded per-tenant families (overflow -> "other"),
  torn-tail-skipping aggregation, byte-identical rollups across runs,
  conviction thresholds, the sanitize_label mirror pin.
- Engine attribution: a real continuous engine writes ONE terminal
  ledger row per request on every terminal path (200/429/504/cancel)
  carrying the accounting the scheduler already computed.
- Identity relay: the gateway stamps X-Tenant-Label (digest, never the
  bearer) on relays, attributes routing-ring rows, ledgers edge rows;
  the replica's /usage and /metrics carry the label and never the key.
- THE noisy-neighbor drill: a chaos-forced TPOT storm under one
  tenant's batch prefill burden yields exactly ONE incident bundle
  convicting that tenant (usage snapshot + injected_fault in the
  manifest); the chaos-free control yields ZERO bundles and
  byte-identical aggregator runs.
- The metering-armed gateway-overhead A/B rides perf_compare.
"""

from __future__ import annotations

import copy
import http.client
import json
import os
import threading
import time

import pytest

from ditl_tpu.telemetry.registry import MetricsRegistry
from ditl_tpu.telemetry.usage import (
    LEDGER_EVENT,
    UsageLedger,
    UsageMeter,
    convict_noisy_neighbor,
    load_usage,
    main as usage_main,
    read_ledger,
    rollup,
    merge_rollups,
    sanitize_label,
    usage_ledger_path,
)

pytestmark = pytest.mark.usage


# ---------------------------------------------------------------------------
# meter / ledger / aggregator units (jax-free)
# ---------------------------------------------------------------------------


def test_sanitize_label_mirrors_admission():
    """usage.sanitize_label is a deliberate copy of the admission
    layer's (telemetry/ cannot import the gateway package) — pinned
    byte-equal over representative inputs, the SLO_CLASS_NAMES mirror
    rule."""
    from ditl_tpu.gateway.admission import sanitize_label as admission_sl
    from ditl_tpu.gateway.admission import tenant_label as admission_tl
    from ditl_tpu.telemetry.usage import tenant_label

    for raw in ("", "anonymous", "free-tier", "sk-abc!@#$%^", "a" * 200,
                "t_3fa21bdeadbe", "white space", "Ünïcodé"):
        assert sanitize_label(raw) == admission_sl(raw)
        assert tenant_label(raw) == admission_tl(raw)
    known = ("free-tier",)
    for raw in ("free-tier", "sk-xyz", "anonymous"):
        assert tenant_label(raw, known) == admission_tl(raw, known)


def test_meter_rollups_families_and_overflow():
    reg = MetricsRegistry()
    meter = UsageMeter(registry=reg, max_tenant_families=2)
    for i, tenant in enumerate(["t_a", "t_b", "t_c", "t_d"]):
        meter.note_terminal({
            "tenant": tenant, "outcome": "200",
            "prompt_tokens": 10 * (i + 1), "generated_tokens": 5,
            "cache_hit_tokens": 2, "device_time_est_s": 0.25,
        })
    snap = meter.snapshot()
    # Two real labels + overflow: the meter is bounded by construction.
    assert set(snap) == {"t_a", "t_b", "other"}
    assert snap["other"]["requests"] == 2
    assert snap["other"]["prompt_tokens"] == 70  # t_c + t_d folded
    assert snap["t_a"]["by_outcome"] == {"200": 1}
    body = reg.render()
    assert "ditl_usage_tenant_t_a_prompt_tokens_total 10" in body
    assert "ditl_usage_tenant_other_prompt_tokens_total 70" in body
    assert "ditl_usage_requests_total 4" in body
    assert "ditl_usage_requests_200_total 4" in body
    assert "ditl_usage_tenant_t_c" not in body
    # An out-of-vocabulary outcome folds into "other", never a new family.
    meter.note_terminal({"tenant": "t_a", "outcome": "teapot"})
    assert "ditl_usage_requests_other_total 1" in reg.render()
    assert meter.snapshot()["t_a"]["by_outcome"] == {"200": 1, "other": 1}


def test_ledger_torn_tail_skipped_and_rollup_deterministic(tmp_path):
    """Kill-mid-write crash consistency: the aggregator skips the torn
    tail (the load_trace rule) and two runs over the same directory are
    byte-identical."""
    d = str(tmp_path)
    ledger = UsageLedger(usage_ledger_path(d, "server-1"), source="server-1")
    for i in range(5):
        ledger.record(tenant="t_a", outcome="200", prompt_tokens=7,
                      generated_tokens=3, device_time_est_s=0.125)
    ledger.record(tenant="t_b", outcome="429", prompt_tokens=9)
    ledger.close()
    # Simulate a SIGKILL mid-write: a torn final line.
    with open(usage_ledger_path(d, "server-1"), "a") as f:
        f.write('{"ts": 1.0, "event": "usage.request", "tenant": "t_tor')
    rows = load_usage(d)
    assert len(rows) == 6  # torn tail skipped, never fatal
    assert all(r["event"] == LEDGER_EVENT for r in rows)
    agg = rollup(rows)
    assert agg["t_a"]["requests"] == 5
    assert agg["t_a"]["prompt_tokens"] == 35
    assert agg["t_a"]["device_time_est_s"] == pytest.approx(0.625)
    assert agg["t_b"]["by_outcome"] == {"429": 1}
    # Byte-identical across two aggregator runs over the same directory.
    one = json.dumps(rollup(load_usage(d)), sort_keys=True)
    two = json.dumps(rollup(load_usage(d)), sort_keys=True)
    assert one == two


def test_load_usage_recursive_over_fleet_layout(tmp_path, capsys):
    """The gateway launcher writes its edge ledger at the ledger_dir
    root and per-replica ledgers in subdirectories — one --dir over the
    root must see the whole fleet, and the CLI must surface (and let
    --source separate) the edge-vs-engine duplication."""
    root = str(tmp_path)
    gw = UsageLedger(usage_ledger_path(root, "gateway"), source="gateway")
    gw.record(tenant="t_a", outcome="200", prompt_tokens=5)
    gw.close()
    sub = os.path.join(root, "r0")
    eng = UsageLedger(usage_ledger_path(sub, "server-1"), source="server-1")
    eng.record(tenant="t_a", outcome="200", prompt_tokens=5,
               generated_tokens=3, device_time_est_s=0.5)
    eng.close()
    rows = load_usage(root)
    assert len(rows) == 2  # both layers, one --dir
    assert usage_main(["--dir", root]) == 0
    text = capsys.readouterr().out
    assert "2 source(s)" in text and "--source" in text  # the dup note
    assert usage_main(["--dir", root, "--source", "server", "--json"]) == 0
    out = json.loads(capsys.readouterr().out)
    assert out["rows"] == 1 and out["sources"] == ["server-1"]
    assert out["tenants"]["t_a"]["generated_tokens"] == 3


def test_read_ledger_filters_foreign_events(tmp_path):
    """A usage file sharing a directory with span journals stays
    parseable: non-usage events are filtered, not mis-billed."""
    path = str(tmp_path / "usage-x.jsonl")
    with open(path, "w") as f:
        f.write(json.dumps({"ts": 1.0, "event": "usage.request",
                            "tenant": "t_a", "outcome": "200"}) + "\n")
        f.write(json.dumps({"ts": 2.0, "event": "span",
                            "name": "gateway.request"}) + "\n")
    assert len(read_ledger(path)) == 1


def test_merge_rollups_sums_tenants_and_outcomes():
    a = {"t_a": {"requests": 2, "prompt_tokens": 10,
                 "by_outcome": {"200": 2}}}
    b = {"t_a": {"requests": 1, "prompt_tokens": 5,
                 "by_outcome": {"429": 1}},
         "t_b": {"requests": 1, "prompt_tokens": 3,
                 "by_outcome": {"200": 1}}}
    merged = merge_rollups([a, b])
    assert merged["t_a"]["requests"] == 3
    assert merged["t_a"]["prompt_tokens"] == 15
    assert merged["t_a"]["by_outcome"] == {"200": 2, "429": 1}
    assert merged["t_b"]["requests"] == 1


def test_conviction_thresholds():
    meter = UsageMeter()
    meter.note_prefill("t_big", 900)
    meter.note_device("t_big", 0.9)
    meter.note_prefill("t_small", 100)
    meter.note_device("t_small", 0.1)
    w = meter.advance_window()
    verdict = convict_noisy_neighbor(w, 0.6, 64, snapshot={})
    assert verdict is not None and verdict["tenant"] == "t_big"
    assert verdict["window_prefill_share"] == 0.9
    assert verdict["window_device_share"] == pytest.approx(0.9)
    # Below the share threshold: nobody convicted.
    assert convict_noisy_neighbor(w, 0.95, 64) is None
    # Thin windows convict nobody (a single small prefill is not a storm).
    meter.note_prefill("t_big", 10)
    assert convict_noisy_neighbor(meter.advance_window(), 0.6, 64) is None
    # advance_window resets: an empty window convicts nobody either.
    assert convict_noisy_neighbor(meter.advance_window(), 0.1, 1) is None


def test_usage_cli(tmp_path, capsys):
    d = str(tmp_path)
    ledger = UsageLedger(usage_ledger_path(d, "gw"), source="gw")
    ledger.record(tenant="t_a", outcome="200", prompt_tokens=4,
                  generated_tokens=2)
    ledger.record(tenant="t_b", outcome="504", prompt_tokens=6)
    ledger.close()
    assert usage_main(["--dir", d, "--json"]) == 0
    out = json.loads(capsys.readouterr().out)
    assert out["rows"] == 2 and set(out["tenants"]) == {"t_a", "t_b"}
    assert usage_main(["--dir", d, "--tenant", "t_b", "--json"]) == 0
    out = json.loads(capsys.readouterr().out)
    assert set(out["tenants"]) == {"t_b"}
    assert usage_main(["--dir", d]) == 0
    text = capsys.readouterr().out
    assert "t_a" in text and "tokens_in=4" in text


# ---------------------------------------------------------------------------
# engine attribution (real continuous engine)
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def tiny_model():
    import jax

    from ditl_tpu.config import ModelConfig
    from ditl_tpu.data.tokenizer import ByteTokenizer
    from ditl_tpu.models import llama

    cfg = ModelConfig(
        vocab_size=512, hidden_size=64, intermediate_size=128, num_layers=2,
        num_heads=4, num_kv_heads=2, head_dim=16, max_seq_len=512,
        dtype="float32", param_dtype="float32",
    )
    params = llama.init_params(jax.random.key(0), cfg)
    return params, cfg, ByteTokenizer()


def test_engine_ledgers_every_terminal_path(tmp_path, tiny_model):
    """One terminal row per request on every path — 200 (completed), 429
    (queue full at submit), 504 (deadline eviction), cancel — carrying
    the accounting the engine already computed; the meter's families
    render on the engine's own /metrics registry."""
    from ditl_tpu.infer.continuous import ContinuousEngine, QueueFullError
    from ditl_tpu.infer.engine import GenerateConfig

    params, cfg, tok = tiny_model
    d = str(tmp_path)
    meter = UsageMeter()
    ledger = UsageLedger(usage_ledger_path(d, "eng"), source="eng")
    eng = ContinuousEngine(
        params, cfg, tok, n_slots=1, decode_chunk=4, max_queue=2,
        gen=GenerateConfig(max_new_tokens=6),
        usage=meter, usage_ledger=ledger,
    )
    prompt = [tok.bos_id] + tok.encode("hello usage")
    # 200: completes, billed to its tenant.
    eng.submit(list(prompt), tenant="t_alice")
    eng.run()
    # 504: deadline expires before the next step admits it.
    rid_expired = eng.submit(list(prompt), tenant="t_bob",
                             deadline_s=0.001)
    time.sleep(0.05)
    eng.step()
    # cancel: queued then abandoned.
    rid_cancel = eng.submit(list(prompt), tenant="t_bob")
    rid_other = eng.submit(list(prompt), tenant="t_alice")
    # 429: the queue cap (2) is full — billed at submit time.
    with pytest.raises(QueueFullError):
        eng.submit(list(prompt), tenant="t_carol")
    assert eng.cancel(rid_cancel)
    eng.run()
    ledger.close()

    rows = load_usage(d)
    by_outcome = {}
    for r in rows:
        by_outcome.setdefault(r["outcome"], []).append(r)
    assert sorted(by_outcome) == ["200", "429", "504", "cancel"]
    ok = by_outcome["200"]
    assert {r["tenant"] for r in ok} == {"t_alice"}
    assert all(r["prompt_tokens"] == len(prompt) for r in ok)
    assert all(r["generated_tokens"] > 0 for r in ok)
    assert all(r["device_time_est_s"] > 0 for r in ok)
    assert all(r["e2e_s"] > 0 and r["queue_wait_s"] >= 0 for r in ok)
    assert by_outcome["429"][0]["tenant"] == "t_carol"
    assert by_outcome["429"][0]["generated_tokens"] == 0
    expired = by_outcome["504"][0]
    assert expired["tenant"] == "t_bob" and expired["req_id"] == rid_expired
    assert by_outcome["cancel"][0]["req_id"] == rid_cancel
    assert rid_other != rid_cancel  # the sibling completed normally
    # Exactly one row per terminal request — no double billing.
    assert len(rows) == 5
    # The meter aggregated the same rows, on the engine's own registry.
    snap = meter.snapshot()
    assert snap["t_alice"]["requests"] == 2
    assert snap["t_carol"]["by_outcome"] == {"429": 1}
    body = eng.metrics.render()
    assert "ditl_usage_tenant_t_alice_prompt_tokens_total" in body
    assert "ditl_usage_requests_total 5" in body


def test_engine_unmetered_writes_nothing(tmp_path, tiny_model):
    """usage=None, usage_ledger=None (the default): zero per-tenant
    state, zero files — the metering-off leg really is off."""
    from ditl_tpu.infer.continuous import ContinuousEngine
    from ditl_tpu.infer.engine import GenerateConfig

    params, cfg, tok = tiny_model
    eng = ContinuousEngine(params, cfg, tok, n_slots=1, decode_chunk=4,
                           gen=GenerateConfig(max_new_tokens=4))
    eng.submit([tok.bos_id] + tok.encode("hi"), tenant="t_x")
    eng.run()
    assert eng.usage is None
    assert "ditl_usage" not in eng.metrics.render()


# ---------------------------------------------------------------------------
# identity relay: server header/fallback, /usage, gateway stamping
# ---------------------------------------------------------------------------


def _request(port, method, path, body=None, headers=None):
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=30.0)
    try:
        conn.request(method, path,
                     body=json.dumps(body).encode() if body else None,
                     headers={"Content-Type": "application/json",
                              **(headers or {})})
        resp = conn.getresponse()
        return resp.status, resp.read()
    finally:
        conn.close()


def test_server_tenant_header_usage_endpoint_and_no_raw_bearer(
    tmp_path, tiny_model
):
    """The replica reads X-Tenant-Label (gateway relay) over its own
    bearer digest; /usage serves the per-tenant rollups; the RAW bearer
    never appears on /usage, /metrics, or the ledger bytes."""
    from ditl_tpu.gateway.admission import tenant_label
    from ditl_tpu.infer.continuous import ContinuousEngine, ThreadedEngine
    from ditl_tpu.infer.engine import GenerateConfig, Generator
    from ditl_tpu.infer.server import make_server

    params, cfg, tok = tiny_model
    d = str(tmp_path)
    meter = UsageMeter()
    ledger = UsageLedger(usage_ledger_path(d, "srv"), source="srv")
    threaded = ThreadedEngine(ContinuousEngine(
        params, cfg, tok, n_slots=2, decode_chunk=4,
        gen=GenerateConfig(max_new_tokens=4),
        usage=meter, usage_ledger=ledger,
    ))
    server = make_server(Generator(params, cfg, tok), port=0,
                         threaded_engine=threaded)
    port = server.server_address[1]
    threading.Thread(target=server.serve_forever, daemon=True).start()
    secret = "sk-secret-bearer-key-123"
    try:
        # Bearer fallback: digested, never raw.
        status, _ = _request(port, "POST", "/v1/completions",
                             {"prompt": "hi", "max_tokens": 3},
                             {"Authorization": f"Bearer {secret}"})
        assert status == 200
        # Relay header wins over the bearer.
        status, _ = _request(port, "POST", "/v1/completions",
                             {"prompt": "hi", "max_tokens": 3},
                             {"Authorization": f"Bearer {secret}",
                              "X-Tenant-Label": "vip_tenant"})
        assert status == 200
        status, body = _request(port, "GET", "/usage")
        assert status == 200
        payload = json.loads(body)
        digest = tenant_label(secret)
        assert digest in payload["tenants"]
        assert "vip_tenant" in payload["tenants"]
        assert payload["tenants"][digest]["generated_tokens"] > 0
        assert secret not in body.decode()
        status, metrics_body = _request(port, "GET", "/metrics")
        assert f"ditl_usage_tenant_{digest}_prompt_tokens_total" \
            in metrics_body.decode()
        assert secret not in metrics_body.decode()
    finally:
        server.close(drain=False)
        threaded.close()
        ledger.close()
    ledger_bytes = open(usage_ledger_path(d, "srv")).read()
    assert secret not in ledger_bytes
    assert digest in ledger_bytes


def test_server_without_meter_404s_usage(tiny_model):
    from ditl_tpu.infer.engine import Generator
    from ditl_tpu.infer.server import make_server

    params, cfg, tok = tiny_model
    server = make_server(Generator(params, cfg, tok), port=0)
    port = server.server_address[1]
    threading.Thread(target=server.serve_forever, daemon=True).start()
    try:
        status, _ = _request(port, "GET", "/usage")
        assert status == 404  # unarmed != zero usage
    finally:
        server.close(drain=False)


def test_gateway_stamps_label_ledgers_edge_rows_and_fans_out_usage(
    tmp_path,
):
    """Stub-replica gateway drill: the relay carries X-Tenant-Label (the
    digest, never the bearer), the ROUTING flight ring attributes the
    request, the edge ledger rows carry outcomes (200 + throttle 429),
    and /usage merges the replicas' rollups fleet-wide."""
    from ditl_tpu.config import GatewayConfig
    from ditl_tpu.gateway import Fleet, InProcessReplica, make_gateway
    from ditl_tpu.gateway.admission import TenantAdmission, tenant_label
    from ditl_tpu.telemetry.flight import ROUTING_RING, FlightRecorder
    from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

    seen_headers: list[dict] = []
    replica_usage = [
        {"t_a": {"requests": 2, "prompt_tokens": 10,
                 "by_outcome": {"200": 2}}},
        {"t_a": {"requests": 1, "prompt_tokens": 5,
                 "by_outcome": {"200": 1}},
         "t_b": {"requests": 3, "prompt_tokens": 9,
                 "by_outcome": {"200": 3}}},
    ]

    class _Stub(ThreadingHTTPServer):
        daemon_threads = True
        allow_reuse_address = True
        usage_payload: dict = {}

        def close(self, drain=True, timeout=30.0):
            self.shutdown()
            self.server_close()

        def kill(self):
            self.close()

    class _Handler(BaseHTTPRequestHandler):
        def log_message(self, *args):
            pass

        def _json(self, status, obj):
            body = json.dumps(obj).encode()
            self.send_response(status)
            self.send_header("Content-Type", "application/json")
            self.send_header("Content-Length", str(len(body)))
            self.end_headers()
            self.wfile.write(body)

        def do_GET(self):
            if self.path.rstrip("/") == "/usage":
                self._json(200, {"requests": 1,
                                 "tenants": self.server.usage_payload})
            else:
                self._json(200, {"status": "ok", "draining": False,
                                 "queue_depth": 0, "active_slots": 0,
                                 "n_slots": 8})

        def do_POST(self):
            self.rfile.read(int(self.headers.get("Content-Length", 0)))
            seen_headers.append(dict(self.headers))
            self._json(200, {"object": "text_completion",
                             "choices": [{"index": 0, "text": "ok",
                                          "finish_reason": "stop"}],
                             "usage": {"prompt_tokens": 1,
                                       "completion_tokens": 1,
                                       "total_tokens": 2}})

    stubs = []

    def factory(payload):
        def build():
            srv = _Stub(("127.0.0.1", 0), _Handler)
            srv.usage_payload = payload
            stubs.append(srv)
            return srv
        return build

    fleet = Fleet([InProcessReplica(f"r{i}", factory(replica_usage[i]))
                   for i in range(2)])
    fleet.start_all()
    for rid in fleet.ids:
        assert fleet.probe(rid, timeout=5.0)
    d = str(tmp_path)
    ledger = UsageLedger(usage_ledger_path(d, "gateway"), source="gateway")
    flight = FlightRecorder()
    # rate cap 1/s, burst 1: the second request from the same tenant
    # throttles — the edge 429 row only the gateway can write.
    admission = TenantAdmission(rate=1.0, burst=1.0)
    server = make_gateway(fleet, config=GatewayConfig(router="round_robin"),
                          admission=admission, flight=flight,
                          usage=ledger, port=0)
    port = server.server_address[1]
    threading.Thread(target=server.serve_forever, daemon=True).start()
    secret = "sk-another-secret-key"
    digest = tenant_label(secret)
    try:
        status, _ = _request(port, "POST", "/v1/completions",
                             {"prompt": "hello", "max_tokens": 2},
                             {"Authorization": f"Bearer {secret}"})
        assert status == 200
        status, _ = _request(port, "POST", "/v1/completions",
                             {"prompt": "hello", "max_tokens": 2},
                             {"Authorization": f"Bearer {secret}"})
        assert status == 429  # tenant throttle (rate 1/s, burst 1)
        # The relay stamped the digest as the ATTRIBUTION identity (the
        # Authorization header itself is still relayed upstream — the
        # replica may need it; the invariant is that accounting surfaces
        # never carry it, asserted on ring/ledger/metrics below).
        relayed = [h for h in seen_headers if "X-Tenant-Label" in h]
        assert relayed and relayed[0]["X-Tenant-Label"] == digest
        # The routing flight ring attributes the request to the tenant.
        ring_rows = flight.ring(ROUTING_RING).dump()
        assert any(r.get("tenant") == digest for r in ring_rows)
        # /usage merges the replicas' per-tenant rollups fleet-wide.
        status, body = _request(port, "GET", "/usage")
        assert status == 200
        payload = json.loads(body)
        assert payload["fleet"]["t_a"]["requests"] == 3
        assert payload["fleet"]["t_b"]["requests"] == 3
        assert set(payload["replicas"]) == {"r0", "r1"}
        assert digest in payload["gateway_tenants"]
    finally:
        server.shutdown()
        server.server_close()
        fleet.stop_all(drain=False)
        ledger.close()
    rows = load_usage(d)
    assert [r["outcome"] for r in rows] == ["200", "429"]
    assert all(r["tenant"] == digest for r in rows)
    assert rows[1].get("throttled") is True
    assert secret not in open(usage_ledger_path(d, "gateway")).read()


# ---------------------------------------------------------------------------
# THE noisy-neighbor acceptance drill
# ---------------------------------------------------------------------------


_LATENCY_FACTOR = 20.0
_DECODE_CHUNK = 8


def _noisy_run(tmp_path, tiny_model, tag: str, storm: bool):
    """One serving leg: warm (compile outside the detector windows),
    flush the compile-polluted histogram window, establish a healthy
    TPOT baseline, then run tenant t_mallory's chunked batch prefills
    against tenant t_alice's decode stream — with ``storm``, every tick
    is stalled by an injected delay sized from the baseline the detector
    itself just measured, so the TPOT p95 jumps however loaded the
    machine is (the storm IS the injected fault); without it an identical
    healthy run."""
    from ditl_tpu import chaos
    from ditl_tpu.infer.continuous import ContinuousEngine
    from ditl_tpu.infer.engine import GenerateConfig
    from ditl_tpu.telemetry.anomaly import (
        AnomalyPlane, ServingAnomalyMonitor, ServingDetector,
    )
    from ditl_tpu.telemetry.flight import FlightRecorder
    from ditl_tpu.telemetry.incident import IncidentManager
    from ditl_tpu.telemetry.serving import ServingMetrics

    params, cfg, tok = tiny_model
    inc_dir = str(tmp_path / f"incidents-{tag}")
    ledger_dir = str(tmp_path / f"usage-{tag}")
    metrics = ServingMetrics()
    flight = FlightRecorder()
    meter = UsageMeter()
    ledger = UsageLedger(usage_ledger_path(ledger_dir, "eng"), source="eng")
    incidents = IncidentManager(
        inc_dir, flight=flight, metrics_render=metrics.render,
        registry=metrics.registry, cooldown_s=3600.0, source=f"eng-{tag}")
    monitor = ServingAnomalyMonitor(
        AnomalyPlane(incidents=incidents),
        # Only the latency-jump detectors are live: storms/queue/ratio
        # detectors are parked high so the drill isolates the tpot_jump
        # + conviction path.
        # latency_factor 20 (not the 3.0 default): an ORGANIC jump on a
        # loaded CI machine (GC pause, scheduler hiccup) must not fire
        # the control leg; the storm leg's stall is sized past it below.
        ServingDetector(storm_threshold=10 ** 6,
                        queue_depth_limit=10 ** 6,
                        latency_factor=_LATENCY_FACTOR, min_samples=16,
                        min_hit_tokens=10 ** 9),
        check_every=4,
        usage=meter, conviction_share=0.5, conviction_min_tokens=32,
    )
    eng = ContinuousEngine(
        params, cfg, tok, n_slots=2, decode_chunk=_DECODE_CHUNK,
        prefill_chunk=32, gen=GenerateConfig(max_new_tokens=8),
        metrics=metrics, flight=flight, usage=meter, usage_ledger=ledger,
    )
    short = [tok.bos_id] + tok.encode("hello")
    batch_prompt = [tok.bos_id] + tok.encode("z" * 300)
    # Warm: compile every program shape the drill uses (short prefill,
    # chunked batch prefill, decode) with the monitor detached — 6+6
    # generated tokens stay under min_samples=16, so the compile-
    # polluted first window can never seed the EMA.
    eng.submit(list(short), tenant="t_alice", max_new_tokens=6)
    eng.submit(list(batch_prompt), tenant="t_alice", max_new_tokens=6,
               slo_class="batch")
    eng.run()
    monitor.observe_serving(eng.stats(), metrics)  # flush warm windows
    eng.anomaly = monitor
    # Healthy baseline: enough decode tokens per observe window (4 ticks
    # x 2 slots x chunk 8) to set the TPOT EMA from clean windows.
    for _ in range(3):
        eng.submit(list(short), tenant="t_alice", max_new_tokens=48)
        eng.submit(list(short), tenant="t_alice", max_new_tokens=48)
        eng.run()
    # The baseline the jump is measured against is set BEFORE the fault is
    # armed: the detector holds a TPOT EMA from the clean windows above.
    baseline = monitor.detector._tpot_ema
    assert baseline is not None and baseline > 0
    if storm:
        # A per-tick stall worth 3x the detector's own threshold over the
        # baseline it measured on THIS machine just now (TPOT = tick
        # interval / decode_chunk): a loaded box's slower baseline raises
        # the stall with it, so the jump never hangs on the box's mood.
        delay = 3 * _LATENCY_FACTOR * baseline * _DECODE_CHUNK
        chaos.arm(chaos.FaultPlane(
            rules=f"engine.tick:delay@delay={delay:.4f},max=60"))
    try:
        # The storm: alice keeps decoding (the victim stream) while
        # mallory's chunked batch prefills burn the scheduler — under
        # injected per-tick stalls the windowed TPOT p95 blows past
        # the factor times the healthy EMA.
        eng.submit(list(short), tenant="t_alice", max_new_tokens=64)
        for _ in range(4):
            eng.submit(list(batch_prompt), tenant="t_mallory",
                       max_new_tokens=4, slo_class="batch")
        while eng.pending:
            eng.step()
            if storm and incidents.created:
                # Convicted: the rest of the storm need not be sat out.
                chaos.disarm()
    finally:
        chaos.disarm()
    ledger.close()
    return eng, metrics, inc_dir, ledger_dir


@pytest.mark.chaos
def test_acceptance_noisy_neighbor_conviction_drill(tmp_path, tiny_model):
    """THE drill (ISSUE 15 acceptance): a chaos-forced one-tenant
    prefill storm on a real engine produces exactly ONE incident bundle
    convicting that tenant (window shares + usage snapshot +
    injected_fault attribution in the manifest); the chaos-free control
    produces ZERO bundles and byte-identical rollups across two
    aggregator runs."""
    from ditl_tpu.telemetry.incident import list_bundles

    # Injected stall on every tick until the conviction lands: windowed
    # TPOT p95 jumps while mallory's chunks dominate the conviction window.
    _, _, inc_dir, ledger_dir = _noisy_run(
        tmp_path, tiny_model, "storm", storm=True)
    bundles = list_bundles(inc_dir)
    assert len(bundles) == 1, [b["trigger"] for b in bundles]
    m = bundles[0]
    assert m["trigger"] == "serving.tpot_jump"
    verdict = m["detail"]["noisy_neighbor"]
    assert verdict["tenant"] == "t_mallory"
    assert verdict["window_prefill_share"] >= 0.5
    assert verdict["window_prefill_tokens"] >= 32
    # The culprit's bill rides the manifest: the usage snapshot carries
    # the dispatch-time accounting even though the storm was still in
    # flight when the detector fired (live_* fields — the convictable-
    # before-terminal contract).
    usage = verdict["usage"]
    # The jump can fire within a chunk or two of the storm's start — the
    # live account must cover at least the convicting window's burden.
    assert usage["live_prefill_tokens"] >= verdict["window_prefill_tokens"]
    assert usage["live_device_s"] > 0
    # Chaos attribution: the storm reads as injected, not organic.
    assert m["injected_fault"]["injected"]["engine.tick:delay"] >= 1
    # The ledger billed mallory's batch rows under its tenant.
    agg = rollup(load_usage(ledger_dir))
    assert agg["t_mallory"]["requests"] == 4
    assert agg["t_mallory"]["prompt_tokens"] >= 4 * 300

    # The chaos-free control: identical traffic, ZERO bundles, and the
    # aggregator is deterministic over its ledger.
    _, _, inc_dir2, ledger_dir2 = _noisy_run(
        tmp_path, tiny_model, "control", storm=False)
    assert list_bundles(inc_dir2) == []
    one = json.dumps(rollup(load_usage(ledger_dir2)), sort_keys=True)
    two = json.dumps(rollup(load_usage(ledger_dir2)), sort_keys=True)
    assert one == two
    agg2 = rollup(load_usage(ledger_dir2))
    assert agg2["t_mallory"]["requests"] == 4
    assert agg2["t_alice"]["by_outcome"]["200"] >= 6


# ---------------------------------------------------------------------------
# the metering-armed overhead A/B + perf_compare gate
# ---------------------------------------------------------------------------


def test_gateway_overhead_metered_ab_and_perf_compare(tmp_path):
    """The ISSUE 15 satellite leg: the same stub-fleet microbench with
    the metering plane armed embeds a usage_metering block (ledger rows
    actually written, tenants labeled), and perf_compare gates
    gateway_rps_metered / metering_overhead_ratio — 0 on the pair, 1 on
    a degraded copy."""
    from ditl_tpu.telemetry.perf_compare import compare_records
    from tests.gateway_drivers import run_gateway_overhead_bench

    row = run_gateway_overhead_bench(60, usage_dir=str(tmp_path / "usage"))
    block = row["usage_metering"]
    assert block["schema"] == 1
    assert block["gateway_rps_metered"] > 0
    # 60 timed + 4 warm requests, each a ledger row; 3 client tenants +
    # the warm tenant.
    assert block["ledger_rows"] == 64
    assert block["tenants"] == 4
    rows = load_usage(str(tmp_path / "usage"))
    assert all(r["outcome"] == "200" for r in rows)
    assert all(r["tenant"].startswith("t_") for r in rows)
    # perf_compare: identical pair passes...
    code, report = compare_records(row, copy.deepcopy(row), 0.05)
    assert code == 0, report
    # ...a degraded metered leg is a gated regression on both keys.
    degraded = copy.deepcopy(row)
    degraded["usage_metering"]["gateway_rps_metered"] = round(
        block["gateway_rps_metered"] * 0.5, 1)
    degraded["usage_metering"]["metering_overhead_ratio"] = round(
        abs(block["metering_overhead_ratio"]) + 0.5, 4)
    code, report = compare_records(row, degraded, 0.05)
    assert code == 1
    assert "gateway_rps_metered" in report
    assert "metering_overhead_ratio" in report
