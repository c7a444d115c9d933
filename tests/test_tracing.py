"""End-to-end request tracing suite (ISSUE 6, ditl_tpu/telemetry/tracing.py
+ trace_export.py + slo.py).

Layers:

- jax-free units: traceparent round-trip, span journal records, request-id
  sanitization, journal rotation, Chrome-trace export field contract, SLO
  burn-rate math, and the provably-jax-free import set (telemetry/,
  gateway/, chaos/ — the prose claim, pinned).
- engine drills: the request-lifecycle span chain (queue -> prefill ->
  decode under one engine.request), and THE interference drill — a long
  co-scheduled prefill produces a victim-side annotation naming the culprit
  request and a nonzero tpot_interference_s observation.
- THE cross-process acceptance drill: one request through a 2-replica
  gateway with a forced chaos retry yields ONE merged trace whose spans
  nest gateway relay (retry tagged) -> replica server -> engine
  queue/prefill/decode across process boundaries, and exports to valid
  Chrome-trace JSON.
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

from ditl_tpu.telemetry.journal import (
    EventJournal,
    merge_journals,
    read_journal,
)
from ditl_tpu.telemetry.registry import LATENCY_BUCKETS_S
from ditl_tpu.telemetry.serving import ServingMetrics
from ditl_tpu.telemetry.slo import BurnRateMonitor, Objective, serving_slo
from ditl_tpu.telemetry.trace_export import (
    load_trace_records,
    spans_for_trace,
    to_chrome_trace,
    trace_ids,
)
from ditl_tpu.telemetry.tracing import (
    Tracer,
    format_traceparent,
    new_request_id,
    parse_traceparent,
    sanitize_request_id,
)

pytestmark = pytest.mark.tracing

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ---------------------------------------------------------------------------
# jax-free units
# ---------------------------------------------------------------------------


def test_traceparent_roundtrip_and_rejects():
    tracer = Tracer(None)
    span = tracer.start_span("root")
    header = format_traceparent(span)
    ctx = parse_traceparent(header)
    assert ctx is not None
    assert ctx.trace_id == span.trace_id and ctx.span_id == span.span_id
    # Child continues the parent's trace.
    child = tracer.start_span("child", parent=ctx)
    assert child.trace_id == span.trace_id
    assert child.parent_id == span.span_id
    assert child.span_id != span.span_id
    # Malformed headers are rejected, never raise.
    for bad in (None, "", "garbage", "00-zz-zz-01",
                "00-" + "0" * 32 + "-" + "1" * 16 + "-01",  # all-zero trace
                "00-" + "a" * 32 + "-" + "0" * 16 + "-01",  # all-zero span
                "ff-" + "a" * 32 + "-" + "b" * 16 + "-01",  # version ff
                "00-" + "a" * 31 + "-" + "b" * 16 + "-01"):  # short trace
        assert parse_traceparent(bad) is None, bad


def test_request_id_sanitization():
    assert sanitize_request_id(None) is None
    assert sanitize_request_id("") is None
    assert sanitize_request_id("abc-123.X:y") == "abc-123.X:y"
    # CR/LF (header injection) and exotic bytes are stripped.
    assert sanitize_request_id("evil\r\nX-Inject: 1") == "evilX-Inject:1"
    assert sanitize_request_id("\r\n") is None
    assert len(sanitize_request_id("a" * 500)) == 128
    assert new_request_id().startswith("req-")


def test_span_records_written_at_end_with_start_ts(tmp_path):
    journal = EventJournal(str(tmp_path / "events-t.jsonl"), source="t")
    tracer = Tracer(journal)
    assert tracer.armed
    root = tracer.start_span("outer", request_id="r1")
    time.sleep(0.02)
    child = tracer.start_span("inner", parent=root)
    child.end(tokens=3)
    tracer.instant("tick", parent=root, n=7)
    root.end()
    root.end()  # idempotent: second end writes nothing
    journal.close()
    recs = read_journal(str(tmp_path / "events-t.jsonl"))
    spans = [r for r in recs if r["event"] == "trace.span"]
    assert [s["name"] for s in spans] == ["inner", "outer"]  # end order
    outer = spans[1]
    inner = spans[0]
    assert inner["trace"] == outer["trace"]
    assert inner["parent"] == outer["span"]
    assert outer["parent"] == ""
    # Start-stamped: outer's ts precedes inner's despite writing later.
    assert outer["ts"] <= inner["ts"]
    assert outer["dur_s"] >= inner["dur_s"] >= 0
    assert outer["request_id"] == "r1" and inner["tokens"] == 3
    instants = [r for r in recs if r["event"] == "trace.instant"]
    assert len(instants) == 1 and instants[0]["name"] == "tick"
    assert instants[0]["trace"] == outer["trace"]
    # Reserved keys are refused, not silently shadowed.
    with pytest.raises(ValueError):
        tracer.start_span("bad", ts=1.0)


def test_unarmed_tracer_mints_ids_but_writes_nothing(tmp_path):
    tracer = Tracer(None)
    span = tracer.start_span("s")
    assert len(span.trace_id) == 32 and len(span.span_id) == 16
    span.end()  # no journal, no crash
    tracer.instant("i")


def test_journal_rotation_bounded_and_merge_ordered(tmp_path):
    """ISSUE 6 satellite: max_bytes caps the journal via segment rotation;
    merge_journals folds rotated segments back into (ts, seq) order."""
    path = str(tmp_path / "events-rot.jsonl")
    journal = EventJournal(path, source="rot", max_bytes=16384)
    payload = "x" * 80  # ~130-byte lines -> ~30 lines per 4096-byte segment
    n_events = 400
    for i in range(n_events):
        journal.event("tick", i=i, pad=payload)
    journal.close()
    files = sorted(os.listdir(tmp_path))
    assert "events-rot.jsonl" in files
    rotated = [f for f in files if ".r" in f]
    assert rotated, "no rotation happened"
    # Bounded: at most KEEP_SEGMENTS files survive, oldest were deleted.
    assert len(rotated) <= 3
    total_bytes = sum(
        os.path.getsize(tmp_path / f) for f in files if f.endswith(".jsonl")
    )
    assert total_bytes <= 16384 + 4096  # cap + one segment of slack
    merged = merge_journals(str(tmp_path))
    assert 0 < len(merged) < n_events  # old segments aged out
    seqs = [r["seq"] for r in merged]
    assert seqs == sorted(seqs), "rotated segments merged out of order"
    # The NEWEST events always survive.
    assert merged[-1]["i"] == n_events - 1
    ts = [r["ts"] for r in merged]
    assert ts == sorted(ts)


def test_journal_rotation_resumes_counter_across_relaunch(tmp_path):
    """A relaunched process reuses its journal path; the segment counter
    must resume from disk — restarting at 0 would os.replace() onto (and
    destroy) the previous incarnation's rotated segments while they are
    still inside the keep budget."""
    path = str(tmp_path / "events-rot.jsonl")
    j1 = EventJournal(path, source="rot", max_bytes=16384)
    for i in range(120):
        j1.event("pre", i=i, pad="x" * 80)
    j1.close()
    pre_rotated = sorted(f for f in os.listdir(tmp_path) if ".r" in f)
    assert pre_rotated, "first incarnation never rotated"
    pre_max = max(int(f.split(".r")[1].split(".")[0]) for f in pre_rotated)
    j2 = EventJournal(path, source="rot", max_bytes=16384)  # "relaunch"
    assert j2._rotated == pre_max
    # Few enough post-relaunch events that pre-relaunch segments stay
    # inside the keep budget — they must survive untouched.
    for i in range(40):
        j2.event("post", i=i, pad="x" * 80)
    j2.close()
    for f in sorted(os.listdir(tmp_path)):
        if ".r" not in f:
            continue
        idx = int(f.split(".r")[1].split(".")[0])
        if idx <= pre_max:
            # A surviving pre-relaunch segment (keep budget may have aged
            # some out) was never clobbered by the second incarnation.
            events = {r["event"] for r in read_journal(str(tmp_path / f))}
            assert events == {"pre"}, f
    merged = merge_journals(str(tmp_path))
    events = [r["event"] for r in merged]
    assert "pre" in events and "post" in events
    assert merged[-1]["event"] == "post" and merged[-1]["i"] == 39


def test_chrome_trace_export_required_fields(tmp_path):
    """Tier-1 export smoke (ISSUE 6 satellite): journal -> merged trace ->
    Chrome-trace JSON round-trips through json.loads and carries the
    required fields (ph, ts, pid, tid) on every event."""
    j1 = EventJournal(str(tmp_path / "events-gateway.jsonl"),
                      source="gateway")
    j2 = EventJournal(str(tmp_path / "events-server-7.jsonl"),
                      source="server-7")
    t1, t2 = Tracer(j1), Tracer(j2)
    root = t1.start_span("gateway.request", request_id="r9")
    relay = t1.start_span("gateway.relay", parent=root, replica="r0")
    # Cross-process continuation: the replica parses the relay's context.
    ctx = parse_traceparent(format_traceparent(relay))
    server = t2.start_span("server.request", parent=ctx)
    t2.instant("engine.tick", tick=1)
    j2.event("replica.died", replica="r0")  # plain journal event
    server.end()
    relay.end(outcome="done")
    root.end()
    j1.close()
    j2.close()

    records = load_trace_records(str(tmp_path))
    ids = trace_ids(records)
    assert list(ids.values()) == [3]  # one trace, three spans
    trace_id = next(iter(ids))
    spans = spans_for_trace(records, trace_id)
    assert [s["name"] for s in spans] == [
        "gateway.request", "gateway.relay", "server.request",
    ]
    blob = json.dumps(to_chrome_trace(records))
    chrome = json.loads(blob)  # the format regression gate
    events = chrome["traceEvents"]
    assert events, "no events exported"
    for ev in events:
        for field in ("ph", "ts", "pid", "tid"):
            assert field in ev, f"event missing {field}: {ev}"
    phases = {ev["ph"] for ev in events}
    assert "X" in phases and "i" in phases and "M" in phases
    names = {ev["args"]["name"] for ev in events if ev["ph"] == "M"}
    assert names == {"gateway", "server-7"}  # one track per process
    # Cross-process nesting survived: server span carries the relay parent.
    sv = next(ev for ev in events if ev["name"] == "server.request")
    rl = next(ev for ev in events if ev["name"] == "gateway.relay")
    assert sv["args"]["parent"] == rl["args"]["span"]
    assert sv["pid"] != rl["pid"]
    # Trace filter keeps untraced process events as backdrop.
    filtered = to_chrome_trace(records, trace_id)["traceEvents"]
    assert any(ev["name"] == "replica.died" for ev in filtered)

    # CLI surface: --list and default export both work.
    out = subprocess.run(
        [sys.executable, "-m", "ditl_tpu.telemetry.trace_export",
         "--dir", str(tmp_path), "--list"],
        capture_output=True, text=True, cwd=REPO_ROOT, timeout=60,
    )
    assert out.returncode == 0, out.stderr
    assert trace_id in out.stdout
    out = subprocess.run(
        [sys.executable, "-m", "ditl_tpu.telemetry.trace_export",
         "--dir", str(tmp_path)],
        capture_output=True, text=True, cwd=REPO_ROOT, timeout=60,
    )
    assert out.returncode == 0, out.stderr
    with open(tmp_path / "trace.json") as f:
        assert json.load(f)["traceEvents"]


def test_slo_burn_rate_multiwindow():
    """Burn rate = windowed error rate / error budget; the alert fires only
    when EVERY window burns above the threshold, and un-burns once the
    fast window goes quiet."""
    m = ServingMetrics()
    slo = serving_slo(
        m, ttft_s=1.0, ttft_target=0.95, tpot_s=0.25, tpot_target=0.95,
        availability_target=0.999, windows=(10.0, 100.0), burn_alert=1.0,
    )
    t0 = 1000.0
    slo.sample(now=t0)  # zero baseline
    for _ in range(9):
        m.ttft.observe(0.1)
        m.completed.inc()
    m.ttft.observe(30.0)  # one breach
    m.completed.inc()
    report = slo.report(now=t0 + 5.0)
    ttft = report["objectives"]["ttft"]
    assert ttft["threshold_s"] == 1.0  # on-ladder threshold, no snapping
    fast = ttft["windows"]["10s"]
    assert fast["requests"] == 10 and fast["errors"] == 1
    assert abs(fast["error_rate"] - 0.1) < 1e-9
    assert abs(fast["burn_rate"] - 2.0) < 1e-6  # 0.1 / 0.05
    assert ttft["alerting"] is True  # both windows share the baseline here
    # Availability: no queue-full/deadline failures -> zero burn.
    avail = report["objectives"]["availability"]
    assert avail["windows"]["10s"]["burn_rate"] == 0.0
    assert avail["alerting"] is False
    # A quiet fast window un-alerts even though the slow window still
    # remembers the breach.
    for _ in range(50):
        m.ttft.observe(0.1)
        m.completed.inc()
    slo.sample(now=t0 + 40.0)
    report = slo.report(now=t0 + 55.0)
    ttft = report["objectives"]["ttft"]
    assert ttft["windows"]["10s"]["errors"] == 0
    assert ttft["windows"]["100s"]["errors"] == 1
    assert ttft["alerting"] is False
    # Burn-rate gauges landed in the serving registry for /metrics.
    rendered = m.registry.render()
    assert "ditl_slo_ttft_burn_rate_w10" in rendered
    assert "ditl_slo_availability_alerting" in rendered


def test_slo_threshold_snaps_down_to_bucket_ladder():
    m = ServingMetrics()
    slo = serving_slo(m, ttft_s=0.3, windows=(10.0, 100.0))
    ttft = next(o for o in slo.objectives if o.name == "ttft")
    assert ttft.threshold_s == 0.25  # largest bound <= 0.3 on the ladder
    assert 0.25 in LATENCY_BUCKETS_S
    with pytest.raises(ValueError):
        serving_slo(m, ttft_s=1e-9)  # below the first bucket


def test_slo_objective_and_monitor_validation():
    good = Objective(name="x", target=0.9, good_total=lambda: (0, 0))
    with pytest.raises(ValueError):
        Objective(name="x", target=1.0, good_total=lambda: (0, 0))
    with pytest.raises(ValueError):
        BurnRateMonitor([])
    with pytest.raises(ValueError):
        BurnRateMonitor([good], windows=())
    with pytest.raises(ValueError):
        BurnRateMonitor([good, good])  # duplicate names


def test_telemetry_config_validation():
    from ditl_tpu.config import Config, TelemetryConfig, parse_overrides

    cfg = parse_overrides(
        Config(), ["telemetry.slo_ttft_s=0.5", "telemetry.journal_max_mb=8"]
    ).telemetry
    assert cfg.slo_ttft_s == 0.5
    assert cfg.journal_max_bytes() == 8 * 1048576
    assert TelemetryConfig().journal_max_bytes() is None
    for bad in (dict(slo_ttft_target=1.0), dict(slo_ttft_target=0.0),
                dict(journal_max_mb=-1), dict(slo_fast_window_s=0),
                dict(slo_fast_window_s=7200.0)):
        with pytest.raises(ValueError):
            TelemetryConfig(**bad)


def test_jax_free_zones_pass_import_layering_rule():
    """The jax-free-on-import claim, delegated to the static pass
    (ISSUE 11): the import-layering rule proves telemetry/ gateway/
    chaos/ client/ AND analysis/ itself never reach jax through
    module-level imports — transitively, over EVERY module in the zones,
    not just the handful a subprocess smoke can afford to list. Lazy
    in-function jax imports must carry a reasoned pragma."""
    import ditl_tpu
    from ditl_tpu.analysis import run

    pkg_dir = os.path.dirname(os.path.abspath(ditl_tpu.__file__))
    diags = run(pkg_dir, rules=["import-layering"])
    assert diags == [], "\n".join(d.format() for d in diags)


def test_observability_packages_are_jax_free_on_import():
    """Belt-and-suspenders runtime smoke behind the static rule above:
    one fresh interpreter actually imports the zone entry points and
    asserts jax never loads — guarding the cases static analysis cannot
    see (import-time side effects, meta-path hooks)."""
    code = (
        "import sys\n"
        "import ditl_tpu.telemetry\n"
        "import ditl_tpu.telemetry.tracing\n"
        "import ditl_tpu.telemetry.trace_export\n"
        "import ditl_tpu.telemetry.slo\n"
        "import ditl_tpu.telemetry.flight\n"
        "import ditl_tpu.telemetry.anomaly\n"
        "import ditl_tpu.telemetry.incident\n"
        "import ditl_tpu.telemetry.catalog\n"
        "import ditl_tpu.telemetry.prof\n"
        "import ditl_tpu.gateway\n"
        "import ditl_tpu.gateway.gateway\n"
        "import ditl_tpu.gateway.replica\n"
        "import ditl_tpu.chaos\n"
        "import ditl_tpu.chaos.plane\n"
        "import ditl_tpu.analysis\n"
        "assert 'jax' not in sys.modules, 'jax leaked into the import graph'\n"
        "print('jax-free ok')\n"
    )
    out = subprocess.run(
        [sys.executable, "-c", code],
        capture_output=True, text=True, cwd=REPO_ROOT, timeout=120,
        env={**os.environ},
    )
    assert out.returncode == 0, out.stderr
    assert "jax-free ok" in out.stdout


# ---------------------------------------------------------------------------
# engine drills (jax, tiny model)
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def tiny():
    import jax

    from ditl_tpu.config import ModelConfig
    from ditl_tpu.data.tokenizer import ByteTokenizer
    from ditl_tpu.models import llama

    cfg = ModelConfig(
        vocab_size=512, hidden_size=64, intermediate_size=128,
        num_layers=2, num_heads=4, num_kv_heads=2, head_dim=16,
        max_seq_len=128, dtype="float32", param_dtype="float32",
    )
    params = llama.init_params(jax.random.key(0), cfg)
    return params, cfg, ByteTokenizer()


def _spans(directory: str) -> list[dict]:
    return [r for r in merge_journals(directory)
            if r.get("event") == "trace.span"]


def test_engine_lifecycle_spans_nest_under_one_request(tiny, tmp_path):
    from ditl_tpu.infer.continuous import ContinuousEngine
    from ditl_tpu.infer.engine import GenerateConfig

    params, cfg, tok = tiny
    journal = EventJournal(str(tmp_path / "events-engine.jsonl"),
                          source="engine")
    eng = ContinuousEngine(
        params, cfg, tok, n_slots=2, decode_chunk=4,
        gen=GenerateConfig(max_new_tokens=8), tracer=Tracer(journal),
    )
    rid = eng.submit(list(range(1, 21)), max_new_tokens=8)
    eng.run()
    spans = _spans(str(tmp_path))
    by_name: dict[str, list[dict]] = {}
    for s in spans:
        by_name.setdefault(s["name"], []).append(s)
    assert set(by_name) >= {"engine.request", "engine.queue",
                            "engine.prefill", "engine.decode"}
    req = by_name["engine.request"][0]
    assert req["req"] == rid and req["parent"] == ""
    assert req["prompt_tokens"] == 20 and req["tokens"] >= 1
    # Every lifecycle span chains under the request span, same trace.
    for name in ("engine.queue", "engine.prefill", "engine.decode"):
        for s in by_name[name]:
            assert s["parent"] == req["span"], name
            assert s["trace"] == req["trace"], name
    assert by_name["engine.prefill"][0]["kind"] == "prompt"
    assert by_name["engine.prefill"][0]["tokens"] == 20
    # The first chunk is the one token the prefill sampled, sent by the tick
    # that ran it; the tick's harvest delivers the rest of its row.
    first, second = by_name["engine.decode"][:2]
    assert (first["first"], first["tokens"]) == (True, 1)
    assert (second["first"], second["tokens"]) == (False, 3)
    assert "queue_wait_s" in by_name["engine.queue"][0]
    # Tick spans mark the scheduler cadence on the same track, and are the
    # only records of that name (no second, instant kind).
    ticks = [r for r in merge_journals(str(tmp_path))
             if r.get("name") == "engine.tick"]
    assert ticks and {r["event"] for r in ticks} == {"trace.span"}
    assert all({"tick", "slots_busy", "prefilling", "queue_depth",
                "prefill_s", "first_tokens"} <= r.keys() for r in ticks)
    assert sum(r["first_tokens"] for r in ticks) == 1
    journal.close()


def test_interference_annotation_names_culprit(tiny, tmp_path):
    """ISSUE 6 acceptance drill 2: a long co-scheduled prefill produces an
    interference annotation naming the culprit request (and its prefill
    length) on the victim's decode span, plus a nonzero
    tpot_interference_s observation."""
    from ditl_tpu.infer.continuous import ContinuousEngine
    from ditl_tpu.infer.engine import GenerateConfig

    params, cfg, tok = tiny
    journal = EventJournal(str(tmp_path / "events-engine.jsonl"),
                          source="engine")
    eng = ContinuousEngine(
        params, cfg, tok, n_slots=2, decode_chunk=2, prefill_chunk=16,
        gen=GenerateConfig(max_new_tokens=24), tracer=Tracer(journal),
    )
    victim = eng.submit(list(range(1, 5)), max_new_tokens=24)
    eng.step()  # admit + prefill victim
    eng.step()  # victim decoding
    culprit = eng.submit(list(range(1, 65)), max_new_tokens=4)  # 4 chunks
    for _ in range(4):
        eng.step()  # culprit prefills chunk-by-chunk, victim decodes
    assert eng.metrics.tpot_interference.count > 0, (
        "no tpot_interference_s observation recorded"
    )
    vreq = next(
        r for r in list(eng._slots) + list(eng._completed.values())
        if r is not None and r.req_id == victim
    )
    assert vreq.interference_s > 0
    eng.run()
    spans = _spans(str(tmp_path))
    victim_decodes = [
        s for s in spans
        if s["name"] == "engine.decode" and s["req"] == victim
    ]
    annotated = [s for s in victim_decodes if "interference_culprit" in s]
    assert annotated, "no victim decode span carries the annotation"
    for s in annotated:
        assert s["interference_culprit"] == culprit
        assert s["culprit_prefill_tokens"] == 16  # the prefill chunk
        assert s["interference_s"] > 0
    # The victim's request span carries the lifetime total.
    vspan = next(s for s in spans
                 if s["name"] == "engine.request" and s["req"] == victim)
    assert vspan["interference_total_s"] > 0
    # /metrics renders the aggregate histogram.
    assert "ditl_serving_tpot_interference_seconds_bucket" in (
        eng.metrics.render()
    )
    journal.close()


# ---------------------------------------------------------------------------
# THE acceptance drill: 2-replica gateway, forced retry, one merged trace
# ---------------------------------------------------------------------------


def test_gateway_trace_merges_across_processes_with_retry(tiny, tmp_path):
    from ditl_tpu import chaos
    from ditl_tpu.chaos import FaultPlane
    from ditl_tpu.config import GatewayConfig
    from ditl_tpu.gateway import Fleet, InProcessReplica, make_gateway
    from ditl_tpu.infer.continuous import ContinuousEngine, ThreadedEngine
    from ditl_tpu.infer.engine import GenerateConfig, Generator
    from ditl_tpu.infer.server import make_server

    params, cfg, tok = tiny
    shared_gen = Generator(params, cfg, tok)
    engines = []
    journals = []
    for i in range(2):
        j = EventJournal(str(tmp_path / f"events-replica-{i}.jsonl"),
                        source=f"replica-{i}")
        journals.append(j)
        engines.append(ThreadedEngine(ContinuousEngine(
            params, cfg, tok, n_slots=2, decode_chunk=4,
            gen=GenerateConfig(max_new_tokens=6), tracer=Tracer(j),
        )))

    def factory(eng):
        # make_server derives the HTTP span layer from the engine's tracer.
        return lambda: make_server(shared_gen, port=0, threaded_engine=eng,
                                   default_max_tokens=6)

    fleet = Fleet([InProcessReplica(f"r{i}", factory(engines[i]))
                   for i in range(2)])
    gw_journal = EventJournal(str(tmp_path / "events-gateway.jsonl"),
                              source="gateway")
    journals.append(gw_journal)
    server = None
    try:
        fleet.start_all()
        for rid in fleet.ids:
            assert fleet.probe(rid, timeout=10.0)
        server = make_gateway(
            fleet, config=GatewayConfig(router="round_robin", max_attempts=3),
            port=0, tracer=Tracer(gw_journal),
        )
        threading.Thread(target=server.serve_forever, daemon=True).start()
        port = server.server_address[1]
        # Force exactly ONE relay failure: attempt 0 errors before any byte
        # moves, attempt 1 retries on the other replica.
        chaos.arm(FaultPlane(rules="gateway.relay:error@max=1"))
        req = urllib.request.Request(
            f"http://127.0.0.1:{port}/v1/completions",
            data=json.dumps({"prompt": "trace me", "max_tokens": 4}).encode(),
            headers={"Content-Type": "application/json",
                     "X-Request-Id": "drill-42"},
            method="POST",
        )
        with urllib.request.urlopen(req, timeout=120) as resp:
            assert resp.status == 200
            # ISSUE 6 satellite: the client's id echoes on the response.
            assert resp.headers["X-Request-Id"] == "drill-42"
            json.loads(resp.read())
        # A generated id comes back when the client sent none — including
        # on the 4xx error path.
        bad = urllib.request.Request(
            f"http://127.0.0.1:{port}/v1/completions",
            data=b"not json", headers={"Content-Type": "application/json"},
            method="POST",
        )
        try:
            urllib.request.urlopen(bad, timeout=30)
            raise AssertionError("expected 400")
        except urllib.error.HTTPError as e:
            assert e.code == 400
            assert e.headers["X-Request-Id"].startswith("req-")
        # /slo renders on the gateway and on a replica.
        with urllib.request.urlopen(
            f"http://127.0.0.1:{port}/slo", timeout=30
        ) as resp:
            gw_slo = json.loads(resp.read())
        assert set(gw_slo["objectives"]) == {"e2e", "availability"}
        addr = fleet.views()[0].address
        with urllib.request.urlopen(
            f"http://{addr[0]}:{addr[1]}/slo", timeout=30
        ) as resp:
            rep_slo = json.loads(resp.read())
        assert set(rep_slo["objectives"]) == {"ttft", "tpot", "availability"}
        # The server span ends a hair after the response bytes; settle.
        deadline = time.monotonic() + 10
        while time.monotonic() < deadline:
            spans = [s for s in _spans(str(tmp_path))
                     if s.get("request_id") == "drill-42"
                     or s["name"].startswith(("gateway.", "engine.",
                                              "server."))]
            if any(s["name"] == "server.request" for s in spans):
                break
            time.sleep(0.05)
    finally:
        chaos.disarm()
        if server is not None:
            server.shutdown()
            server.server_close()
        fleet.stop_all(drain=False)
        for eng in engines:
            eng.close()
        for j in journals:
            j.close()

    records = merge_journals(str(tmp_path))
    roots = [r for r in records if r.get("event") == "trace.span"
             and r["name"] == "gateway.request"]
    # Exactly the traced request roots a span (the bad-json 400 fails at
    # parse, before routing — nothing worth a trace happened).
    assert len(roots) == 1
    root = roots[0]
    assert root.get("request_id") == "drill-42"
    trace = spans_for_trace(records, root["trace"])
    # ONE merged trace: every span of this request carries the same id.
    assert {s["trace"] for s in trace} == {root["trace"]}
    by_id = {s["span"]: s for s in trace}
    names = [s["name"] for s in trace]
    assert names.count("gateway.relay") == 2, names
    relays = [s for s in trace if s["name"] == "gateway.relay"]
    relays.sort(key=lambda s: s["attempt"])
    # Attempt 0: the injected connection failure, tagged retryable.
    assert relays[0]["outcome"] == "retry"
    assert relays[0]["injected_fault"] is True
    assert relays[0]["retry"] is False
    # Attempt 1: the retry, tagged as such, relayed to completion.
    assert relays[1]["outcome"] == "done"
    assert relays[1]["retry"] is True
    assert relays[1]["replica"] != relays[0]["replica"]
    for r in relays:
        assert r["parent"] == root["span"]
    # Cross-process nesting: server.request's parent IS the successful
    # relay attempt's span, recorded in a DIFFERENT journal/process track.
    srv = next(s for s in trace if s["name"] == "server.request")
    assert srv["parent"] == relays[1]["span"]
    assert srv["source"] != root["source"]
    assert srv["request_id"] == "drill-42"
    # Engine lifecycle under the server span: queue -> prefill -> decode.
    ereq = next(s for s in trace if s["name"] == "engine.request")
    assert ereq["parent"] == srv["span"]
    assert ereq["source"] == srv["source"]
    for name in ("engine.queue", "engine.prefill", "engine.decode"):
        child = next(s for s in trace if s["name"] == name)
        assert child["parent"] == ereq["span"], name
    # Parent start times precede (or equal) child start times up the chain.
    chain = [root, relays[1], srv, ereq]
    for parent, child in zip(chain, chain[1:]):
        assert child["ts"] >= parent["ts"] - 0.05
    # And the whole thing exports to valid Chrome-trace JSON.
    chrome = json.loads(json.dumps(to_chrome_trace(records, root["trace"])))
    events = chrome["traceEvents"]
    for ev in events:
        for field in ("ph", "ts", "pid", "tid"):
            assert field in ev
    exported = {ev["name"] for ev in events if ev["ph"] == "X"}
    assert {"gateway.request", "gateway.relay", "server.request",
            "engine.request", "engine.decode"} <= exported
    # One track per process: gateway + the serving replica (at least).
    tracks = {ev["args"]["name"] for ev in events if ev["ph"] == "M"}
    assert "gateway" in tracks and len(tracks) >= 2
    assert by_id  # silence linters: structure asserted above


# ---------------------------------------------------------------------------
# engine-thread tick phases (ISSUE 23): what the engine thread was doing
# ---------------------------------------------------------------------------

TICK_PHASES = ("schedule", "prefill", "dispatch", "fetch", "harvest", "spill")


@pytest.fixture(scope="module")
def tick_spans(tiny, tmp_path_factory):
    """Span records of a threaded paged engine with a host tier (so that
    every phase, ``spill`` included, has something to do) serving two
    requests with a pause between them."""
    from ditl_tpu.infer.continuous import ContinuousEngine, ThreadedEngine
    from ditl_tpu.infer.engine import GenerateConfig

    params, cfg, tok = tiny
    directory = tmp_path_factory.mktemp("ticks")
    journal = EventJournal(str(directory / "events-engine.jsonl"), source="engine")
    te = ThreadedEngine(ContinuousEngine(
        params, cfg, tok, n_slots=2, decode_chunk=4, cache_mode="paged",
        page_size=16, host_tier_mb=1, gen=GenerateConfig(max_new_tokens=8),
        tracer=Tracer(journal),
    ))
    try:
        te.generate_one(list(range(1, 21)), max_new_tokens=8)
        time.sleep(0.2)  # nothing pending: the driver idles
        te.generate_one(list(range(1, 40)), max_new_tokens=8)
    finally:
        te.close()
        journal.close()
    return _spans(str(directory))


@pytest.mark.parametrize("phase", TICK_PHASES)
def test_tick_phase_spans_are_children_of_their_tick(tick_spans, phase):
    ticks = {s["span"]: s for s in tick_spans if s["name"] == "engine.tick"}
    mine = [s for s in tick_spans if s["name"] == f"engine.tick.{phase}"]
    assert ticks and mine
    for s in mine:
        tick = ticks[s["parent"]]
        assert s["tick"] == tick["tick"] and s["trace"] == tick["trace"]
        assert tick["ts"] <= s["ts"] + 1e-6
        assert s["ts"] + s["dur_s"] <= tick["ts"] + tick["dur_s"] + 1e-3


def test_tick_phases_never_overlap_and_fit_inside_the_tick(tick_spans):
    ticks = [s for s in tick_spans if s["name"] == "engine.tick"]
    assert len({t["tick"] for t in ticks}) == len(ticks)  # one span a step()
    for tick in ticks:
        kids = sorted((s for s in tick_spans if s["parent"] == tick["span"]),
                      key=lambda s: s["ts"])
        assert {k["name"].rsplit(".", 1)[1] for k in kids} <= set(TICK_PHASES)
        assert sum(k["dur_s"] for k in kids) <= tick["dur_s"] + 1e-3
        for a, b in zip(kids, kids[1:]):
            assert a["ts"] + a["dur_s"] <= b["ts"] + 1e-3


def test_engine_idle_lies_between_ticks(tick_spans):
    idle = [s for s in tick_spans if s["name"] == "engine.idle"]
    ticks = [s for s in tick_spans if s["name"] == "engine.tick"]
    assert idle and all("tick" in s and s["parent"] == "" for s in idle)
    for s in idle:
        for t in ticks:  # an idle wait and a tick never share an instant
            assert (s["ts"] + s["dur_s"] <= t["ts"] + 1e-3
                    or t["ts"] + t["dur_s"] <= s["ts"] + 1e-3)
    assert max(s["dur_s"] for s in idle) >= 0.15  # the pause between requests


def test_unarmed_engine_opens_no_tick_span(tiny, monkeypatch):
    """Unarmed: a tick neither writes nor allocates a span for its phases
    (request spans still mint ids for propagation, as before)."""
    from ditl_tpu.infer.continuous import ContinuousEngine
    from ditl_tpu.infer.engine import GenerateConfig
    from ditl_tpu.telemetry import tracing

    params, cfg, tok = tiny
    eng = ContinuousEngine(params, cfg, tok, n_slots=2, decode_chunk=4,
                           gen=GenerateConfig(max_new_tokens=4))
    started = []
    real = tracing.Tracer.start_span

    def spy(self, name, *a, **kw):
        started.append(name)
        return real(self, name, *a, **kw)

    monkeypatch.setattr(tracing.Tracer, "start_span", spy)
    eng.submit(list(range(1, 21)), max_new_tokens=4)
    eng.run()
    assert not [n for n in started if n.startswith(("engine.tick", "engine.idle"))]
    assert eng._tick_span is None and eng._phase_span is None
    assert eng._phase("engine.tick.fetch") is None
