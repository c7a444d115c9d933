"""Disaggregated prefill/decode serving (ISSUE 9): replica roles, class-
and cache-hit-aware routing, windowed hit-ratio freshness, and the
homogeneous-vs-heterogeneous mixed-trace A/B.

Three tiers of coverage in one file:

- jax-free units: role parsing/knob derivation, class->role candidate
  steering (incl. the dead-prefill-heavy degradation), the measured-ratio
  spill pick with absent/stale fallback, the Fleet's windowed hit-ratio
  deltas (counter-reset and age-out semantics), and the SLO-name mirror
  across all three duplicated surfaces;
- stub-replica gateway drills: class steering over live HTTP, per-class
  routed/relayed/429 counters, per-role gauges, recent-ratio gauges, and
  a dead prefill-heavy replica degrading to hybrid serving;
- THE acceptance drill: the same seeded mixed trace (long batch prompts +
  interactive streams) through ``gateway_drivers.run_gateway_bench``
  against a 3-replica homogeneous fleet and a 1-prefill-heavy +
  2-decode-heavy fleet — zero failed requests, every batch relay on the
  prefill-heavy replica and every interactive one on a decode-heavy
  (trace spans), per-role serving sub-blocks, and the perf_compare gate
  passing a row against its copy while failing a degraded copy.
"""

from __future__ import annotations

import json
import threading
import time
import urllib.error
import urllib.request
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

import pytest

from ditl_tpu.config import GatewayConfig
from ditl_tpu.gateway import (
    Fleet,
    GatewayMetrics,
    InProcessReplica,
    ReplicaHandle,
    ReplicaView,
    make_gateway,
    make_policy,
    parse_roles,
    prompt_token_estimate,
    role_candidates,
    role_knobs,
)
from ditl_tpu.gateway.roles import ROLES

pytestmark = [pytest.mark.disagg, pytest.mark.gateway]


# ---------------------------------------------------------------------------
# Unit layer (no jax, no servers)
# ---------------------------------------------------------------------------


def test_slo_class_names_mirror_all_surfaces():
    """Three jax-free copies of the class names exist by design (gateway/
    admission.py, telemetry/serving.py) next to the engine's SLO_CLASSES;
    none may drift."""
    from ditl_tpu.gateway.admission import SLO_CLASS_NAMES as gw_names
    from ditl_tpu.infer.continuous import SLO_CLASSES
    from ditl_tpu.telemetry.serving import SLO_CLASS_NAMES as tm_names

    assert tuple(sorted(gw_names)) == tuple(sorted(SLO_CLASSES))
    assert tuple(sorted(tm_names)) == tuple(sorted(SLO_CLASSES))


def test_parse_roles_and_knob_derivation():
    assert parse_roles("", 3) == ["hybrid"] * 3
    assert parse_roles("prefill_heavy,decode_heavy", 3) == [
        "prefill_heavy", "decode_heavy", "hybrid"]
    with pytest.raises(ValueError, match="unknown replica role"):
        parse_roles("bogus", 2)
    with pytest.raises(ValueError, match="roles specified for"):
        parse_roles("hybrid,hybrid,hybrid", 2)

    base = dict(n_slots=4, decode_chunk=4, prefill_chunk=16, token_budget=32)
    hyb = role_knobs("hybrid", **base)
    assert (hyb["n_slots"], hyb["prefill_chunk"], hyb["token_budget"]) == \
        (4, 16, 32)
    pre = role_knobs("prefill_heavy", **base)
    # Fewer slots, 4x chunk, 4x budget, deeper page pool — and the budget
    # still covers a full decode tick plus one chunk.
    assert pre["n_slots"] == 2 and pre["prefill_chunk"] == 64
    assert pre["token_budget"] >= pre["n_slots"] * 4 + pre["prefill_chunk"]
    assert pre["pages_scale"] > 1.0
    dec = role_knobs("decode_heavy", **base)
    # Doubled slots with the tightest legal budget.
    assert dec["n_slots"] == 8 and dec["prefill_chunk"] == 16
    assert dec["token_budget"] == 8 * 4 + 16
    # Feature-off bases stay off: a role must not arm chunking/budgeting
    # the operator disabled.
    off = role_knobs("prefill_heavy", n_slots=4, decode_chunk=4,
                     prefill_chunk=0, token_budget=0)
    assert off["prefill_chunk"] == 0 and off["token_budget"] == 0
    with pytest.raises(ValueError, match="unknown replica role"):
        role_knobs("bogus", n_slots=4)


def _view(rid, role="hybrid", outstanding=0, queue_depth=0, capacity=4,
          recent_hit=0, recent_miss=0):
    return ReplicaView(
        id=rid, address=("127.0.0.1", 0), outstanding=outstanding,
        queue_depth=queue_depth, active_slots=0, capacity=capacity,
        live=True, draining=False, role=role,
        recent_cache_hit_tokens=recent_hit,
        recent_cache_miss_tokens=recent_miss,
    )


def test_role_candidates_class_steering():
    pre, dec, hyb = (_view("p", "prefill_heavy"), _view("d", "decode_heavy"),
                     _view("h", "hybrid"))
    fleet = [pre, dec, hyb]
    # Interactive (and unclassed) avoids prefill_heavy.
    assert {v.id for v in role_candidates(fleet, "interactive")} == {"d", "h"}
    assert {v.id for v in role_candidates(fleet, None)} == {"d", "h"}
    # Batch/best_effort (long_prompt_tokens=0 => all of them) avoids
    # decode_heavy.
    assert {v.id for v in role_candidates(fleet, "batch")} == {"p", "h"}
    assert {v.id for v in role_candidates(fleet, "best_effort")} == {"p", "h"}
    # Threshold: a SHORT batch prompt is not steered.
    assert {v.id for v in role_candidates(fleet, "batch", prompt_tokens=3,
                                          long_prompt_tokens=10)} == \
        {"p", "d", "h"}
    assert {v.id for v in role_candidates(fleet, "batch", prompt_tokens=20,
                                          long_prompt_tokens=10)} == \
        {"p", "h"}
    # Homogeneous fleet: steering is a no-op.
    homog = [_view("a"), _view("b")]
    assert role_candidates(homog, "interactive") == homog
    # Degradation: with the prefill_heavy replica dead (absent from the
    # candidate set) batch work falls back to the full set — no class is
    # ever unroutable.
    assert {v.id for v in role_candidates([dec], "batch")} == {"d"}
    assert {v.id for v in role_candidates([pre], "interactive")} == {"p"}
    assert prompt_token_estimate({"prompt": "a b c d"}) == 4
    assert prompt_token_estimate(
        {"messages": [{"role": "user", "content": "x y"}]}) == 2


def test_affinity_spill_prefers_measured_recent_ratio():
    """When the home saturates, the spill walk steers toward the routable
    replica whose WINDOWED hit ratio says it is actively reusing prefixes;
    absent/stale ratios keep the deterministic ring-walk target."""
    policy = make_policy("affinity")
    key = "hot-prefix"
    views = [_view(f"r{i}", capacity=2) for i in range(4)]
    home = policy.pick(key, views).id
    peers = [v.id for v in views if v.id != home]

    def saturated(recent: dict):
        return [
            _view(v.id, outstanding=2 if v.id == home else 0, capacity=2,
                  recent_hit=recent.get(v.id, (0, 0))[0],
                  recent_miss=recent.get(v.id, (0, 0))[1])
            for v in views
        ]

    # No ratios anywhere: the deterministic ring-walk spill (old behavior).
    walk_target = policy.pick(key, saturated({})).id
    assert walk_target != home
    assert policy.pick(key, saturated({})).id == walk_target  # stable
    # A DIFFERENT peer shows a live windowed ratio: the spill follows the
    # measurement instead of the walk.
    rated = next(p for p in peers if p != walk_target)
    picked = policy.pick(key, saturated({rated: (30, 10)})).id
    assert picked == rated
    # The best ratio wins when several peers are warm.
    other = next(p for p in peers if p not in (walk_target, rated))
    picked = policy.pick(
        key, saturated({rated: (30, 10), other: (99, 1)})).id
    assert picked == other
    # A zero recent ratio (active but missing everything) is NOT evidence
    # it holds the prefix: deterministic walk again.
    assert policy.pick(key, saturated({rated: (0, 50)})).id == walk_target
    # Home healthy again: traffic goes home regardless of peer ratios.
    healthy = [_view(v.id, recent_hit=50) for v in views]
    assert policy.pick(key, healthy).id == home


class _FakeHandle(ReplicaHandle):
    """Probe-only handle: serves whatever health dict the test sets."""

    def __init__(self, rid, role="hybrid"):
        super().__init__(rid, role=role)
        self.payload: dict = {"status": "ok", "n_slots": 2}

    def alive(self):
        return True

    @property
    def address(self):
        return ("127.0.0.1", 1)

    def fetch_health(self, timeout=2.0):
        return dict(self.payload)


def test_fleet_windowed_recent_ratio_freshness():
    """/health hit/miss counters are lifetime-cumulative: the Fleet's
    per-poll deltas give a windowed recent ratio that (a) tracks what the
    replica is doing NOW, (b) ages out to None on idle replicas, and (c)
    survives counter resets (replica restart) without nonsense negative
    deltas."""
    h = _FakeHandle("r0")
    fleet = Fleet([h], cache_window_polls=3)

    def probe(hit, miss):
        h.payload = {"status": "ok", "n_slots": 2,
                     "cache_hit_tokens": hit, "cache_miss_tokens": miss}
        assert fleet.probe("r0")
        return fleet.views()[0]

    v = probe(0, 0)       # first sample: no delta yet
    assert v.recent_cache_hit_ratio is None
    v = probe(80, 20)     # +80/+20 in one window
    assert v.recent_cache_hit_ratio == pytest.approx(0.8)
    assert v.cache_hit_ratio == pytest.approx(0.8)
    # Idle polls age the activity out of the bounded window: the LIFETIME
    # ratio stays sticky at 0.8 while the recent one goes stale (None).
    for _ in range(3):
        v = probe(80, 20)
    assert v.cache_hit_ratio == pytest.approx(0.8)  # stale-sticky
    assert v.recent_cache_hit_ratio is None         # windowed: honest
    # Counter reset (replica restarted with a fresh engine): the window
    # clears instead of recording a negative delta...
    v = probe(10, 0)
    assert v.recent_cache_hit_ratio is None
    # ...and the next delta measures the NEW engine.
    v = probe(20, 0)
    assert v.recent_cache_hit_ratio == pytest.approx(1.0)


def test_replica_view_slot_pressure_and_role_defaults():
    v = ReplicaView(id="r0", address=("h", 1), outstanding=0, queue_depth=0,
                    active_slots=3, capacity=4, live=True, draining=False)
    assert v.role == "hybrid" and v.slot_pressure == pytest.approx(0.75)
    assert v.ttft_p95_s is None and v.tpot_p95_s is None
    assert "hybrid" in ROLES


# ---------------------------------------------------------------------------
# Stub-replica layer: role steering + class counters over live HTTP
# ---------------------------------------------------------------------------


class _RoleStubServer(ThreadingHTTPServer):
    daemon_threads = True
    allow_reuse_address = True
    label = "stub"
    health_extra: dict = {}
    behavior = "ok"

    def close(self, drain=True, timeout=30.0):
        self.shutdown()
        self.server_close()

    def kill(self):
        self.close()


class _RoleStubHandler(BaseHTTPRequestHandler):
    def log_message(self, *args):
        pass

    def _json(self, status, payload, headers=()):
        body = json.dumps(payload).encode()
        self.send_response(status)
        self.send_header("Content-Type", "application/json")
        for k, v in headers:
            self.send_header(k, v)
        self.send_header("Content-Length", str(len(body)))
        self.end_headers()
        self.wfile.write(body)

    def do_GET(self):
        self._json(200, {"status": "ok", "draining": False,
                         "queue_depth": 0, "active_slots": 1, "n_slots": 2,
                         **self.server.health_extra})

    def do_POST(self):
        self.rfile.read(int(self.headers.get("Content-Length", 0)))
        if self.server.behavior == "busy":
            self._json(429, {"error": {"message": "queue full",
                                       "type": "rate_limit_error"}},
                       headers=[("Retry-After", "2")])
            return
        self._json(200, {
            "object": "text_completion",
            "choices": [{"index": 0, "text": self.server.label,
                         "finish_reason": "stop"}],
            "usage": {"prompt_tokens": 1, "completion_tokens": 1,
                      "total_tokens": 2},
        })


def _stub(rid, role="hybrid", health_extra=None, behavior="ok",
          handle_role=None):
    def factory():
        server = _RoleStubServer(("127.0.0.1", 0), _RoleStubHandler)
        server.label = rid
        server.health_extra = dict(health_extra or {})
        server.behavior = behavior
        return server

    return InProcessReplica(rid, factory,
                            role=handle_role if handle_role else role)


def _post(port, body, headers=None, timeout=30):
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}/v1/completions",
        data=json.dumps(body).encode(),
        headers={"Content-Type": "application/json", **(headers or {})},
        method="POST",
    )
    try:
        with urllib.request.urlopen(req, timeout=timeout) as resp:
            return resp.status, json.loads(resp.read())
    except urllib.error.HTTPError as e:
        return e.code, json.loads(e.read())


def _scrape(port, path="/metrics"):
    with urllib.request.urlopen(
        f"http://127.0.0.1:{port}{path}", timeout=10
    ) as resp:
        return resp.read().decode()


def _start(fleet, cfg, metrics):
    server = make_gateway(fleet, config=cfg, metrics=metrics, port=0)
    threading.Thread(target=server.serve_forever, daemon=True).start()
    return server, server.server_address[1]


def test_gateway_steers_classes_and_exposes_role_metrics():
    """Interactive work lands on the decode-heavy replica, batch on the
    prefill-heavy one (role read from /health on r1, from the HANDLE on r0
    — both sources work); the /metrics exposition carries the per-class
    routed/relayed counters, per-role routed counters and latency gauges,
    and the windowed recent-ratio gauge next to the lifetime one."""
    # r0: role only on the handle (health omits it). r1: role only in
    # health (handle says hybrid) — the health report must win.
    fleet = Fleet([
        _stub("r0", role="prefill_heavy"),
        _stub("r1", handle_role="hybrid",
              health_extra={"role": "decode_heavy", "ttft_p95_s": 0.12,
                            "tpot_p95_s": 0.034,
                            "cache_hit_tokens": 0, "cache_miss_tokens": 0}),
    ])
    fleet.start_all()
    for rid in fleet.ids:
        assert fleet.probe(rid, timeout=5.0)
    metrics = GatewayMetrics()
    server, port = _start(
        fleet, GatewayConfig(router="least_outstanding"), metrics)
    try:
        # Second poll with moved counters: the windowed recent ratio
        # appears (deltas 30 hit / 10 miss).
        fleet.handle("r1")  # r1's stub health mutates via health_extra
        fleet._state("r1").handle._server.health_extra.update(
            {"cache_hit_tokens": 30, "cache_miss_tokens": 10})
        assert fleet.probe("r1", timeout=5.0)

        status, out = _post(port, {"prompt": "hi", "slo_class": "interactive"})
        assert (status, out["choices"][0]["text"]) == (200, "r1")
        status, out = _post(port, {"prompt": "a long batch prompt here",
                                   "slo_class": "batch"})
        assert (status, out["choices"][0]["text"]) == (200, "r0")
        status, out = _post(port, {"prompt": "hi"})  # unclassed -> default
        assert (status, out["choices"][0]["text"]) == (200, "r1")
        # Header steering works too (the gateway pin contract).
        status, out = _post(port, {"prompt": "hi"},
                            headers={"X-SLO-Class": "batch"})
        assert (status, out["choices"][0]["text"]) == (200, "r0")

        body = _scrape(port)
        assert "ditl_gateway_routed_by_class_interactive_total 1" in body
        assert "ditl_gateway_routed_by_class_batch_total 2" in body
        assert "ditl_gateway_routed_by_class_default_total 1" in body
        assert "ditl_gateway_relayed_by_class_interactive_total 1" in body
        assert "ditl_gateway_role_decode_heavy_routed_total 2" in body
        assert "ditl_gateway_role_prefill_heavy_routed_total 2" in body
        assert "ditl_gateway_role_decode_heavy_ttft_p95_s 0.12" in body
        assert "ditl_gateway_role_decode_heavy_tpot_p95_s 0.034" in body
        assert "ditl_gateway_role_prefill_heavy_replicas_live 1" in body
        assert ("ditl_gateway_replica_r1_recent_prefix_cache_hit_ratio 0.75"
                in body)
        assert "ditl_gateway_fleet_recent_prefix_cache_hit_ratio 0.75" in body
        stats = json.loads(_scrape(port, "/stats"))
        assert stats["replicas"]["r0"]["role"] == "prefill_heavy"
        assert stats["replicas"]["r1"]["role"] == "decode_heavy"
        assert stats["replicas"]["r1"]["ttft_p95_s"] == 0.12
        assert stats["replicas"]["r1"]["recent_prefix_cache_hit_ratio"] == \
            pytest.approx(0.75)
        assert "slot_pressure" in stats["replicas"]["r0"]
    finally:
        server.shutdown()
        server.server_close()
        fleet.stop_all(drain=False)


def test_dead_prefill_heavy_degrades_to_hybrid_serving():
    """Kill the only prefill-heavy replica: batch work must fall back to
    the decode-heavy survivor (200, not 503) — no request class becomes
    unroutable. Fleet-saturated 429s are counted per class."""
    fleet = Fleet([
        _stub("r0", role="prefill_heavy"),
        _stub("r1", role="decode_heavy"),
    ])
    fleet.start_all()
    for rid in fleet.ids:
        assert fleet.probe(rid, timeout=5.0)
    metrics = GatewayMetrics()
    server, port = _start(
        fleet, GatewayConfig(router="least_outstanding", max_attempts=3),
        metrics)
    try:
        fleet.handle("r0").kill()
        fleet.probe("r0", timeout=1.0)  # corpse: live -> False
        status, out = _post(port, {"prompt": "big batch job",
                                   "slo_class": "batch"}, timeout=60)
        assert (status, out["choices"][0]["text"]) == (200, "r1")
    finally:
        server.shutdown()
        server.server_close()
        fleet.stop_all(drain=False)

    # Saturated fleet: the 429 is attributed to the request's class.
    busy = Fleet([_stub("b0", role="decode_heavy", behavior="busy")])
    busy.start_all()
    assert busy.probe("b0", timeout=5.0)
    metrics = GatewayMetrics()
    server, port = _start(busy, GatewayConfig(router="least_outstanding"),
                          metrics)
    try:
        status, _ = _post(port, {"prompt": "hi", "slo_class": "interactive"})
        assert status == 429
        assert "ditl_gateway_429_by_class_interactive_total 1" in \
            _scrape(port)
    finally:
        server.shutdown()
        server.server_close()
        busy.stop_all(drain=False)


# ---------------------------------------------------------------------------
# Acceptance: the mixed trace on a homogeneous and a disaggregated fleet
# ---------------------------------------------------------------------------


def test_disagg_fleet_beats_homogeneous_on_mixed_trace(tmp_path):
    """THE acceptance drill: the same seeded mixed trace (long batch-class
    prompts + interactive short streams) through run_gateway_bench against
    a 3-replica homogeneous fleet and a 1-prefill-heavy + 2-decode-heavy
    fleet (unchunked/unbudgeted legs — the starkest role contrast: a
    whole-prompt long prefill is the stall the roles keep off interactive
    replicas). Whether the stall is SHORTER is a timing on shared CPU
    cores and is not asserted here; what the roles decide is:

    - zero failed requests (every request returned 200 — the driver
      raises otherwise) and the batch prompts generated tokens;
    - role-routing decisions are visible in the exported trace spans
      (every batch relay landed on the prefill-heavy replica, every
      interactive relay on a decode-heavy one), so no decode-heavy
      replica ever ran a long batch prefill;
    - the row carries fleet_roles + per-role serving sub-blocks, and the
      perf_compare gate passes a row against its copy while failing a
      synthetically degraded copy (direction sense on the class keys)."""
    from ditl_tpu.telemetry.perf_compare import compare_records
    from tests.gateway_drivers import run_gateway_bench

    # Short prompts are kept SMALL relative to the longs (8 words ~ 60
    # byte-tokens vs 32 words ~ 300).
    kw = dict(
        slots=2, decode_chunk=2, prompt_len=8, max_new=16,
        prefill_chunk=0, token_budget=0,  # unchunked/unbudgeted legs
    )
    homog = run_gateway_bench(3, roles="", **kw)
    trace_out = str(tmp_path / "disagg_trace.json")
    disagg = run_gateway_bench(
        3, roles="prefill_heavy,decode_heavy,decode_heavy",
        trace_out=trace_out, **kw)

    assert homog["gateway"]["fleet_roles"] == ["hybrid"] * 3
    assert disagg["gateway"]["fleet_roles"] == [
        "prefill_heavy", "decode_heavy", "decode_heavy"]
    # Same trace, all requests served (the driver raises on any non-200).
    assert homog["requests"] == disagg["requests"] > 0

    h_s, d_s = homog["serving"], disagg["serving"]
    # Precondition: the homogeneous fleet DID co-schedule long prefills
    # against interactive decode streams.
    assert h_s["interactive_interference_count"] > 0
    assert h_s["interactive_interference_max_s"] > 0.0
    # The interactive TTFT instrument ran on both fleets.
    assert h_s["interactive_ttft_p95_s"] is not None
    assert d_s["interactive_ttft_p95_s"] is not None
    # Batch work was not starved: the long prompts generated tokens on
    # both fleets (same trace => same request count; tokens are summed
    # fleet-wide and every request completed).
    assert homog["generated_tokens"] > 0
    assert disagg["generated_tokens"] > 0
    # Per-role sub-blocks: the prefill-heavy replica absorbed prompt work,
    # the decode-heavy ones saw interactive TTFTs.
    by_role = disagg["gateway"]["serving_by_role"]
    assert set(by_role) == {"prefill_heavy", "decode_heavy"}
    assert by_role["decode_heavy"]["interactive_ttft_p95_s"] is not None
    # Decode-heavy replicas never ran a long batch prefill: any
    # interference their interactive streams absorbed came from SHORT
    # interactive prompts.
    assert (by_role["decode_heavy"]["batch_ttft_p95_s"] is None
            or by_role["prefill_heavy"]["batch_ttft_p95_s"] is not None)

    # Role-routing decisions are span-visible: every batch relay went to
    # the prefill-heavy replica, every interactive one to a decode-heavy.
    with open(trace_out) as f:
        events = json.load(f)["traceEvents"]
    relays = [e for e in events
              if e.get("name") == "gateway.relay" and "args" in e]
    assert relays, "no gateway.relay spans in the exported trace"
    classed = [e["args"] for e in relays if "slo_class" in e["args"]]
    batch = [a for a in classed if a["slo_class"] == "batch"]
    interactive = [a for a in classed if a["slo_class"] == "interactive"]
    assert batch and interactive
    assert all(a["role"] == "prefill_heavy" for a in batch), batch
    assert all(a["role"] == "decode_heavy" for a in interactive)

    # perf_compare gates the disagg row: identical copy passes, a
    # synthetically degraded copy (interactive latency worsened) fails
    # with the new keys named.
    disagg_copy = json.loads(json.dumps(disagg))
    code, report = compare_records(disagg, disagg_copy, 0.05)
    assert code == 0, report
    degraded = json.loads(json.dumps(homog))
    degraded["serving"]["interactive_interference_p95_s"] = (
        (homog["serving"]["interactive_interference_p95_s"] or 0.001) * 3)
    degraded["serving"]["interactive_ttft_p95_s"] = \
        homog["serving"]["interactive_ttft_p95_s"] * 3
    code, report = compare_records(homog, degraded, 0.05)
    assert code == 1
    assert "interactive_ttft_p95_s" in report
