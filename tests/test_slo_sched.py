"""Stall-free SLO-class scheduling + measured prefix-cache hit accounting
(ISSUE 8).

Three tiers of coverage in one file:

- jax-free units: class-ordered tenant pinning, the allocator's eviction
  counter, the serving bench summary + its perf_compare gate, and the
  engine/gateway SLO-name mirror;
- engine-level drills over tiny models: class-ordered admission, the
  best-effort-first preemption rule, prefix-cache hit/miss accounting with
  the TTFT split, and THE mixed-workload drill — one long batch-class
  prompt co-scheduled against interactive decode streams, budgeted vs
  unbudgeted on the same trace;
- a real 3-replica paged fleet behind the gateway: affinity routing yields
  a measured engine cache-hit ratio > 0 where round-robin yields exactly 0
  on an equivalent trace — the affinity router's docstring claim pinned to
  a measurement for the first time.
"""

from __future__ import annotations

import json
import threading
import time
import urllib.error
import urllib.request
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

import jax
import pytest

from ditl_tpu.config import GatewayConfig, ModelConfig
from ditl_tpu.data.tokenizer import ByteTokenizer
from ditl_tpu.gateway import (
    Fleet,
    GatewayMetrics,
    InProcessReplica,
    TenantAdmission,
    make_gateway,
)
from ditl_tpu.gateway.admission import SLO_CLASS_NAMES
from ditl_tpu.infer.continuous import (
    SLO_CLASSES,
    BadRequestError,
    ContinuousEngine,
    ThreadedEngine,
)
from ditl_tpu.infer.engine import GenerateConfig, Generator
from ditl_tpu.infer.paged_cache import PageAllocator
from ditl_tpu.infer.server import make_server
from ditl_tpu.models import llama
from ditl_tpu.telemetry.serving import (
    ServingMetrics,
    merged_histogram,
    serving_bench_summary,
    snapshot_serving,
)

pytestmark = pytest.mark.slo_sched


# ---------------------------------------------------------------------------
# Unit layer (no jax device work)
# ---------------------------------------------------------------------------


def test_slo_class_names_mirror_engine():
    """gateway/admission.py duplicates the class names to stay jax-free on
    import; the two surfaces must never drift."""
    assert tuple(sorted(SLO_CLASS_NAMES)) == tuple(sorted(SLO_CLASSES))
    # interactive must outrank batch must outrank best_effort.
    assert (SLO_CLASSES["interactive"] < SLO_CLASSES["batch"]
            < SLO_CLASSES["best_effort"])


def test_tenant_admission_pins_slo_class():
    ta = TenantAdmission(rate=100.0,
                         per_tenant={"bulk": {"slo_class": "batch"}},
                         slo_class="")
    assert ta.acquire("bulk").slo_class == "batch"
    assert ta.acquire("someone-else").slo_class == ""
    # A default pin covers every tenant; per-tenant overrides win.
    ta2 = TenantAdmission(slo_class="best_effort",
                          per_tenant={"vip": {"slo_class": "interactive"}})
    assert ta2.acquire("anyone").slo_class == "best_effort"
    assert ta2.acquire("vip").slo_class == "interactive"
    with pytest.raises(ValueError, match="unknown SLO class"):
        TenantAdmission(slo_class="bogus")
    with pytest.raises(ValueError, match="unknown SLO class"):
        TenantAdmission(per_tenant={"t": {"slo_class": "urgent"}})


def test_gateway_config_validates_tenant_slo_class():
    with pytest.raises(ValueError, match="tenant_slo_class"):
        GatewayConfig(tenant_slo_class="urgent")
    assert GatewayConfig(tenant_slo_class="batch").tenant_slo_class == "batch"


def test_page_allocator_counts_evictions():
    fired = []
    alloc = PageAllocator(4, on_evict=fired.append)
    pages = alloc.alloc(3)  # the whole usable pool
    alloc.publish_chain(list(range(32)), 16, pages[:2])
    for pid in pages:
        alloc.release(pid)  # cache refs keep the 2 published pages resident
    assert alloc.evictions == 0
    got = alloc.alloc(2)  # 1 free + 1 via LRU eviction
    assert len(got) == 2
    assert alloc.evictions == 1
    # The callback now carries the evicted group (ISSUE 13): the claimed
    # parent plus its cascaded child, parent first, with exact chain blocks.
    assert len(fired) == 1
    group = fired[0]
    assert [pid for pid, _, _ in group] == [pages[0], pages[1]]
    assert group[0][1] == 0 and group[0][2] == (tuple(range(16)),)
    assert group[1][2] == (tuple(range(16)), tuple(range(16, 32)))


def test_merged_histogram_and_bench_summary_gate():
    a, b = ServingMetrics(), ServingMetrics()
    for v in (0.01, 0.02, 0.04):
        a.tpot_interference.observe(v)
    b.tpot_interference.observe(0.08)
    a.note_prefix_cache(48, 16)
    b.note_prefix_cache(0, 64)
    merged = merged_histogram([a.tpot_interference, b.tpot_interference])
    assert merged.count == 4
    assert merged.sum == pytest.approx(0.15)
    with pytest.raises(ValueError, match="bucket ladders differ"):
        merged_histogram([a.tpot_interference, a.ttft])
    summary = serving_bench_summary([a, b])
    assert summary["interference_count"] == 4
    assert summary["prefix_cache_hit_ratio"] == pytest.approx(48 / 128)
    assert summary["interference_p95_s"] > summary["interference_p50_s"]
    # A post-warm-up snapshot restricts the summary to the timed region
    # (warm-up TTFT/compile seconds and misses must not reach the gate).
    base = snapshot_serving([a, b])
    a.tpot_interference.observe(0.02)
    a.note_prefix_cache(16, 0)
    delta = serving_bench_summary([a, b], since=base)
    assert delta["interference_count"] == 1
    assert delta["prefix_cache_hit_tokens"] == 16
    assert delta["prefix_cache_hit_ratio"] == 1.0

    # The perf_compare gate accepts the serving block and regresses when
    # interference p95 rises or the hit ratio falls (direction sense).
    from ditl_tpu.telemetry.perf_compare import compare_records

    base = {"metric": "fleet", "schema": 1, "value": 100.0,
            "serving": dict(summary)}
    same = json.loads(json.dumps(base))
    code, report = compare_records(base, same, 0.05)
    assert code == 0, report
    worse = json.loads(json.dumps(base))
    worse["serving"]["interference_p95_s"] *= 2.0
    worse["serving"]["prefix_cache_hit_ratio"] *= 0.5
    code, report = compare_records(base, worse, 0.05)
    assert code == 1
    assert "interference_p95_s" in report
    assert "prefix_cache_hit_ratio" in report


def test_pod_driver_rejects_non_default_class():
    from ditl_tpu.infer.podserve import PodContinuousDriver

    assert PodContinuousDriver.supports_slo_classes is False
    PodContinuousDriver._reject_slo_class(None)
    PodContinuousDriver._reject_slo_class("interactive")
    with pytest.raises(BadRequestError, match="pod"):
        PodContinuousDriver._reject_slo_class("batch")


# ---------------------------------------------------------------------------
# Engine layer: class ordering, budget drill, prefix accounting
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def tiny_setup():
    cfg = ModelConfig(
        vocab_size=512, hidden_size=64, intermediate_size=128, num_layers=2,
        num_heads=4, num_kv_heads=2, head_dim=16, max_seq_len=256,
        dtype="float32", param_dtype="float32",
    )
    params = llama.init_params(jax.random.key(0), cfg)
    return params, cfg, ByteTokenizer()


def _drain(eng):
    done = {}
    while eng.pending:
        eng.step()
        for req in eng.take_finished():
            done[req.req_id] = req
    return done


def test_submit_validates_slo_class_and_budget_config(tiny_setup):
    params, cfg, tok = tiny_setup
    eng = ContinuousEngine(params, cfg, tok, n_slots=2, decode_chunk=4)
    with pytest.raises(BadRequestError, match="slo_class"):
        eng.submit([1, 2, 3], slo_class="urgent")
    with pytest.raises(ValueError, match="token_budget"):
        ContinuousEngine(params, cfg, tok, n_slots=2, decode_chunk=4,
                         token_budget=7)  # < 2 x 4
    with pytest.raises(ValueError, match="token_budget"):
        ContinuousEngine(params, cfg, tok, n_slots=2, decode_chunk=4,
                         token_budget=-1)


def test_queue_orders_by_class_then_arrival(tiny_setup):
    """One slot: a later interactive submission is admitted before an
    earlier batch/best_effort one; arrival order breaks ties in-class."""
    params, cfg, tok = tiny_setup
    eng = ContinuousEngine(params, cfg, tok, n_slots=1, decode_chunk=2,
                           gen=GenerateConfig(max_new_tokens=4))
    rb = eng.submit([1] + list(range(5, 15)), slo_class="best_effort")
    ra = eng.submit([1] + list(range(20, 30)), slo_class="batch")
    ri = eng.submit([1] + list(range(40, 50)), slo_class="interactive")
    done = _drain(eng)
    t = {r: done[r].t_admitted for r in (rb, ra, ri)}
    assert t[ri] < t[ra] < t[rb]
    assert done[ri].slo_class == "interactive"


def test_preemption_evicts_best_effort_before_interactive(tiny_setup):
    """Pool pressure with an OLDER best_effort and a younger interactive
    request: the best_effort one is preempted (the pre-SLO rule would have
    evicted the youngest — the interactive request). Both still finish."""
    params, cfg, tok = tiny_setup
    eng = ContinuousEngine(
        params, cfg, tok, n_slots=4, decode_chunk=4, cache_mode="paged",
        page_size=16, n_pages=10, admission="optimistic",
        gen=GenerateConfig(max_new_tokens=96),
    )
    rb = eng.submit([1] + list(range(5, 21)), slo_class="best_effort")
    ri = eng.submit([1] + list(range(30, 46)), slo_class="interactive")
    preempted_classes = set()
    done = {}
    while eng.pending:
        eng.step()
        for r in eng._queue:
            # A queued request that was ever admitted is a preemption
            # requeue (fresh requests have no admission stamp yet).
            if r.t_admitted:
                preempted_classes.add(r.slo_class)
        for req in eng.take_finished():
            done[req.req_id] = req
    assert eng.preemptions >= 1
    assert preempted_classes == {"best_effort"}
    assert len(done[rb].tokens) == 96 and len(done[ri].tokens) == 96


def test_prefix_cache_accounting_and_ttft_split(tiny_setup):
    """Second prompt sharing a 2-page prefix: hit tokens move, the TTFT
    histogram splits by hit/miss, and /stats-shaped numbers agree."""
    params, cfg, tok = tiny_setup
    eng = ContinuousEngine(
        params, cfg, tok, n_slots=2, decode_chunk=4, cache_mode="paged",
        page_size=16, gen=GenerateConfig(max_new_tokens=4),
    )
    shared = [1] + list(range(3, 36))  # 34 tokens: 2 full pages
    r1 = eng.submit(shared + [100, 101])
    _drain(eng)
    r2 = eng.submit(shared + [120, 121, 122])
    _drain(eng)
    m = eng.metrics
    assert m.prefix_cache_hit_tokens.value == 32  # 2 pages on request 2
    assert m.prefix_cache_miss_tokens.value > 0
    assert m.ttft_cache_miss.count == 1  # request 1
    assert m.ttft_cache_hit.count == 1   # request 2
    pc = eng.stats()["prefix_cache"]
    assert pc["hit_tokens"] == 32
    assert 0.0 < pc["hit_ratio"] < 1.0
    assert pc["evictions"] == 0
    assert eng.stats()["queue_by_class"]["interactive"] == 0
    del r1, r2


def test_prefix_matched_admission_debits_only_the_suffix(tiny_setup):
    """A registered-prefix hit costs no device work, so it must not debit
    the tick's token budget (nor inflate max_tick_prefill_tokens — the
    number the budget bound is audited against)."""
    params, cfg, tok = tiny_setup
    eng = ContinuousEngine(params, cfg, tok, n_slots=2, decode_chunk=4,
                           token_budget=64,
                           gen=GenerateConfig(max_new_tokens=2))
    prefix = [1] + list(range(3, 50))  # 48 tokens
    eng.register_prefix(prefix)
    eng.submit(prefix + [60, 61, 62])
    _drain(eng)
    # 51-token prompt, 48 from the registered prefix: only the 3-token
    # suffix was prefilled (and debited).
    assert eng.max_tick_prefill_tokens == 3
    assert eng.metrics.prefix_cache_hit_tokens.value == 48


def test_note_prefix_cache_is_idempotent(tiny_setup):
    """A mid-prefill preemption victim re-admits as FRESH; the second
    admission must not re-count its prompt (nor flip it to a hit off its
    own just-published pages)."""
    from ditl_tpu.infer.continuous import Request

    params, cfg, tok = tiny_setup
    eng = ContinuousEngine(params, cfg, tok, n_slots=1, decode_chunk=4)
    req = Request(req_id=0, prompt=list(range(40)), max_new_tokens=4,
                  temperature=0.0, top_p=1.0, seed=0)
    eng._note_prefix_cache(req, 0)
    eng._note_prefix_cache(req, 32)  # re-admission claiming its own pages
    assert req.cache_hit_tokens == 0 and req.cache_miss_tokens == 40
    assert eng.metrics.prefix_cache_hit_tokens.value == 0
    assert eng.metrics.prefix_cache_miss_tokens.value == 40


@pytest.fixture(scope="module")
def drill_setup():
    """Bigger tiny model for the budget drill: a 225-token prefill is
    visibly more work than a decode tick, so every co-scheduled tick
    leaves an interference observation."""
    cfg = ModelConfig(
        vocab_size=512, hidden_size=256, intermediate_size=688, num_layers=2,
        num_heads=8, num_kv_heads=4, head_dim=32, max_seq_len=512,
        dtype="float32", param_dtype="float32",
    )
    params = llama.init_params(jax.random.key(0), cfg)
    tok = ByteTokenizer()
    # A random model emits pad or EOS now and then, which ends a stream
    # early (whichever init the seed happens to give): zero their head
    # columns, so their logit is exactly 0 under greedy decoding's maximum
    # of 510 random ones, and a stream ends only at its token budget.
    head = params["lm_head"]["kernel"]
    params["lm_head"]["kernel"] = head.at[:, [tok.pad_id, tok.eos_id]].set(0.0)
    return params, cfg, tok


LONG_PROMPT = [1] + list(range(2, 226))  # 225 tokens
SHORT_PROMPTS = [[1] + list(range(s, s + 16)) for s in (5, 40, 80)]


def _mixed_drill(params, cfg, tok, *, prefill_chunk, token_budget):
    eng = ContinuousEngine(
        params, cfg, tok, n_slots=4, decode_chunk=4,
        prefill_chunk=prefill_chunk, token_budget=token_budget,
        gen=GenerateConfig(max_new_tokens=48),
    )
    # Warm-up: compile every program OUTSIDE the measured phase (the
    # interference attribution measures wall seconds; a compile inside the
    # measured phase would swamp the comparison).
    eng.submit(list(LONG_PROMPT), max_new_tokens=2)
    eng.submit(list(SHORT_PROMPTS[0]), max_new_tokens=2)
    _drain(eng)
    eng.interference_max_s = 0.0
    eng.max_tick_prefill_tokens = 0
    base_obs = eng.metrics.tpot_interference.count
    # Measured phase: three interactive streams mid-decode, then the long
    # batch-class prompt lands.
    short_ids = [eng.submit(list(p), slo_class="interactive")
                 for p in SHORT_PROMPTS]
    for _ in range(2):
        eng.step()
    long_id = eng.submit(list(LONG_PROMPT), slo_class="batch",
                         max_new_tokens=4)
    done = _drain(eng)
    return {
        "tokens": {r: done[r].tokens for r in short_ids + [long_id]},
        "short_ids": short_ids,
        "long_id": long_id,
        "max_tick_prefill": eng.max_tick_prefill_tokens,
        "interference_max_s": eng.interference_max_s,
        "interference_obs": eng.metrics.tpot_interference.count - base_obs,
        "ttft_count": eng.metrics.ttft.count,
        "done": done,
    }


def test_mixed_workload_budget_bounds_interference(drill_setup):
    """THE acceptance drill: one long batch-class prompt co-scheduled
    against 3 interactive decode streams, budgeted (chunked, token budget)
    vs unbudgeted (whole-prompt prefill) on the same seeds/trace.

    - per-tick prefill under the budget never exceeds the configured
      allowance, while the unbudgeted scheduler spends the whole prompt in
      one tick (the deterministic form of "interference bounded by the
      budget");
    - the budgeted run spreads the prefill over many ticks, so victims see
      many interference observations where the unbudgeted run sees one
      (both instruments read, neither timing ordered against the other:
      a stall measured on shared CPU cores is not evidence);
    - outputs are token-identical across both schedulers (budgeting
      reshuffles WHEN work runs, never what it computes), so interactive
      TTFT cannot regress for correctness reasons, and every stream
      completes its full budget (no starvation under the budget)."""
    params, cfg, tok = drill_setup
    budget = 4 * 4 + 16  # n_slots x decode_chunk + one 16-token chunk
    budgeted = _mixed_drill(params, cfg, tok,
                            prefill_chunk=16, token_budget=budget)
    unbudgeted = _mixed_drill(params, cfg, tok,
                              prefill_chunk=0, token_budget=0)
    # Deterministic bound: the budgeted scheduler's worst tick spent at
    # most the allowance; the unbudgeted one swallowed the whole prompt.
    assert budgeted["max_tick_prefill"] <= budget
    assert unbudgeted["max_tick_prefill"] >= len(LONG_PROMPT)
    # The stall instrument ran on both legs.
    assert budgeted["interference_max_s"] > 0.0
    assert unbudgeted["interference_max_s"] > 0.0
    # The budgeted run spread the prefill across many ticks — victims saw
    # many small observations instead of one big one.
    assert budgeted["interference_obs"] > unbudgeted["interference_obs"]
    # Token-identical outputs: scheduling is invisible to sampling.
    assert budgeted["tokens"] == unbudgeted["tokens"]
    # No starvation: every interactive stream delivered its full budget
    # and the long prompt completed too.
    for r in budgeted["short_ids"]:
        assert len(budgeted["tokens"][r]) == 48
    assert len(budgeted["tokens"][budgeted["long_id"]]) == 4


# ---------------------------------------------------------------------------
# HTTP layer: server slo_class surface + gateway pinning (stub replicas)
# ---------------------------------------------------------------------------


def _post(port, body, path="/v1/completions", headers=None, timeout=60):
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}{path}", data=json.dumps(body).encode(),
        headers={"Content-Type": "application/json", **(headers or {})},
        method="POST",
    )
    try:
        with urllib.request.urlopen(req, timeout=timeout) as resp:
            return resp.status, json.loads(resp.read())
    except urllib.error.HTTPError as e:
        return e.code, json.loads(e.read())


def test_server_slo_class_surface(tiny_setup):
    """Payload + header parsing on the replica: valid classes serve,
    garbage 400s, the header wins over the payload (the gateway pin
    contract), and /stats//metrics expose the new accounting."""
    params, cfg, tok = tiny_setup
    eng = ThreadedEngine(ContinuousEngine(
        params, cfg, tok, n_slots=2, decode_chunk=4, cache_mode="paged",
        page_size=16, gen=GenerateConfig(max_new_tokens=4), token_budget=32,
    ))
    server = make_server(Generator(params, cfg, tok), port=0,
                         threaded_engine=eng, default_max_tokens=4)
    threading.Thread(target=server.serve_forever, daemon=True).start()
    port = server.server_address[1]
    try:
        status, _ = _post(port, {"prompt": "hello", "max_tokens": 2,
                                 "slo_class": "batch"})
        assert status == 200
        status, out = _post(port, {"prompt": "hello", "max_tokens": 2,
                                   "slo_class": "urgent"})
        assert status == 400 and "slo_class" in out["error"]["message"]
        # Header precedence: a valid header shadows a bogus payload value.
        status, _ = _post(port, {"prompt": "hello", "max_tokens": 2,
                                 "slo_class": "urgent"},
                          headers={"X-SLO-Class": "best_effort"})
        assert status == 200
        status, out = _post(port, {"prompt": "hello", "max_tokens": 2},
                            headers={"X-SLO-Class": "nope"})
        assert status == 400
        with urllib.request.urlopen(
            f"http://127.0.0.1:{port}/v1/stats", timeout=10
        ) as resp:
            stats = json.loads(resp.read())
        assert stats["token_budget"] == 32
        assert "hit_tokens" in stats["prefix_cache"]
        assert set(stats["queue_by_class"]) == set(SLO_CLASSES)
        with urllib.request.urlopen(
            f"http://127.0.0.1:{port}/metrics", timeout=10
        ) as resp:
            metrics = resp.read().decode()
        for family in ("ditl_serving_prefix_cache_hit_tokens_total",
                       "ditl_serving_prefix_cache_miss_tokens_total",
                       "ditl_serving_prefix_cache_evictions_total",
                       "ditl_serving_prefix_cache_hit_ratio",
                       "ditl_serving_request_ttft_cache_hit_seconds_bucket",
                       "ditl_serving_request_ttft_cache_miss_seconds_bucket"):
            assert family in metrics, family
        with urllib.request.urlopen(
            f"http://127.0.0.1:{port}/health", timeout=10
        ) as resp:
            health = json.loads(resp.read())
        assert "cache_hit_tokens" in health
    finally:
        server.close(drain=False)
        eng.close()


class _EchoClassServer(ThreadingHTTPServer):
    daemon_threads = True
    allow_reuse_address = True

    def close(self, drain=True, timeout=30.0):
        self.shutdown()
        self.server_close()

    def kill(self):
        self.close()


class _EchoClassHandler(BaseHTTPRequestHandler):
    def log_message(self, *args):
        pass

    def do_GET(self):
        body = json.dumps({"status": "ok", "draining": False,
                           "queue_depth": 0, "active_slots": 0,
                           "n_slots": 2, "cache_hit_tokens": 30,
                           "cache_miss_tokens": 70}).encode()
        self.send_response(200)
        self.send_header("Content-Length", str(len(body)))
        self.end_headers()
        self.wfile.write(body)

    def do_POST(self):
        self.rfile.read(int(self.headers.get("Content-Length", 0)))
        body = json.dumps({
            "object": "text_completion",
            "choices": [{"index": 0,
                         "text": self.headers.get("X-SLO-Class", ""),
                         "finish_reason": "stop"}],
        }).encode()
        self.send_response(200)
        self.send_header("Content-Type", "application/json")
        self.send_header("Content-Length", str(len(body)))
        self.end_headers()
        self.wfile.write(body)


def test_gateway_stamps_pinned_class_and_aggregates_ratio():
    """A pinned tenant's relays carry X-SLO-Class (overriding the client's
    own header), unpinned clients' headers pass through, garbage headers
    are not forwarded — and the gateway /metrics carries the per-replica +
    fleet prefix-cache hit ratios sourced from health polls."""
    fleet = Fleet([InProcessReplica(
        "r0", lambda: _EchoClassServer(("127.0.0.1", 0), _EchoClassHandler)
    )])
    fleet.start_all()
    assert fleet.probe("r0", timeout=5.0)
    admission = TenantAdmission(
        rate=1000.0, per_tenant={"bulk": {"slo_class": "batch"}})
    metrics = GatewayMetrics()
    server = make_gateway(fleet, config=GatewayConfig(router="round_robin"),
                          admission=admission, metrics=metrics, port=0)
    threading.Thread(target=server.serve_forever, daemon=True).start()
    port = server.server_address[1]
    try:
        _, out = _post(port, {"prompt": "x"},
                       headers={"Authorization": "Bearer bulk",
                                "X-SLO-Class": "interactive"})
        assert out["choices"][0]["text"] == "batch"  # pin wins
        _, out = _post(port, {"prompt": "x"},
                       headers={"X-SLO-Class": "best_effort"})
        assert out["choices"][0]["text"] == "best_effort"  # passthrough
        status, out = _post(port, {"prompt": "x"},
                            headers={"X-SLO-Class": "garbage!"})
        assert status == 400  # reject-don't-drop, same as the replica
        assert "X-SLO-Class" in out["error"]["message"]
        with urllib.request.urlopen(
            f"http://127.0.0.1:{port}/metrics", timeout=10
        ) as resp:
            text = resp.read().decode()
        assert "ditl_gateway_replica_r0_prefix_cache_hit_ratio 0.3" in text
        assert "ditl_gateway_fleet_prefix_cache_hit_ratio 0.3" in text
        with urllib.request.urlopen(
            f"http://127.0.0.1:{port}/stats", timeout=10
        ) as resp:
            stats = json.loads(resp.read())
        assert stats["replicas"]["r0"]["prefix_cache_hit_ratio"] == 0.3
    finally:
        server.shutdown()
        server.server_close()
        fleet.stop_all(drain=False)


# ---------------------------------------------------------------------------
# Acceptance: affinity routing produces measured cache hits; round-robin ~0
# ---------------------------------------------------------------------------

N_REPLICAS = 3


@pytest.fixture(scope="module")
def paged_engine_pool(tiny_setup):
    params, cfg, tok = tiny_setup
    engines = [
        ThreadedEngine(ContinuousEngine(
            params, cfg, tok, n_slots=2, decode_chunk=4, cache_mode="paged",
            page_size=16, gen=GenerateConfig(max_new_tokens=6), max_queue=64,
        ))
        for _ in range(N_REPLICAS)
    ]
    yield engines
    for eng in engines:
        eng.close()


@pytest.fixture()
def paged_fleet(tiny_setup, paged_engine_pool):
    params, cfg, tok = tiny_setup
    shared_gen = Generator(params, cfg, tok)

    def factory(eng):
        return lambda: make_server(
            shared_gen, port=0, threaded_engine=eng, default_max_tokens=4,
        )

    fl = Fleet([
        InProcessReplica(f"r{i}", factory(paged_engine_pool[i]))
        for i in range(N_REPLICAS)
    ])
    fl.start_all()
    for rid in fl.ids:
        assert fl.probe(rid, timeout=5.0)
    yield fl
    fl.stop_all(drain=False)


def _hit_tokens(engines):
    return sum(
        int(e._engine.metrics.prefix_cache_hit_tokens.value) for e in engines
    )


def _prefix_trace(tag, groups=4, per_group=2):
    """Interleaved trace: ``groups`` distinct ~48-char prefixes (3+ full
    16-token pages after the BOS), ``per_group`` requests each with unique
    suffixes. With 3 replicas and per_group=2, round-robin sends the two
    requests of every group to DIFFERENT replicas (positions g and
    groups+g mod 3 with groups=4 never coincide), so its measured hit
    count is exactly zero — not merely smaller."""
    prefixes = [
        " ".join(f"{tag}grp{g} word{j:02d}" for j in range(4))
        for g in range(groups)
    ]
    trace = []
    for i in range(per_group):
        for g, prefix in enumerate(prefixes):
            trace.append(f"{prefix} item {g}-{i}")
    return trace


def test_affinity_routing_yields_measured_cache_hits(paged_fleet,
                                                     paged_engine_pool):
    """ISSUE 8 acceptance: the router docstring's claim — routed affinity
    hit => engine KV reuse — pinned to a real measured number. Affinity
    routing on a repeated-prefix trace yields engine-measured cache-hit
    tokens > 0; round-robin on an equivalent trace yields exactly 0. The
    gateway exposes the measured per-replica ratios next to its affinity
    hit-rate so the two are directly comparable."""
    trace_a = _prefix_trace("a")
    cfg = GatewayConfig(router="affinity", affinity_prefix_tokens=4)
    metrics = GatewayMetrics()
    server = make_gateway(paged_fleet, config=cfg, metrics=metrics, port=0)
    threading.Thread(target=server.serve_forever, daemon=True).start()
    port = server.server_address[1]
    before = _hit_tokens(paged_engine_pool)
    try:
        for prompt in trace_a:
            status, _ = _post(port, {"prompt": prompt, "max_tokens": 2},
                              timeout=120)
            assert status == 200
        affinity_hits = _hit_tokens(paged_engine_pool) - before
        affinity_ratio = metrics.affinity_ratio()
        # Refresh health state so the gateway's /metrics aggregation sees
        # the engines' post-trace counters (normally the supervisor's job).
        for rid in paged_fleet.ids:
            assert paged_fleet.probe(rid, timeout=5.0)
        with urllib.request.urlopen(
            f"http://127.0.0.1:{port}/metrics", timeout=10
        ) as resp:
            text = resp.read().decode()
    finally:
        server.shutdown()
        server.server_close()
    assert affinity_ratio == 1.0  # every repeated key went home
    # Each group's second request reuses >= 3 full pages of its prefix.
    assert affinity_hits >= 4 * 3 * 16
    assert "ditl_gateway_fleet_prefix_cache_hit_ratio" in text
    assert "ditl_gateway_replica_r0_prefix_cache_hit_ratio" in text

    # Round-robin, fresh prefixes (the affinity leg's published pages must
    # not contaminate the A/B): measured engine hits are exactly zero.
    trace_b = _prefix_trace("b")
    rr_metrics = GatewayMetrics()
    server = make_gateway(paged_fleet,
                          config=GatewayConfig(router="round_robin"),
                          metrics=rr_metrics, port=0)
    threading.Thread(target=server.serve_forever, daemon=True).start()
    port = server.server_address[1]
    before = _hit_tokens(paged_engine_pool)
    try:
        for prompt in trace_b:
            status, _ = _post(port, {"prompt": prompt, "max_tokens": 2},
                              timeout=120)
            assert status == 200
        rr_hits = _hit_tokens(paged_engine_pool) - before
    finally:
        server.shutdown()
        server.server_close()
    assert rr_hits == 0, (
        f"round-robin spread same-prefix requests across replicas yet the "
        f"engines still reused {rr_hits} tokens"
    )
    assert affinity_hits > rr_hits
