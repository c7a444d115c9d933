"""Server API surface beyond basic completions: /v1/embeddings and OpenAI
n / best_of multi-choice serving."""

from __future__ import annotations

import json
import threading
import urllib.error
import urllib.request

import jax
import numpy as np
import pytest

from ditl_tpu.config import ModelConfig
from ditl_tpu.data.tokenizer import ByteTokenizer
from ditl_tpu.infer.continuous import ContinuousEngine, ThreadedEngine
from ditl_tpu.infer.engine import GenerateConfig, Generator
from ditl_tpu.infer.server import make_server
from ditl_tpu.models import llama
from tests.prom_helpers import exposition_index, sample_family


@pytest.fixture(scope="module")
def setup():
    cfg = ModelConfig(
        vocab_size=512,
        hidden_size=64,
        intermediate_size=128,
        num_layers=2,
        num_heads=4,
        num_kv_heads=2,
        head_dim=16,
        max_seq_len=128,
        dtype="float32",
        param_dtype="float32",
    )
    params = llama.init_params(jax.random.key(0), cfg)
    tok = ByteTokenizer()
    return params, cfg, tok


def _serve(params, cfg, tok, **engine_kw):
    threaded = None
    max_pending = engine_kw.pop("max_pending", None)
    if engine_kw.pop("continuous", False):
        threaded = ThreadedEngine(ContinuousEngine(
            params, cfg, tok, n_slots=8, decode_chunk=4,
            gen=GenerateConfig(max_new_tokens=10), **engine_kw,
        ))
    server = make_server(
        Generator(params, cfg, tok), host="127.0.0.1", port=0,
        threaded_engine=threaded, default_max_tokens=10,
        max_pending=max_pending,
    )
    threading.Thread(target=server.serve_forever, daemon=True).start()
    return server, threaded, server.server_address[1]


def _post(port, path, body, expect_error=False):
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}{path}", data=json.dumps(body).encode(),
        headers={"Content-Type": "application/json"},
    )
    try:
        with urllib.request.urlopen(req, timeout=120) as resp:
            return resp.status, json.loads(resp.read())
    except urllib.error.HTTPError as e:
        assert expect_error, e.read()
        return e.code, json.loads(e.read())


@pytest.mark.slow
def test_embeddings_endpoint(setup):
    params, cfg, tok = setup
    server, threaded, port = _serve(params, cfg, tok)
    try:
        status, out = _post(port, "/v1/embeddings", {
            "input": ["hello world", "completely different text", "hello world"],
        })
        assert status == 200
        assert out["object"] == "list"
        vecs = [np.asarray(d["embedding"]) for d in out["data"]]
        assert [d["index"] for d in out["data"]] == [0, 1, 2]
        assert all(v.shape == (cfg.hidden_size,) for v in vecs)
        # unit-normalized; identical inputs identical, different differ
        for v in vecs:
            assert abs(np.linalg.norm(v) - 1.0) < 1e-5
        np.testing.assert_allclose(vecs[0], vecs[2], atol=1e-6)
        assert np.linalg.norm(vecs[0] - vecs[1]) > 1e-3
        assert out["usage"]["prompt_tokens"] > 0
        # single string input
        status, out = _post(port, "/v1/embeddings", {"input": "hello world"})
        assert status == 200 and len(out["data"]) == 1
        np.testing.assert_allclose(
            np.asarray(out["data"][0]["embedding"]), vecs[0], atol=1e-6
        )
        # bad input
        status, _ = _post(port, "/v1/embeddings", {"input": 42},
                          expect_error=True)
        assert status == 400
    finally:
        server.shutdown()


@pytest.mark.slow
def test_n_choices_continuous(setup):
    """n sampled completions ride shared decode ticks and come back as
    distinct, seed-reproducible choices."""
    params, cfg, tok = setup
    server, threaded, port = _serve(params, cfg, tok, continuous=True)
    try:
        body = {"prompt": "story:", "n": 3, "temperature": 0.9,
                "max_tokens": 8, "seed": 11}
        status, out = _post(port, "/v1/completions", body)
        assert status == 200
        texts = [c["text"] for c in out["choices"]]
        assert len(texts) == 3
        assert [c["index"] for c in out["choices"]] == [0, 1, 2]
        assert len(set(texts)) > 1  # sampled copies differ
        status, out2 = _post(port, "/v1/completions", body)
        assert [c["text"] for c in out2["choices"]] == texts  # seed-pinned
    finally:
        server.shutdown()
        threaded.close()


@pytest.mark.slow
def test_best_of_ranks_by_logprob(setup):
    params, cfg, tok = setup
    server, threaded, port = _serve(
        params, cfg, tok, continuous=True, logprobs_k=1,
    )
    try:
        status, out = _post(port, "/v1/completions", {
            "prompt": "story:", "n": 2, "best_of": 4, "temperature": 0.9,
            "max_tokens": 8, "seed": 3,
        })
        assert status == 200
        assert len(out["choices"]) == 2
        # chat spelling works too
        status, out = _post(port, "/v1/chat/completions", {
            "messages": [{"role": "user", "content": "hi"}],
            "n": 2, "temperature": 0.8, "max_tokens": 8,
        })
        assert status == 200
        assert len(out["choices"]) == 2
        assert all("message" in c for c in out["choices"])
    finally:
        server.shutdown()
        threaded.close()


@pytest.mark.slow
def test_best_of_validation(setup):
    params, cfg, tok = setup
    server, threaded, port = _serve(params, cfg, tok, continuous=True)
    try:
        status, _ = _post(port, "/v1/completions", {
            "prompt": "x", "n": 3, "best_of": 2,
        }, expect_error=True)
        assert status == 400
        # Engine-level request validation (seed out of int32) is the
        # CLIENT's fault: 400, never a 500 from the catch-all.
        status, _ = _post(port, "/v1/completions", {
            "prompt": "x", "seed": 2**40, "max_tokens": 4,
        }, expect_error=True)
        assert status == 400
        status, _ = _post(port, "/v1/completions", {
            "prompt": "x", "n": 2, "stream": True,
        }, expect_error=True)
        assert status == 400
        # best_of > n without logprobs-armed engine: lock-step fallback
        # computes its own logprobs, so this still succeeds
        status, out = _post(port, "/v1/completions", {
            "prompt": "x", "n": 1, "best_of": 2, "temperature": 0.7,
            "max_tokens": 6,
        })
        assert status == 200 and len(out["choices"]) == 1
    finally:
        server.shutdown()
        threaded.close()


def test_prometheus_metrics_endpoint(setup):
    params, cfg, tok = setup
    server, threaded, port = _serve(params, cfg, tok, continuous=True)
    try:
        with urllib.request.urlopen(
            f"http://127.0.0.1:{port}/metrics", timeout=30
        ) as resp:
            assert resp.status == 200
            assert resp.headers["Content-Type"].startswith("text/plain")
            body = resp.read().decode()
        assert "ditl_serving_up 1" in body
        assert "ditl_serving_n_slots 8" in body
        assert "# TYPE ditl_serving_queue_depth gauge" in body
        # every non-comment line parses as "name value"; the registry now
        # carries the serving families plus the SLO burn-rate gauges
        # (ISSUE 6 — refreshed on every /metrics scrape)
        for line in body.strip().splitlines():
            if line.startswith("#"):
                continue
            name, value = line.rsplit(" ", 1)
            float(value)
            assert name.startswith(("ditl_serving_", "ditl_slo_"))
        assert "ditl_slo_ttft_burn_rate_w300" in body
        assert "ditl_slo_availability_alerting" in body
    finally:
        server.shutdown()
        threaded.close()


def test_stats_carry_the_compile_counter_and_a_new_shape_raises_it(setup):
    """ISSUE 23: /v1/stats holds the process's compile counter (cumulative,
    read at both ends of a window); a request whose prompt needs a program
    that has not been built raises it."""
    from ditl_tpu.utils.profiling import compile_counter

    compile_counter()  # serve() registers it before anything compiles
    params, cfg, tok = setup
    server, threaded, port = _serve(params, cfg, tok, continuous=True)

    def stats():
        with urllib.request.urlopen(
            f"http://127.0.0.1:{port}/v1/stats", timeout=30
        ) as resp:
            return json.loads(resp.read())

    try:
        _post(port, "/v1/completions", {"prompt": "hi", "max_tokens": 3})
        a = stats()
        assert a["compile_count_cum"] >= 1 and a["compile_s_cum"] > 0
        _post(port, "/v1/completions", {"prompt": "hi", "max_tokens": 3})
        b = stats()  # the same shapes again: nothing compiles
        assert b["compile_count_cum"] == a["compile_count_cum"]
        # a prompt in another prefill bucket: a forced recompile
        _post(port, "/v1/completions", {"prompt": "long " * 20, "max_tokens": 3})
        c = stats()
        assert c["compile_count_cum"] > b["compile_count_cum"]
        assert c["compile_s_cum"] > b["compile_s_cum"]
    finally:
        server.shutdown()
        threaded.close()


def _scrape_metrics(port: int) -> str:
    with urllib.request.urlopen(
        f"http://127.0.0.1:{port}/metrics", timeout=30
    ) as resp:
        assert resp.status == 200
        return resp.read().decode()


@pytest.mark.telemetry
def test_metrics_exposition_invariants_live_server(setup):
    """ISSUE 3 acceptance: a LIVE continuous-batching server serves real
    histogram series (TTFT / per-token / e2e) and `_total` counters on
    /metrics, obeying the Prometheus text-format contract — every sample's
    family declares a TYPE, histogram buckets are cumulative and end in
    +Inf, and counters are monotonic across two scrapes with traffic in
    between."""
    params, cfg, tok = setup
    server, threaded, port = _serve(params, cfg, tok, continuous=True)
    try:
        status, _ = _post(port, "/v1/completions",
                          {"prompt": "hello", "max_tokens": 6})
        assert status == 200
        body1 = _scrape_metrics(port)
        types1, samples1 = exposition_index(body1)
        # Every sample has a declared family TYPE.
        for name in samples1:
            fam = sample_family(name)
            assert fam in types1, f"sample {name} has no # TYPE for {fam}"
        # Real histogram series from the live engine, not flattened gauges.
        for fam in ("ditl_serving_request_ttft_seconds",
                    "ditl_serving_decode_token_seconds",
                    "ditl_serving_request_e2e_seconds",
                    "ditl_serving_request_queue_wait_seconds"):
            assert types1[fam] == "histogram", fam
            buckets = [
                (n, v) for n, v in samples1.items()
                if n.startswith(f"{fam}_bucket")
            ]
            counts = [v for _, v in buckets]
            assert counts == sorted(counts), f"{fam} buckets not cumulative"
            assert buckets[-1][0] == f'{fam}_bucket{{le="+Inf"}}'
            assert buckets[-1][1] == samples1[f"{fam}_count"]
        assert samples1["ditl_serving_request_ttft_seconds_count"] >= 1
        assert samples1["ditl_serving_request_e2e_seconds_count"] >= 1
        # Counters end in _total, are typed under that name, and carried
        # the request.
        counter_fams = [f for f, k in types1.items() if k == "counter"]
        assert "ditl_serving_requests_total" in counter_fams
        for fam in counter_fams:
            assert fam.endswith("_total") and fam in samples1, fam
        assert samples1["ditl_serving_requests_total"] >= 1
        assert samples1["ditl_serving_tokens_generated_total"] >= 1
        # Monotonic across scrapes with traffic in between.
        status, _ = _post(port, "/v1/completions",
                          {"prompt": "again", "max_tokens": 4})
        assert status == 200
        _, samples2 = exposition_index(_scrape_metrics(port))
        for fam in counter_fams:
            assert samples2[fam] >= samples1[fam], fam
        assert (samples2["ditl_serving_requests_total"]
                > samples1["ditl_serving_requests_total"])
        # No duplicate TYPE declarations (family collisions between the
        # registry and the flattened stats gauges).
        type_lines = [ln for ln in body1.splitlines()
                      if ln.startswith("# TYPE ")]
        fams = [ln.split(" ", 3)[2] for ln in type_lines]
        assert len(fams) == len(set(fams)), "duplicate metric family"
    finally:
        server.shutdown()
        threaded.close()


@pytest.mark.tracing
@pytest.mark.telemetry
def test_request_id_echo_slo_endpoint_and_interference_family(setup):
    """ISSUE 6 satellites on the live server: every response carries a
    stable X-Request-Id (client-provided echoed, otherwise generated —
    including on SSE), /slo renders the burn-rate evaluation, and the
    interference histogram family obeys the exposition invariants."""
    params, cfg, tok = setup
    server, threaded, port = _serve(params, cfg, tok, continuous=True)
    try:
        req = urllib.request.Request(
            f"http://127.0.0.1:{port}/v1/completions",
            data=json.dumps({"prompt": "hi", "max_tokens": 3}).encode(),
            headers={"Content-Type": "application/json",
                     "X-Request-Id": "client-id-7"},
        )
        with urllib.request.urlopen(req, timeout=120) as resp:
            assert resp.headers["X-Request-Id"] == "client-id-7"
        req = urllib.request.Request(
            f"http://127.0.0.1:{port}/v1/completions",
            data=json.dumps({"prompt": "hi", "max_tokens": 3,
                             "stream": True}).encode(),
            headers={"Content-Type": "application/json"},
        )
        with urllib.request.urlopen(req, timeout=120) as resp:
            # Generated on the SSE path too (headers precede the stream).
            assert resp.headers["X-Request-Id"].startswith("req-")
            assert resp.headers["Content-Type"].startswith(
                "text/event-stream")
            resp.read()
        # /slo: the three server objectives, graded over real traffic.
        with urllib.request.urlopen(
            f"http://127.0.0.1:{port}/slo", timeout=30
        ) as resp:
            slo = json.loads(resp.read())
        assert set(slo["objectives"]) == {"ttft", "tpot", "availability"}
        avail = slo["objectives"]["availability"]
        assert avail["total"] >= 2  # both completions above
        for obj in slo["objectives"].values():
            for w in obj["windows"].values():
                assert w["errors"] <= w["requests"]
        # Interference histogram family: typed, cumulative, +Inf-closed —
        # the prom_helpers invariants extended to the ISSUE 6 metrics.
        types, samples = exposition_index(_scrape_metrics(port))
        fam = "ditl_serving_tpot_interference_seconds"
        assert types[fam] == "histogram"
        buckets = [(n, v) for n, v in samples.items()
                   if n.startswith(f"{fam}_bucket")]
        counts = [v for _, v in buckets]
        assert counts == sorted(counts)
        assert buckets[-1][0] == f'{fam}_bucket{{le="+Inf"}}'
        assert buckets[-1][1] == samples[f"{fam}_count"]
        # SLO burn-rate gauges are typed gauges in the same exposition.
        for name, kind in types.items():
            if name.startswith("ditl_slo_"):
                assert kind == "gauge", name
        assert any(n.startswith("ditl_slo_ttft_burn_rate_w") for n in types)
    finally:
        server.shutdown()


@pytest.mark.telemetry
def test_metrics_lockstep_server_records_e2e(setup):
    """The lock-step (no continuous engine) server still exposes e2e
    latency + request counters on /metrics."""
    params, cfg, tok = setup
    server, _, port = _serve(params, cfg, tok)
    try:
        status, _ = _post(port, "/v1/completions",
                          {"prompt": "x", "max_tokens": 4})
        assert status == 200
        types, samples = exposition_index(_scrape_metrics(port))
        assert samples["ditl_serving_requests_total"] >= 1
        assert samples["ditl_serving_request_e2e_seconds_count"] >= 1
        assert types["ditl_serving_request_e2e_seconds"] == "histogram"
    finally:
        server.shutdown()


def test_tokenize_detokenize_endpoints(setup):
    params, cfg, tok = setup
    server, _, port = _serve(params, cfg, tok)
    try:
        status, out = _post(port, "/tokenize", {"prompt": "hello"})
        assert status == 200
        assert out["tokens"][0] == tok.bos_id
        assert out["tokens"][1:] == tok.encode("hello")
        assert out["count"] == len(out["tokens"])
        status, out2 = _post(port, "/detokenize", {"tokens": out["tokens"]})
        assert status == 200 and out2["prompt"] == "hello"
        status, out3 = _post(
            port, "/tokenize", {"prompt": "hi", "add_special_tokens": False}
        )
        assert status == 200 and out3["tokens"] == tok.encode("hi")
        status, _ = _post(port, "/tokenize", {"prompt": 5}, expect_error=True)
        assert status == 400
        status, _ = _post(port, "/detokenize", {"tokens": "x"},
                          expect_error=True)
        assert status == 400
    finally:
        server.shutdown()


@pytest.mark.prof
def test_profile_endpoint_returns_collapsed_stacks(setup):
    """/profile?seconds=N (ISSUE 18) on the replica server: a transient
    sampler capture comes back as non-empty parseable collapsed stacks;
    a malformed seconds value is a 400, not a stack trace."""
    from ditl_tpu.telemetry.prof import parse_collapsed

    params, cfg, tok = setup
    server, _, port = _serve(params, cfg, tok)
    try:
        with urllib.request.urlopen(
            f"http://127.0.0.1:{port}/profile?seconds=0.3", timeout=60
        ) as resp:
            assert resp.status == 200
            text = resp.read().decode()
        stacks = parse_collapsed(text)
        assert stacks, "profile endpoint returned no stacks"
        # the serving threads themselves are among the sampled stacks
        assert any("serve_forever" in s or "select" in s or "poll" in s
                   for s in stacks)
        with pytest.raises(urllib.error.HTTPError) as err:
            urllib.request.urlopen(
                f"http://127.0.0.1:{port}/profile?seconds=nope", timeout=60)
        assert err.value.code == 400
    finally:
        server.shutdown()


def test_chat_template_used_when_tokenizer_has_one(setup):
    from ditl_tpu.infer.server import _chat_prompt

    class FakeTok:
        chat_template = "{{messages}}"

        def apply_chat_template(self, messages):
            return "<|templated|>" + messages[0]["content"]

    msgs = [{"role": "user", "content": "hi"}]
    assert _chat_prompt(msgs, FakeTok()) == "<|templated|>hi"
    # no template -> plain-text turns
    assert _chat_prompt(msgs, None) == "user: hi\nassistant:"


@pytest.mark.slow
def test_generate_many_cancels_orphans_on_midloop_failure(setup):
    """A QueueFullError on copy k must cancel copies 0..k-1: no unconsumed
    Request may park in ThreadedEngine._results, and the engine drains."""
    import time

    from ditl_tpu.infer.continuous import QueueFullError

    params, cfg, tok = setup
    eng = ContinuousEngine(
        params, cfg, tok, n_slots=2, decode_chunk=4,
        gen=GenerateConfig(max_new_tokens=8),
    )
    te = ThreadedEngine(eng)
    orig = eng.submit
    calls = []

    def failing_submit(prompt, **kw):
        if len(calls) >= 2:
            raise QueueFullError("full")
        calls.append(1)
        return orig(prompt, **kw)

    eng.submit = failing_submit
    try:
        with pytest.raises(QueueFullError):
            te.generate_many([tok.bos_id, 5, 6], 4, temperature=0.5)
        deadline = time.time() + 30
        while eng.pending and time.time() < deadline:
            time.sleep(0.05)
        assert eng.pending == 0
        assert te._results == {}
    finally:
        eng.submit = orig
        te.close()


def test_lockstep_overload_concurrent_clients_result_or_429(setup):
    """ISSUE 4 satellite: M threads against a 1-slot lockstep server
    (max_pending=1) must each get either a result or a well-formed 429 —
    never a hang or a 500 — and the 429 counter must move on /metrics."""
    import concurrent.futures

    params, cfg, tok = setup
    server, _, port = _serve(params, cfg, tok, max_pending=1)
    barrier = threading.Barrier(6)

    def one(i):
        barrier.wait()
        req = urllib.request.Request(
            f"http://127.0.0.1:{port}/v1/completions",
            data=json.dumps({"prompt": f"load {i}",
                             "max_tokens": 4}).encode(),
            headers={"Content-Type": "application/json"}, method="POST",
        )
        try:
            with urllib.request.urlopen(req, timeout=120) as resp:
                return resp.status, dict(resp.headers), json.loads(resp.read())
        except urllib.error.HTTPError as e:
            return e.code, dict(e.headers or {}), json.loads(e.read())

    try:
        with concurrent.futures.ThreadPoolExecutor(max_workers=6) as pool:
            outcomes = list(pool.map(one, range(6)))
        statuses = [s for s, _, _ in outcomes]
        assert set(statuses) <= {200, 429}, statuses
        assert 200 in statuses  # someone actually got served
        assert 429 in statuses  # and the cap actually rejected
        n_429 = statuses.count(429)
        for status, headers, body in outcomes:
            if status == 429:
                assert body["error"]["type"] == "rate_limit_error"
                # Backlog-aware Retry-After, clamped to [1, 30].
                assert 1 <= int(headers["Retry-After"]) <= 30
            else:
                assert "choices" in body
        _, samples = exposition_index(_scrape_metrics(port))
        assert samples["ditl_serving_queue_full_total"] == n_429
        assert samples["ditl_serving_requests_total"] == statuses.count(200)
    finally:
        server.shutdown()


def test_drain_lifecycle_health_503_and_close(setup):
    """ISSUE 4 satellite: drain() flips /health to draining, new
    completion work answers 503 while metadata routes stay up, and
    close(drain=True) completes; /health also carries the load signal
    (queue_depth / active_slots / n_slots) the gateway router consumes."""
    params, cfg, tok = setup
    server, _, port = _serve(params, cfg, tok)
    base = f"http://127.0.0.1:{port}"
    try:
        with urllib.request.urlopen(f"{base}/health", timeout=30) as r:
            health = json.loads(r.read())
        assert health["status"] == "ok"
        assert health["draining"] is False
        assert health["queue_depth"] == 0
        assert health["active_slots"] == 0
        assert health["n_slots"] == 1  # lockstep: the device lock is 1 slot
        server.drain()
        with urllib.request.urlopen(f"{base}/health", timeout=30) as r:
            health = json.loads(r.read())
        assert health["status"] == "draining" and health["draining"] is True
        status, body = _post(port, "/v1/completions",
                             {"prompt": "x", "max_tokens": 2},
                             expect_error=True)
        assert status == 503
        assert body["error"]["type"] == "unavailable_error"
        # Metadata routes keep serving while draining (health polling and
        # tokenization must not go dark mid-drain).
        status, _ = _post(port, "/tokenize", {"prompt": "hi"})
        assert status == 200
        with urllib.request.urlopen(f"{base}/stats", timeout=30) as r:
            stats = json.loads(r.read())
        assert stats["draining"] is True and stats["inflight"] == 0
    finally:
        server.close(drain=True, timeout=10)


@pytest.mark.slow
def test_n_lockstep_fallback(setup):
    """No continuous engine at all: n/best_of serve through one replicated
    lock-step batch."""
    params, cfg, tok = setup
    server, _, port = _serve(params, cfg, tok)
    try:
        status, out = _post(port, "/v1/completions", {
            "prompt": "story:", "n": 2, "best_of": 3, "temperature": 0.9,
            "max_tokens": 6, "seed": 5,
        })
        assert status == 200
        assert len(out["choices"]) == 2
    finally:
        server.shutdown()


def test_http11_keepalive_and_sse_terminates_cleanly(setup):
    """End-to-end HTTP/1.1 (ISSUE 14): two JSON completions ride ONE
    client connection (real keep-alive — the HTTP/1.0 default used to
    close after every response), and an SSE stream on that same kept-
    alive connection opts out with an explicit Connection: close,
    delimits at EOF, and terminates cleanly (a fresh connection still
    serves afterwards)."""
    import http.client

    params, cfg, tok = setup
    server, threaded, port = _serve(params, cfg, tok, continuous=True)
    try:
        conn = http.client.HTTPConnection("127.0.0.1", port, timeout=120)
        for i in range(2):
            conn.request(
                "POST", "/v1/completions",
                body=json.dumps({"prompt": f"hi{i}",
                                 "max_tokens": 3}).encode(),
                headers={"Content-Type": "application/json"},
            )
            resp = conn.getresponse()
            out = json.loads(resp.read())
            assert resp.status == 200
            assert resp.version == 11  # HTTP/1.1 status line
            assert not resp.will_close  # keep-alive actually happened
            assert out["usage"]["completion_tokens"] == 3
        # SSE on the SAME kept-alive connection: the server must close it
        # (close-delimited body), and the stream must read through [DONE].
        conn.request(
            "POST", "/v1/completions",
            body=json.dumps({"prompt": "hi", "max_tokens": 3,
                             "stream": True}).encode(),
            headers={"Content-Type": "application/json"},
        )
        resp = conn.getresponse()
        assert resp.status == 200
        assert resp.headers["Content-Type"].startswith("text/event-stream")
        assert resp.will_close  # explicit Connection: close on SSE
        body = resp.read().decode()
        events = [line for line in body.splitlines()
                  if line.startswith("data: ")]
        assert events and events[-1] == "data: [DONE]"
        conn.close()
        # The connection died with the stream, not the server.
        status, out = _post(port, "/v1/completions",
                            {"prompt": "hi", "max_tokens": 2})
        assert status == 200
    finally:
        server.close(drain=True, timeout=10)
        if threaded is not None:
            threaded.close()
