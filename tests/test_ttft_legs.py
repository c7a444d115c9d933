"""A request's time to first token, leg by leg (ISSUE 36): what an armed
tracer stamps on ``server.request``, ``engine.prefill`` and a request's
first ``engine.decode``, and the readers that walk the chain
(``benchmarks/layer_metrics/_ttft.py`` and the six metrics built on it).

- the engine: three requests admitted in one step say what each prefill
  queued behind (``ahead``, ``ahead_tokens``, ``behind_tokens``, ``shared``,
  ``decode_queued``); the stamps of one request are ordered and its legs
  telescope to ``first_write_s``;
- the server: one SSE request whose body arrives late; ``server.request``
  starts at the handler's entry and carries ``first_write_s``, ``events``
  and ``status``; the submit is the start of the ``engine.queue`` under it;
- unarmed: the same token streams, and nothing of the above is built;
- the readers on a journal written by hand: the hand-reckoned value, a
  journal without the new attributes (the parent a new metric is first read
  on), no traced run; ``first_wait_decode_share_chat`` on a synthetic trace
  with clock marks, and every other reader clear of that trace's capture.

Counts, identities and the order of stamps of ONE run on one clock; no
assertion orders wall-clock readings of different runs.
"""

from __future__ import annotations

import gzip
import json
import os
import socket
import sys
import threading
import time

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmarks")
for _p in (ROOT, BENCH):
    if _p not in sys.path:
        sys.path.insert(0, _p)

from harness import load_module  # noqa: E402
from layer_metrics import _scopes, _ttft  # noqa: E402

from ditl_tpu.telemetry.journal import EventJournal, merge_journals  # noqa: E402
from ditl_tpu.telemetry.tracing import NULL_TRACER, Tracer  # noqa: E402

READERS = ("http_overhead_p50_ms", "ttft_inside_p95_ms", "queue_wait_p95_ms",
           "first_wait_p95_ms", "first_wait_other_prefill_tokens_p95_chat",
           "first_wait_decode_share_chat")


def reader(name):
    return load_module(os.path.join(BENCH, "layer_metrics", f"{name}.py"))


# ---------------------------------------------------------------------------
# the engine (tiny model, CPU)
# ---------------------------------------------------------------------------

# Distinct first tokens, so that no prompt finds another's pages: each
# prefill runs its whole prompt, padded to a power of two of at least a page.
PROMPTS = ([10 + i for i in range(10)], [100 + i for i in range(40)],
           [200 + i for i in range(20)])
BUCKETS = (16, 64, 32)
LATE_PROMPT = [300 + i for i in range(13)]


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
    return llama.init_params(jax.random.key(0), cfg), cfg, ByteTokenizer()


def engine(tiny, tracer=None):
    from ditl_tpu.infer.continuous import ContinuousEngine
    from ditl_tpu.infer.engine import GenerateConfig

    params, cfg, tok = tiny
    return ContinuousEngine(
        params, cfg, tok, n_slots=4, decode_chunk=4, cache_mode="paged",
        page_size=16, gen=GenerateConfig(max_new_tokens=8), tracer=tracer,
    )


def drive(eng, front=None):
    """Three requests admitted in one step, a fourth behind the running
    tick; ``front``: a tracer that opens each one's ``server.request``."""
    spans = []

    def submit(prompt):
        span = None if front is None else front.start_span("server.request")
        spans.append(span)
        return eng.submit(prompt, max_new_tokens=8, trace=span)

    rids = [submit(p) for p in PROMPTS]
    eng.step()  # admits all three, dispatches a decode program, sends 3 firsts
    rids.append(submit(LATE_PROMPT))
    out = eng.run()
    for span in spans:
        if span is not None:  # what the SSE writer would stamp
            span.end(first_write_s=round(time.time() - span.t0, 6))
    return rids, out, spans


@pytest.fixture(scope="module")
def armed(tiny, tmp_path_factory):
    """(request ids, tokens, run directory) of an engine with an armed tracer."""
    run_dir = tmp_path_factory.mktemp("armed")
    os.makedirs(run_dir / "spans")
    journal = EventJournal(str(run_dir / "spans" / "events-server-1.jsonl"),
                           source="server-1")
    tracer = Tracer(journal)
    rids, out, _ = drive(engine(tiny, tracer), front=tracer)
    journal.close()
    return rids, out, str(run_dir)


def spans_of(run_dir, name, **match):
    return [r for r in merge_journals(os.path.join(run_dir, "spans"))
            if r.get("event") == "trace.span" and r["name"] == name
            and all(r.get(k) == v for k, v in match.items())]


@pytest.mark.parametrize("i", range(3))
def test_a_prefill_says_what_was_enqueued_around_it(armed, i):
    rids, _, run_dir = armed
    (prefill,) = spans_of(run_dir, "engine.prefill", req=rids[i])
    (first,) = spans_of(run_dir, "engine.decode", req=rids[i], first=True)
    assert (prefill["kind"], prefill["bucket"]) == ("prompt", BUCKETS[i])
    assert prefill["ahead"] == i
    assert prefill["ahead_tokens"] == sum(BUCKETS[:i])
    assert first["behind_tokens"] == sum(BUCKETS[i + 1:])
    assert first["shared"] == 3 and first["fetch_wait_s"] >= 0.0
    assert prefill["tick"] == first["tick"] == 1


@pytest.mark.parametrize("i,queued", [(0, 0), (3, 1)])
def test_decode_queued_counts_the_program_no_one_has_fetched(armed, i, queued):
    """0 on an idle engine, 1 behind the tick the step before dispatched."""
    rids, _, run_dir = armed
    (prefill,) = spans_of(run_dir, "engine.prefill", req=rids[i])
    assert prefill["decode_queued"] == queued
    if i == 3:
        (first,) = spans_of(run_dir, "engine.decode", req=rids[i], first=True)
        assert (prefill["ahead"], first["shared"], first["behind_tokens"]) == (0, 1, 0)
        assert prefill["tick"] == first["tick"] == 2


@pytest.mark.parametrize("i", range(4))
def test_the_stamps_of_a_request_are_ordered_and_its_legs_telescope(armed, i):
    rids, _, run_dir = armed
    end = lambda s: s["ts"] + s["dur_s"]  # noqa: E731
    (queue,) = spans_of(run_dir, "engine.queue", req=rids[i])
    (prefill,) = spans_of(run_dir, "engine.prefill", req=rids[i])
    (first,) = spans_of(run_dir, "engine.decode", req=rids[i], first=True)
    assert end(queue) <= prefill["ts"] + 1e-5
    assert prefill["ts"] <= end(prefill) <= end(first) + 1e-5
    (rec,) = [r for r in _ttft.requests(_ttft.journal_paths(run_dir), 0.0, 1e12)
              if r["req"] == rids[i]]
    legs = [rec[leg] for leg in _ttft.LEGS]
    assert all(x is not None and x >= -1e-5 for x in legs), legs
    assert sum(legs) == pytest.approx(rec["first_write_s"], abs=1e-5)
    assert rec["queue"] == pytest.approx(queue["dur_s"], abs=5e-6)


def test_token_streams_are_identical_armed_and_unarmed(tiny, armed, monkeypatch):
    from ditl_tpu.infer.continuous import ContinuousEngine

    def never(*a, **kw):
        raise AssertionError("an unarmed engine built a traced attribute")

    monkeypatch.setattr(ContinuousEngine, "_first_attrs", never)
    eng = engine(tiny)
    rids, out, spans = drive(eng, front=NULL_TRACER)
    assert [out[r] for r in rids] == [armed[1][r] for r in armed[0]]
    # the server's unarmed span was handed through and never written on
    assert all(set(s.attrs) == {"first_write_s"} for s in spans)


# ---------------------------------------------------------------------------
# the server (one SSE request whose body arrives late)
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def sse(tiny, tmp_path_factory):
    """(the ``data:`` events the client read, the instant before it sent the
    body, run directory) of one streamed /v1/completions."""
    from ditl_tpu.infer.continuous import ThreadedEngine
    from ditl_tpu.infer.engine import Generator
    from ditl_tpu.infer.server import make_server

    params, cfg, tok = tiny
    run_dir = tmp_path_factory.mktemp("sse")
    os.makedirs(run_dir / "spans")
    journal = EventJournal(str(run_dir / "spans" / "events-server-1.jsonl"),
                           source="server-1")
    threaded = ThreadedEngine(engine(tiny, Tracer(journal)))
    server = make_server(Generator(params, cfg, tok), host="127.0.0.1", port=0,
                         threaded_engine=threaded, default_max_tokens=8)
    threading.Thread(target=server.serve_forever, daemon=True).start()
    body = json.dumps({"prompt": "the first token, leg by leg", "max_tokens": 8,
                       "temperature": 0, "stream": True}).encode()
    try:
        with socket.create_connection(("127.0.0.1", server.server_address[1]),
                                      timeout=120) as sock:
            sock.sendall((f"POST /v1/completions HTTP/1.1\r\nHost: x\r\n"
                          f"Content-Type: application/json\r\n"
                          f"Content-Length: {len(body)}\r\n"
                          f"Connection: close\r\n\r\n").encode())
            time.sleep(0.5)  # the handler is entered and waits for the body
            t_body = time.time()
            sock.sendall(body)
            raw = b""
            while chunk := sock.recv(65536):
                raw += chunk
    finally:
        server.shutdown()
        threaded.close()
        journal.close()
    assert raw.startswith(b"HTTP/1.1 200"), raw[:200]
    events = [ln[5:].strip() for ln in raw.split(b"\n") if ln.startswith(b"data:")]
    assert events[-1] == b"[DONE]"
    return events[:-1], t_body, str(run_dir)


def test_server_request_starts_at_the_handlers_entry(sse):
    _, t_body, run_dir = sse
    (span,) = spans_of(run_dir, "server.request")
    (queue,) = spans_of(run_dir, "engine.queue")
    assert span["ts"] <= t_body  # opened before the body was there to read
    assert queue["ts"] - span["ts"] >= 0.25  # so the submit waited for the body


def test_server_request_carries_the_boundaries_of_the_first_token(sse):
    events, _, run_dir = sse
    (span,) = spans_of(run_dir, "server.request")
    (queue,) = spans_of(run_dir, "engine.queue")
    assert 0.0 <= queue["ts"] - span["ts"] <= span["first_write_s"] <= span["dur_s"]
    assert span["events"] == len(events) >= 2  # a token's event and the last
    assert span["status"] == 200 and span["route"] == "completions"


def test_the_legs_of_a_served_request_telescope_to_first_write_s(sse):
    _, _, run_dir = sse
    (rec,) = _ttft.requests(_ttft.journal_paths(run_dir), 0.0, 1e12)
    legs = [rec[leg] for leg in _ttft.LEGS]
    assert all(x is not None and x >= -1e-5 for x in legs), legs
    assert sum(legs) == pytest.approx(rec["first_write_s"], abs=1e-5)
    assert rec["http_in"] >= 0.25 and rec["decode_queued"] == 0
    assert "first_wait" in _ttft.table([rec])


# ---------------------------------------------------------------------------
# the readers, on a journal written by hand
# ---------------------------------------------------------------------------

WINDOW = (100.0, 130.0)


def span(name, ts, dur_s, **attrs):
    return {"event": "trace.span", "name": name, "ts": ts, "dur_s": dur_s, **attrs}


def request(req, t0, *, submit, queue, admit, dispatch, first_wait, http_out,
            kind="prompt", first=True, with_attributes=True, queued=(0, 0, 0, 1)):
    """The spans of one request from its legs (seconds); ``queued``: bucket,
    ahead_tokens, behind_tokens, decode_queued."""
    bucket, ahead_tokens, behind_tokens, decode_queued = queued
    b1 = t0 + submit
    b2, b3 = b1 + queue, b1 + queue + admit
    b4 = b3 + dispatch
    b5 = b4 + first_wait
    server = span("server.request", t0, b5 + http_out - t0 + 0.5, span=f"s{req}",
                  parent="", route="completions")
    prefill = span("engine.prefill", b3, dispatch, req=req, parent=f"e{req}",
                   kind=kind, tokens=10, offset=0)
    decode = span("engine.decode", b2, b5 - b2, req=req, parent=f"e{req}",
                  first=first, tokens=1)
    if with_attributes:
        server.update(first_write_s=b5 + http_out - t0, events=9, status=200)
        prefill.update(tick=7, bucket=bucket, ahead=int(ahead_tokens > 0),
                       ahead_tokens=ahead_tokens, decode_queued=decode_queued)
        decode.update(tick=7, fetch_wait_s=0.01, shared=2, behind_tokens=behind_tokens)
    else:  # the parent opens server.request behind the body's read
        server["ts"] = t0 + submit / 2
    return [
        span("engine.queue", b1, queue, req=req, parent=f"e{req}"),
        prefill,
        span("engine.decode", b5, 0.05, req=req, parent=f"e{req}", first=False, tokens=3),
        decode,
        span("engine.request", b1, 1.0, req=req, span=f"e{req}", parent=f"s{req}"),
        server,
    ]


def hand_journal(tmp_path, with_attributes=True):
    """Three requests of the window with every boundary; one before the
    window, one whose prefill is chunked, one that never got a first token;
    three that touch the device profiler's capture (wall 110 to 112,
    ``synthetic_trace``) and one inside its length behind it, each slower
    than any of the first three: the decode share's, and no other reader's."""
    kw = {"with_attributes": with_attributes}
    lines = ["not json", json.dumps({"event": "jit.compile", "ts": 101.0})]
    for spans in (
        request(1, 100.0, submit=.004, queue=.030, admit=.002, dispatch=.004,
                first_wait=.058, http_out=.002, queued=(256, 0, 2048, 1), **kw),
        request(2, 101.0, submit=.006, queue=.050, admit=.004, dispatch=.005,
                first_wait=.131, http_out=.004, queued=(128, 2048, 0, 1), **kw),
        request(3, 102.0, submit=.002, queue=.010, admit=.001, dispatch=.003,
                first_wait=.031, http_out=.003, queued=(64, 0, 0, 0), **kw),
        request(4, 99.0, submit=.5, queue=.9, admit=.5, dispatch=.5,
                first_wait=.9, http_out=.5, queued=(4096, 9999, 9999, 1), **kw),
        request(5, 103.0, submit=.5, queue=.020, admit=.5, dispatch=.5,
                first_wait=.9, http_out=.5, kind="chunk", queued=(512, 9999, 9999, 1), **kw),
        request(6, 104.0, submit=.5, queue=.040, admit=.5, dispatch=.5,
                first_wait=.9, http_out=.5, first=False, queued=(512, 9999, 9999, 1), **kw),
        request(7, 109.5, submit=.004, queue=.530, admit=.002, dispatch=.004,
                first_wait=.058, http_out=.002, queued=(256, 9999, 9999, 1), **kw),
        request(8, 110.5, submit=.006, queue=.550, admit=.004, dispatch=.005,
                first_wait=.131, http_out=.004, queued=(128, 9999, 9999, 1), **kw),
        request(9, 111.5, submit=.002, queue=.510, admit=.001, dispatch=.003,
                first_wait=.031, http_out=.003, queued=(64, 9999, 9999, 1), **kw),
        request(10, 113.0, submit=.1, queue=.9, admit=.1, dispatch=.1,
                first_wait=.9, http_out=.1, queued=(2048, 9999, 9999, 1), **kw),
    ):
        lines += [json.dumps(s) for s in spans]
    # a resumed request's second prefill is no first token's
    lines.append(json.dumps(span("engine.prefill", 105.0, 0.5, req=1, parent="e1",
                                 kind="resume", tokens=99, offset=0)))
    run_dir = tmp_path / "run"
    (run_dir / "spans").mkdir(parents=True)
    (run_dir / "spans" / "events-server-1.jsonl").write_text("\n".join(lines) + "\n")
    return run_dir


def synthetic_trace(run_dir, clock=True):
    """A trace whose clock reads 0 at wall 110.0, with a mark every tenth of
    a second of its 2 s, and whose device events span [0.01, 2.03] s: of
    requests 7, 8 and 9 the first two's ``first_wait`` lie inside whole
    ([0.040, 0.098], [1.065, 1.196]), the third's ([2.016, 2.047]) does not.
    Decode runs cover 0.030 + 0.050 + 0.006 s of the two."""
    ps = lambda s: int(round(s * 1e12))  # noqa: E731
    run = lambda name, a, b: [name, ps(a), ps(b - a)]  # noqa: E731
    trace = {
        "devices": {"0": [[1, ps(0.01), ps(0.5)], [1, ps(1.5), ps(0.53)]]},
        "meta": {"0": {"1": ["fusion.1", "jit(paged_decode)/mlp"]}},
        "modules": {"0": [run("jit_paged_decode", 0.0, 0.070),
                          run("jit_paged_prefill", 0.070, 0.095),
                          run("jit_paged_decode", 1.100, 1.150),
                          run("jit_paged_decode", 1.190, 1.300),
                          run("jit_paged_decode", 2.0, 2.03)]},
        "clock": [[i * 10**8, 110 * 10**9 + i * 10**8] for i in range(21)]
        if clock else [],
    }
    path = run_dir / "trace" / "plugins" / "profile" / "t" / "host.json.gz"
    path.parent.mkdir(parents=True)
    with gzip.open(path, "wt") as f:
        json.dump(trace, f)
    return str(path)


def a_run(monkeypatch, run_dir, **trace_kw):
    path = synthetic_trace(run_dir, **trace_kw)
    monkeypatch.setattr(_scopes, "trace_file", lambda run: path)
    return {"workload": "w", "trace": {"busy_s": 1.0}, "window_wall": list(WINDOW)}


# Over requests 1, 2, 3, clear of the capture: http_in + http_out: 6, 10, 5 ms;
# first_write_s: 100, 200, 50; engine.queue (with requests 5 and 6): 30, 50, 10,
# 20, 40; first_wait: 58, 131, 31; other requests' padded tokens: 2048, 2048, 0.
# Over requests 7 and 8, inside it: the decode program's share of their
# first_waits, (0.030 + 0.050 + 0.006) / (0.058 + 0.131).
KNOWN = {
    "http_overhead_p50_ms": 6.0,
    "ttft_inside_p95_ms": 190.0,
    "queue_wait_p95_ms": 48.0,
    "first_wait_p95_ms": 123.7,
    "first_wait_other_prefill_tokens_p95_chat": 2048.0,
    "first_wait_decode_share_chat": 100.0 * 0.086 / 0.189,
}
WITHOUT_ATTRIBUTES = dict(KNOWN, http_overhead_p50_ms=0.0, ttft_inside_p95_ms=0.0,
                          first_wait_other_prefill_tokens_p95_chat=0.0)


@pytest.mark.parametrize("name", READERS)
def test_a_reader_on_a_known_journal(monkeypatch, tmp_path, name):
    run = a_run(monkeypatch, hand_journal(tmp_path))
    assert reader(name).read(run) == pytest.approx(KNOWN[name], abs=1e-6)


@pytest.mark.parametrize("name", READERS)
def test_a_journal_without_the_new_attributes_reads_a_number(monkeypatch, tmp_path, name):
    """The parent a new metric is first read on: the readers of spans it
    writes read true values, the others 0.0, none None."""
    run = a_run(monkeypatch, hand_journal(tmp_path, with_attributes=False))
    assert reader(name).read(run) == pytest.approx(WITHOUT_ATTRIBUTES[name], abs=1e-6)


@pytest.mark.parametrize("name", READERS)
def test_an_empty_journal_reads_zero_and_no_traced_run_none(monkeypatch, tmp_path, name):
    empty = tmp_path / "empty"
    (empty / "spans").mkdir(parents=True)
    assert reader(name).read(a_run(monkeypatch, empty)) == 0.0
    assert reader(name).read({"workload": "w", "trace": None,
                              "window_wall": list(WINDOW)}) is None


def test_without_a_clock_mark_nothing_is_measured_or_left_out(monkeypatch, tmp_path):
    """No mark: the decode share has no clock to lay spans on, and no reader
    knows of a capture (request 10's queue of 900 ms is the p95 then)."""
    run = a_run(monkeypatch, hand_journal(tmp_path), clock=False)
    assert reader("first_wait_decode_share_chat").read(run) is None
    assert reader("queue_wait_p95_ms").read(run) > 550.0


def test_the_capture_and_its_length_behind_it_are_left_out(monkeypatch, tmp_path):
    run_dir = hand_journal(tmp_path)
    run = a_run(monkeypatch, run_dir)
    assert _ttft.capture_wall(_scopes.trace_file(run)) == pytest.approx((110.0, 112.0))
    assert [r["req"] for r in _ttft.run_requests(run)] == [1, 2, 3]
    assert [r["req"] for r in _ttft.run_requests(run, clear_of_capture=False)] == [
        1, 2, 3, 7, 8, 9, 10]
    late = dict(_ttft.run_requests(run, clear_of_capture=False)[-1], t0=114.001)
    assert _ttft.quiet([late], (110.0, 112.0)) == [late]
    assert sorted(q["req"] for q in _ttft.run_queue_spans(run)) == [1, 2, 3, 5, 6]


def test_the_records_of_the_known_journal(tmp_path):
    run_dir = hand_journal(tmp_path)
    paths = _ttft.journal_paths(str(run_dir))
    records = _ttft.requests(paths, *WINDOW)[:3]
    assert [r["req"] for r in records] == [1, 2, 3]
    assert _ttft.dropped(paths, *WINDOW) == 2  # the chunked one, the one with no token
    first = records[0]
    assert [round(1e3 * first[leg], 3) for leg in _ttft.LEGS] == [4, 30, 2, 4, 58, 2]
    assert (first["bucket"], first["ahead_tokens"], first["behind_tokens"],
            first["decode_queued"]) == (256, 0, 2048, 1)
    for r in records:
        assert sum(r[leg] for leg in _ttft.LEGS) == pytest.approx(r["first_write_s"])
    assert [first[k] for k in _ttft.OF_SERVER + _ttft.OF_FIRST] == [200, 9, 2048, 2, 0.01]
    head, row = _ttft.table(records).splitlines()[-2:]
    # the slowest one's row: every attribute of the three spans under its name
    assert dict(zip(head.split(), row.split())) == {
        "req": "2", "total": "200.0", "status": "200", "events": "9", "http_in": "6.0",
        "queue": "50.0", "admit": "4.0", "dispatch": "5.0", "first_wait": "131.0",
        "http_out": "4.0", "tick": "7", "bucket": "128", "ahead": "1",
        "ahead_tokens": "2048", "decode_queued": "1", "behind_tokens": "0",
        "shared": "2", "fetch_wait": "10.0"}


def test_the_cli_counts_what_it_left_out(tmp_path, capsys):
    run_dir = hand_journal(tmp_path)
    (run_dir / "run.json").write_text(json.dumps({"window_wall": list(WINDOW)}))
    assert _ttft.main([str(run_dir)]) == 0
    first_line = capsys.readouterr().out.splitlines()[0]
    assert first_line.startswith("7 requests") and "left out: 2 without one, 0 at" in first_line


@pytest.mark.parametrize("name", READERS)
def test_a_readers_constants_are_the_manifests(name):
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    (entry,) = [m for m in manifest["per_layer"] if m["name"] == name]
    mod = reader(name)
    assert (entry["layer"], entry["unit"], entry["moves"], entry["source"]) == (
        mod.LAYER, mod.UNIT, mod.MOVES, mod.SOURCE)
    assert entry["better"] == "lower"
    # every serving cell, the closed loop of PR 44 too: these six read the
    # server's own spans, whatever the arrivals
    assert entry["workloads"] == [w["name"] for w in manifest["workloads"]
                                  if "train" not in w["traffic"]]
