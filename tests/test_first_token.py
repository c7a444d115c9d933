"""A request's first token leaves with the tick that ran its prefill.

The prefill samples the first token; the tick that ran it fetches it right
behind the decode dispatch and puts it on the request's stream alone
(``ContinuousEngine._send_first_tokens``), and the tick's harvest delivers
the rest of the row. These tests pin, on the CPU and by counts and
identities only: the token streams are what ``engine.run()`` returns without
a stream, in every scheduler mode (the default engine, whose ticks are
double-buffered, at 4 and at 16 steps a program, and the serial order it is
held against); the one-token chunk is on the stream
before the tick's own fetch begins; the paths that end or interrupt a request
around its first token end it once; and the counter that says it engaged."""

import copy
import itertools
import queue
import re

import jax
import pytest

from ditl_tpu.config import ModelConfig
from ditl_tpu.data.tokenizer import ByteTokenizer
from ditl_tpu.infer.continuous import ContinuousEngine, ThreadedEngine
from ditl_tpu.infer.engine import GenerateConfig
from ditl_tpu.models import llama
from ditl_tpu.telemetry.journal import EventJournal, merge_journals
from ditl_tpu.telemetry.tracing import Tracer
from ditl_tpu.telemetry.usage import UsageLedger, load_usage, usage_ledger_path

CHUNK = 4  # decode steps a plain tick: the engine's default
PREFILL_CHUNK = 16
MAX_NEW = 11


@pytest.fixture(scope="module")
def setup():
    cfg = ModelConfig(
        vocab_size=512, hidden_size=64, intermediate_size=128, num_layers=2,
        num_heads=4, num_kv_heads=2, head_dim=16, max_seq_len=128,
        dtype="float32", param_dtype="float32",
    )
    return llama.init_params(jax.random.key(0), cfg), cfg, ByteTokenizer()


def _engine(setup, *, cache="paged", spec=False, serial=False, chunk=CHUNK,
            chunked=False, tok=None, **kw):
    params, cfg, tok0 = setup
    kw.setdefault("n_slots", 3)
    kw.setdefault("gen", GenerateConfig(max_new_tokens=MAX_NEW, temperature=0.0))
    if cache == "paged":
        kw.setdefault("page_size", 16)
        kw.setdefault("n_pages", 40)
    if spec:
        # threshold 0: every tick speculates, so two runs of one engine take
        # the same programs whatever its acceptance average has become
        kw.update(speculative=True, spec_k=3, spec_threshold=0.0)
    if serial:  # the order the default engine's double-buffered one is held to
        kw["pipeline_ticks"] = False
    return ContinuousEngine(
        params, cfg, tok or tok0, decode_chunk=chunk, cache_mode=cache,
        prefill_chunk=PREFILL_CHUNK if chunked else 0, **kw)


def _prompts(tok):
    # 41, 4 and 20 tokens: the first spans three prefill chunks
    return [[tok.bos_id] + tok.encode(p) for p in
            ("hello world hello world hello world hello", "abc",
             "the quick brown fox")]


# greedy, sampled, greedy: (temperature, seed) by prompt
SAMPLING = ((0.0, 0), (0.8, 1), (0.0, 2))


def _drain(q):
    """Chunks of a finished request's stream; the terminal None comes once
    and last."""
    items = []
    while True:
        item = q.get_nowait()
        if item is None:
            break
        items.append(item)
    assert q.empty(), "something followed the terminal None"
    return items


# the serial order at 4 steps a program; the default engine at 4 and at 16
TICKS = {"serial-4": dict(serial=True, chunk=4), "default-4": dict(serial=False, chunk=4),
         "default-16": dict(serial=False, chunk=16)}
over_ticks = pytest.mark.parametrize("ticks", TICKS.values(), ids=TICKS.keys())

MODES = [
    pytest.param(dict(cache=c, spec=s, chunked=k, **TICKS[t]),
                 id="-".join((c, "spec" if s else "plain", t,
                              "chunked" if k else "whole")))
    for c, s, t, k in itertools.product(
        ("paged", "contiguous"), (False, True), TICKS, (False, True))
]


@pytest.fixture(scope="module", params=MODES)
def mode_engine(request, setup, tmp_path_factory):
    """One engine a mode, shared by that mode's tests: each leaves it with
    nothing pending."""
    d = tmp_path_factory.mktemp("spans")
    journal = EventJournal(str(d / "events-engine.jsonl"), source="engine")
    eng = _engine(setup, tracer=Tracer(journal), **request.param)
    yield eng, request.param, str(d)
    journal.close()


def test_streamed_chunks_are_the_run_tokens(mode_engine, setup):
    """(a) Greedy and sampled: the chunks of a stream, concatenated, are the
    tokens ``run()`` returns for the same prompt and seed without one, and
    what the serial engine of the same mode returns."""
    eng, mode, _ = mode_engine
    prompts = _prompts(setup[2])
    kw = [dict(temperature=t, seed=s) for t, s in SAMPLING]
    rids = [eng.submit(p, **k) for p, k in zip(prompts, kw)]
    res = eng.run()
    golden = [res[r] for r in rids]
    assert all(golden), golden
    if not mode["serial"] and mode["cache"] == "paged":
        # (contiguous caches are held to the serial order, by the same kind of
        # identity, in tests/test_pipeline_ticks.py)
        held_to = _engine(setup, **{**mode, "serial": True})
        rids = [held_to.submit(p, **k) for p, k in zip(prompts, kw)]
        res = held_to.run()
        assert [res[r] for r in rids] == golden
    qs = [queue.Queue() for _ in prompts]
    rids = [eng.submit(p, stream=q, **k) for p, q, k in zip(prompts, qs, kw)]
    res = eng.run()
    for rid, q, want in zip(rids, qs, golden):
        chunks = _drain(q)
        assert sum(chunks, []) == want == res[rid]
        assert len(chunks[0]) == 1  # the prefill's token, alone


def test_first_token_is_out_before_the_ticks_fetch(mode_engine, setup, monkeypatch):
    """(b) The step that finishes a request's prefill puts a one-token chunk
    on its stream, and it is there when that tick's (or, double-buffered,
    any tick's) own fetch begins. A serial plain tick adds the rest of its
    row, ``decode_chunk - 1`` tokens, in the same step; the default engine
    with the next step."""
    eng, mode, _ = mode_engine
    tok = setup[2]
    q = queue.Queue()
    seen_at_fetch = []
    eng.step()  # idle: nothing queued, and run() drained the trailing tick
    for name in ("_plain_finish", "_spec_finish"):
        finish = getattr(eng, name)

        def spy(rec, finish=finish):
            seen_at_fetch.append(list(q.queue))
            return finish(rec)

        monkeypatch.setattr(eng, name, spy)
    # 43 tokens no other test sends (a paged engine would match their pages)
    prompt = [tok.bos_id] + tok.encode("first token first token first token first!")
    eng.submit(prompt, stream=q)
    steps = 0
    while q.empty():
        eng.step()
        steps += 1
        assert steps <= 3
    # chunked: 43 tokens are three chunks of at most 16, one a step
    assert steps == (3 if mode["chunked"] else 1)
    assert eng.metrics.ttft.count >= 1
    after_step = list(q.queue)
    first = after_step[0]
    assert len(first) == 1
    if not mode["serial"]:
        assert after_step == [first] and not seen_at_fetch
        eng.step()
        after_step = list(q.queue)
    if after_step[-1] is None:  # 16 steps a program: the row ended in its first
        assert mode["chunk"] > MAX_NEW and after_step.pop() is None
    assert len(after_step) == 2
    if not mode["spec"]:
        assert len(after_step[1]) == min(mode["chunk"], MAX_NEW) - 1
    res = eng.run()
    (tokens,) = res.values()
    assert sum(_drain(q), []) == tokens
    assert seen_at_fetch and all(s and s[0] == first for s in seen_at_fetch)


def test_counter_and_tick_span_count_the_first_tokens(mode_engine, setup):
    """(d) ``first_tokens_early_total`` goes up by one for each admitted
    request, and the ``engine.tick`` spans' ``first_tokens`` add up to it."""
    eng, _, span_dir = mode_engine
    before = eng.stats()["first_tokens_early_total"]
    admitted = eng.metrics.admitted.value
    for p in _prompts(setup[2]):
        eng.submit(p)
    res = eng.run()
    assert all(res.values())  # none ended on its first token
    total = eng.stats()["first_tokens_early_total"]
    assert total - before == eng.metrics.admitted.value - admitted == 3
    ticks = [r for r in merge_journals(span_dir)
             if r.get("name") == "engine.tick"]
    assert ticks and all("first_tokens" in r for r in ticks)
    assert sum(r["first_tokens"] for r in ticks) == total


# -- (c) the ways a request ends or is interrupted around its first token ----


def _golden(setup, prompt, **kw):
    eng = _engine(setup, serial=True, **kw)
    rid = eng.submit(prompt)
    return eng.run()[rid]


def _ledgered(setup, tmp_path, **kw):
    ledger = UsageLedger(usage_ledger_path(str(tmp_path), "eng"), source="eng")
    return _engine(setup, usage_ledger=ledger, **kw), ledger


def _usage_rows(ledger, tmp_path):
    ledger.close()
    return load_usage(str(tmp_path))


@pytest.mark.parametrize("at", [0, 1], ids=["first-token-eos", "second-token-eos"])
def test_eos_at_the_first_tokens(setup, tmp_path, at):
    """The end-of-text id as the first sampled token ends the request with
    no token and no early send, as it did; as the second, the one-token
    chunk is all the stream gets."""
    tok = setup[2]
    prompt = _prompts(tok)[2]
    want = _golden(setup, prompt)
    ends = copy.copy(tok)
    ends.eos_id = want[at]
    eng, ledger = _ledgered(setup, tmp_path, tok=ends)
    q = queue.Queue()
    rid = eng.submit(prompt, stream=q)
    res = eng.run()
    assert res[rid] == want[:at]
    assert _drain(q) == ([want[:1]] if at else [])
    assert eng.stats()["first_tokens_early_total"] == at
    (row,) = _usage_rows(ledger, tmp_path)
    assert (row["outcome"], row["generated_tokens"]) == ("200", at)
    assert eng._slots == [None] * eng.n_slots


@pytest.mark.parametrize("cache", ["paged", "contiguous"])
def test_max_new_tokens_one(setup, tmp_path, cache):
    tok = setup[2]
    prompt = _prompts(tok)[2]
    want = _golden(setup, prompt, cache=cache)
    eng, ledger = _ledgered(setup, tmp_path, cache=cache)
    q = queue.Queue()
    rid = eng.submit(prompt, stream=q, max_new_tokens=1)
    res = eng.run()
    assert res[rid] == want[:1]
    assert _drain(q) == [want[:1]]
    (row,) = _usage_rows(ledger, tmp_path)
    assert (row["outcome"], row["generated_tokens"]) == ("200", 1)


@over_ticks
def test_cancel_right_after_the_first_token(setup, tmp_path, ticks):
    """Double-buffered, the cancel falls between the early token and the harvest
    of its tick, which then skips the dead request: one terminal None, one
    usage row that bills what was sent, no slot or page left."""
    tok = setup[2]
    prompt = _prompts(tok)[2]
    want = _golden(setup, prompt)
    eng, ledger = _ledgered(setup, tmp_path, **ticks)
    q = queue.Queue()
    rid = eng.submit(prompt, stream=q)
    eng.step()
    sent = sum(q.queue, [])
    assert sent == (want[:CHUNK] if ticks["serial"] else want[:1])
    assert eng.cancel(rid)
    eng.step()  # double-buffered: the cancelled request's tick is harvested here
    assert not eng.pending
    assert sum(_drain(q), []) == sent
    (row,) = _usage_rows(ledger, tmp_path)
    assert (row["outcome"], row["generated_tokens"]) == ("cancel", len(sent))
    assert eng._slots == [None] * eng.n_slots
    assert eng.allocator.n_free + eng.allocator.n_evictable == eng.n_pages - 1


# 17-token prompts on pages of 16: admission takes two pages, and the third
# falls due when 12 tokens are out (17 + 12 + one tick's 4 > 32).
PROMPT_A = [1] + list(range(5, 21))
PROMPT_B = [1] + list(range(30, 46))


def test_preempted_between_its_prefill_and_the_dispatch(setup):
    """Four usable pages. B is admitted, and prefilled, in the very tick in
    which A's top-up falls due: the pool is dry, so B, the younger, is
    preempted before the dispatch with its first token still pending. That
    token is not sent early (the resume's tick emits it), and both streams
    are what an uncontended engine gives."""
    gen = GenerateConfig(max_new_tokens=24)
    solo = _engine(setup, gen=gen, serial=True)
    ra, rb = solo.submit(PROMPT_A), solo.submit(PROMPT_B)
    ref = solo.run()
    eng = _engine(setup, gen=gen, n_pages=5, admission="optimistic")
    qa, qb = queue.Queue(), queue.Queue()
    a = eng.submit(PROMPT_A, stream=qa)
    for _ in range(3):
        eng.step()
    b = eng.submit(PROMPT_B, stream=qb)
    eng.step()
    assert eng.preemptions == 1 and eng._queue[0].req_id == b
    assert eng._queue[0].preempted and eng._queue[0].tokens == []
    assert qb.empty()
    assert eng.stats()["first_tokens_early_total"] == 1
    res = eng.run()
    assert res[a] == ref[ra] and res[b] == ref[rb]
    assert sum(_drain(qa), []) == ref[ra]
    assert sum(_drain(qb), []) == ref[rb]
    assert eng.stats()["first_tokens_early_total"] == 1


@over_ticks
def test_preempt_then_resume_streams(setup, ticks):
    """A pool too small for both: the younger is preempted in flight, after
    its first token went out, and resumed. Nothing is sent twice."""
    gen = GenerateConfig(max_new_tokens=96)
    solo = _engine(setup, gen=gen, max_cache_len=None, serial=True)
    ra, rb = solo.submit(PROMPT_A), solo.submit(PROMPT_B)
    ref = solo.run()
    eng = _engine(setup, gen=gen, n_pages=10, admission="optimistic", **ticks)
    qa, qb = queue.Queue(), queue.Queue()
    a, b = eng.submit(PROMPT_A, stream=qa), eng.submit(PROMPT_B, stream=qb)
    res = eng.run()
    assert eng.preemptions >= 1
    assert res[a] == ref[ra] and res[b] == ref[rb]
    for q, want in ((qa, ref[ra]), (qb, ref[rb])):
        chunks = _drain(q)
        assert sum(chunks, []) == want and len(chunks[0]) == 1
    assert eng.stats()["first_tokens_early_total"] == 2


@pytest.mark.parametrize("spec", [False, True], ids=["plain", "spec"])
def test_logprobs_ride_the_first_token(setup, spec):
    """The first token's stats come with it, from the same fetch; every
    chunk's stats line up with its tokens, and the whole with the stats of
    the same request without a stream."""
    prompt = _prompts(setup[2])[2]
    eng = _engine(setup, spec=spec, logprobs_k=3)
    q = queue.Queue()
    plain = eng.submit(prompt, logprobs=2)
    streamed = eng.submit(prompt, logprobs=2, stream=q)
    while eng.pending:
        eng.step()
    ref, got = eng._completed[plain], eng._completed[streamed]
    assert got.tokens == ref.tokens and got.lp_top_ids == ref.lp_top_ids
    assert got.lp_token == pytest.approx(ref.lp_token, rel=1e-5)
    chunks = _drain(q)
    toks0, lp0 = chunks[0]
    assert len(toks0) == 1 and [len(r) for r in lp0["top_ids"]] == [2]
    # greedy: the chosen token is the likeliest, and its logprob the top one
    assert lp0["top_ids"][0][0] == toks0[0]
    assert lp0["token_logprobs"][0] == pytest.approx(lp0["top_logprobs"][0][0])
    assert sum((t for t, _ in chunks), []) == got.tokens
    n = len(got.tokens)
    for key, whole in (("token_logprobs", got.lp_token),
                       ("top_ids", [r[:2] for r in got.lp_top_ids]),
                       ("top_logprobs", [r[:2] for r in got.lp_top])):
        assert sum((lp[key] for _, lp in chunks), []) == whole[:n]
    assert all(len(t) == len(lp["token_logprobs"]) for t, lp in chunks)


def test_guided_request(setup):
    from ditl_tpu.infer import grammar as G

    tok = setup[2]
    g = G.compile_regex(r"[0-9]{1,6}", tok)
    eng = _engine(setup, fsm_capacity=256)
    prompt = [tok.bos_id] + tok.encode("n =")
    rid = eng.submit(prompt, grammar=g)
    want = eng.run()[rid]
    assert re.fullmatch(r"[0-9]{1,6}", tok.decode(want))
    masked = eng.metrics.grammar_masked.value
    q = queue.Queue()
    rid = eng.submit(prompt, grammar=g, stream=q)
    assert eng.run()[rid] == want
    chunks = _drain(q)
    assert sum(chunks, []) == want and len(chunks[0]) == 1
    assert eng.metrics.grammar_masked.value - masked == len(want)


def test_threaded_stream_starts_with_one_token(setup):
    """What the server relays: ``stream_one``'s first chunk is one token."""
    tok = setup[2]
    threaded = ThreadedEngine(_engine(setup))
    try:
        prompt = _prompts(tok)[2]
        chunks = list(threaded.stream_one(prompt, max_new_tokens=9))
        assert len(chunks[0]) == 1 and len(chunks[1]) == CHUNK - 1
        assert sum(chunks, []) == threaded.generate_one(prompt, max_new_tokens=9)
        assert threaded.stats()["first_tokens_early_total"] == 2
    finally:
        threaded.close()
