"""Optimistic paged admission + preemption (VERDICT r3 missing #2).

``admission="optimistic"`` admits past the worst-case page reservation and
preempts the youngest request on pool exhaustion. These tests pin the two
contract points: CAPACITY — at equal pool bytes, strictly more requests
decode concurrently than reserve-mode admission allows — and EXACTNESS —
a preempted-and-resumed request's output is token-identical (f32) to an
uncontended run, across greedy, sampled, logprobs, streaming, and
pipelined-tick compositions."""

import queue as _queue

import jax
import pytest

from ditl_tpu.config import ModelConfig
from ditl_tpu.data.tokenizer import ByteTokenizer
from ditl_tpu.infer.continuous import ContinuousEngine
from ditl_tpu.infer.engine import GenerateConfig
from ditl_tpu.models import llama


@pytest.fixture(scope="module")
def setup():
    cfg = ModelConfig(
        vocab_size=512, hidden_size=64, intermediate_size=128, num_layers=2,
        num_heads=4, num_kv_heads=2, head_dim=16, max_seq_len=256,
        dtype="float32", param_dtype="float32",
    )
    params = llama.init_params(jax.random.key(0), cfg)
    return params, cfg, ByteTokenizer()


def _engine(setup, **kw):
    params, cfg, tok = setup
    kw.setdefault("n_slots", 4)
    kw.setdefault("decode_chunk", 4)
    kw.setdefault("cache_mode", "paged")
    kw.setdefault("page_size", 16)
    kw.setdefault("gen", GenerateConfig(max_new_tokens=16))
    return ContinuousEngine(params, cfg, tok, **kw)


def _max_concurrency(eng, prompts, **submit_kw):
    """Drive to completion, tracking the peak number of slots decoding."""
    rids = [eng.submit(p, **submit_kw) for p in prompts]
    peak = 0
    results = {}
    while eng.pending:
        eng.step()
        peak = max(peak, sum(
            r is not None and not r.prefilling for r in eng._slots
        ))
        for req in eng.take_finished():
            results[req.req_id] = req.tokens
    return peak, [results[r] for r in rids]


# Pool: 12 usable pages of 16 tokens = 192 resident tokens. Each request:
# 17-token prompt + max_new 144 => worst case ceil(161/16) = 11 pages, so
# reserve-mode admission fits ONE request at a time. Actual decode runs to
# max_new... so optimistic mode must preempt to finish; with max_new=32
# (actual budget), 3 pages actual use.
PROMPT = [1] + list(range(5, 21))


def test_optimistic_strictly_more_concurrent_at_equal_pool(setup):
    """Equal pool bytes, pessimistic max_tokens: reserve-mode worst-case
    reservation (11 pages each vs 12 usable) serializes the pool to one
    request at a time; optimistic admission runs all three concurrently,
    preempting as the pool tightens — same tokens either way."""
    prompts = [PROMPT, [1] + list(range(30, 46)), [1] + list(range(50, 66))]
    kw = dict(n_pages=13, gen=GenerateConfig(max_new_tokens=144))
    reserve = _engine(setup, admission="reserve", **kw)
    peak_r, out_r = _max_concurrency(reserve, prompts)
    optimistic = _engine(setup, admission="optimistic", **kw)
    peak_o, out_o = _max_concurrency(optimistic, prompts)
    assert out_o == out_r  # identical tokens either way
    assert peak_r == 1  # worst-case reservation serializes the pool
    assert peak_o == 3  # optimistic shares it
    assert optimistic.preemptions >= 1  # pressure was real


def test_preemption_exact_resume_greedy(setup):
    """Pool too small for both requests' ACTUAL budgets: the youngest is
    preempted mid-flight and resumed after the oldest finishes; outputs are
    token-identical to an uncontended run."""
    a, b = PROMPT, [1] + list(range(30, 46))
    gen = GenerateConfig(max_new_tokens=96)
    solo = _engine(setup, n_pages=20, gen=gen)
    ra, rb = solo.submit(a), solo.submit(b)
    ref = solo.run()
    expect_a, expect_b = ref[ra], ref[rb]

    # 9 usable pages: each request actually needs ceil((17+96+chunk)/16)=8
    # pages at full budget -> both cannot run concurrently to completion;
    # optimistic admits both, then preempts the younger (b) when the pool
    # runs dry, resumes it after a completes.
    eng = _engine(setup, n_pages=10, admission="optimistic", gen=gen)
    ra, rb = eng.submit(a), eng.submit(b)
    res = eng.run()
    assert res[ra] == expect_a
    assert res[rb] == expect_b
    assert eng.preemptions >= 1


def test_preemption_exact_resume_sampled_logprobs(setup):
    """Sampled + logprobs across a preemption: the PRNG split chain and the
    pending logprob stats survive the round trip — tokens and top-id
    rankings identical; logprob floats agree to ~1 ulp (the resume prefill
    recomputes the generated tokens' KV with a batched matmul whose f32
    tiling differs from the original step-by-step decode writes)."""
    a, b = PROMPT, [1] + list(range(30, 46))
    gen = GenerateConfig(max_new_tokens=96)
    outs = []
    for n_pages, admission in ((20, "reserve"), (10, "optimistic")):
        eng = _engine(setup, n_pages=n_pages, admission=admission, gen=gen,
                      logprobs_k=2)
        rids = [eng.submit(p, temperature=0.9, top_p=0.95, seed=s,
                           logprobs=2)
                for p, s in ((a, 7), (b, 8))]
        done = {}
        while eng.pending:
            eng.step()
            for req in eng.take_finished():
                done[req.req_id] = req
        outs.append([done[r] for r in rids])
        if admission == "optimistic":
            assert eng.preemptions >= 1
    for ref, got in zip(*outs):
        assert got.tokens == ref.tokens  # token-identical through preemption
        assert got.lp_top_ids == ref.lp_top_ids
        assert got.lp_token == pytest.approx(ref.lp_token, rel=1e-4)
        for rrow, grow in zip(ref.lp_top, got.lp_top):
            assert grow == pytest.approx(rrow, rel=1e-4)


def test_preemption_streaming_and_pipelined(setup):
    """Preemption composes with pipelined ticks and streaming: chunks pause
    during requeue, resume, and arrive with exactly one terminal None."""
    a, b = PROMPT, [1] + list(range(30, 46))
    gen = GenerateConfig(max_new_tokens=96)
    solo = _engine(setup, n_pages=20, gen=gen)
    ra, rb = solo.submit(a), solo.submit(b)
    ref = solo.run()

    eng = _engine(setup, n_pages=10, admission="optimistic", gen=gen,
                  pipeline_ticks=True)
    qa: _queue.Queue = _queue.Queue()
    qb: _queue.Queue = _queue.Queue()
    na, nb = eng.submit(a, stream=qa), eng.submit(b, stream=qb)
    res = eng.run()
    assert res[na] == ref[ra] and res[nb] == ref[rb]
    assert eng.preemptions >= 1
    for q, rid in ((qa, ra), (qb, rb)):
        chunks, sentinels = [], 0
        while not q.empty():
            item = q.get_nowait()
            if item is None:
                sentinels += 1
            else:
                chunks.extend(item)
        assert chunks == ref[rid] and sentinels == 1


def test_cancel_of_preempted_request_that_finished_while_queued(setup):
    """Pipelined ticks can finish a preempted request via the lagged
    harvest while it still sits in the queue. A cancel landing in that
    window must not push a second terminal None to the stream (the SSE
    contract is exactly one) and must discard the completed result."""
    gen = GenerateConfig(max_new_tokens=8)
    eng = _engine(setup, n_pages=40, admission="optimistic", gen=gen,
                  pipeline_ticks=True)
    q: _queue.Queue = _queue.Queue()
    rid = eng.submit(PROMPT, stream=q)
    eng.step()  # dispatch tick 1 (pending)
    eng.step()  # dispatch tick 2 (2nd chunk of 8), harvest tick 1
    # Preempt while tick 2 — which completes the 8-token budget — is
    # pending: its lagged harvest then finishes the request IN THE QUEUE.
    victim = eng._slots.index(next(r for r in eng._slots if r is not None))
    eng._preempt_slot(victim)
    # Finish the pending tick directly (a step() would re-admit the queued
    # request first in this uncontended pool; in production the window
    # exists whenever the pool is still too tight to resume immediately).
    rec, eng._pending_fetch = eng._pending_fetch, None
    eng._finish_tick(rec)  # lagged harvest: request finishes while queued
    req = next(r for r in eng._queue if r.req_id == rid)
    assert req.finished and req.preempted
    assert eng.cancel(rid)
    assert rid not in eng._completed  # result discarded, not served
    assert not any(r.req_id == rid for r in eng._queue)
    sentinels = 0
    while not q.empty():
        if q.get_nowait() is None:
            sentinels += 1
    assert sentinels == 1  # exactly one terminal None despite the cancel


@pytest.mark.slow
def test_preemption_resume_with_draft_model_spec(setup):
    """ADVICE r4 (medium): resume must re-prefill the DRAFT model's cache
    with the full resumed context, not just the prompt — otherwise the
    drafter attends the slot's prior occupant's stale KV at every position
    past the prompt, collapsing acceptance (and, for sampled requests,
    shifting the realized stream through the rejection residual).

    Deterministic probe: a PERFECT drafter (draft == target) holds
    acceptance well above the bonus-only floor; after a resume into a
    FOREIGN slot, a prompt-only draft re-prefill would leave it drafting
    against the other request's context, and post-resume acceptance drops
    to ~the floor. Pins post-resume acceptance high + tokens exact."""
    params, cfg, tok = setup
    a, b = PROMPT, [1] + list(range(30, 46))
    # prefill_chunk=32 with the preemption taken past 20 generated tokens:
    # the resume context (37+) exceeds the chunk, so the draft re-prefill
    # exercises the CHUNKED suffix path (resume contexts reach buckets no
    # prompt does; the draft prefill honors prefill_chunk like the target's
    # resume loop).
    kw = dict(
        n_slots=2, n_pages=40, admission="optimistic", speculative=True,
        spec_k=4, draft_params=params, draft_cfg=cfg, prefill_chunk=32,
        gen=GenerateConfig(max_new_tokens=96),
    )
    ref_eng = _engine(setup, **kw)
    rids = [ref_eng.submit(p) for p in (a, b)]
    ref = ref_eng.run()

    # Force the stale-slot case deterministically: preempt b (slot 1)
    # mid-flight, hold it queued until a finishes, so b resumes into slot
    # 0 — whose DRAFT cache holds a's KV at every position past b's
    # prompt length.
    eng = _engine(setup, **kw)
    ra, rb = eng.submit(a), eng.submit(b)
    while True:
        eng.step()
        breq = eng._slots[1]
        if breq is not None and breq.req_id == rb and len(breq.tokens) >= 20:
            break
    eng._preempt_slot(1)
    held = eng._queue.pop(0)  # park b so it cannot resume into slot 1
    while any(r is not None for r in eng._slots):
        eng.step()  # drive a to completion; slot 0 frees
    pre_t, pre_f = held.spec_tokens, held.spec_forwards
    eng._queue.insert(0, held)
    eng.step()
    assert eng._slots[0] is held  # resumed into the foreign slot
    res = eng.run()
    assert res[ra] == ref[rids[0]]
    assert res[rb] == ref[rids[1]]  # greedy exactness is unconditional
    # The drafter kept drafting against b's REAL context after the resume:
    # acceptance stays near its uncontended level (>2 tokens/forward with
    # k=4 on random weights), not the ~1.0 bonus-only floor a stale-context
    # drafter collapses to.
    post = (held.spec_tokens - pre_t) / max(1, held.spec_forwards - pre_f)
    assert post > 2.0


def test_prefilling_younger_is_preempted_not_the_needy_oldest(setup):
    """ADVICE r4 (low): when every younger request is still mid-prefill,
    the pool squeeze must pick a prefilling YOUNGER victim — requeued as a
    fresh request — never the needy oldest (the no-deadlock invariant)."""
    params, cfg, tok = setup
    gen = GenerateConfig(max_new_tokens=32)
    long_b = [1] + list(range(2, 152))  # 151 tokens: 5 chunks of 32
    solo = _engine(setup, n_pages=40, gen=gen, prefill_chunk=32)
    ra, rb = solo.submit(PROMPT), solo.submit(long_b)
    ref = solo.run()

    eng = _engine(setup, n_pages=40, admission="optimistic", gen=gen,
                  prefill_chunk=32)
    ra2 = eng.submit(PROMPT)
    eng.step()  # a admitted and decoding
    rb2 = eng.submit(long_b)
    eng.step()  # b admitted, still prefilling (151 > 32)
    areq = next(r for r in eng._slots if r is not None and r.req_id == ra2)
    breq = next(r for r in eng._slots if r is not None and r.req_id == rb2)
    assert breq.prefilling
    victim = eng._pick_victim(areq)
    assert victim == breq.slot  # prefilling slots are eligible victims now
    eng._preempt_slot(victim)
    # Mid-prefill victims requeue FRESH: no frontier capture, no preempted
    # flag — re-admission prefix-matches the published whole pages.
    assert not breq.preempted and not breq.prefilling
    assert breq in eng._queue
    assert eng.preemptions == 1
    res = eng.run()
    assert res[ra2] == ref[ra] and res[rb2] == ref[rb]


def test_optimistic_with_guided_early_finish(setup):
    """Guided requests finish far below max_tokens: optimistic admission
    turns the unused pessimistic budget into real concurrency, and the FSM
    state survives preemption (grammar still enforced on resume)."""
    params, cfg, tok = setup
    from ditl_tpu.infer import grammar as G

    g = G.compile_regex("[ab]{1,6}", tok)
    gen = GenerateConfig(max_new_tokens=144)
    prompts = [PROMPT, [1] + list(range(30, 46)), [1] + list(range(50, 66))]

    def run(admission, n_pages):
        eng = _engine(setup, n_pages=n_pages, admission=admission, gen=gen,
                      fsm_capacity=g.n_states + 2)
        return _max_concurrency(eng, prompts, grammar=g)

    peak_r, out_r = run("reserve", 13)
    peak_o, out_o = run("optimistic", 13)
    assert out_o == out_r
    assert peak_r == 1 and peak_o == 3
    for out in out_o:
        text = tok.decode(out)
        assert 1 <= len(text) <= 6 and set(text) <= {"a", "b"}


@pytest.mark.slow
def test_anti_thrash_hysteresis_engages_and_releases(setup):
    """VERDICT r4 weak #7: under sustained arrivals into a pool that barely
    covers the working set, optimistic admission preempt-thrashes (the
    −45% row). The guard watches resume-prefilled vs generated tokens per
    window, degrades NEW admissions to worst-case reservation past the
    engage ratio, and releases only when the window is quiet AND the
    backlog drained (the ratio alone would oscillate: degradation
    suppresses the symptom it measures). Pins: engage fires once (no
    oscillation), preemption/resume waste collapses, outputs stay exact,
    and a post-drain light workload releases the switch."""
    gen = GenerateConfig(max_new_tokens=96)
    prompts = [[1] + list(range(5 + 3 * i, 21 + 3 * i)) for i in range(12)]
    solo = _engine(setup, n_pages=60, gen=gen)
    rids = [solo.submit(p) for p in prompts]
    ref = solo.run()
    expect = [ref[r] for r in rids]

    def run_thrash(window):
        # 12 usable pages, 12 staggered arrivals of ~7-page actual
        # footprints: continuous three-way contention, repeated
        # preempt/resume cycles.
        eng = _engine(setup, n_pages=13, admission="optimistic", gen=gen,
                      thrash_window=window)
        out, i, steps = {}, 0, 0
        while eng.pending or i < len(prompts):
            if i < len(prompts) and steps % 6 == 0:
                out[i] = eng.submit(prompts[i])
                i += 1
            eng.step()
            steps += 1
            assert steps < 5000
        results = {rid: req.tokens for rid, req in eng._completed.items()}
        eng._completed.clear()
        toks = [results[out[i]] for i in range(len(prompts))]
        assert toks == expect  # exactness regardless of the guard
        return eng

    unguarded = run_thrash(10_000_000)  # window never closes: guard off
    guarded = run_thrash(8)
    assert unguarded.admission_degrades == 0
    assert guarded.admission_degrades == 1  # engaged ONCE — no oscillation
    # Worst-case reservations stop the ping-pong: wasted resume-prefill
    # work and preemptions collapse.
    assert guarded.preemptions < unguarded.preemptions / 2
    assert guarded.resume_prefill_tokens < unguarded.resume_prefill_tokens / 2
    # The backlog kept the switch engaged to the end of the thrash phase;
    # a light post-drain workload releases it (queue empty + quiet window).
    rid = guarded.submit([1] + list(range(50, 60)))
    res = guarded.run()
    assert len(res[rid]) > 0
    assert not guarded._degraded  # released
