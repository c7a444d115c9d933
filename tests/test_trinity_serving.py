"""Trinity-Mini through the serving engine (ISSUE 48): prefill in chunks and
paged decode through the full layers' pool AND the window layers' pool
against the plain reference's full forward pass, on rows that cross the
window; window pages released and freed behind a row, counted; a prefix hit
at the whole length, at a shorter one and none; eviction, preemption and
resume with both pools' counts sound; the engine's counters and the modes
that refuse. The window (32) is shorter than every context here, so the mask
and the allocator do real work. A file of its own so that the test runner can
give it a worker of its own (tests/test_trinity.py has the model)."""

from __future__ import annotations

import jax
import numpy as np
import pytest

from ditl_tpu.infer.page_format import MODES, page_format
from ditl_tpu.models.presets import get_preset
from tests import family
from tests.family import ask, prompt_of

ref = family.reference("trinity_mini")
PRESET = "trinity-mini"

# float32 on both sides, sums in another order: 1e-6 is what that leaves,
# 1e-4 a hundred times of room and a hundred times under a wrong page,
# position or window.
TOL = 1e-4

TINY = dict(num_layers=8, layer_types="wwwa" * 2, first_k_dense_replace=1, vocab_size=512,
            hidden_size=64, intermediate_size=128, expert_ffn_hidden_size=32, num_heads=4,
            num_kv_heads=2, head_dim=16, num_experts=16, num_experts_per_tok=4,
            experts_held_first=0, experts_held_count=8, sliding_window=32,
            embedding_multiplier=8.0, max_seq_len=512, dtype="float32", param_dtype="float32")
OVERRIDES = [f"{k}={v}" for k, v in TINY.items()]
CONFIG = {"preset": "trinity-mini", "reference": "trinity_mini"}
PS, REACH = 16, 2  # pages of 16 tokens: a window of 32 reaches 2 pages back


CFG = family.tiny(PRESET, TINY)
# the options most cases ask for: two rows of up to 160 tokens
ROWS = dict(page_size=PS, max_cache_len=160)
# one row of up to 256 tokens, prefilled in chunks longer than the window
LONG = dict(page_size=PS, prefill_chunk=32, n_slots=1, max_cache_len=256)


def sound(eng):
    """Both pools' counts, recomputed from who holds what."""
    al = eng.allocator
    held = [0] * al.window_pages
    for slot, (lo, hi) in enumerate(eng._slot_span):
        for i in range(lo, hi):
            w = int(al.companion[eng._slot_pages[slot][i]])
            assert w, (slot, i)
            held[w] += 1
    for f in al._wcached:
        assert f in al._page_key  # the cache holds companions of published pages only
        held[int(al.companion[f])] += 1
    assert held == al._wref
    named = [int(w) for w in al.companion if w]
    assert len(named) == len(set(named))  # a window page is one full page's companion
    assert sorted(named + list(al._wfree)) == list(range(1, al.window_pages))
    assert al.n_evictable == al.scan_evictable()
    return True


def test_paged_prefill_then_decode_through_both_pools_matches_the_reference():
    """Prompts of 5-70 tokens on pages of 16, then 24 new tokens (a flush and
    a page boundary in every row), most rows crossing the window of 32:
    log-probabilities against ONE uncached pass of the reference."""
    import paged_check

    verdict = paged_check.check(CONFIG, OVERRIDES, seed=3, prompt_tokens=(5, 20, 33, 40, 70),
                                new_tokens=24, page_size=PS, rehearsal=True)
    assert verdict["served_tokens"] > 90
    assert verdict["logprob_err_over_logit_rms"] < TOL, verdict


def test_a_document_in_chunks_a_hit_in_both_pools_and_a_row_decoding_across_the_window():
    """``benchmarks/window_check.py`` at a tiny size: 96 tokens in three chunks
    of 32 (longer than the window: each later chunk reads 2 window pages and
    every full page), a question that hits the 96 cached tokens in both pools,
    and a row of 28 tokens that decodes 40, its first pages falling behind its
    own window in decode; with the window switched off in the reference the
    same comparison fails."""
    import window_check

    verdict = window_check.check(CONFIG, OVERRIDES, seed=3, doc_tokens=96, question_tokens=7,
                                 new_tokens=10, short_prompt=28, long_answer=40, page_size=PS,
                                 prefill_chunk=32, rehearsal=True)
    assert verdict["ok"], verdict
    assert verdict["logprob_err_over_logit_rms"] < TOL
    assert verdict["logprob_err_without_the_window"] > 0.1
    assert verdict["prefix_hit_tokens"] == 96 and verdict["prefix_hits_whole"] == 1
    # six pages of the document: all but the last window's two go back
    assert verdict["window_pages_freed"]["document"] >= 3
    assert verdict["window_pages_released"]["long_answer"] >= 2


def test_chunked_and_whole_prefill_and_a_prefix_hit_give_one_answer(engines):
    prompt = prompt_of(np.random.default_rng(0), 71)
    outs = []
    for chunk in (0, 32):
        eng = engines(family.model(ref, CFG), **ROWS, prefill_chunk=chunk)
        before = eng.stats()
        answers = []
        for _ in range(2):  # the second finds the first one's published pages
            answers.append(ask(eng, prompt, 6))
            assert sound(eng)
        outs.append(answers)
        st = eng.stats()
        assert st["prefix_cache"]["hit_tokens"] - before["prefix_cache"]["hit_tokens"] == 64
        assert st["prefix_hits_whole"] - before["prefix_hits_whole"] == 1
    assert outs[0][0] == outs[0][1] == outs[1][0] == outs[1][1]


def test_a_live_row_holds_its_window_and_the_tick_in_flight_and_no_more(engines):
    """Guarantee (a): at every tick a row holds at most ``ceil(window / ps) +
    1`` window pages of context plus those of the chunk or tick in flight, in
    chunked prefill and in decode; what it lets go is counted."""
    eng = engines(family.model(ref, CFG), **LONG)
    before = eng.stats()
    rid = eng.submit(prompt_of(np.random.default_rng(1), 120), max_new_tokens=100,
                     temperature=0.0)
    widest = 0
    while eng.pending:
        eng.step()
        lo, hi = eng._slot_span[0]
        widest = max(widest, hi - lo)
        assert sound(eng)
    assert len(eng.take_result(rid)) == 100
    # a 32-token chunk: 2 pages in flight; a tick of 4 steps, double-buffered: 1
    assert widest <= REACH + 1 + 2
    st = eng.stats()
    # 220 tokens are 14 pages; the last window's pages stay with the cache
    assert (st["window_pages_released_total"] - before["window_pages_released_total"]
            >= 14 - (REACH + 1))
    # some stay with the cache
    assert st["window_pages_freed_total"] - before["window_pages_freed_total"] >= 8
    # the row is gone: what is still in the pool is the cache's alone, and
    # this row added no more to it than two windows' pages
    assert st["window_pages_total"] - st["window_pages_free"] == st["window_pages_cached_evictable"]
    assert (st["window_pages_cached_evictable"] - before["window_pages_cached_evictable"]
            <= 2 * (REACH + 1))


def test_a_hit_at_the_whole_length_at_a_shorter_one_and_none(engines):
    """Guarantee (b): a hit is granted at length P only where the full pool
    has [0, P) and the window pool covers the last window below P; else at the
    longest shorter P for which both hold; else not at all."""
    rng = np.random.default_rng(2)
    doc = prompt_of(rng, 96)  # 6 whole pages
    eng = engines(family.model(ref, CFG), **LONG)
    al = eng.allocator
    was = (al.hits_whole, al.hits_short, al.hits_refused)
    hit_was = eng.stats()["prefix_cache"]["hit_tokens"]

    def hits():  # (whole, short, refused) since this case began
        return tuple(n - w for n, w in zip((al.hits_whole, al.hits_short, al.hits_refused), was))

    first = ask(eng, doc + [7, 8, 9])
    assert hits() == (0, 0, 0)
    # whole: the document's six pages, its last two in the window pool too
    assert ask(eng, doc + [7, 8, 9]) == first
    assert hits() == (1, 0, 0)
    hit = eng.stats()["prefix_cache"]["hit_tokens"]
    assert hit - hit_was == 96
    # shorter: a prompt that shares only the first five pages finds all five in
    # the full pool, but the window pool kept the document's LAST window
    # (pages 4 and 5): at five pages page 3 is missing, and at no shorter
    # length do both hold, so the hit is refused and counted
    branch = doc[:80] + prompt_of(rng, 20)[1:]
    ask(eng, branch)
    assert hits() == (1, 0, 1)
    assert eng.stats()["prefix_cache"]["hit_tokens"] == hit
    assert sound(eng)
    # a prompt that runs PAST a document's end is granted the document's
    # length, shorter than what the full pool could give (another document, so
    # that what the engine holds of it is this part's alone)
    doc = prompt_of(rng, 96)
    answer = ask(eng, doc, 20)  # publishes document + answer: 7 pages, the tip's window kept
    longer = doc + answer + prompt_of(rng, 30)[1:]
    # evict the window companions of the answer's page only: the full pool still
    # matches 7 pages, the window pool covers the last window below page 6
    tip = al.match_prefix(longer, PS)
    assert len(tip) == 7
    for pid in tip:
        al.release(pid)
    was = (al.hits_whole, al.hits_short, al.hits_refused)
    victim = tip[-1]
    del al._wcached[victim]
    al._drop(victim, by_row=False)
    assert ask(eng, longer, 20)[:1]  # served
    assert hits()[:2] == (0, 1)
    assert sound(eng)


def test_eviction_preemption_and_resume_keep_both_pools_counts_sound(engines):
    """Guarantee (c): two long answers in pools too small for both rows and a
    cache; a row is preempted (its pages published, its holds given up) and
    comes back, re-prefilling what neither pool holds; both answers equal
    what each gets alone, and both pools' counts add up at every tick."""
    rng = np.random.default_rng(5)
    prompts = [prompt_of(rng, 40) for _ in range(2)]
    roomy = engines(family.model(ref, CFG), **ROWS, prefill_chunk=0)
    alone = [ask(roomy, p, 60) for p in prompts]
    # an engine of its own: the pools' sizes are what is under test
    eng = family.engine(family.model(ref, CFG), **ROWS, n_pages=10, window_pages=7,
                        admission="optimistic")
    ids = [eng.submit(p, max_new_tokens=60, temperature=0.0) for p in prompts]
    while eng.pending:
        eng.step()
        assert sound(eng)
    out = {r.req_id: r.tokens for r in eng.take_finished()}
    assert eng.stats()["preemptions"] >= 1
    assert [out[i] for i in ids] == alone
    # eviction: further documents push cached pages out of both pools; the first
    # prompt then misses, is prefilled anew and decodes the same tokens
    for _ in range(3):
        ask(eng, prompt_of(rng, 60), 4)
        assert sound(eng)
    assert eng.stats()["prefix_cache"]["evictions"] > 0
    rid = eng.submit(prompts[0], max_new_tokens=60, temperature=0.0)
    assert eng.run()[rid] == alone[0]
    assert sound(eng)


def test_the_window_pool_evicts_a_cached_companion_and_the_hit_is_refused():
    rng = np.random.default_rng(6)
    # an engine of its own: the window pool's size (5 usable pages) is what is under test
    eng = family.engine(family.model(ref, CFG), page_size=PS, n_slots=1, n_pages=40,
                        window_pages=6)
    docs = [prompt_of(rng, 64) for _ in range(3)]
    answers = [ask(eng, d) for d in docs]
    st = eng.stats()
    assert st["window_pool_evictions"] > 0 and st["prefix_cache"]["evictions"] == 0
    # the first document's full pages are all there, its window pages are not
    rid = eng.submit(docs[0], max_new_tokens=3, temperature=0.0)
    assert eng.run()[rid] == answers[0]
    assert eng.allocator.hits_refused >= 1
    assert sound(eng)


@pytest.mark.parametrize("pages", [1, 2, 4], ids="{}-pages-a-step".format)
def test_the_engine_counts_the_pages_each_kind_walked(pages, monkeypatch):
    """PAGES, from the rows' positions, whatever a step of either list takes
    (``pages_a_step``: the window's list in groups too, from the row's first
    page inside the window), so the same work reads the same; and the same
    tokens come out."""
    from tests.rect_walk import derive_pages_a_step

    cfg = CFG
    derive_pages_a_step(monkeypatch, pages, jax.ShapeDtypeStruct(
        (cfg.num_kv_heads, PS, cfg.head_dim), cfg.dtype))
    # an engine of its own: it is built under the patch, which a shared program would keep
    eng = family.engine(family.model(ref, cfg), page_size=PS, n_slots=4, decode_chunk=8)
    assert eng.attn_pages_a_step == eng.stats()["attn_pages_a_step"] == pages
    prompt = prompt_of(np.random.default_rng(7), 70)
    rid = eng.submit(prompt, max_new_tokens=16, temperature=0.0)
    steps = len(eng.run()[rid])
    st = eng.stats()
    # a step of the program that began at ``starts`` walks ceil(starts / 16)
    # full pages and, of them, those from the page of starts - 31 on
    starts = [len(prompt) + (j // 8) * 8 for j in range(steps)]
    assert st["full_pages_walked_total"] == sum(-(-s // PS) for s in starts)
    assert st["window_pages_walked_total"] == sum(
        -(-s // PS) - (s - 31) // PS for s in starts)
    # one row: the engine's own list is the full layers' (their pages, every
    # tick once), in steps of ``pages``
    ticks = starts[::8]
    assert st["attn_pages_listed_total"] == sum(-(-s // PS) for s in ticks)
    assert st["attn_page_steps_total"] == sum(-(-(-(-s // PS)) // pages) for s in ticks)
    assert st["window_kv_bytes_per_token"] == 6 * 2 * 2 * 16 * 4  # 6 window layers, float32
    assert st["kv_bytes_per_token"] == 2 * 2 * 2 * 16 * 4
    assert st["moe_assign_held"] + st["moe_assign_absent"] == st["moe_assignments_total"]
    assert eng.moe_assignments.shape == (7, 8 + 2)  # the leading dense layer has none


def test_a_traced_engines_tick_span_carries_the_windows_counters(tmp_path):
    from ditl_tpu.telemetry.journal import EventJournal, merge_journals
    from ditl_tpu.telemetry.tracing import Tracer

    journal = EventJournal(str(tmp_path / "events-engine.jsonl"), source="engine")
    # an engine of its own: the tracer and its journal are the case's
    eng = family.engine(family.model(ref, CFG), **ROWS, tracer=Tracer(journal))
    rng = np.random.default_rng(8)
    ids = [eng.submit(prompt_of(rng, n), max_new_tokens=80, temperature=0.0) for n in (50, 70)]
    eng.run()
    journal.close()
    ticks = [r for r in merge_journals(str(tmp_path)) if "window_pages_walked" in r]
    assert ticks and len(ids) == 2
    st = eng.stats()
    assert sum(t["window_pages_walked"] for t in ticks) == st["window_pages_walked_total"]
    assert sum(t["full_pages_walked"] for t in ticks) == st["full_pages_walked_total"]
    assert all(0 < t["window_pages_live"] <= t["window_pages_total"] for t in ticks)
    assert sum(t["window_pages_released"] for t in ticks) <= st["window_pages_released_total"]
    assert sum(t["window_pages_freed"] for t in ticks) > 0
    assert all(t["attn_steps_walked"] > 0 for t in ticks)


@pytest.mark.parametrize("mode", MODES)
def test_every_mode_the_two_pools_cannot_carry_is_refused_by_name(mode):
    fmt = page_format(CFG, n_pages=8, page_size=PS, n_slots=2, decode_chunk=4)
    assert fmt.carries == frozenset()
    with pytest.raises(ValueError, match="two page pools"):
        fmt.refuse(mode)


@pytest.mark.parametrize("kw, match", [
    (dict(cache_mode="contiguous"), "contiguous cache"),
    (dict(speculative=True), "speculative ticks"),
    (dict(host_tier_mb=1), "host tier"),
])
def test_the_engine_refuses_at_construction(kw, match):
    with pytest.raises(ValueError, match=match):
        family.engine(family.model(ref, CFG), **{**ROWS, **kw})


def test_window_pages_belong_to_a_model_with_window_layers(engines):
    cfg = get_preset("tiny-llama")
    with pytest.raises(ValueError, match="no window attention layer"):
        family.engine(family.model(None, cfg), max_cache_len=64, window_pages=8)
    eng = engines(family.model(ref, CFG), **ROWS, prefill_chunk=0)
    with pytest.raises(ValueError, match="two page pools"):
        eng.register_prefix([1, 2, 3])
