"""DeepSeek-V3.2 through the serving engine (ISSUE 44): prefill in chunks and
paged decode through the latent pool AND the index-key pool against the plain
reference's full forward pass, a prompt that shares a cached prefix, the index
pool's pages published, matched, evicted and preempted with their latent
pages, the engine's counters, and the modes that refuse. ``index_topk`` is
smaller than every context here, so the selection does real work. A file of
its own so that the test runner can give it a worker of its own
(tests/test_deepseek.py has the model)."""

from __future__ import annotations

import jax
import numpy as np
import pytest

from ditl_tpu.data.tokenizer import ByteTokenizer
from ditl_tpu.models import llama
from tests import family
from tests.family import ask, prompt_of

ref = family.reference("deepseek_v32")
PRESET = "deepseek-v3.2"

# float32 on both sides, sums in another order (tests/test_deepseek.py): 1e-6
# is what that leaves, 1e-4 a hundred times of room and a hundred times under
# a wrong page, position or selected set.
TOL = 1e-4

TINY = dict(num_layers=3, first_k_dense_replace=1, vocab_size=512, hidden_size=64,
            intermediate_size=128, expert_ffn_hidden_size=32, num_heads=4, num_kv_heads=4,
            head_dim=24, q_lora_rank=32, kv_lora_rank=32, qk_nope_head_dim=16,
            qk_rope_head_dim=8, v_head_dim=16, index_n_heads=4, index_head_dim=16,
            index_topk=16, num_experts=32, num_experts_per_tok=4, n_group=4, topk_group=2,
            experts_held_first=0, experts_held_count=8, max_seq_len=512,
            rope_yarn_original_max_len=64, dtype="float32", param_dtype="float32")
OVERRIDES = [f"{k}={v}" for k, v in TINY.items()]
CONFIG = {"preset": "deepseek-v3.2", "reference": "deepseek_v32"}


CFG = family.tiny(PRESET, TINY)


def test_paged_prefill_then_decode_through_both_pools_matches_the_reference():
    """Prompts of 5-40 tokens on pages of 16, then 24 new tokens (a flush and a
    page boundary in every row), 16 selected of up to 64: log-probabilities
    against ONE uncached pass of the reference."""
    import paged_check

    verdict = paged_check.check(CONFIG, OVERRIDES, seed=3, prompt_tokens=(5, 20, 33, 40),
                                new_tokens=24, page_size=16, rehearsal=True)
    assert verdict["served_tokens"] > 60
    assert verdict["logprob_err_over_logit_rms"] < TOL, verdict


def test_a_document_prefilled_in_chunks_then_a_question_on_its_cached_prefix():
    """``benchmarks/dsa_check.py`` at a tiny size: 96 tokens in three chunks of
    32 (later chunks gather both pools' pages), a question that hits the 96
    cached tokens, 10 decoded tokens; the served log-probabilities against
    the reference handed the sets the ENGINE's programs chose (tapped from its
    prefill chunks and decode steps) and against the reference's own pass,
    and both overlaps of the selected sets, layer by layer."""
    import dsa_check

    verdict = dsa_check.check(CONFIG, OVERRIDES, seed=3, doc_tokens=96, question_tokens=7,
                              new_tokens=10, page_size=16, prefill_chunk=32, rehearsal=True)
    assert verdict["ok"], verdict
    assert verdict["prefix_hit_tokens"] == 96 and verdict["selected_per_query_max"] == 16
    # every query of document, question and answer reported once, by the
    # chunk or the step that served it, with exactly min(16, t + 1) entries
    assert verdict["every_query_tapped_once_with_min_k_entries"]
    assert verdict["queries_that_chose"] == 96
    assert verdict["logprob_err_over_logit_rms"] < TOL
    assert verdict["logprob_err_given_the_engines_sets"] < TOL
    for name in ("selected_overlap", "selected_overlap_same_stream"):
        assert len(verdict[name]) == 3 and min(verdict[name]) > 0.999, name
    assert max(verdict["score_err_from_the_stream"]) < TOL


def test_the_tap_is_off_in_serving_and_traces_nothing():
    from ditl_tpu.models import dsa

    assert dsa.TAP is None
    ids = np.zeros((1, 40), np.int32)
    text = jax.jit(lambda p: llama.forward(p, ids, CFG)).lower(
        family.seeded(ref, CFG)).as_text()
    assert "callback" not in text


def test_chunked_and_whole_prefill_and_a_prefix_hit_give_one_answer(engines):
    prompt = prompt_of(np.random.default_rng(0), 71)
    outs = []
    for chunk in (0, 32):
        eng = engines(family.model(ref, CFG), prefill_chunk=chunk)
        hit_was = eng.stats()["prefix_cache"]["hit_tokens"]
        # the second finds the first one's published pages
        outs.append([ask(eng, prompt, 6) for _ in range(2)])
        assert eng.stats()["prefix_cache"]["hit_tokens"] - hit_was >= 64
    assert outs[0][0] == outs[0][1] == outs[1][0] == outs[1][1]


def test_the_index_pool_lives_under_the_latent_pools_page_table():
    """One page id names a latent page and its index-key page: what is
    published is found again with both, what is evicted loses both, and the
    page manager's bytes count both."""
    cfg = CFG
    # an engine of its own: the pool's size (9 usable pages of 16 tokens) is what is under test
    eng = family.engine(family.model(ref, cfg), n_pages=10)
    assert set(eng.cache) == {"cp", "ip"}
    assert eng.cache["cp"].shape == (3, 10, 16, 128) and eng.cache["ip"].shape == (3, 10, 16, 16)
    assert eng.page_bytes == 3 * 16 * (128 + 16) * 4  # float32 here
    assert eng.index_pool_bytes == 3 * 10 * 16 * 16 * 4
    rng = np.random.default_rng(1)
    docs = [prompt_of(rng, 49) for _ in range(3)]
    first = {}
    for i, doc in enumerate(docs[:2]):
        rid = eng.submit(doc, max_new_tokens=4, temperature=0.0)
        first[i] = eng.run()[rid]
    ip = np.asarray(eng.cache["ip"])
    cp = np.asarray(eng.cache["cp"])
    written = np.abs(cp).sum(axis=(0, 2, 3)) > 0  # pages some latent entry was written to
    assert (written == (np.abs(ip).sum(axis=(0, 2, 3)) > 0)).all() and written.sum() >= 6
    # matched: the same prompt again hits its pages and decodes the same tokens
    rid = eng.submit(docs[0], max_new_tokens=4, temperature=0.0)
    assert eng.run()[rid] == first[0]
    assert eng.stats()["prefix_cache"]["hit_tokens"] >= 48
    # evicted: a third document pushes cached pages out; the first document
    # then misses, is prefilled anew into other pages of BOTH pools, and
    # still decodes the same tokens
    for doc in (docs[2], docs[1], docs[2]):
        rid = eng.submit(doc, max_new_tokens=4, temperature=0.0)
        eng.run()
    assert eng.stats()["prefix_cache"]["evictions"] > 0
    rid = eng.submit(docs[0], max_new_tokens=4, temperature=0.0)
    assert eng.run()[rid] == first[0]


def test_a_preempted_row_comes_back_with_both_its_pages(engines):
    """Two long answers in a pool too small for both: one row is preempted,
    its pages given up, and recomputed later; both answers equal what each
    gets alone."""
    rng = np.random.default_rng(2)
    prompts = [prompt_of(rng, 31) for _ in range(2)]
    roomy = engines(family.model(ref, CFG), prefill_chunk=0)
    alone = [ask(roomy, p, 40) for p in prompts]
    # an engine of its own: the pool's size is what is under test
    eng = family.engine(family.model(ref, CFG), n_pages=8, admission="optimistic")
    ids = [eng.submit(p, max_new_tokens=40, temperature=0.0) for p in prompts]
    out = eng.run()
    assert eng.stats()["preemptions"] >= 1
    assert [out[i] for i in ids] == alone


def test_the_engine_counts_the_context_it_scored_and_the_entries_it_selected():
    cfg = CFG
    # an engine of its own: its counters are read whole, and nothing else asks for these options
    eng = family.engine(family.model(ref, cfg), n_slots=4, max_cache_len=64, decode_chunk=8)
    tok = ByteTokenizer()
    prompt = [tok.bos_id] + list(range(7, 27))  # 21 tokens: over index_topk at once
    rid = eng.submit(prompt, max_new_tokens=12, temperature=0.0)
    steps = len(eng.run()[rid])
    st = eng.stats()
    ctx = sum(len(prompt) + j + 1 for j in range(steps))
    assert st["decode_ctx_tokens"] == ctx
    assert st["dsa_ctx_tokens"] == ctx * cfg.num_layers
    assert st["dsa_selected_tokens"] == steps * cfg.index_topk * cfg.num_layers
    assert st["index_pool_bytes"] == eng.index_pool_bytes > 0
    assert st["moe_assign_held"] + st["moe_assign_absent"] == st["moe_assignments_total"]
    # experts sit in the two expert layers only: the leading dense layer has none
    assert st["moe_assignments_total"] == (len(prompt) + steps) * 4 * 2
    assert eng.moe_assignments.shape == (2, 8 + 2)


def test_a_traced_engines_tick_span_counts_the_index_pages_walked(tmp_path):
    """``dsa_index_pages`` of an ``engine.tick`` span: the flushed pages of
    the rows live in each step of the tick's program, in every layer, which
    is what the index walk fetches (``ops/dsa_index.py``; here, off the TPU,
    the gather stands in and the count is the program's own arithmetic)."""
    from ditl_tpu.telemetry.journal import EventJournal, merge_journals
    from ditl_tpu.telemetry.tracing import Tracer

    cfg = CFG
    journal = EventJournal(str(tmp_path / "events-engine.jsonl"), source="engine")
    # an engine of its own: the tracer and its journal are the case's
    eng = family.engine(family.model(ref, cfg), max_cache_len=96, tracer=Tracer(journal))
    tok = ByteTokenizer()
    prompts = [[tok.bos_id] + list(range(7, 7 + n)) for n in (20, 36)]  # 21 and 37 tokens
    ids = [eng.submit(p, max_new_tokens=12, temperature=0.0) for p in prompts]
    out = eng.run()
    journal.close()
    ticks = [r for r in merge_journals(str(tmp_path)) if "dsa_index_pages" in r]
    chunk, ps, layers = eng.decode_chunk, eng.page_size, cfg.num_layers
    # both rows decode through the first program: 2 and 3 pages, every step
    assert ticks[0]["dsa_index_pages"] == (2 + 3) * chunk * layers
    # a row's step j runs in its program j // chunk, whose starts are the
    # tokens it held when that program began
    want = sum(-(-(len(p) + j // chunk * chunk) // ps)
               for p, i in zip(prompts, ids) for j in range(len(out[i]))) * layers
    assert sum(t["dsa_index_pages"] for t in ticks) == want
    assert all(t["dsa_index_pages"] * ps >= t["dsa_ctx_tokens"] - chunk * 2 * layers * chunk
               for t in ticks)


@pytest.mark.parametrize("mode, kw", [
    ("contiguous cache", dict(cache_mode="contiguous")),
    ("speculative ticks", dict(speculative=True)),
    ("host tier", dict(host_tier_mb=1)),
    ("a mesh", dict(mesh="one")),
    ("int8 page pools", dict(kv="int8")),
])
def test_modes_that_cannot_carry_a_latent_page_refuse_the_index_pool_in_the_same_words(mode, kw):
    kw = dict(kw)
    cfg = family.tiny(PRESET, TINY, kv_cache_dtype=kw.pop("kv", ""))
    if kw.get("mesh"):
        kw["mesh"] = jax.sharding.Mesh(np.array(jax.devices()[:1]), ("tensor",))
    # shapes alone: the engine refuses before it reads a weight
    params = jax.eval_shape(lambda: llama.init_params(jax.random.key(0), cfg))
    with pytest.raises(ValueError, match=mode) as e:
        family.engine((params, cfg), max_cache_len=64, **kw)
    assert "latent page pool" in str(e.value)


def test_handoff_and_pod_serving_refuse_both_pools(engines):
    from ditl_tpu.infer.podserve import PodContinuousDriver

    eng = engines(family.model(ref, CFG), prefill_chunk=0)
    with pytest.raises(ValueError, match="handoff"):
        eng.export_kv(list(range(3, 40)))
    with pytest.raises(ValueError, match="handoff"):
        eng.import_kv(b"")
    with pytest.raises(ValueError, match="pod serving"):
        PodContinuousDriver(eng)
