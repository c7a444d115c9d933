"""A stack of retention layers through the serving engine (ISSUE 56): a state
a slot and NOTHING else, no page pool. Prefill in a padded bucket then
hundreds of cached steps against the plain reference's one full pass (the
attention form: no feature map, no state), chunked prefill, slot reuse,
admission by slots alone, the counters, and every option that cannot carry
such a cache refusing by name (tests/test_retention.py has the model)."""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ditl_tpu.data.tokenizer import ByteTokenizer
from ditl_tpu.infer.engine import GenerateConfig
from ditl_tpu.models import llama
from ditl_tpu.ops import retention as ret
from tests import family

ref = family.reference("brumby")
PRESET = "brumby-14b"

# Float32 on both sides, the same weights: a prefill in chunks of 16 and then
# one cached step a token against the attention form over all tokens. The
# measured error after 200 steps is 1e-6 of the logits' rms; 1e-4 gives that a
# hundred times of room. A state kept in bfloat16 and a state lost between
# ticks are both refused below.
TOL = 1e-4

TINY = dict(vocab_size=512, hidden_size=64, intermediate_size=128, num_layers=3,
            layer_types="rrr", num_heads=4, num_kv_heads=2, head_dim=16, ret_chunk=16,
            max_seq_len=1024, dtype="float32")
OVERRIDES = [f"{k}={v}" for k, v in TINY.items()]
CONFIG = {"preset": "brumby-14b", "reference": "brumby"}


CFG = family.tiny(PRESET, TINY)
# four rows of up to 512 tokens, 16 new tokens unless a request says otherwise
ROWS = dict(n_slots=4, max_cache_len=512, gen=GenerateConfig(max_new_tokens=16))
# the same with the two likeliest tokens' log-probabilities beside the chosen one's
LOGPROBS = dict(ROWS, logprobs_k=2)


def prompt(n, seed):
    return family.prompt_of(np.random.default_rng(seed), n)


def serve(eng, prompts, **kw):
    """(tokens, chosen-token log-probabilities) of each prompt, in order."""
    rids = [eng.submit(p, temperature=0.0, **kw) for p in prompts]
    while eng.pending:
        eng.step()
    done = {r.req_id: r for r in eng.take_finished()}
    return [(done[r].tokens, done[r].lp_token) for r in rids]


def check(new_tokens=200, seed=3):
    import paged_check

    return paged_check.check(CONFIG, OVERRIDES, seed=seed, prompt_tokens=(5, 21, 40, 70),
                             new_tokens=new_tokens, page_size=16, rehearsal=True)


def close(a, b):
    (ta, la), (tb, lb) = a, b
    return ta == tb and np.allclose(la, lb, atol=2e-5)


def test_prefill_in_a_padded_bucket_then_200_cached_steps_match_one_full_pass():
    """Prompts of 5-70 tokens: every prefill ends inside a padded
    power-of-two bucket and crosses chunk boundaries, and the state has to be
    the LAST REAL token's. Then 200 tokens, each tick carrying the state on.
    Log-probabilities against ONE uncached pass of the reference."""
    verdict = check()
    assert verdict["served_tokens"] == 4 * 200
    assert verdict["logprob_err_over_logit_rms"] < TOL, verdict


def test_a_bfloat16_state_where_float32_is_stated_is_refused(monkeypatch):
    fold = ret.ret_fold
    monkeypatch.setattr(ret, "ret_fold", lambda *a: fold(*a).astype(jnp.bfloat16).astype(
        jnp.float32))
    assert check()["logprob_err_over_logit_rms"] > 3 * TOL


def test_a_tick_whose_held_tokens_are_dropped_instead_of_folded_is_refused(monkeypatch):
    """The deferred fold broken on purpose (ISSUE 57): a tick's last step
    decays the state and writes it, and the ``v (outer) phi(k)`` of the
    tick's tokens never joins it. Every step INSIDE a tick still reads its
    own tick's tokens from beside the state, so only a comparison that runs
    across ticks sees it: the 200 steps do."""
    fold = ret.ret_fold
    monkeypatch.setattr(ret, "ret_fold", lambda big, pks, vc, decay: fold(
        big, pks, jnp.zeros_like(vc), decay))
    verdict = check()
    assert not verdict["ok"] and verdict["logprob_err_over_logit_rms"] > 100 * TOL


def test_a_state_that_is_not_carried_between_ticks_is_refused(monkeypatch):
    """The timed path broken on purpose: every tick starts from the state the
    prefill seated (the tick's own updates are dropped)."""
    def broken(build):
        def builder(self, *key):
            program = build(self, *key)

            def run(params, pools, *rest):
                kept = {k: jnp.copy(v) for k, v in pools.items()}
                out, *others = program(params, pools, *rest)
                return ({**out, **kept}, *others)

            return run

        return builder

    family.patch_builder(monkeypatch, "paged_decode", broken)
    verdict = check(new_tokens=48)
    assert not verdict["ok"] and verdict["logprob_err_over_logit_rms"] > 100 * TOL


def test_chunked_prefill_carries_the_state_between_chunks(engines):
    """A 70-token prompt in chunks of 32 (two whole chunks and a tail of 6 in
    a padded bucket), decode ticks of other slots in between."""
    prompts = [prompt(70, 1), prompt(9, 2), prompt(45, 3)]
    model = family.model(ref, CFG)
    whole = serve(engines(model, **LOGPROBS), prompts, max_new_tokens=24, logprobs=2)
    chunked = serve(engines(model, **LOGPROBS, prefill_chunk=32), prompts,
                    max_new_tokens=24, logprobs=2)
    assert all(close(a, b) for a, b in zip(whole, chunked))


def test_a_slot_reseated_starts_from_zero_state():
    """One slot: the second request sits where the first one's state was,
    which is never cleared and never read, and answers as it did alone in a
    slot nothing had sat in."""
    a, b = prompt(30, 4), prompt(12, 5)
    # an engine of its own: ``alone`` is held to a slot that no request has used
    eng = family.engine(family.model(ref, CFG), **dict(LOGPROBS, n_slots=1))
    alone = serve(eng, [b], max_new_tokens=20, logprobs=2)
    assert float(jnp.abs(eng.cache["ret"]).max()) > 0  # the last tenant's, left behind
    both = serve(eng, [a, b], max_new_tokens=20, logprobs=2)
    assert close(both[1], alone[0])


def test_no_pool_is_built_and_admission_goes_by_slots_alone():
    """Six requests on two slots, each far longer than any page count could
    cover were pages asked for: the tree holds the state and nothing else,
    no request takes a page, and whoever waits waits for a SLOT."""
    # an engine of its own: two slots for six requests is what is under test
    eng = family.engine(family.model(ref, CFG), **dict(ROWS, n_slots=2, max_cache_len=256))
    assert set(eng.cache) == {"ret", "retz"} and eng.page_format.page_bytes == 0
    assert not eng.page_format.pooled and eng.page_format.pages_for(10_000) == 0
    rids = [eng.submit(prompt(40 + 10 * i, 20 + i), temperature=0.0, max_new_tokens=60)
            for i in range(6)]
    busiest = 0
    while eng.pending:
        eng.step()
        st = eng.stats()
        busiest = max(busiest, st["slots_busy"])
        assert st["pages_total"] == 0 and all(not p for p in eng._slot_pages)
        assert st["slots_busy"] + st["queue_depth"] <= 6
    done = {r.req_id: r for r in eng.take_finished()}
    assert busiest == 2 and all(len(done[r].tokens) == 60 for r in rids)
    assert eng.preemptions == 0 and eng.stats()["kv_bytes_per_token"] == 0


def test_a_row_is_bounded_by_max_cache_len_and_a_page_count_is_refused():
    from ditl_tpu.infer.continuous import BadRequestError

    model = family.model(ref, CFG)
    # an engine of its own: the cap is what is under test, and nothing runs through it
    eng = family.engine(model, **dict(ROWS, max_cache_len=128))
    with pytest.raises(BadRequestError, match="cache cap 128"):
        eng.submit(prompt(100, 1), max_new_tokens=64)
    with pytest.raises(ValueError, match="n_pages sizes a page pool"):
        family.engine(model, n_pages=64)


def test_requests_that_share_a_prefix_answer_as_their_uncached_runs_do(engines):
    """48 shared tokens, then each its own tail: nothing is published or
    matched, every prompt is prefilled from its first token."""
    shared = prompt(48, 8)
    prompts = [shared + prompt(10, 9)[1:], shared + prompt(20, 10)[1:]]
    model = family.model(ref, CFG)
    eng = engines(model, **LOGPROBS)
    first = serve(eng, prompts[:1], max_new_tokens=20, logprobs=2)
    second = serve(eng, prompts[1:], max_new_tokens=20, logprobs=2)  # after the first
    # engines of their own: "uncached" is an engine that has never seen the shared tokens
    fresh = [serve(family.engine(model, **LOGPROBS), [p], max_new_tokens=20, logprobs=2)[0]
             for p in prompts]
    assert close(first[0], fresh[0]) and close(second[0], fresh[1])
    assert eng.stats()["prefix_cache"]["hit_tokens"] == 0


def test_the_counters_say_what_ran(tmp_path):
    from ditl_tpu.telemetry.journal import EventJournal, merge_journals
    from ditl_tpu.telemetry.tracing import Tracer

    journal = EventJournal(str(tmp_path / "events-engine.jsonl"), source="engine")
    # an engine of its own: the tracer and its journal are the case's, its counters read whole
    eng = family.engine(family.model(ref, CFG), **dict(ROWS, n_slots=3),
                        tracer=Tracer(journal))
    outs = serve(eng, [prompt(9, 11), prompt(20, 12)], max_new_tokens=13)
    st = eng.stats()
    # the step that emits a token computes the next one, the last one's too
    assert st["ssm_row_steps_total"] == sum(len(t) for t, _ in outs) == 26
    # a row's state is READ every step and WRITTEN once a tick of 4, by the
    # tick's last step if the row is live there: 13 tokens are three whole
    # ticks and a step, and the row that ends at that step is not folded
    assert st["decode_chunk"] == 4 and st["ret_row_folds_total"] == 2 * 3
    journal.close()
    ticks = [e for e in merge_journals(str(tmp_path)) if e.get("name") == "engine.tick"
             and "ssm_row_steps" in e]
    assert ticks and all("ret_row_folds" in e for e in ticks)
    assert sum(e["ssm_row_steps"] for e in ticks) == 26
    assert sum(e["ret_row_folds"] for e in ticks) == 6
    assert st["ssm_state_bytes_per_slot"] == 3 * 2 * 144 * (16 + 1) * 4
    assert st["ssm_state_bytes_resident"] == 3 * st["ssm_state_bytes_per_slot"]
    assert st["ssm_slots_seated"] == 0
    assert st["attn_pages_a_step"] == 0 and st["attn_pages_listed_total"] == 0
    assert eng.cache["ret"].shape[:3] == (3, 3, 2) and eng.cache["ret"].dtype == jnp.float32


@pytest.mark.parametrize("chunk", [1, 4, 16])
def test_a_ticks_tails_are_its_held_tokens_and_its_flush_drops_them(chunk):
    """The format alone (ISSUE 57): room for a tick's ``k``, ``v`` and
    ``log g`` a layer a step a row a kv head, float32 zeros, never part of the
    donated tree; a tick of one step holds nothing; every step's live rows
    are counted as read, the last step's as written."""
    from ditl_tpu.infer.page_format import StateSlots

    fmt = StateSlots(CFG, n_pages=0, page_size=16, n_slots=3, decode_chunk=chunk)
    tails = fmt.tails0(3)
    lead = (3, chunk, 3, 2)
    assert {k: v.shape for k, v in tails.items()} == (
        {} if chunk == 1 else {"hk": (*lead, 16), "hv": (*lead, 16), "hl": lead})
    assert all(v.dtype == jnp.float32 and not v.any() for v in tails.values())
    const, carried = fmt.split(fmt.fresh())
    assert not const and set(carried) == {"ret", "retz"}
    assert set(fmt.flush(const, {**tails, **carried}, None, None, None)) == {"ret", "retz"}
    acc = dict.fromkeys(fmt.counters, jnp.int32(0))
    for t in range(chunk):
        acc = fmt.count(acc, t=jnp.int32(t), alive=jnp.asarray([True, False, True]),
                        lengths=None, starts=None, meta={}, counted={})
    assert (int(acc["ssm_row_steps"]), int(acc["ret_row_folds"])) == (2 * chunk, 2)


@pytest.mark.parametrize("mode, kw", [
    ("contiguous cache", dict(cache_mode="contiguous")),
    ("speculative ticks", dict(speculative=True)),
    ("host tier", dict(host_tier_mb=1)),
    ("a mesh", dict(mesh="one")),
    ("int8 page pools", dict(kv="int8")),
    ("LoRA adapters", dict(lora=True)),
])
def test_options_that_cannot_carry_a_state_a_slot_refuse_by_name(mode, kw):
    kw = dict(kw)
    cfg = family.tiny(PRESET, TINY, kv_cache_dtype=kw.pop("kv", ""))
    if kw.get("mesh"):
        kw["mesh"] = jax.sharding.Mesh(np.array(jax.devices()[:1]), ("tensor",))
    # shapes alone: the engine refuses before it reads a weight
    params = jax.eval_shape(lambda: llama.init_params(jax.random.key(0), cfg))
    if kw.pop("lora", False):
        params = {**params, "layers": {**params["layers"], "lora": {}}}
    with pytest.raises(ValueError, match=mode):
        family.engine((params, cfg), max_cache_len=64, **kw)


def test_handoff_prefix_registration_pod_serving_and_the_lock_step_engine_refuse(engines):
    from ditl_tpu.infer.engine import Generator
    from ditl_tpu.infer.podserve import PodContinuousDriver

    params, cfg = family.model(ref, CFG)
    tok = ByteTokenizer()
    eng = engines((params, cfg), **LOGPROBS)
    with pytest.raises(ValueError, match="handoff"):
        eng.export_kv(list(range(3, 40)))
    with pytest.raises(ValueError, match="handoff"):
        eng.import_kv(b"")
    with pytest.raises(ValueError, match="register_prefix"):
        eng.register_prefix(list(range(3, 40)))
    with pytest.raises(ValueError, match="pod serving"):
        PodContinuousDriver(eng)
    with pytest.raises(ValueError, match="lock-step engine"):
        Generator(params, cfg, tok).generate_tokens([[1, 5, 6]], GenerateConfig(max_new_tokens=4))
    with pytest.raises(ValueError, match="recurrent state"):
        llama.forward(params, jnp.zeros((1, 4), jnp.int32), cfg, cache={},
                      cache_index=jnp.int32(0))
