"""A state-space stack through the serving engine (ISSUE 41): a recurrent
state a slot beside the page pool. Prefill in a padded bucket then hundreds of
cached steps against the plain reference's one full pass, chunked prefill,
slot reuse, preempt-and-resume, prompts that share a prefix, the counters, and
every option that cannot carry a recurrent state refusing by name. A file of
its own so that the test runner can give it a worker of its own
(tests/test_granite_hybrid.py has the model)."""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ditl_tpu.data.tokenizer import ByteTokenizer
from ditl_tpu.infer.engine import GenerateConfig
from ditl_tpu.models import llama
from ditl_tpu.ops import ssd
from tests import family

ref = family.reference("granite_hybrid")
PRESET = "granite-4.0-h-micro"

# Float32 on both sides, the same weights: a prefill in chunks of 16 and then
# one cached step a token against a scan over all tokens. The measured error
# after 304 steps is 1.4e-5 of the logits' rms (sums in another order, 300
# times over); 1e-4 gives that seven times of room. A state kept in bfloat16
# (2^-9 a rounding, every step) reads 2.4e-3, twenty-four times over, and a
# state lost between ticks order 1: both are refused below.
TOL = 1e-4

TINY = dict(vocab_size=512, hidden_size=32, intermediate_size=64, num_layers=6, num_heads=4,
            num_kv_heads=2, head_dim=8, layer_types="mmamma", ssm_heads=4, ssm_head_dim=16,
            ssm_state=8, ssm_chunk=16, attention_multiplier=1 / 16, max_seq_len=1024,
            dtype="float32")
OVERRIDES = [f"{k}={v}" for k, v in TINY.items()]
CONFIG = {"preset": "granite-4.0-h-micro", "reference": "granite_hybrid"}


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


def check(new_tokens=304, seed=3):
    import paged_check

    return paged_check.check(CONFIG, OVERRIDES, seed=seed, prompt_tokens=(5, 21, 40, 70),
                             new_tokens=new_tokens, page_size=16, rehearsal=True)


def test_prefill_in_a_padded_bucket_then_300_cached_steps_match_one_full_pass():
    """Prompts of 5-70 tokens on pages of 16: every prefill ends inside a
    padded power-of-two bucket, and the state has to be the LAST REAL
    token's. Then 304 tokens: 19 ticks of 16 steps, each carrying the state
    on. Log-probabilities against ONE uncached pass of the reference."""
    verdict = check()
    assert verdict["served_tokens"] == 4 * 304
    assert verdict["logprob_err_over_logit_rms"] < TOL, verdict


def test_a_bfloat16_state_where_float32_is_stated_is_refused(monkeypatch):
    step = ssd.ssd_step

    def rounded(state, *a):
        y, new = step(state, *a)
        return y, new.astype(jnp.bfloat16).astype(jnp.float32)

    monkeypatch.setattr(ssd, "ssd_step", rounded)
    assert check()["logprob_err_over_logit_rms"] > 3 * TOL


def test_a_state_that_is_not_carried_between_ticks_is_refused(monkeypatch):
    """The timed path broken on purpose: every tick starts from the state the
    prefill seated (the tick's own updates are dropped)."""
    def broken(build):
        def builder(self, *key):
            program = build(self, *key)

            def run(params, pools, *rest):
                kept = {k: jnp.copy(pools[k]) for k in ("ssm", "conv")}
                out, *others = program(params, pools, *rest)
                return ({**out, **kept}, *others)

            return run

        return builder

    family.patch_builder(monkeypatch, "paged_decode", broken)
    verdict = check(new_tokens=48)
    assert not verdict["ok"] and verdict["logprob_err_over_logit_rms"] > 100 * TOL


def close(a, b):
    """Two runs of one request: the same tokens, log-probabilities within
    float32's reordering."""
    (ta, la), (tb, lb) = a, b
    return ta == tb and np.allclose(la, lb, atol=2e-5)


def test_chunked_prefill_carries_the_state_between_chunks(engines):
    """A 70-token prompt in chunks of 32 (two whole chunks and a tail of 6
    in a padded bucket), decode ticks of other slots in between."""
    prompts = [prompt(70, 1), prompt(9, 2), prompt(45, 3)]
    model = family.model(ref, CFG)
    whole = serve(engines(model, **LOGPROBS), prompts, max_new_tokens=24, logprobs=2)
    chunked = serve(engines(model, **LOGPROBS, prefill_chunk=32), prompts,
                    max_new_tokens=24, logprobs=2)
    assert all(close(a, b) for a, b in zip(whole, chunked))


def test_a_slot_reused_after_release_carries_nothing_over():
    """One slot: the second request sits where the first one's state was, and
    answers as it did alone in a slot nothing had sat in."""
    a, b = prompt(30, 4), prompt(12, 5)
    # an engine of its own: ``alone`` is held to a slot that no request has used
    eng = family.engine(family.model(ref, CFG), **dict(LOGPROBS, n_slots=1))
    alone = serve(eng, [b], max_new_tokens=20, logprobs=2)
    both = serve(eng, [a, b], max_new_tokens=20, logprobs=2)
    assert close(both[1], alone[0])


def test_preempt_and_resume_equals_an_uninterrupted_run(engines):
    """Optimistic admission on a pool too small for both: the younger
    request is preempted, its state dropped, and its resume prefills prompt
    and answer so far again from token 0."""
    a, b = prompt(17, 6), prompt(17, 7)
    kw = dict(max_new_tokens=96, logprobs=2)
    model = family.model(ref, CFG)
    solo = serve(engines(model, **LOGPROBS), [a, b], **kw)  # a pool with room for both
    # an engine of its own: the pool's size is what is under test
    eng = family.engine(model, **LOGPROBS, n_pages=10, admission="optimistic")
    res = serve(eng, [a, b], **kw)
    assert eng.preemptions >= 1 and eng.resume_prefill_tokens > 17
    assert all(close(x, y) for x, y in zip(res, solo))


def test_requests_that_share_a_prefix_answer_as_their_uncached_runs_do(engines):
    """48 shared tokens (three whole pages), then each its own tail: no page
    is published or matched, every prompt is prefilled from its first token."""
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
    assert eng.stats()["pages_cached_evictable"] == 0


def test_the_counters_say_what_ran():
    # an engine of its own: its counters are read whole and its slot count is in them
    eng = family.engine(family.model(ref, CFG), **dict(ROWS, n_slots=3))
    outs = serve(eng, [prompt(9, 11), prompt(20, 12)], max_new_tokens=13)
    st = eng.stats()
    # the step that emits a token computes the next one, the last one's too
    assert st["ssm_row_steps_total"] == sum(len(t) for t, _ in outs) == 26
    assert st["ssm_state_bytes_per_slot"] == 4 * (4 * 16 * 8 * 4 + 3 * (64 + 16) * 4)
    assert st["ssm_state_bytes_resident"] == 3 * st["ssm_state_bytes_per_slot"]
    assert st["ssm_slots_seated"] == 0
    assert eng.cache["kp"].shape[0] == 2  # pages for the attention layers alone
    assert eng.cache["ssm"].shape[:2] == (4, 3) and eng.cache["ssm"].dtype == jnp.float32


@pytest.mark.parametrize("mode, kw", [
    ("contiguous cache", dict(cache_mode="contiguous")),
    ("speculative ticks", dict(speculative=True)),
    ("host tier", dict(host_tier_mb=1)),
    ("a mesh", dict(mesh="one")),
    ("int8 page pools", dict(kv="int8")),
    ("LoRA adapters", dict(lora=True)),
])
def test_options_that_cannot_carry_a_recurrent_state_refuse_by_name(mode, kw):
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
        llama.forward(params, jnp.zeros((1, 4), jnp.int32), cfg,
                      cache={"k": jnp.zeros((2, 1, 8, 2, 8)), "v": jnp.zeros((2, 1, 8, 2, 8))},
                      cache_index=jnp.int32(0))
