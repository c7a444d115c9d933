"""LongCat-Flash through the serving engine (ISSUE 33): prefill then decode
through the latent page pool against the plain reference's full forward pass,
chunked prefill and prefix reuse on latent pages, the engine's counters, and
the modes that refuse a latent pool. A file of its own so that the test
runner can give it a worker of its own (tests/test_longcat.py has the model)."""

from __future__ import annotations

import jax
import numpy as np
import pytest

from ditl_tpu.data.tokenizer import ByteTokenizer
from ditl_tpu.models import llama
from tests import family
from tests.family import ask, prompt_of

ref = family.reference("longcat_flash")
PRESET = "longcat-flash"

# Both sides compute in float32 on the same weights; they differ in the order
# of their sums (a grouped matmul and a scatter-add against a masked loop,
# blocked against whole softmax, absorbed against decompressed attention):
# 1e-6 relative is what float32 leaves of that over two layers, 1e-4 gives it
# a hundred times of room and is a hundred times under any wrong term.
TOL = 1e-4

TINY = dict(vocab_size=512, hidden_size=64, intermediate_size=128, expert_ffn_hidden_size=32,
            num_layers=2, num_heads=4, num_kv_heads=4, head_dim=24, q_lora_rank=32,
            kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
            num_experts=32, zero_expert_num=16, num_experts_per_tok=4, max_seq_len=256,
            dtype="float32")
OVERRIDES = [f"{k}={v}" for k, v in TINY.items()]


# the share of the expert layer that the serving cell holds: experts 8 to 15
CFG = family.tiny(PRESET, TINY, experts_held_first=8, experts_held_count=8)


def test_paged_prefill_then_decode_matches_the_references_full_forward():
    """Prompts of 5-40 tokens on pages of 16 (a prefill of one to three pages),
    then 24 new tokens: a tick's flush after 16 steps and a page boundary in
    every row, log-probabilities against ONE uncached pass of the reference."""
    import paged_check

    verdict = paged_check.check(
        {"preset": "longcat-flash", "reference": "longcat_flash"},
        OVERRIDES + ["experts_held_first=8", "experts_held_count=8"], seed=3,
        prompt_tokens=(5, 20, 33, 40), new_tokens=24, page_size=16, rehearsal=True)
    assert verdict["served_tokens"] > 60
    assert verdict["logprob_err_over_logit_rms"] < TOL, verdict


def test_chunked_prefill_gathers_latent_pages_as_context(engines):
    """A prompt longer than the prefill chunk: later chunks gather the earlier
    ones' latent pages and decompress them; a second request with the same
    prompt finds its pages by their hashes (a page's hash never looks inside)."""
    prompt = prompt_of(np.random.default_rng(0), 71)
    outs = []
    for chunk in (0, 32):
        eng = engines(family.model(ref, CFG), prefill_chunk=chunk)
        hit_was = eng.stats()["prefix_cache"]["hit_tokens"]
        # the second finds the first one's published pages
        outs.append([ask(eng, prompt, 6) for _ in range(2)])
        assert eng.stats()["prefix_cache"]["hit_tokens"] - hit_was >= 64
    assert outs[0][0] == outs[0][1] == outs[1][0] == outs[1][1]


def test_the_engine_counts_assignments_by_kind_and_the_context_it_read():
    cfg = CFG
    tok = ByteTokenizer()
    # an engine of its own: its counters are read whole
    eng = family.engine(family.model(ref, cfg), n_slots=4, max_cache_len=64, decode_chunk=8)
    prompt = [tok.bos_id, 7, 8, 9, 10]
    rid = eng.submit(prompt, max_new_tokens=12, temperature=0.0)
    n_out = len(eng.run()[rid])
    st = eng.stats()
    assert st["moe_assign_held"] + st["moe_assign_zero"] + st["moe_assign_absent"] == \
        st["moe_assignments_total"]
    # the prompt's tokens and every decode step's one live row, 4 choices, 2 layers
    steps = n_out  # the step that emits a token computes the next one, the last one's too
    assert st["moe_assignments_total"] == (len(prompt) + steps) * 4 * cfg.num_layers
    # step j reads the prompt, the tokens before it and its own entry
    assert st["decode_ctx_tokens"] == sum(len(prompt) + j + 1 for j in range(steps))


@pytest.mark.parametrize("mode, kw", [
    ("contiguous cache", dict(cache_mode="contiguous")),
    ("speculative ticks", dict(speculative=True)),
    ("host tier", dict(host_tier_mb=1)),
    ("a mesh", dict(mesh="one")),
    ("int8 page pools", dict(kv="int8")),
])
def test_modes_that_cannot_carry_a_latent_page_refuse_by_name(mode, kw):
    kw = dict(kw)
    cfg = family.tiny(PRESET, TINY, kv_cache_dtype=kw.pop("kv", ""))
    if kw.get("mesh"):
        kw["mesh"] = jax.sharding.Mesh(np.array(jax.devices()[:1]), ("tensor",))
    # shapes alone: the engine refuses before it reads a weight
    params = jax.eval_shape(lambda: llama.init_params(jax.random.key(0), cfg))
    with pytest.raises(ValueError, match=mode):
        family.engine((params, cfg), max_cache_len=64, **kw)


def test_handoff_and_pod_serving_refuse_a_latent_pool(engines):
    from ditl_tpu.infer.podserve import PodContinuousDriver

    eng = engines(family.model(ref, CFG), prefill_chunk=0)
    with pytest.raises(ValueError, match="handoff"):
        eng.export_kv(list(range(3, 40)))
    with pytest.raises(ValueError, match="handoff"):
        eng.import_kv(b"")
    with pytest.raises(ValueError, match="pod serving"):
        PodContinuousDriver(eng)
