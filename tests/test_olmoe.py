"""OLMoE on the normal path, at a small OLMoE-shaped size on the CPU in
float32, against the plain reference (``benchmarks/reference/olmoe.py``):
logits on packed rows, the loss and every gradient (router term included),
token-exactness of the expert layer, prefill then paged decode through
``ContinuousEngine``, and the experts' counters.

Tolerances. Both sides compute in float32 with the same seeded weights, so
they differ only in the order of their sums: the program sorts the (token,
choice) pairs and runs grouped matmuls, the reference runs every expert on
every token and weights by zero. 1e-4 of each tensor's largest magnitude is
~100 float32 roundings of headroom over the ~1e-6 seen, and far under what
any dropped term would move (a missing q/k scale moves logits by ~1e-1 here,
renormalising the gates by ~3e-1).
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ditl_tpu.config import ModelConfig
from ditl_tpu.models import llama
from ditl_tpu.models.moe import moe_block
from ditl_tpu.models.presets import get_preset
from ditl_tpu.train.step import loss_fn
from tests import family

ref = family.reference("olmoe")
TOL = 1e-4

# OLMoE's shape in small: as many kv heads as query heads, q/k normalisation,
# many narrow experts with several a token, gates not renormalised, untied head.
CFG = ModelConfig(
    name="olmoe-small", vocab_size=512, hidden_size=64, intermediate_size=32,
    num_layers=3, num_heads=4, num_kv_heads=4, head_dim=16, max_seq_len=128,
    rope_theta=10000.0, rms_norm_eps=1e-5, qk_norm=True,
    num_experts=16, num_experts_per_tok=4, norm_topk_prob=False,
    dtype="float32", param_dtype="float32", remat="none",
)


def _sizes(cfg):
    return ref.sizes(cfg, {})


def _packed_batch(seed=0, rows=2, seq=48):
    """Rows of three packed documents each, positions restarting at each,
    and a loss mask that drops a document's first tokens and the row's tail."""
    rng = np.random.default_rng(seed)
    ids = rng.integers(3, CFG.vocab_size, size=(rows, seq)).astype(np.int32)
    seg = np.ones_like(ids)
    pos = np.zeros_like(ids)
    mask = np.ones(ids.shape, np.float32)
    for r in range(rows):
        cuts = np.sort(rng.choice(np.arange(4, seq - 4), 2, replace=False))
        seg[r] = np.searchsorted(cuts, np.arange(seq), side="right") + 1
        starts = np.concatenate([[0], cuts])
        pos[r] = np.arange(seq) - starts[seg[r] - 1]
        mask[r, pos[r] < 2] = 0.0
        mask[r, seq - 5:] = 0.0
    return {"input_ids": jnp.asarray(ids), "positions": jnp.asarray(pos),
            "segment_ids": jnp.asarray(seg), "loss_mask": jnp.asarray(mask)}


def _close(got, want, what=""):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    scale = max(np.abs(want).max(), 1e-6)
    assert np.abs(got - want).max() <= TOL * scale, (
        f"{what}: max |diff| {np.abs(got - want).max():.3e} against a largest "
        f"magnitude of {scale:.3e}")


def test_the_preset_is_the_published_model():
    cfg = get_preset("olmoe-1b-7b")
    assert (cfg.num_layers, cfg.hidden_size, cfg.num_heads, cfg.num_kv_heads,
            cfg.head_dim) == (16, 2048, 16, 16, 128)
    assert (cfg.num_experts, cfg.num_experts_per_tok, cfg.intermediate_size) == (64, 8, 1024)
    assert cfg.qk_norm and not cfg.norm_topk_prob and not cfg.attention_bias
    assert not cfg.tie_embeddings and cfg.vocab_size == 50304 and cfg.max_seq_len == 4096
    # shapes alone: the published size is never drawn
    shapes = jax.eval_shape(lambda: llama.init_params(jax.random.key(0), cfg))
    assert sum(x.size for x in jax.tree.leaves(shapes)) == 6_919_161_856
    # Mixtral's gates still sum to 1
    assert get_preset("mixtral-8x7b").norm_topk_prob


@pytest.mark.parametrize("renormalise", [False, True])
def test_logits_match_the_reference_on_packed_rows(renormalise):
    cfg = dataclasses.replace(CFG, norm_topk_prob=renormalise)
    params = family.seeded(ref, cfg)
    b = _packed_batch()
    kw = {"positions": b["positions"], "segment_ids": b["segment_ids"]}
    got = llama.forward(params, b["input_ids"], cfg, **kw)
    want = ref.forward(params, b["input_ids"], _sizes(cfg), **kw)["logits"]
    _close(got, want, "logits")
    # the two settings are different models (or the flag would be dead)
    other = dataclasses.replace(cfg, norm_topk_prob=not renormalise)
    moved = np.abs(np.asarray(llama.forward(params, b["input_ids"], other, **kw) - got)).max()
    assert moved > 100 * TOL * np.abs(np.asarray(want)).max()


def test_a_dropped_qk_scale_would_show():
    params = family.seeded(ref, CFG)
    b = _packed_batch()
    want = ref.forward(params, b["input_ids"], _sizes(CFG))["logits"]
    ones = jax.tree.map(lambda x: x, params)
    for name in ("q_norm", "k_norm"):
        ones["layers"]["attn"][name] = jnp.ones_like(params["layers"]["attn"][name])
    got = llama.forward(ones, b["input_ids"], CFG)
    assert np.abs(np.asarray(got - want)).max() > 100 * TOL * np.abs(np.asarray(want)).max()


def test_loss_and_every_gradient_match_the_reference():
    params = family.seeded(ref, CFG)
    b = _packed_batch(seed=1)
    sizes = _sizes(CFG)
    kw = {"positions": b["positions"], "segment_ids": b["segment_ids"]}

    def want_fn(p):
        out = ref.forward(p, b["input_ids"], sizes, **kw)
        return ref.loss(out, b["input_ids"], b["loss_mask"], sizes)

    (got, metrics), got_grads = jax.value_and_grad(
        lambda p: loss_fn(p, b, CFG), has_aux=True)(params)
    want, want_grads = jax.value_and_grad(want_fn)(params)
    _close(got, want, "loss")
    # the router term is in the loss, and is what the rows will carry
    aux = ref.router_aux(ref.forward(params, b["input_ids"], sizes, **kw), b["loss_mask"])
    _close(metrics["router_aux_loss"], aux, "router_aux_loss")
    _close(got - metrics["loss"], CFG.router_aux_coef * aux, "loss - cross-entropy")
    assert float(metrics["moe_load_max_over_mean"]) >= 1.0
    flat_got = jax.tree_util.tree_leaves_with_path(got_grads)
    flat_want = jax.tree.leaves(want_grads)
    assert len(flat_got) == len(flat_want)
    for (path, g), w in zip(flat_got, flat_want):
        assert np.abs(np.asarray(w)).max() > 0, f"{path}: the reference's gradient is zero"
        _close(g, w, jax.tree_util.keystr(path))


def test_a_token_is_the_same_alone_and_among_tokens_that_choose_its_experts():
    """Token-exactness. Sixty-four copies of one token all choose the same
    four experts of sixteen: a capacity of ceil(k T / E x 1.25) = 20 rows an
    expert would drop two thirds of them."""
    moe = jax.tree.map(lambda w: w[0], family.seeded(None, CFG, 3)["layers"]["moe"])
    token = jax.random.normal(jax.random.key(4), (1, 1, CFG.hidden_size), jnp.float32)
    alone, _, counts = moe_block(moe, token, CFG)
    crowd, _, crowd_counts = moe_block(moe, jnp.tile(token, (1, 64, 1)), CFG)
    np.testing.assert_allclose(np.asarray(crowd), np.tile(np.asarray(alone), (1, 64, 1)),
                               rtol=1e-6, atol=1e-7)
    assert np.abs(np.asarray(alone)).max() > 1e-3
    assert sorted(np.asarray(counts)) == [0] * 12 + [1] * 4
    assert sorted(np.asarray(crowd_counts)) == [0] * 12 + [64] * 4
    # and among strangers
    others = jax.random.normal(jax.random.key(5), (1, 40, CFG.hidden_size), jnp.float32)
    mixed, _, _ = moe_block(moe, jnp.concatenate([others, token], axis=1), CFG)
    np.testing.assert_allclose(np.asarray(mixed[:, -1:]), np.asarray(alone),
                               rtol=1e-6, atol=1e-7)


def test_counts_see_live_tokens_only_and_the_output_does_not_change():
    moe = jax.tree.map(lambda w: w[0], family.seeded(None, CFG, 3)["layers"]["moe"])
    h = jax.random.normal(jax.random.key(6), (2, 10, CFG.hidden_size), jnp.float32)
    mask = jnp.asarray(np.arange(20).reshape(2, 10) % 3 != 0)
    out_all, aux_all, counts_all = moe_block(moe, h, CFG)
    out, aux, counts = moe_block(moe, h, CFG, token_mask=mask)
    k = CFG.num_experts_per_tok
    assert int(counts_all.sum()) == 20 * k and int(counts.sum()) == int(mask.sum()) * k
    np.testing.assert_array_equal(np.asarray(out), np.asarray(out_all))
    live_only, aux_live, counts_live = moe_block(
        moe, h.reshape(1, 20, -1)[:, np.asarray(mask).reshape(-1)], CFG)
    np.testing.assert_array_equal(np.asarray(counts), np.asarray(counts_live))
    np.testing.assert_allclose(float(aux), float(aux_live), rtol=1e-6)
    assert abs(float(aux) - float(aux_all)) > 1e-6


def test_the_tpu_kernel_and_xlas_grouped_matmul_agree(monkeypatch):
    """On one TPU chip the grouped matmuls are the megablox kernel; here it
    runs in Pallas interpret mode (steered from the test: 32 tokens x 4 = one
    128-row tile) against ``jax.lax.ragged_dot``, forward and backward. Same
    products in float32, summed in tiles: 1e-5."""
    from ditl_tpu.models import moe as moe_mod

    cfg = dataclasses.replace(CFG, hidden_size=128, intermediate_size=128)
    moe = jax.tree.map(lambda w: w[0], family.seeded(None, cfg, 8)["layers"]["moe"])
    h = jax.random.normal(jax.random.key(9), (2, 16, cfg.hidden_size), jnp.float32)

    def run():
        def f(m, x):
            out, aux, _ = moe_block(m, x, cfg)
            return (out ** 2).sum() + aux, out
        (_, out), grads = jax.value_and_grad(f, argnums=(0, 1), has_aux=True)(moe, h)
        return out, grads

    want_out, want_grads = run()
    monkeypatch.setattr(moe_mod, "_use_gmm", lambda rows, mesh: rows % 128 == 0)
    got_out, got_grads = run()
    np.testing.assert_allclose(np.asarray(got_out), np.asarray(want_out), rtol=1e-5, atol=1e-5)
    for g, w in zip(jax.tree.leaves(got_grads), jax.tree.leaves(want_grads)):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), rtol=1e-4,
                                   atol=1e-5 * float(np.abs(np.asarray(w)).max()))


def test_a_cached_forward_addresses_the_experts_inside_the_stack(monkeypatch):
    """Where the kernel runs, a serving forward pass hands it every layer's
    experts and a layer index, so that the layer loop does not copy a layer's
    experts in front of it (``experts_in_place``). Same logits and counts as
    the sliced path; interpret mode, 2 x 16 tokens x 4 = one 128-row tile."""
    from ditl_tpu.infer.cache import init_cache
    from ditl_tpu.models import moe as moe_mod

    cfg = dataclasses.replace(CFG, hidden_size=128, intermediate_size=128, head_dim=32)
    params = family.seeded(None, cfg, 12)
    ids = jnp.asarray(np.random.default_rng(12).integers(3, 500, (2, 16)), jnp.int32)
    mask = jnp.tril(jnp.ones((16, 32), bool), k=0)[None].repeat(2, 0)

    def run():
        cache = init_cache(cfg, 2, 32)
        logits, _, counts = llama.forward(
            params, ids, cfg, cache=cache, cache_index=0, attn_mask=mask, with_moe_counts=True)
        return np.asarray(logits), np.asarray(counts)

    want_logits, want_counts = run()
    seen = []
    real = moe_mod._grouped

    def spy(x, w, sizes, row_expert, cd, mesh, layer=None):
        seen.append((w.shape, layer is not None))
        return real(x, w, sizes, row_expert, cd, mesh, layer)

    monkeypatch.setattr(moe_mod, "_use_gmm", lambda rows, mesh: rows % 128 == 0)
    monkeypatch.setattr(moe_mod, "_grouped", spy)
    got_logits, got_counts = run()
    # the kernel got the whole stack (L, E, d, f), not a layer's slice
    assert seen and all(in_place and shape[:2] == (cfg.num_layers, cfg.num_experts)
                        for shape, in_place in seen)
    np.testing.assert_array_equal(got_counts, want_counts)
    np.testing.assert_allclose(got_logits, want_logits, rtol=1e-4, atol=1e-4)
    # int8 experts keep the sliced path (their scales are per layer)
    from ditl_tpu.ops.quant import quantize_weights

    assert not moe_mod.experts_in_place(quantize_weights(params)["layers"]["moe"], 128, None)


@pytest.fixture(scope="module")
def served():
    """Three prompts of uneven length through a paged engine of four slots
    (one stays dead), greedy, with the returned log-probabilities."""
    params = family.seeded(ref, CFG, 7)
    eng = family.engine((params, CFG), n_slots=4, decode_chunk=4, logprobs_k=5)
    rng = np.random.default_rng(11)
    prompts = [family.prompt_of(rng, n + 1, vocab=250) for n in (5, 21, 38)]
    new = (9, 6, 12)
    ids = [eng.submit(p, max_new_tokens=n, temperature=0.0, logprobs=5)
           for p, n in zip(prompts, new)]
    while eng.pending:
        eng.step()
    done = {r.req_id: r for r in eng.take_finished()}
    return params, eng, [(p, done[i]) for p, i in zip(prompts, ids)]


def test_prefill_then_paged_decode_matches_the_references_full_forward(served):
    params, _eng, pairs = served
    sizes = _sizes(CFG)
    for prompt, req in pairs:
        n = len(req.tokens)
        assert n >= 2 and len(req.lp_token) == n
        full = jnp.asarray([prompt + req.tokens], jnp.int32)
        logp = np.asarray(jax.nn.log_softmax(
            ref.forward(params, full, sizes)["logits"][0], axis=-1), np.float64)
        for j in range(n):
            at = len(prompt) + j - 1  # the position whose logits chose token j
            assert abs(req.lp_token[j] - logp[at, req.tokens[j]]) <= TOL * 10, (j, n)
            for i, lp in zip(req.lp_top_ids[j], req.lp_top[j]):
                assert abs(lp - logp[at, i]) <= TOL * 10
            # greedy: the served token is the reference's argmax too
            assert req.tokens[j] == int(np.argmax(logp[at]))


def test_the_engine_counts_live_rows_and_real_tokens_only(served):
    _params_, eng, pairs = served
    stats = eng.stats()
    k, layers = CFG.num_experts_per_tok, CFG.num_layers
    total = stats["moe_assignments_total"]
    assert total == int(eng.moe_assignments.sum()) and total % (k * layers) == 0
    per_layer = eng.moe_assignments.sum(axis=1)
    assert (per_layer == per_layer[0]).all()
    tokens = total // (k * layers)
    prefilled = sum(len(p) for p, _ in pairs)
    decoded = sum(len(r.tokens) for _, r in pairs)
    # every prompt token once; a decode forward for each token but, at most,
    # the last of a request. Bucket padding (16-token pages: 75 padded rows
    # here) or the dead fourth slot (4 rows a tick) would break the bound.
    assert prefilled + decoded - len(pairs) <= tokens <= prefilled + decoded
    assert 1.0 <= stats["moe_load_max_over_mean"] <= CFG.num_experts
    assert 0 < stats["moe_experts_touched_mean"] <= min(CFG.num_experts, 3 * k)
    assert eng.moe_decode_steps % eng.decode_chunk == 0 and eng.moe_decode_steps > 0
