"""Kanana-2-30B-A3B on the TRAINING path: the single pre-norm latent block
without its three extras (no query latent, no YaRN, no indexer; models/dsa.py)
decompressed through the flash kernels at two widths, a held share of a
sigmoid-routed expert layer WITH a backward pass (models/moe.py), a router
bias that is a buffer, a loss without an auxiliary term; against the plain
reference ``benchmarks/reference/kanana2.py`` on seeded weights.
"""

from __future__ import annotations

import dataclasses
import json
import math
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ditl_tpu.config import DataConfig, ModelConfig, TrainConfig
from ditl_tpu.models import llama
from ditl_tpu.models import moe as moe_mod
from ditl_tpu.models.presets import get_preset
from ditl_tpu.ops.attention import _xla_attention
from ditl_tpu.ops.flash_attention import flash_attention, supports
from ditl_tpu.train.step import loss_fn, moe_metric_names
from tests import family

ref = family.reference("kanana2")
PRESET = "kanana-2-30b-a3b"

TINY = dict(num_layers=2, first_k_dense_replace=1, vocab_size=512, hidden_size=64,
            intermediate_size=128, expert_ffn_hidden_size=32, num_heads=4, num_kv_heads=4,
            head_dim=24, kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8,
            v_head_dim=16, num_experts=32, num_experts_per_tok=4, experts_held_first=8,
            experts_held_count=8, max_seq_len=512, dtype="float32", param_dtype="float32")
CFG = family.tiny(PRESET, TINY)
# heads the flash kernels tile on the CPU: 128 wide in q and k (64 + 64 rotary), 64 in v
FLASH = dict(num_heads=2, num_kv_heads=2, head_dim=128, qk_nope_head_dim=64,
             qk_rope_head_dim=64, v_head_dim=64, attention_impl="flash", loss_impl="fused")


def rel(got, want):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return float(np.sqrt(np.mean((got - want) ** 2)) / (np.sqrt(np.mean(want ** 2)) + 1e-30))


def packed_batch(cfg, rows=2, s=128, seed=1):
    """Three documents of uneven length a row, positions restarting at each."""
    ids = jax.random.randint(jax.random.key(seed), (rows, s), 3, cfg.vocab_size)
    seg, pos = np.ones((rows, s), np.int32), np.tile(np.arange(s, dtype=np.int32), (rows, 1))
    for r in range(rows):
        for j, cut in enumerate((50 + 7 * r, 90)):
            seg[r, cut:] = j + 2
            pos[r, cut:] = np.arange(s - cut)
    return {"input_ids": ids, "positions": jnp.asarray(pos), "segment_ids": jnp.asarray(seg),
            "loss_mask": jnp.ones((rows, s), jnp.float32)}


def names(path):
    return "/".join(str(getattr(k, "key", k)) for k in path)


# float32 on the same weights: the two sides differ in the order of their sums
# (flash blocks against a masked softmax, a grouped matmul and a scatter-add
# against a masked loop over experts, a blockwise loss against a log-softmax):
# 1e-6 relative is what float32 leaves of that over three layers; 1e-4 gives it
# a hundred times of room and is far under any wrong term. bfloat16 at these
# TOY widths (hidden 64) rounds every product by 0.4% and flips a choice of a
# router whose 32 seeded scores lie a few thousandths apart in one token of
# fifty: the logits land 3-4% off and the held experts' gradient, which only
# the flipped tokens move, 10-20%; the bounds say "the same function", the
# float32 cases say "the same arithmetic" (the published widths are held on
# the chip: benchmarks/train_grad_check.py).
CASES = {
    "float32-xla-naive": (dict(), 1e-4, 1e-4, 1e-4),
    "float32-flash-fused": (FLASH, 1e-4, 1e-4, 1e-4),
    "bfloat16-flash-fused": ({**FLASH, "dtype": "bfloat16"}, 8e-2, 5e-3, 4e-1),
}


@pytest.mark.parametrize("case", CASES)
def test_the_program_matches_the_reference_on_logits_loss_and_every_gradient_leaf(case):
    kw, logits_tol, loss_tol, grad_tol = CASES[case]
    cfg = family.tiny(PRESET, TINY, **kw)
    params, batch = family.seeded(ref, cfg), packed_batch(cfg)
    sizes = ref.sizes(cfg, {})
    ids, pos, seg = batch["input_ids"], batch["positions"], batch["segment_ids"]
    got = jax.jit(lambda p: llama.forward(p, ids, cfg, positions=pos, segment_ids=seg))(params)
    want = ref.forward(params, ids, sizes, positions=pos, segment_ids=seg)["logits"]
    assert rel(got, want) < logits_tol
    (loss, metrics), grads = jax.jit(jax.value_and_grad(
        lambda p: loss_fn(p, batch, cfg), has_aux=True))(params)
    want_loss, want_grads = jax.jit(jax.value_and_grad(lambda p: ref.loss(
        ref.forward(p, ids, sizes, positions=pos, segment_ids=seg), ids,
        batch["loss_mask"], sizes)))(params)
    assert abs(float(loss) - float(want_loss)) / float(want_loss) < loss_tol
    assert float(metrics["loss"]) == float(loss)  # no auxiliary term joins it
    flat = jax.tree_util.tree_flatten_with_path(grads)[0]
    assert len(flat) == 28
    for (path, g), w in zip(flat, jax.tree.leaves(want_grads)):
        if names(path).endswith("router_bias"):  # it only chooses: no gradient
            assert not np.asarray(g).any() and not np.asarray(w).any()
            continue
        assert rel(g, w) < grad_tol, (names(path), rel(g, w))


# ---------------------------------------------------------------------------
# The flash kernels at two widths
# ---------------------------------------------------------------------------


def _qkv(b=1, s=256, h=2, d=192, dv=128, dtype=jnp.float32):
    kq, kk, kv = jax.random.split(jax.random.key(5), 3)
    return (jax.random.normal(kq, (b, s, h, d), dtype), jax.random.normal(kk, (b, s, h, d), dtype),
            jax.random.normal(kv, (b, s, h, dv), dtype))


def _out_and_grads(attn, q, k, v):
    out, vjp = jax.vjp(attn, q, k, v)
    return (out, *vjp(0.5 + out))


@pytest.mark.parametrize("blocks", [(128, 128, 0, 0), (256, 128, 128, 256)],
                         ids=lambda b: "x".join(map(str, b)))
@pytest.mark.parametrize("packed", [False, True], ids=["one-document", "packed"])
def test_flash_at_192_and_128_matches_xla_forward_and_both_backward_kernels(
        monkeypatch, packed, blocks):
    from ditl_tpu.ops import flash_attention as fa

    q, k, v = _qkv()
    seg = None
    if packed:
        row = np.concatenate([np.full(n, i + 1, np.int32) for i, n in enumerate((100, 92, 64))])
        seg = jnp.asarray(row[None])
    bq, bkv, bqb, bkvb = blocks

    def flash(q, k, v):
        return flash_attention(q, k, v, causal=True, segment_ids=seg, block_q=bq,
                               block_kv=bkv, block_q_bwd=bqb, block_kv_bwd=bkvb)

    got = _out_and_grads(flash, q, k, v)
    want = _out_and_grads(lambda q, k, v: _xla_attention(
        q, k, v, causal=True, segment_ids=seg), q, k, v)
    assert got[0].shape == (1, 256, 2, 128) and got[1].shape == q.shape
    assert got[2].shape == k.shape and got[3].shape == v.shape
    for g, w, name in zip(got, want, ("out", "dq", "dk", "dv")):
        np.testing.assert_allclose(g, w, atol=5e-4, rtol=5e-4, err_msg=name)
    if packed:  # the same numbers, not close ones, with the skipping off
        def whole(seg, block):
            lo = jnp.zeros((seg.shape[0], seg.shape[1] // block), jnp.int32)
            return lo, lo + np.iinfo(np.int32).max

        monkeypatch.setattr(fa, "_block_ranges", whole)
        for g, w, name in zip(got, _out_and_grads(flash, q, k, v), ("out", "dq", "dk", "dv")):
            np.testing.assert_array_equal(g, w, err_msg=name)


def test_the_gate_takes_a_width_for_the_values_and_one_width_as_before():
    assert supports(8192, 8192, 192, v_dim=128) and supports(256, 256, 128, v_dim=64)
    assert supports(2048, 2048, 64) and supports(2048, 2048, 128)
    assert not supports(256, 256, 192)  # one width of 192: the statistics tile to 128s
    assert not supports(256, 256, 192, v_dim=96) and not supports(256, 256, 100, v_dim=128)


# ---------------------------------------------------------------------------
# The held share's backward pass
# ---------------------------------------------------------------------------


def _moe_inputs(cfg, bias):
    params = family.seeded(ref, cfg)["layers"]["sparse"]["moe"]
    m = jax.tree.map(lambda w: w[0], params)
    m["router_bias"] = jnp.asarray(bias, jnp.float32)
    u = jax.random.normal(jax.random.key(9), (2, 64, cfg.hidden_size), jnp.float32)
    return m, u


def _loop_over_experts(m, u, cfg):
    """The share as a masked loop: every held expert over every token."""
    first, count = cfg.experts_held_first, cfg.experts_held_count
    p = jax.nn.sigmoid(u @ m["router"])
    _, top = jax.lax.top_k(p + m["router_bias"], cfg.num_experts_per_tok)
    w = jax.nn.one_hot(top, cfg.num_experts).sum(axis=-2) * p
    w = w / w.sum(axis=-1, keepdims=True) * cfg.routed_scaling_factor
    ffn = lambda t, x: (jax.nn.silu(x @ t["w_gate"]) * (x @ t["w_up"])) @ t["w_down"]  # noqa: E731
    out = ffn(m["shared"], u)
    for j in range(count):
        out = out + w[..., first + j:first + j + 1] * ffn(
            {k: m[k][j] for k in ("w_gate", "w_up", "w_down")}, u)
    return out


SKEWS = {"even": 0.0, "every-pair-held": 5.0, "none-held": -5.0}


@pytest.mark.parametrize("skew", SKEWS)
def test_the_held_shares_backward_matches_a_loop_over_experts_at_any_skew(skew):
    cfg = CFG
    bias = np.zeros(cfg.num_experts, np.float32)
    bias[cfg.experts_held_first:cfg.experts_held_first + cfg.experts_held_count] = SKEWS[skew]
    m, u = _moe_inputs(cfg, bias)
    g = jax.random.normal(jax.random.key(3), u.shape, jnp.float32)

    def program(m, u):
        out, _, counts = moe_mod.moe_block(m, u, cfg, static_buffers=True)
        return (out * g).sum(), counts

    (got, counts), got_grads = jax.jit(jax.value_and_grad(program, argnums=(0, 1), has_aux=True))(m, u)
    want, want_grads = jax.jit(jax.value_and_grad(
        lambda m, u: (_loop_over_experts(m, u, cfg) * g).sum(), argnums=(0, 1)))(m, u)
    pairs = u.shape[0] * u.shape[1] * cfg.num_experts_per_tok
    held = int(counts[:-2].sum())
    # no pair is dropped at any skew: all T x k of them fit the static buffers
    assert held == {"even": held, "every-pair-held": pairs, "none-held": 0}[skew]
    assert 0 < held < pairs or skew != "even"
    assert int(counts.sum()) == pairs
    assert rel(got, want) < 1e-4  # one scalar out of 8,192 products that cancel
    flat = jax.tree_util.tree_flatten_with_path(got_grads)[0]
    for (path, a), b in zip(flat, jax.tree.leaves(want_grads)):
        if names(path).endswith("router_bias"):
            assert not np.asarray(a).any()
        elif skew == "none-held" and names(path).split("/")[-1] in ("w_gate", "w_up", "w_down") \
                and "shared" not in names(path):
            assert not np.asarray(a).any() and not np.asarray(b).any()
        else:
            assert rel(a, b) < 1e-4, (names(path), rel(a, b))


def test_the_static_buffers_give_the_loops_output_and_an_empty_one_runs_no_matmul():
    cfg = CFG
    m, u = _moe_inputs(cfg, np.zeros(cfg.num_experts, np.float32))
    a = moe_mod.moe_block(m, u, cfg, static_buffers=True)
    b = moe_mod.moe_block(m, u, cfg)
    np.testing.assert_allclose(a[0], b[0], atol=1e-6, rtol=1e-6)
    np.testing.assert_array_equal(a[2], b[2])
    text = str(jax.make_jaxpr(lambda m, u: moe_mod.moe_block(m, u, cfg, static_buffers=True))(m, u))
    pairs = u.shape[0] * u.shape[1] * cfg.num_experts_per_tok
    buffers = -(-pairs // moe_mod.held_rows(pairs))
    assert text.count("cond[") >= buffers and "while[" not in text
    assert "while[" in str(jax.make_jaxpr(lambda m, u: moe_mod.moe_block(m, u, cfg))(m, u))


def _linear(f):
    return lambda *a: (f(*a) * jax.random.normal(jax.random.key(4), (2, 64, 64))).sum()


def test_the_eight_shares_add_up_to_the_uncut_layer_forward_and_gradient():
    """Every share's routed part, and the shared expert ONCE: the layer that
    holds all 32 experts, in its output and in the gradient of the parameters
    every chip holds (the shared expert, the router) and of the stream."""
    cfg = family.tiny(PRESET, TINY, experts_held_first=0, experts_held_count=32)
    m, u = _moe_inputs(cfg, np.random.default_rng(0).normal(0, 0.02, 32))
    shared_only = lambda m, u: (jax.nn.silu(u @ m["shared"]["w_gate"])  # noqa: E731
                                * (u @ m["shared"]["w_up"])) @ m["shared"]["w_down"]

    def share(s):
        c = family.tiny(PRESET, TINY, experts_held_first=4 * s, experts_held_count=4)
        return lambda m, u: moe_mod.moe_block(
            {**m, **{k: m[k][4 * s:4 * s + 4] for k in ("w_gate", "w_up", "w_down")}}, u, c,
            static_buffers=True)[0]

    whole = lambda m, u: moe_mod.moe_block(m, u, cfg, static_buffers=True)[0]  # noqa: E731
    summed = lambda m, u: sum(share(s)(m, u) for s in range(8)) - 7 * shared_only(m, u)  # noqa: E731
    np.testing.assert_allclose(jax.jit(summed)(m, u), whole(m, u), atol=2e-5, rtol=2e-5)
    got = jax.jit(jax.grad(_linear(summed), argnums=(0, 1)))(m, u)
    want = jax.jit(jax.grad(_linear(whole), argnums=(0, 1)))(m, u)
    assert rel(got[1], want[1]) < 1e-5
    for name in ("w_gate", "w_up", "w_down"):
        assert rel(got[0]["shared"][name], want[0]["shared"][name]) < 1e-5
        assert rel(got[0][name], want[0][name]) < 1e-5  # each expert from its one share
    assert rel(got[0]["router"], want[0]["router"]) < 1e-5


# ---------------------------------------------------------------------------
# The trainer's side: a buffer, no auxiliary term, the step's counters
# ---------------------------------------------------------------------------


def test_the_router_bias_is_a_buffer_no_update_and_no_decay():
    from ditl_tpu.train.state import create_train_state
    from ditl_tpu.train.step import _build_step_fn
    from ditl_tpu.parallel.sharding import DEFAULT_RULES

    cfg = CFG
    tc = TrainConfig(weight_decay=0.1, learning_rate=1e-2, warmup_steps=0, total_steps=10)
    state = create_train_state(jax.random.key(0), cfg, tc)
    bias = jax.random.normal(jax.random.key(1), (1, 32)) * 0.02
    state.params["layers"]["sparse"]["moe"]["router_bias"] = bias
    step = jax.jit(_build_step_fn(cfg, tc, None, DEFAULT_RULES))
    new, metrics = step(step(state, packed_batch(cfg))[0], packed_batch(cfg, seed=2))
    moe0, moe1 = state.params["layers"]["sparse"]["moe"], new.params["layers"]["sparse"]["moe"]
    np.testing.assert_array_equal(moe1["router_bias"], bias)
    assert rel(moe1["router"], moe0["router"]) > 1e-4  # its neighbour trains and decays
    assert set(metrics) >= {"moe_held_assign_share", "moe_load_max_over_mean", "router_aux_loss"}
    assert 0.0 < float(metrics["moe_held_assign_share"]) < 1.0
    # an olmoe-shaped tree has no buffer: every leaf trains, as before
    from ditl_tpu.train.state import lora_mask
    assert all(jax.tree.leaves(lora_mask({"layers": {"moe": {"router": 1, "w_up": 2}}})))


def test_a_frozen_router_is_left_as_the_checkpoint_has_it_and_keeps_no_moments():
    from ditl_tpu.train.state import create_train_state, lora_mask
    from ditl_tpu.train.step import _build_step_fn
    from ditl_tpu.parallel.sharding import DEFAULT_RULES

    cfg = CFG
    tc = TrainConfig(weight_decay=0.1, learning_rate=1e-2, warmup_steps=0, total_steps=10,
                     frozen="router")
    state = create_train_state(jax.random.key(0), cfg, tc)
    step = jax.jit(_build_step_fn(cfg, tc, None, DEFAULT_RULES))
    new, _ = step(step(state, packed_batch(cfg))[0], packed_batch(cfg, seed=2))
    moe0, moe1 = state.params["layers"]["sparse"]["moe"], new.params["layers"]["sparse"]["moe"]
    np.testing.assert_array_equal(moe1["router"], moe0["router"])  # no update, no decay
    assert rel(moe1["w_up"], moe0["w_up"]) > 1e-4  # the experts beside it train
    mask = lora_mask(state.params, ("router",))
    assert not mask["layers"]["sparse"]["moe"]["router"]
    assert not mask["layers"]["sparse"]["moe"]["router_bias"]  # a buffer all the same
    assert mask["layers"]["sparse"]["moe"]["shared"]["w_up"]
    free = create_train_state(jax.random.key(0), cfg, dataclasses.replace(tc, frozen=""))
    size = lambda st: sum(x.size for x in jax.tree.leaves(st.opt_state))  # noqa: E731
    assert size(free) - size(state) == 2 * moe0["router"].size  # AdamW's two moments
    with pytest.raises(ValueError, match="names no leaf"):
        create_train_state(jax.random.key(0), cfg, dataclasses.replace(tc, frozen="rooter"))


def test_a_given_choice_takes_the_place_of_the_routers_own():
    cfg = CFG
    m, u = _moe_inputs(cfg, np.zeros(cfg.num_experts, np.float32))
    gates = jax.nn.sigmoid(u.reshape(-1, u.shape[-1]).astype(jnp.float32)
                           @ m["router"].astype(jnp.float32))
    own = jax.lax.top_k(gates + m["router_bias"], cfg.num_experts_per_tok)[1]
    a = moe_mod.moe_block(m, u, cfg, static_buffers=True)
    b = moe_mod.moe_block({**m, "choice": own[:, ::-1]}, u, cfg, static_buffers=True)
    np.testing.assert_allclose(np.asarray(a[0]), np.asarray(b[0]), rtol=1e-5, atol=1e-6)
    np.testing.assert_array_equal(a[2], b[2])
    held_only = jnp.broadcast_to(
        cfg.experts_held_first + jnp.arange(cfg.num_experts_per_tok), own.shape)
    c = moe_mod.moe_block({**m, "choice": held_only}, u, cfg, static_buffers=True)
    assert int(c[2][:-2].sum()) == own.size and int(c[2][-2:].sum()) == 0  # every pair held


def test_an_indexed_block_keeps_the_loop_its_passes_had():
    from ditl_tpu.models import dsa

    seen = []
    real = moe_mod.moe_block

    def spy(*a, static_buffers=False, **kw):
        seen.append(static_buffers)
        return real(*a, static_buffers=static_buffers, **kw)

    for cfg in (CFG, dataclasses.replace(
            get_preset("deepseek-v3.2"), num_layers=2, first_k_dense_replace=1, vocab_size=512,
            hidden_size=64, intermediate_size=128, expert_ffn_hidden_size=32, num_heads=4,
            num_kv_heads=4, head_dim=24, q_lora_rank=32, kv_lora_rank=32, qk_nope_head_dim=16,
            qk_rope_head_dim=8, v_head_dim=16, index_n_heads=4, index_head_dim=16,
            index_topk=16, num_experts=32, num_experts_per_tok=4, n_group=4, topk_group=2,
            experts_held_first=0, experts_held_count=8, rope_yarn_original_max_len=64)):
        ids = jnp.zeros((1, 32), jnp.int32)
        with pytest.MonkeyPatch.context() as mp:
            mp.setattr(moe_mod, "moe_block", spy)
            # shapes alone: the pass is traced, no weight is drawn
            jax.eval_shape(lambda p: llama.forward(p, ids, cfg),  # noqa: B023
                           jax.eval_shape(lambda: llama.init_params(jax.random.key(0), cfg)))  # noqa: B023
    assert seen == [True, False]  # the trained block walks static buffers, the indexed one loops


def test_the_loss_has_no_auxiliary_term_and_keeps_the_reading():
    cfg = CFG
    params, batch = family.seeded(ref, cfg), packed_batch(cfg)
    loss, metrics = jax.jit(lambda p: loss_fn(p, batch, cfg))(params)
    assert float(loss) == float(metrics["loss"]) and float(metrics["router_aux_loss"]) > 0
    with_term, m2 = jax.jit(lambda p: loss_fn(
        p, batch, dataclasses.replace(cfg, router_aux_coef=0.01)))(params)
    assert float(with_term) == pytest.approx(
        float(m2["loss"]) + 0.01 * float(m2["router_aux_loss"]), rel=1e-6)
    assert moe_metric_names(cfg, None) == (
        "router_aux_loss", "moe_load_max_over_mean", "moe_held_assign_share")
    assert moe_metric_names(get_preset("olmoe-1b-7b"), None) == (
        "router_aux_loss", "moe_load_max_over_mean")


def test_the_corpus_at_fixed_lengths_packs_every_row_alike_whatever_the_seed():
    from ditl_tpu.data import load_text_dataset
    from ditl_tpu.data.tokenizer import get_tokenizer

    lengths = (128, 64, 32, 16, 8, 4, 4)
    tok = get_tokenizer("byte")
    corpora = []
    for seed in (0, 2 ** 31 + 5):
        dc = DataConfig(synthetic=True, seed=seed, synthetic_examples=21,
                        synthetic_doc_tokens=",".join(map(str, lengths)))
        texts = load_text_dataset(dc).texts
        assert [len(tok.encode(t)) + 2 for t in texts] == list(lengths) * 3
        corpora.append(texts)
    assert corpora[0] != corpora[1]  # the seed draws the content
    with pytest.raises(ValueError, match="bos"):
        load_text_dataset(DataConfig(synthetic=True, synthetic_doc_tokens="8,2"))
    assert TrainConfig().init_seed == -1  # the initial draw follows train.seed, as before


# ---------------------------------------------------------------------------
# The configuration: one fact a family, and every other combination by name
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("kw, said", [
    (dict(first_k_dense_replace=0), "q_lora_rank"),  # the double layer has a query latent
    (dict(experts_held_count=0), "held share"),
    (dict(index_n_heads=4), "index_n_heads"),
    (dict(index_n_heads=4, index_head_dim=16, index_topk=16), "query latent"),
    (dict(mla_scale_kv_lora=True), "mla_scale"),
    (dict(zero_expert_num=4), "zero_expert_num"),
    (dict(first_k_dense_replace=3), "first_k_dense_replace"),
    (dict(n_group=3, topk_group=1), "n_group"),
])
def test_a_combination_no_block_carries_is_refused_by_name(kw, said):
    with pytest.raises(ValueError, match=said):
        family.tiny(PRESET, TINY, **kw)


def test_the_three_latent_families_are_told_apart_by_one_fact_each():
    kanana, deepseek, longcat = (get_preset(n) for n in (
        "kanana-2-30b-a3b", "deepseek-v3.2", "longcat-flash"))
    assert (kanana.dsa_layer, kanana.indexed, kanana.double_layer) == (True, False, False)
    assert (deepseek.dsa_layer, deepseek.indexed, deepseek.double_layer) == (True, True, False)
    assert (longcat.dsa_layer, longcat.indexed, longcat.double_layer) == (False, False, True)
    with pytest.raises(ValueError, match="rope_yarn"):
        ModelConfig(rope_yarn_factor=2.0)
    # one group that always stays is no group limiting
    a, b = CFG, family.tiny(PRESET, TINY, n_group=0, topk_group=0)
    m, u = _moe_inputs(a, np.zeros(32, np.float32))
    np.testing.assert_array_equal(moe_mod.moe_block(m, u, a)[0], moe_mod.moe_block(m, u, b)[0])
    # shapes alone: this size is never drawn
    tree = jax.eval_shape(lambda: llama.init_params(jax.random.key(0), CFG))
    assert "index" not in tree["layers"]["sparse"] and "wq" in tree["layers"]["sparse"]["attn"]
    assert set(tree["layers"]["sparse"]["attn"]) == {"wq", "w_kva", "kv_norm", "w_kvb", "wo"}


def test_the_preset_and_its_cut_count_their_parameters_and_hold_the_configuration_file():
    with open(os.path.join(family.BENCH, "configs", "kanana-2-30b-a3b-cut1.json")) as f:
        config = json.load(f)
    cfg = dataclasses.replace(get_preset("kanana-2-30b-a3b"), **config["model_overrides"],
                              **config["train_overrides"])
    assert ref.check_sizes(cfg, config) == []
    # shapes alone: this size is never drawn
    shapes = jax.eval_shape(lambda: llama.init_params(jax.random.key(0), cfg))
    n = sum(math.prod(x.shape) for x in jax.tree.leaves(shapes))
    assert n == config["cut"]["parameters"] == 575_955_968
    assert n * 16 == config["cut"]["parameter_bytes_f32_adamw"]
    assert n * 18 == config["cut"]["parameter_bytes_with_bf16_copy"]
    assert jax.tree.structure(llama.param_logical_axes(cfg), is_leaf=lambda x: isinstance(
        x, tuple)) == jax.tree.structure(shapes)
    whole = jax.eval_shape(lambda: llama.init_params(
        jax.random.key(0), get_preset("kanana-2-30b-a3b")))
    assert llama.num_params(whole) == config["cut"]["published_parameters"] == 30_670_815_104
    row = [json.loads(line) for line in open(
        "/opt/skills/guides/model-configs/architectures.jsonl")
        if "kanana-2-30b-a3b-instruct-2601" in line] if os.path.exists(
        "/opt/skills/guides/model-configs/architectures.jsonl") else []
    for key, value in (row[0]["config"].items() if row else ()):
        assert config[key] == value, key
    assert ref.forward_flops_per_token(config, 1366.0) * 3 == pytest.approx(1.95e9, rel=0.01)


def test_serving_it_is_refused_by_name_and_a_cached_forward_too():
    from ditl_tpu.infer.page_format import page_format

    cfg = CFG
    with pytest.raises(ValueError, match="trained, not served"):
        page_format(cfg, n_pages=8, page_size=16, n_slots=2, decode_chunk=1)
    params = family.seeded(ref, cfg)
    cache = {"c": jnp.zeros((2, 1, 1, 32, 128)), "i": jnp.zeros((2, 1, 1, 32, 16))}
    with pytest.raises(ValueError, match="no.*cached forward"):
        llama.forward(params, jnp.ones((1, 8), jnp.int32), cfg, cache=cache, cache_index=0,
                      attn_mask=jnp.ones((1, 8, 32), bool))
