"""LongCat-Flash on the normal path (ISSUE 33): the shortcut-connected double
layer with latent attention (models/mla.py) and a share of a 768-way expert
layer (models/moe.py) against the plain reference
(benchmarks/reference/longcat_flash.py), at small sizes on the CPU, seeded
random weights: the forward pass, absorbed against decompressed attention, the
kernel against the gather, the sum of the shares, the rows no held expert
sees and the device counters (tests/test_longcat_serving.py has the engine)."""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ditl_tpu.models import llama, mla
from ditl_tpu.models import moe as moe_mod
from ditl_tpu.models.presets import get_preset
from ditl_tpu.ops import mla_attention
from tests import family, rect_walk
from tests.family import rel

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
CFG = family.tiny(PRESET, TINY)
OVERRIDES = [f"{k}={v}" for k, v in TINY.items()]


def test_forward_matches_the_reference():
    cfg = family.tiny(PRESET, TINY, experts_held_first=8, experts_held_count=8)
    params = family.seeded(ref, cfg)
    ids = jax.random.randint(jax.random.key(1), (2, 40), 3, cfg.vocab_size)
    seg = jnp.concatenate([jnp.ones((2, 25), jnp.int32), 2 * jnp.ones((2, 15), jnp.int32)], 1)
    pos = jnp.concatenate([jnp.arange(25), jnp.arange(15)])[None].repeat(2, 0)
    for kw in ({}, {"positions": pos, "segment_ids": seg}):
        got = jax.jit(lambda p: llama.forward(p, ids, cfg, **kw))(params)  # noqa: B023
        want = ref.forward(params, ids, ref.sizes(cfg, {}), **kw)["logits"]
        assert rel(got, want) < TOL


def _latents(cfg, a, h, pos):
    """The stored entries of ``h`` (B, S, D) as the prefill row writes them."""
    b, s, _ = h.shape
    row = jnp.zeros((b, s, mla.latent_width(cfg)), jnp.float32)
    idx = jnp.arange(s)
    allowed = jnp.broadcast_to(idx[None, :, None] >= idx[None, None, :], (b, s, s))
    out, row = mla._mla_sublayer(a, h, cfg=cfg, positions=pos, allowed=allowed, cache=row,
                                 cache_index=0, paged=None, pool=None, cd=jnp.float32)
    return out, row


def test_absorbed_decode_equals_decompressed_attention():
    cfg = CFG
    a = jax.tree.map(lambda w: w[0], family.seeded(ref, cfg)["layers"]["attn"]["sub1"])
    b, s, ps = 3, 37, 16
    h = jax.random.normal(jax.random.key(2), (b, s, cfg.hidden_size))
    pos = jnp.broadcast_to(jnp.arange(s), (b, s))
    want, row = _latents(cfg, a, h, pos)  # decompressed, causal: the last row is the check
    # the first s - 1 entries in pages (row b's pages are b * 3 + 1 ...), the last in the tail
    n_p = -(-(s - 1) // ps)
    pages = jnp.zeros((1 + b * n_p, ps, row.shape[-1]))
    table = 1 + jnp.arange(b * n_p).reshape(b, n_p)
    padded = jnp.pad(row[:, :s - 1], ((0, 0), (0, n_p * ps - (s - 1)), (0, 0)))
    pages = pages.at[table.reshape(-1)].set(padded.reshape(b * n_p, ps, -1))
    paged = {"table": table, "lengths": jnp.full((b,), s), "starts": jnp.full((b,), s - 1),
             "t": 0}
    got, tail = mla._mla_sublayer(
        a, h[:, -1:], cfg=cfg, positions=pos[:, -1:], allowed=None,
        cache=jnp.zeros((b, 8, row.shape[-1])), cache_index=None, paged=paged, pool=pages,
        cd=jnp.float32)
    assert rel(got[:, 0], want[:, -1]) < TOL
    assert rel(tail[:, 0], row[:, -1]) < 1e-6  # the entry the step wrote


@pytest.mark.pallas
def test_the_interpreted_kernel_equals_the_gather():
    b, h, dl, vw, ps, maxp, t = 3, 4, 128, 64, 16, 3, 8
    ks = jax.random.split(jax.random.key(0), 3)
    q = jax.random.normal(ks[0], (b, h, dl))
    pool = jax.random.normal(ks[1], (1 + b * maxp, ps, dl))
    tail = jax.random.normal(ks[2], (b, t, dl))
    table = 1 + jnp.arange(b * maxp).reshape(b, maxp)
    starts = jnp.array([32, 5, 0])  # a dead row too
    lengths = jnp.array([37, 5, 0])
    kw = dict(tail=tail, starts=starts, value_width=vw, scale=0.1)
    want = mla_attention.mla_paged_attention_xla(q, pool, table, lengths, **kw)
    got = mla_attention.mla_paged_attention(q, pool, table, lengths, interpret=True, **kw)
    assert rel(got, want) < 1e-5
    assert not np.asarray(got[2]).any()  # a dead slot: zeros, not NaN


@pytest.mark.pallas
@pytest.mark.parametrize("name", list(rect_walk.SCENARIOS))
def test_the_latent_walk_over_the_list_gives_the_rectangles_numbers(name):
    """``mla_paged_attention`` on its work list against the rectangular walk
    it replaced (``tests/rect_walk.py``: the same ``_accumulate`` on a grid
    of every slot by every page-table position) and against the gather: a
    row with ``lengths > 0`` bit-equal, a row with ``lengths == 0`` exactly
    zero whether the list names it or not; an empty list all zeros."""
    from ditl_tpu.ops.paged_attention import decode_steps

    starts, lengths, listed = rect_walk.rows_of(name)
    ps, maxp = rect_walk.PAGE_SIZE, rect_walk.MAX_PAGES
    b, h, dl, vw, t = len(starts), 4, 128, 64, 8
    ks = jax.random.split(jax.random.key(3), 4)
    q = jax.random.normal(ks[0], (b, h, dl))
    pool = jax.random.normal(ks[1], (23, ps, dl))
    tail = jax.random.normal(ks[2], (b, t, dl))
    table = jax.random.randint(ks[3], (b, maxp), 1, 23)
    kw = dict(tail=tail, starts=starts, value_width=vw, scale=0.1)
    steps = decode_steps(starts, listed, page_size=ps, max_pages=maxp)
    got = np.asarray(mla_attention.mla_paged_attention(
        q, pool, table, lengths, steps=steps, interpret=True, **kw))
    rect = np.asarray(rect_walk.mla_paged_attention_rect(q, pool, table, lengths, **kw))
    live = np.asarray(lengths) > 0
    np.testing.assert_array_equal(got[live], rect[live])
    assert not got[~live].any() and np.isfinite(got).all()
    want = mla_attention.mla_paged_attention_xla(q, pool, table, lengths, **kw)
    np.testing.assert_allclose(got, np.asarray(want), atol=2e-5)
    own = mla_attention.mla_paged_attention(q, pool, table, lengths, interpret=True, **kw)
    np.testing.assert_array_equal(np.asarray(own), got)  # the list a caller leaves out


def _moe_of(cfg, full, first, count):
    """Layer 0 of the uncut model's expert block, cut to a share."""
    m = jax.tree.map(lambda w: w[0], full)
    return {**m, **{k: m[k][first:first + count] for k in ("w_gate", "w_up", "w_down")}}


def test_the_shares_add_up_to_the_uncut_expert_block():
    """32 routed + 16 zero experts, top-4, four shares of 8: the shares'
    routed parts plus the zero experts' part counted once are the uncut
    reference's MoE(u)."""
    cfg = family.tiny(PRESET, TINY, num_layers=1)
    full = family.seeded(ref, cfg)["layers"]["moe"]
    u = jax.random.normal(jax.random.key(4), (2, 9, cfg.hidden_size))
    sizes = ref.sizes(cfg, {})
    want, _ = ref._experts(full, 0, u, sizes)
    zero_part, _ = ref._experts(full, 0, u, {**sizes, "experts_held": (0, 0)})
    total = -3 * zero_part  # each share computes the identities again
    for s in range(4):
        share = dataclasses.replace(cfg, experts_held_first=8 * s, experts_held_count=8)
        out, _, counts = moe_mod.moe_block(_moe_of(cfg, full, 8 * s, 8), u, share)
        total = total + out
        assert int(counts.sum()) == 2 * 9 * 4
    assert rel(total, want) < TOL
    whole, _, _ = moe_mod.moe_block(_moe_of(cfg, full, 0, 32), u, cfg)
    assert rel(whole, want) < TOL


@pytest.mark.parametrize("kind", ["zero", "absent", "held"])
def test_rows_whose_choices_are_all_of_one_kind(kind):
    """A selection bias that sends every choice of every row to zero-compute
    experts, to experts held elsewhere (the grouped matmul sees no row: the
    loop over buffers runs zero times), or to experts held here."""
    cfg = family.tiny(PRESET, TINY, num_layers=1, experts_held_first=8, experts_held_count=8)
    full = family.seeded(ref, cfg)["layers"]["moe"]
    where = {"zero": slice(32, 48), "absent": slice(16, 32), "held": slice(8, 16)}[kind]
    full = {**full, "router_bias": full["router_bias"].at[:, where].add(1.0)}
    u = jax.random.normal(jax.random.key(5), (1, 6, cfg.hidden_size))
    out, _, counts = moe_mod.moe_block(jax.tree.map(lambda w: w[0], full), u, cfg)
    want, _ = ref._experts(full, 0, u, ref.sizes(cfg, {}))
    held, zero, absent = moe_mod.split_counts(counts, cfg)
    got = {"held": int(held.sum()), "zero": int(zero), "absent": int(absent)}
    assert got == {"held": 0, "zero": 0, "absent": 0, kind: 6 * 4}
    if kind == "absent":
        assert not np.asarray(out).any()
    else:
        assert rel(out, want) < TOL


def test_the_device_counters_equal_a_host_recount_of_the_live_rows():
    cfg = family.tiny(PRESET, TINY, experts_held_first=8, experts_held_count=8)
    params = family.seeded(ref, cfg)
    ids = jax.random.randint(jax.random.key(6), (3, 11), 3, cfg.vocab_size)
    live = jnp.arange(11)[None, :] < jnp.array([11, 4, 0])[:, None]  # a dead row among them
    _, counts = jax.jit(lambda p: llama.forward(
        p, ids, cfg, token_mask=live, with_moe_counts=True))(params)
    chosen = np.asarray(ref.forward(params, ids, ref.sizes(cfg, {}))["chosen"])  # (L, B, S, 48)
    chosen = chosen * np.asarray(live)[None, :, :, None]
    held, zero, absent = (np.asarray(x) for x in moe_mod.split_counts(counts, cfg))
    np.testing.assert_array_equal(held, chosen[..., 8:16].sum(axis=(1, 2)))
    np.testing.assert_array_equal(zero, chosen[..., 32:].sum(axis=(1, 2, 3)))
    np.testing.assert_array_equal(held.sum(-1) + zero + absent, [15 * 4] * cfg.num_layers)


@pytest.mark.parametrize("kw, said", [
    (dict(zero_expert_num=0, experts_held_count=0, router_bias=True), "router_bias"),
    (dict(zero_expert_num=0, experts_held_count=0, routed_scaling_factor=6.0),
     "routed_scaling_factor"),
    (dict(num_experts=0, zero_expert_num=0, router_bias=False, routed_scaling_factor=1.0,
          num_experts_per_tok=0), "double layer"),
    (dict(q_lora_rank=0), "q_lora_rank"),
])
def test_a_setting_nothing_would_read_is_refused(kw, said):
    """No field of the family is silently ignored: the selection bias and the
    scale exist on the share's path only, latent attention in the double
    layer only. The block kind is derived (``kv_lora_rank > 0``), and the
    rotary pairing is what ``mla.py`` does, so neither is a field."""
    with pytest.raises(ValueError, match=said):
        family.tiny(PRESET, TINY, **kw)
    assert CFG.double_layer and not get_preset("olmoe-1b-7b").double_layer
    fields = {f.name for f in dataclasses.fields(CFG)}
    assert not fields & {"double_layer", "rope_interleaved"}


def test_the_preset_and_its_share_count_their_parameters():
    """The published model and the cell's cut, counted from shapes alone."""
    whole = jax.eval_shape(lambda: llama.init_params(jax.random.key(0), get_preset("longcat-flash")))
    assert llama.num_params(whole) == 560_664_980_480
    cut = dataclasses.replace(get_preset("longcat-flash"), num_layers=4, vocab_size=16384,
                              experts_held_count=16)
    assert llama.num_params(
        jax.eval_shape(lambda: llama.init_params(jax.random.key(0), cut))) == 5_172_749_312
    with pytest.raises(ValueError, match="outside"):
        dataclasses.replace(cut, experts_held_first=500, experts_held_count=16)


def test_the_converter_round_trips_the_double_layer_and_a_share_of_it():
    """Our tree -> HF's names -> our tree, bit for bit; a share names its held
    experts by their PUBLISHED indices and loads only those from the whole."""
    from ditl_tpu.models import convert

    cfg = family.tiny(PRESET, TINY, num_layers=1, param_dtype="float32")
    params = family.seeded(ref, cfg)
    sd = convert.state_dict_from_params(params, cfg)
    assert sd["model.layers.0.self_attn.1.kv_b_proj.weight"].shape == (4 * 32, 32)
    assert sd["model.layers.0.mlp.router.classifier.weight"].shape == (48, 64)
    assert "model.layers.0.mlp.experts.31.down_proj.weight" in sd
    back = convert.params_from_state_dict(sd, cfg)
    jax.tree.map(np.testing.assert_array_equal, jax.tree.map(np.asarray, params), back)
    share = dataclasses.replace(cfg, experts_held_first=8, experts_held_count=8)
    held = convert.params_from_state_dict(sd, share)["layers"]["moe"]
    np.testing.assert_array_equal(held["w_up"], np.asarray(params["layers"]["moe"]["w_up"])[:, 8:16])
    assert held["router"].shape == (1, 64, 48)
