"""DeepSeek-V3.2 on the normal path (ISSUE 44): latent attention in a single
pre-norm block behind a lightning indexer (models/dsa.py), a sigmoid
group-limited router with a shared expert (models/moe.py) and a leading dense
layer, against the plain reference (benchmarks/reference/deepseek_v32.py), at
small sizes on the CPU, seeded random weights, with the selection doing real
work (``index_topk`` 16 of 96 tokens). tests/test_deepseek_serving.py has the
engine."""

from __future__ import annotations

import dataclasses
import json
import math
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ditl_tpu.config import ModelConfig
from ditl_tpu.models import dsa, llama
from ditl_tpu.models import moe as moe_mod
from ditl_tpu.models.presets import get_preset
from tests import family
from tests.family import rel

ref = family.reference("deepseek_v32")
PRESET = "deepseek-v3.2"

# Both sides compute in float32 on the same weights; they differ in the order
# of their sums (absorbed against decompressed attention, a gather of the
# selected entries against a mask over all, a grouped matmul and a scatter-add
# against a masked loop): 1e-6 relative is what float32 leaves of that over
# three layers, 1e-4 gives it a hundred times of room and is a hundred times
# under any wrong term or any query that selected another set.
TOL = 1e-4

TINY = dict(num_layers=3, first_k_dense_replace=1, vocab_size=512, hidden_size=64,
            intermediate_size=128, expert_ffn_hidden_size=32, num_heads=4, num_kv_heads=4,
            head_dim=24, q_lora_rank=32, kv_lora_rank=32, qk_nope_head_dim=16,
            qk_rope_head_dim=8, v_head_dim=16, index_n_heads=4, index_head_dim=16,
            index_topk=16, num_experts=32, num_experts_per_tok=4, n_group=4, topk_group=2,
            experts_held_first=0, experts_held_count=8, max_seq_len=512,
            rope_yarn_original_max_len=64, dtype="float32", param_dtype="float32")
CFG = family.tiny(PRESET, TINY)


def sample(cfg, shape=(2, 96), seed=1):
    return jax.random.randint(jax.random.key(seed), shape, 3, cfg.vocab_size)


@pytest.mark.parametrize("packed", [False, True], ids=["plain", "packed"])
def test_forward_matches_the_reference_where_the_selection_does_real_work(packed):
    cfg = CFG
    params, ids = family.seeded(ref, cfg), sample(cfg)
    kw = {}
    if packed:  # two documents a row: a query selects inside its own
        seg = jnp.concatenate([jnp.ones((2, 60), jnp.int32), 2 * jnp.ones((2, 36), jnp.int32)], 1)
        pos = jnp.concatenate([jnp.arange(60), jnp.arange(36)])[None].repeat(2, 0)
        kw = {"positions": pos, "segment_ids": seg}
    got = jax.jit(lambda p: llama.forward(p, ids, cfg, **kw))(params)
    want = ref.forward(params, ids, ref.sizes(cfg, {}), **kw)
    assert rel(got, want["logits"]) < TOL
    per_query = np.asarray(want["selected"]).sum(axis=-1)  # (L, B, S)
    assert per_query.max() == cfg.index_topk and per_query[:, :, 0].max() == 1


def test_the_programs_selected_sets_are_the_references():
    cfg = CFG
    params, ids = family.seeded(ref, cfg), sample(cfg)
    import dsa_check

    dsa.TAP = tap = dsa_check.Tapped(cfg.num_layers, 96, rows=2, by_row=True)
    try:  # what the program chose, reported by the program itself
        got = jax.block_until_ready(llama.forward(params, ids, cfg))
        jax.effects_barrier()
    finally:
        dsa.TAP = None
    theirs = ref.forward(params, ids, ref.sizes(cfg, {}))["selected"]
    mine, theirs = tap.sets, np.asarray(theirs)
    assert (tap.seen == 1).all()
    assert mine.shape == theirs.shape == (cfg.num_layers, 2, 96, 96)
    # exactly min(index_topk, t + 1) entries a query, none after it
    want_n = np.minimum(np.arange(96) + 1, cfg.index_topk)
    assert (mine.sum(axis=-1) == want_n).all() and not np.triu(mine, 1).any()
    assert (mine == theirs).mean() > 0.999  # float32 both: a tie at the boundary at most
    handed = ref.forward(params, ids, ref.sizes(cfg, {}), selected=jnp.asarray(mine))
    assert rel(got, handed["logits"]) < TOL


def test_a_context_of_at_most_index_topk_is_dense_latent_attention():
    """Everything is selected: the reference is bit for bit its own dense
    pass, the program (which then skips the indexer) within tolerance."""
    cfg = family.tiny(PRESET, TINY, index_topk=96)
    dense = family.tiny(PRESET, TINY, index_topk=4096)
    params, ids = family.seeded(ref, cfg), sample(cfg)
    a = ref.forward(params, ids, ref.sizes(cfg, {}))
    b = ref.forward(params, ids, ref.sizes(dense, {}))
    assert np.array_equal(np.asarray(a["logits"]), np.asarray(b["logits"]))
    assert np.asarray(a["selected"]).sum() == cfg.num_layers * 2 * 96 * 97 // 2
    got = llama.forward(params, ids, cfg)
    assert rel(got, a["logits"]) < TOL
    # and a selection that bites changes the answer: the mechanism is not idle
    sparse = llama.forward(params, ids, CFG)
    assert rel(sparse, a["logits"]) > 0.05


def _moe_of(cfg, full, first, count):
    """The expert block's weights of a share of ``full`` (an uncut block)."""
    share = dict(full)
    for k in ("w_gate", "w_up", "w_down"):
        share[k] = full[k][first:first + count]
    return share


def test_the_shares_routed_parts_and_the_shared_expert_once_add_up_to_the_uncut_layer():
    whole = family.tiny(PRESET, TINY, experts_held_first=0, experts_held_count=32)
    params = family.seeded(ref, whole)
    full = jax.tree.map(lambda w: w[0], params["layers"]["sparse"]["moe"])  # one layer's
    u = jax.random.normal(jax.random.key(5), (2, 24, whole.hidden_size), jnp.float32)
    want, _, counts = moe_mod.moe_block(full, u, whole)
    assert int(counts[:32].sum()) == 2 * 24 * 4 and int(counts[32:].sum()) == 0
    shared_only = {k: v for k, v in full.items() if k != "shared"}
    total = jnp.zeros_like(want)
    for first in range(0, 32, 2):  # 16 shares of 2 experts, the shared expert left out
        cfg = family.tiny(PRESET, TINY, experts_held_first=first, experts_held_count=2)
        part, _, c = moe_mod.moe_block(_moe_of(cfg, shared_only, first, 2), u, cfg)
        total = total + part
        assert int(c.sum()) == 2 * 24 * 4  # held + absent: every choice is counted
    one = family.tiny(PRESET, TINY, experts_held_first=0, experts_held_count=2)
    with_shared, _, _ = moe_mod.moe_block(_moe_of(one, full, 0, 2), u, one)
    without, _, _ = moe_mod.moe_block(_moe_of(one, shared_only, 0, 2), u, one)
    total = total + (with_shared - without)  # the shared expert, once
    assert rel(total, want) < TOL
    # and the reference's block agrees with the program's on a share
    cfg = family.tiny(PRESET, TINY, experts_held_first=8, experts_held_count=8)
    stacked = jax.tree.map(lambda w: w[None], _moe_of(cfg, full, 8, 8))
    theirs, _ = ref._experts(stacked, 0, u, ref.sizes(cfg, {}))
    mine, _, _ = moe_mod.moe_block(_moe_of(cfg, full, 8, 8), u, cfg)
    assert rel(mine, theirs) < TOL


def test_group_limited_choice_on_a_hand_made_case():
    """8 experts in 4 groups of 2, the best 2 groups stay, 2 experts chosen.
    Group scores are the sums of their two largest: A 0.9 + 0.1, B 0.6 + 0.5,
    C 0.8 + 0.0, D 0.4 + 0.3 -> B (1.1) and A (1.0) stay; C's 0.8, the second
    largest score of all, is out of reach; chosen: A's 0.9 and B's 0.6."""
    scores = jnp.asarray([[0.9, 0.1, 0.6, 0.5, 0.8, 0.0, 0.4, 0.3]], jnp.float32)
    limited = np.asarray(moe_mod.group_limited(scores, 4, 2))
    assert np.isneginf(limited[0, 4:]).all() and (limited[0, :4] == np.asarray(scores)[0, :4]).all()
    sizes = {"n_group": 4, "topk_group": 2, "num_experts_per_tok": 2}
    assert np.asarray(ref.choose(scores, sizes))[0].tolist() == [1, 0, 1, 0, 0, 0, 0, 0]
    assert sorted(np.asarray(jax.lax.top_k(limited, 2)[1])[0].tolist()) == [0, 2]
    # the bias enters the choice and not the weights
    cfg = family.tiny(PRESET, TINY, num_experts=8, n_group=4, topk_group=2,
                      num_experts_per_tok=2, experts_held_first=0, experts_held_count=8,
                      n_shared_experts=0)
    d = cfg.hidden_size
    moe = moe_mod.init_moe_params(jax.random.key(0), cfg, n_layers=1)
    moe = jax.tree.map(lambda w: w[0], moe)
    u = jax.random.normal(jax.random.key(1), (1, 6, d), jnp.float32)
    plain, _, c0 = moe_mod.moe_block(moe, u, cfg)
    pushed = {**moe, "router_bias": jnp.asarray([0, 0, 0, 0, 0, 0, 9.0, 9.0], jnp.float32)}
    biased, _, c1 = moe_mod.moe_block(pushed, u, cfg)
    assert c1[6] == 6 and c1[7] == 6 and c0.sum() == c1.sum() == 12  # group D always wins
    stacked = jax.tree.map(lambda w: w[None], {**pushed, "shared": {
        k: jnp.zeros_like(pushed[k][0]) for k in ("w_gate", "w_up", "w_down")}})
    theirs, chosen = ref._experts(stacked, 0, u, ref.sizes(cfg, {}))
    assert np.asarray(chosen)[..., 6:].all() and rel(biased, theirs) < TOL
    assert rel(biased, plain) > 1e-3


def test_yarn_frequencies_against_the_closed_form():
    cfg = get_preset("deepseek-v3.2")
    got = dsa.yarn_inv_freq(cfg)
    dim, theta, factor, orig = 64, 10000.0, 40.0, 4096
    low = math.floor(dim * math.log(orig / (32 * 2 * math.pi)) / (2 * math.log(theta)))
    high = math.ceil(dim * math.log(orig / (1 * 2 * math.pi)) / (2 * math.log(theta)))
    assert (low, high) == (10, 23)
    for i in range(dim // 2):
        f = theta ** (-2 * i / dim)
        ramp = min(max((i - low) / (high - low), 0.0), 1.0)
        assert got[i] == pytest.approx(f * (1 - ramp) + f / factor * ramp, rel=1e-6)
    assert got[0] == pytest.approx(1.0) and got[31] == pytest.approx(theta ** (-62 / 64) / 40)
    assert np.allclose(got, np.asarray(ref.yarn_inv_freq(dim, theta, ref.sizes(cfg, {})[
        "rope_scaling"])), rtol=1e-6)
    m = 0.1 * math.log(40.0) + 1.0
    assert dsa.softmax_scale(cfg) == pytest.approx(192 ** -0.5 * m * m)
    plain = family.tiny(PRESET, TINY, rope_yarn_factor=0.0)
    assert dsa.softmax_scale(plain) == pytest.approx(24 ** -0.5)


@pytest.mark.parametrize("kw, said", [
    (dict(index_topk=0), "index_n_heads"),
    (dict(first_k_dense_replace=0), "lightning indexer"),
    (dict(first_k_dense_replace=3), "lightning indexer"),
    (dict(index_head_dim=4), "lightning indexer"),
    (dict(scoring_func="tanh"), "scoring_func"),
    (dict(n_group=5), "n_group"),
    (dict(topk_group=1, num_experts_per_tok=12), "n_group"),
    (dict(experts_held_count=0), "held share"),
])
def test_a_setting_nothing_would_read_is_refused(kw, said):
    with pytest.raises(ValueError, match=said):
        family.tiny(PRESET, TINY, **kw)


def test_an_indexer_without_its_block_is_refused():
    with pytest.raises(ValueError, match="index_n_heads"):
        ModelConfig(index_n_heads=4)
    with pytest.raises(ValueError, match="first_k_dense_replace"):
        ModelConfig(first_k_dense_replace=1)


def test_the_preset_and_its_cut_count_their_parameters_and_hold_the_configuration_file():
    with open(os.path.join(family.BENCH, "configs", "deepseek-v3.2-cut1.json")) as f:
        config = json.load(f)
    import reference_check
    from harness import model_override_args

    cfg = reference_check.model_config(config, model_override_args(config, "serve"))
    assert ref.check_sizes(cfg, config) == []
    # shapes alone: this size is never drawn
    shapes = jax.eval_shape(lambda: llama.init_params(jax.random.key(0), cfg))
    n = sum(math.prod(a.shape) for a in jax.tree.leaves(shapes))
    assert n == config["cut"]["parameters"] == 4_635_518_208
    assert {a.dtype for a in jax.tree.leaves(shapes)} == {jnp.dtype("bfloat16"),
                                                         jnp.dtype("float32")}  # the biases
    assert config["cut"]["parameter_bytes_bf16"] == 2 * n
    entry = config["cut"]["cache_entry_bytes"]
    from ditl_tpu.models.mla import latent_width

    assert entry["latent"] == 2 * latent_width(cfg) == 1280
    assert entry["index_key"] == 2 * cfg.index_head_dim == 256
    assert entry["a_token"] == cfg.num_layers * (entry["latent"] + entry["index_key"]) == 7680
    # a width that drifts from the file is named
    assert ref.check_sizes(dataclasses.replace(cfg, index_topk=1024), config)
    # the catalog's every number is in the file under its key
    assert set(config["reduced"]) == {"num_hidden_layers", "first_k_dense_replace",
                                      "n_routed_experts", "vocab_size",
                                      "num_nextn_predict_layers"}
    assert config["num_hidden_layers"] == 61 and config["index_topk"] == 2048
    assert "16 chips share each layer" in config["cut"]["what"]


def test_the_references_flops_count_the_selected_entries_not_the_context():
    with open(os.path.join(family.BENCH, "configs", "deepseek-v3.2-cut1.json")) as f:
        config = json.load(f)
    short, long = (ref.forward_flops_per_token(config, c) for c in (2048.0, 33000.0))
    # past index_topk only the indexer's scores grow: 5 layers x 64 x 128 x 2 a key
    assert long - short == pytest.approx(5 * (33000 - 2048) * 2 * 64 * 128)


def test_loss_is_the_mean_next_token_cross_entropy():
    cfg = CFG
    params, ids = family.seeded(ref, cfg), sample(cfg, (2, 20))
    out = ref.forward(params, ids, ref.sizes(cfg, {}))
    mask = jnp.ones(ids.shape, jnp.float32)
    logp = jax.nn.log_softmax(out["logits"][:, :-1], -1)
    want = -jnp.take_along_axis(logp, ids[:, 1:, None], -1).mean()
    assert float(ref.loss(out, ids, mask, {})) == pytest.approx(float(want), rel=1e-6)
