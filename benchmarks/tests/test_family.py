"""A family is its reference module: a second family, added from a temporary
directory with no file of the tree touched, passes the reference check; a
width that differs from its file still fails; its own FLOP count is used."""
import json
import os

import flops
import jax
import pytest
import reference_check as rc
from conftest import BENCH

# A llama-style decoder without attention bias (the program's ``tiny-llama``
# preset), whose config.json states ``attention_bias`` where Qwen2's has no
# such key, and whose loss has a second term beside the logits' (as a
# router's auxiliary loss would be).
FAMILY = '''
import os
import jax.numpy as jnp
from harness import load_module

_q = load_module(os.path.join(%(bench)r, "reference", "qwen2.py"))
PUBLISHED = {**_q.PUBLISHED, "attention_bias": "attention_bias"}


def check_sizes(cfg, config):
    want = {field: config[key] for field, key in PUBLISHED.items()}
    want["head_dim"] = config["head_dim"]
    return [f"{k}: program {getattr(cfg, k)!r}, configuration file {v!r}"
            for k, v in want.items() if getattr(cfg, k) != v]


def sizes(cfg, config):
    return {**_q.sizes(cfg, config), "attention_bias": cfg.attention_bias,
            "aux": config["aux_term"]}


def forward(params, input_ids, sizes, **kw):
    assert sizes["attention_bias"] is False
    attn = dict(params["layers"]["attn"])
    n = attn["wq"].shape[0]
    for b, w in (("bq", "wq"), ("bk", "wk"), ("bv", "wv")):
        assert b not in attn  # the program made none
        attn[b] = jnp.zeros((n, attn[w].shape[-1]), attn[w].dtype)
    params = {**params, "layers": {**params["layers"], "attn": attn}}
    return {"logits": _q.forward(params, input_ids, sizes, **kw), "aux": sizes["aux"]}


def loss(outputs, input_ids, loss_mask, sizes):
    return _q.loss(outputs["logits"], input_ids, loss_mask) + outputs["aux"]


def forward_flops_per_token(config, context_mean):
    return 12345.0 + context_mean
'''

CONFIG = {  # ditl_tpu/models/presets.py "tiny-llama", under config.json's keys
    "preset": "tiny-llama", "reference": "llamaplain",
    "hidden_size": 256, "intermediate_size": 688, "num_hidden_layers": 4,
    "num_attention_heads": 8, "num_key_value_heads": 4, "head_dim": 32,
    "vocab_size": 32000, "rope_theta": 500000.0, "rms_norm_eps": 1e-5,
    "tie_word_embeddings": False, "max_position_embeddings": 2048,
    "attention_bias": False, "aux_term": 0.0,
}


@pytest.fixture
def family_dir(tmp_path):
    (tmp_path / "llamaplain.py").write_text(FAMILY % {"bench": BENCH})
    return str(tmp_path)


@pytest.mark.parametrize("role", ["serve", "train"])
def test_a_second_family_from_a_temporary_directory_passes_the_check(family_dir, role):
    """Not a rehearsal: the sizes are checked against the file by the
    family's own table, float32 on the CPU."""
    spec = {"role": role, "model_overrides": ["dtype=float32"]}
    with jax.default_matmul_precision("highest"):
        v = rc.compare(CONFIG, spec, seed=5, reference_dir=family_dir)
    assert v.get("error") is None and v["ok"], v
    assert v["logits_rel_rms"] < 1e-4, v
    if role == "train":
        assert v["loss_rel"] < 1e-5, v


def test_a_loss_term_beside_the_logits_reaches_the_verdict(family_dir):
    """The family's loss reads more than logits: a term the program does not
    compute shows as a loss that differs."""
    spec = {"role": "train", "model_overrides": ["dtype=float32"]}
    with jax.default_matmul_precision("highest"):
        v = rc.compare({**CONFIG, "aux_term": 0.5}, spec, seed=5, reference_dir=family_dir)
    assert v["logits_rel_rms"] < 1e-4 and v["loss_rel"] > 0.03 and not v["ok"], v


@pytest.mark.parametrize("key,value", [("hidden_size", 512), ("attention_bias", True),
                                       ("head_dim", 64)])
def test_a_width_that_differs_from_that_familys_file_fails(family_dir, key, value):
    v = rc.compare({**CONFIG, key: value}, {"role": "serve", "model_overrides": []},
                   reference_dir=family_dir)
    assert not v["ok"] and key in v["error"], v


def test_a_familys_own_flop_count_is_used_and_qwen2_keeps_the_dense_one(
        family_dir, monkeypatch):
    with open(os.path.join(BENCH, "configs", "qwen2-0.5b.json")) as f:
        dense = flops.forward_flops_per_token(json.load(f), 208.5)
    assert dense > 1e8  # today's count: reference/qwen2.py exports none
    os.mkdir(os.path.join(family_dir, "reference"))
    os.rename(os.path.join(family_dir, "llamaplain.py"),
              os.path.join(family_dir, "reference", "llamaplain.py"))
    monkeypatch.setattr(flops, "HERE", family_dir)
    assert flops.forward_flops_per_token(CONFIG, 100.0) == 12445.0
    assert flops.train_flops_per_token(CONFIG, 100.0) == 3 * 12445.0


def test_rehearsal_overrides_of_a_configuration_follow_the_shared_tiny_size():
    import types

    from generators import train_job

    ctx = types.SimpleNamespace(
        traffic={"launch_args": ["model.loss_impl=fused"], "log_every": 4},
        config={"preset": "tiny-moe", "rehearsal_overrides": ["num_experts=4"]},
        rehearsal={"train_launch_args": ["model.hidden_size=64"]}, seed=1,
        run_dir="/nowhere", chips=1)
    argv, model = train_job.build_argv(ctx)
    assert model == ["loss_impl=fused", "hidden_size=64", "num_experts=4"]
    assert argv.index("model.hidden_size=64") < argv.index("model.num_experts=4")
    ctx.rehearsal = None
    assert "model.num_experts=4" not in train_job.build_argv(ctx)[0]
