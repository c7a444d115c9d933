"""HF checkpoint import parity: torch LlamaForCausalLM logits == ours.

Builds tiny randomly-initialized HF models locally (no network) and checks
that the converted param tree reproduces the HF forward pass — the strongest
evidence the RoPE/RMSNorm/GQA/SwiGLU conventions match exactly.
"""

import jax.numpy as jnp
import numpy as np
import pytest

torch = pytest.importorskip("torch")
transformers = pytest.importorskip("transformers")

from ditl_tpu.models import llama
from ditl_tpu.models.convert import (
    config_from_hf,
    params_from_state_dict,
    state_dict_from_params,
)


def _tiny_hf_llama(tie=False):
    cfg = transformers.LlamaConfig(
        vocab_size=256,
        hidden_size=64,
        intermediate_size=128,
        num_hidden_layers=2,
        num_attention_heads=4,
        num_key_value_heads=2,
        max_position_embeddings=128,
        rms_norm_eps=1e-5,
        rope_theta=10000.0,
        tie_word_embeddings=tie,
        attention_bias=False,
        mlp_bias=False,
    )
    torch.manual_seed(0)
    return transformers.LlamaForCausalLM(cfg)


@pytest.mark.parametrize("tie", [False, True])
def test_llama_logits_parity(tie):
    model = _tiny_hf_llama(tie=tie).eval()
    cfg = config_from_hf(model.config, dtype="float32")
    params = params_from_state_dict(model.state_dict(), cfg)

    rng = np.random.default_rng(0)
    ids = rng.integers(0, 256, size=(2, 16)).astype(np.int64)
    with torch.no_grad():
        hf_logits = model(torch.from_numpy(ids)).logits.numpy()

    ours = np.asarray(llama.forward(params, jnp.asarray(ids, jnp.int32), cfg))
    np.testing.assert_allclose(ours, hf_logits, rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("tie", [False, True])
def test_qwen2_logits_parity(tie):
    """Qwen2-family: q/k/v attention bias (+ tied embeddings on the small
    variants) — torch Qwen2ForCausalLM logits == ours."""
    cfg_hf = transformers.Qwen2Config(
        vocab_size=256,
        hidden_size=64,
        intermediate_size=128,
        num_hidden_layers=2,
        num_attention_heads=4,
        num_key_value_heads=2,
        max_position_embeddings=128,
        rms_norm_eps=1e-6,
        rope_theta=10000.0,
        tie_word_embeddings=tie,
    )
    torch.manual_seed(2)
    model = transformers.Qwen2ForCausalLM(cfg_hf).eval()
    # Qwen2 initializes biases to zero; give them real values so the test
    # actually exercises the bias path.
    with torch.no_grad():
        for layer in model.model.layers:
            for p in (layer.self_attn.q_proj.bias,
                      layer.self_attn.k_proj.bias,
                      layer.self_attn.v_proj.bias):
                p.copy_(torch.randn_like(p) * 0.1)
    cfg = config_from_hf(model.config, dtype="float32")
    assert cfg.attention_bias and cfg.tie_embeddings == tie
    params = params_from_state_dict(model.state_dict(), cfg)
    assert "bq" in params["layers"]["attn"]

    rng = np.random.default_rng(2)
    ids = rng.integers(0, 256, size=(2, 16)).astype(np.int64)
    with torch.no_grad():
        hf_logits = model(torch.from_numpy(ids)).logits.numpy()

    ours = np.asarray(llama.forward(params, jnp.asarray(ids, jnp.int32), cfg))
    np.testing.assert_allclose(ours, hf_logits, rtol=2e-4, atol=2e-4)


def test_qwen2_export_roundtrip(tmp_path):
    """Export a bias-carrying model as a native Qwen2 checkpoint and read
    it back bit-for-bit."""
    import jax

    from ditl_tpu.models.convert import export_hf_model, load_hf_model

    from ditl_tpu.config import ModelConfig

    cfg = ModelConfig(
        name="tiny-qwen", vocab_size=256, hidden_size=64,
        intermediate_size=128, num_layers=2, num_heads=4, num_kv_heads=2,
        head_dim=16, max_seq_len=128, attention_bias=True,
        param_dtype="float32", dtype="float32",
    )
    params = llama.init_params(jax.random.key(3), cfg)
    # non-zero biases so the round-trip carries information
    params["layers"]["attn"]["bq"] = params["layers"]["attn"]["bq"] + 0.25
    export_hf_model(params, cfg, str(tmp_path / "hf"))
    back_params, back_cfg = load_hf_model(str(tmp_path / "hf"), dtype="float32")
    assert back_cfg.attention_bias
    np.testing.assert_array_equal(
        np.asarray(back_params["layers"]["attn"]["bq"]),
        np.asarray(params["layers"]["attn"]["bq"]),
    )
    rng = np.random.default_rng(3)
    ids = jnp.asarray(rng.integers(0, 256, size=(1, 12)), jnp.int32)
    np.testing.assert_allclose(
        np.asarray(llama.forward(back_params, ids, back_cfg)),
        np.asarray(llama.forward(params, ids, cfg)),
        rtol=1e-5, atol=1e-5,
    )


def test_mixtral_logits_parity():
    # One layer: the router softmax amplifies float noise across layers (a
    # ~4e-5 block-output difference can flip near-tie routing downstream), so
    # depth-stacked comparisons are only loosely bounded; one layer is tight.
    cfg_hf = transformers.MixtralConfig(
        vocab_size=256,
        hidden_size=64,
        intermediate_size=128,
        num_hidden_layers=1,
        num_attention_heads=4,
        num_key_value_heads=2,
        num_local_experts=4,
        num_experts_per_tok=2,
        max_position_embeddings=128,
        rms_norm_eps=1e-5,
        rope_theta=10000.0,
    )
    torch.manual_seed(1)
    model = transformers.MixtralForCausalLM(cfg_hf).eval()
    cfg = config_from_hf(model.config, dtype="float32")
    assert cfg.num_experts == 4 and cfg.num_experts_per_tok == 2
    params = params_from_state_dict(model.state_dict(), cfg)

    rng = np.random.default_rng(1)
    ids = rng.integers(0, 256, size=(2, 16)).astype(np.int64)
    with torch.no_grad():
        hf_logits = model(torch.from_numpy(ids)).logits.numpy()

    ours = np.asarray(llama.forward(params, jnp.asarray(ids, jnp.int32), cfg))
    np.testing.assert_allclose(ours, hf_logits, rtol=5e-4, atol=5e-4)


def _tiny_hf_olmoe(norm_topk_prob=False):
    cfg_hf = transformers.OlmoeConfig(
        vocab_size=256, hidden_size=64, intermediate_size=32,
        num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=4,
        num_experts=8, num_experts_per_tok=2, norm_topk_prob=norm_topk_prob,
        max_position_embeddings=128, rms_norm_eps=1e-5, rope_theta=10000.0,
        tie_word_embeddings=False,
    )
    torch.manual_seed(2)
    model = transformers.OlmoeForCausalLM(cfg_hf).eval()
    with torch.no_grad():  # a norm scale of 1 would let a dropped scale pass
        for layer in model.model.layers:
            for norm in (layer.self_attn.q_norm, layer.self_attn.k_norm):
                norm.weight.mul_(1.0 + 0.3 * torch.randn_like(norm.weight))
    return model


@pytest.mark.parametrize("norm_topk_prob", [False, True])
def test_olmoe_logits_parity_and_round_trip(norm_topk_prob):
    """The published model's Hugging Face names load (``mlp.gate``,
    ``mlp.experts.{j}.gate_proj / up_proj / down_proj``, ``self_attn.q_norm /
    k_norm``), the program agrees with ``OlmoeForCausalLM`` on its logits, and
    the export gives the state dict back. Two layers in float32: a router's
    near-tie can flip on float noise, so the tolerance is Mixtral's."""
    model = _tiny_hf_olmoe(norm_topk_prob)
    cfg = config_from_hf(model.config, dtype="float32")
    assert (cfg.num_experts, cfg.num_experts_per_tok) == (8, 2)
    assert cfg.qk_norm and cfg.norm_topk_prob is norm_topk_prob
    assert not cfg.attention_bias and not cfg.tie_embeddings
    sd = model.state_dict()
    params = params_from_state_dict(sd, cfg)
    assert params["layers"]["attn"]["q_norm"].shape == (2, 64)
    assert params["layers"]["moe"]["w_gate"].shape == (2, 8, 64, 32)

    rng = np.random.default_rng(2)
    ids = rng.integers(0, 256, size=(2, 16)).astype(np.int64)
    with torch.no_grad():
        hf_logits = model(torch.from_numpy(ids)).logits.numpy()
    ours = np.asarray(llama.forward(params, jnp.asarray(ids, jnp.int32), cfg))
    np.testing.assert_allclose(ours, hf_logits, rtol=5e-4, atol=5e-4)

    back = state_dict_from_params(params, cfg)
    assert set(back) == {k for k in sd if "rotary_emb" not in k}
    for k, v in back.items():
        np.testing.assert_allclose(v, sd[k].numpy(), rtol=1e-6, atol=1e-7)


def test_olmoe_export_loads_in_transformers(tmp_path):
    model = _tiny_hf_olmoe()
    cfg = config_from_hf(model.config, dtype="float32")
    params = params_from_state_dict(model.state_dict(), cfg)
    from ditl_tpu.models.convert import export_hf_model

    export_hf_model(params, cfg, str(tmp_path / "out"))
    again = transformers.OlmoeForCausalLM.from_pretrained(str(tmp_path / "out")).eval()
    ids = torch.from_numpy(np.arange(12, dtype=np.int64)[None] + 3)
    with torch.no_grad():
        np.testing.assert_allclose(again(ids).logits.numpy(), model(ids).logits.numpy(),
                                   rtol=1e-5, atol=1e-5)
    assert again.config.norm_topk_prob is False and again.config.num_experts == 8


def test_config_from_hf_fields():
    model = _tiny_hf_llama()
    cfg = config_from_hf(model.config)
    assert cfg.vocab_size == 256
    assert cfg.hidden_size == 64
    assert cfg.num_layers == 2
    assert cfg.num_heads == 4
    assert cfg.num_kv_heads == 2
    assert cfg.head_dim == 16
    assert cfg.rms_norm_eps == 1e-5


def test_trainer_init_from_hf(tmp_path):
    """End-to-end: save a tiny HF checkpoint to disk, fine-tune from it, and
    confirm the starting params came from the checkpoint (not random init)."""
    import jax

    from ditl_tpu.config import Config, DataConfig, TrainConfig
    from ditl_tpu.train.trainer import train

    hf_cfg = transformers.LlamaConfig(
        vocab_size=512,  # >= byte tokenizer's 259
        hidden_size=64,
        intermediate_size=128,
        num_hidden_layers=2,
        num_attention_heads=4,
        num_key_value_heads=2,
        max_position_embeddings=128,
    )
    torch.manual_seed(0)
    model = transformers.LlamaForCausalLM(hf_cfg)
    model.save_pretrained(tmp_path / "hf_ckpt")
    cfg = config_from_hf(model.config)

    out = train(
        Config(
            model=cfg,
            data=DataConfig(
                synthetic=True, synthetic_examples=64, batch_size=8, seq_len=32,
                num_epochs=1,
            ),
            train=TrainConfig(
                total_steps=2, warmup_steps=1, log_every=100,
                init_from_hf=str(tmp_path / "hf_ckpt"),
            ),
        )
    )
    assert out["steps"] == 2
    assert np.isfinite(out["final_loss"])


def test_trainer_init_from_hf_with_lora(tmp_path):
    """LoRA fine-tune from an HF base: adapters keep fresh init, base weights
    come from the checkpoint, and config mismatches are rejected."""
    import dataclasses

    from ditl_tpu.config import Config, DataConfig, TrainConfig
    from ditl_tpu.train.trainer import train

    hf_cfg = transformers.LlamaConfig(
        vocab_size=512, hidden_size=64, intermediate_size=128,
        num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
        max_position_embeddings=128,
    )
    torch.manual_seed(0)
    transformers.LlamaForCausalLM(hf_cfg).save_pretrained(tmp_path / "hf")
    cfg = dataclasses.replace(config_from_hf(hf_cfg), lora_rank=4)

    out = train(
        Config(
            model=cfg,
            data=DataConfig(synthetic=True, synthetic_examples=64, batch_size=8,
                            seq_len=32, num_epochs=1),
            train=TrainConfig(total_steps=2, warmup_steps=1, log_every=100,
                              init_from_hf=str(tmp_path / "hf")),
        )
    )
    assert out["steps"] == 2 and np.isfinite(out["final_loss"])

    # Wrong architecture must fail loudly, not train on garbage.
    wrong = dataclasses.replace(cfg, num_layers=4)
    with pytest.raises(ValueError, match="does not match the model config"):
        train(
            Config(
                model=wrong,
                data=DataConfig(synthetic=True, synthetic_examples=64,
                                batch_size=8, seq_len=32, num_epochs=1),
                train=TrainConfig(total_steps=1, warmup_steps=1,
                                  init_from_hf=str(tmp_path / "hf")),
            )
        )


def test_export_roundtrip(tmp_path):
    """params -> HF export dir -> from_pretrained -> logits parity."""
    import dataclasses

    import jax

    from ditl_tpu.models.convert import export_hf_model, load_hf_model

    cfg0 = config_from_hf(_tiny_hf_llama().config, dtype="float32")
    params = llama.init_params(jax.random.key(7), cfg0)
    export_hf_model(params, cfg0, str(tmp_path / "export"))

    model = transformers.AutoModelForCausalLM.from_pretrained(
        str(tmp_path / "export"), local_files_only=True
    ).eval()
    rng = np.random.default_rng(3)
    ids = rng.integers(0, 256, size=(2, 16)).astype(np.int64)
    with torch.no_grad():
        hf_logits = model(torch.from_numpy(ids)).logits.numpy()
    ours = np.asarray(llama.forward(params, jnp.asarray(ids, jnp.int32), cfg0))
    np.testing.assert_allclose(ours, hf_logits, rtol=2e-4, atol=2e-4)

    # Round-trip through load_hf_model reproduces the exact same tree.
    params2, cfg2 = load_hf_model(str(tmp_path / "export"))
    flat1 = jax.tree_util.tree_leaves_with_path(params)
    flat2 = dict(
        (jax.tree_util.keystr(p), a) for p, a in jax.tree_util.tree_leaves_with_path(params2)
    )
    for path, leaf in flat1:
        np.testing.assert_allclose(
            np.asarray(leaf), flat2[jax.tree_util.keystr(path)], rtol=1e-6, atol=1e-6
        )


def test_merge_lora_preserves_function():
    """Merged W + (alpha/r)AB computes exactly the adapted model's logits."""
    import dataclasses

    import jax

    from ditl_tpu.models.lora import merge_lora

    base_cfg = config_from_hf(_tiny_hf_llama().config, dtype="float32")
    lora_cfg = dataclasses.replace(base_cfg, lora_rank=4)
    params = llama.init_params(jax.random.key(5), lora_cfg)
    # Give B nonzero values so the adapters actually do something.
    params["layers"]["lora"] = jax.tree.map(
        lambda x: x + 0.01, params["layers"]["lora"]
    )
    ids = jnp.asarray(np.random.default_rng(4).integers(0, 256, size=(2, 16)), jnp.int32)
    adapted = llama.forward(params, ids, lora_cfg)

    merged = merge_lora(params, lora_cfg)
    assert "lora" not in merged["layers"]
    merged_logits = llama.forward(merged, ids, base_cfg)
    np.testing.assert_allclose(
        np.asarray(merged_logits), np.asarray(adapted), rtol=2e-4, atol=2e-4
    )


def test_export_rejects_unmerged_lora():
    import dataclasses

    from ditl_tpu.models.convert import state_dict_from_params

    cfg = dataclasses.replace(
        config_from_hf(_tiny_hf_llama().config, dtype="float32"), lora_rank=4
    )
    import jax

    params = llama.init_params(jax.random.key(0), cfg)
    with pytest.raises(ValueError, match="merge_lora"):
        state_dict_from_params(params, cfg)


def test_llama31_rope_scaling_parity():
    """HF 'llama3' rope_scaling (the Llama-3.1 long-context NTK scheme) is
    reproduced exactly — including at positions past the original context."""
    cfg_hf = transformers.LlamaConfig(
        vocab_size=256,
        hidden_size=64,
        intermediate_size=128,
        num_hidden_layers=2,
        num_attention_heads=4,
        num_key_value_heads=2,
        max_position_embeddings=128,
        rope_theta=10000.0,
        rope_scaling={
            "rope_type": "llama3",
            "factor": 4.0,
            "low_freq_factor": 1.0,
            "high_freq_factor": 4.0,
            "original_max_position_embeddings": 32,
        },
    )
    torch.manual_seed(3)
    model = transformers.LlamaForCausalLM(cfg_hf).eval()
    cfg = config_from_hf(model.config, dtype="float32")
    assert cfg.rope_scaling_factor == 4.0
    assert cfg.rope_scaling_original_max_len == 32
    params = params_from_state_dict(model.state_dict(), cfg)

    rng = np.random.default_rng(5)
    # 96 tokens: well past the 32-token original context, where scaling bites.
    ids = rng.integers(0, 256, size=(1, 96)).astype(np.int64)
    with torch.no_grad():
        hf_logits = model(torch.from_numpy(ids)).logits.numpy()
    ours = np.asarray(llama.forward(params, jnp.asarray(ids, jnp.int32), cfg))
    np.testing.assert_allclose(ours, hf_logits, rtol=3e-4, atol=3e-4)


def test_unsupported_rope_scaling_rejected():
    cfg_hf = transformers.LlamaConfig(
        vocab_size=256, hidden_size=64, intermediate_size=128,
        num_hidden_layers=1, num_attention_heads=4, num_key_value_heads=2,
        max_position_embeddings=128,
        rope_scaling={"rope_type": "yarn", "factor": 2.0},
    )
    with pytest.raises(ValueError, match="unsupported rope_scaling"):
        config_from_hf(cfg_hf)


def test_export_roundtrips_rope_scaling(tmp_path):
    """Exported HF config carries the llama3 rope_scaling block."""
    import dataclasses

    import jax

    from ditl_tpu.models.convert import export_hf_model

    cfg = dataclasses.replace(
        config_from_hf(_tiny_hf_llama().config, dtype="float32"),
        rope_scaling_factor=8.0,
        rope_scaling_original_max_len=32,
    )
    params = llama.init_params(jax.random.key(9), cfg)
    export_hf_model(params, cfg, str(tmp_path / "scaled"))
    reloaded = transformers.AutoConfig.from_pretrained(
        str(tmp_path / "scaled"), local_files_only=True
    )
    assert reloaded.rope_scaling is not None
    assert reloaded.rope_scaling.get("rope_type") == "llama3"
    assert reloaded.rope_scaling["factor"] == 8.0


def test_export_cli_from_orbax_checkpoint(tmp_path):
    """Orbax training checkpoint -> `python -m ditl_tpu.models.convert` ->
    loadable HF directory (full train-to-serve-anywhere workflow)."""
    import jax

    from ditl_tpu.models.convert import main as convert_main
    from ditl_tpu.models.presets import PRESETS
    from ditl_tpu.config import Config, DataConfig, ModelConfig, TrainConfig
    from ditl_tpu.train.trainer import train

    model = ModelConfig(
        name="tiny-export", vocab_size=512, hidden_size=64,
        intermediate_size=128, num_layers=2, num_heads=4, num_kv_heads=2,
        head_dim=16, max_seq_len=64,
    )
    PRESETS["tiny-export"] = model  # register so the CLI can resolve it
    try:
        train(
            Config(
                model=model,
                data=DataConfig(synthetic=True, synthetic_examples=64,
                                batch_size=8, seq_len=32, num_epochs=1),
                train=TrainConfig(total_steps=2, warmup_steps=1, log_every=100,
                                  checkpoint_dir=str(tmp_path / "ckpt"),
                                  checkpoint_every=1),
            )
        )
        rc = convert_main([
            "--checkpoint-dir", str(tmp_path / "ckpt"),
            "--preset", "tiny-export",
            "--out", str(tmp_path / "hf_out"),
        ])
        assert rc == 0
        reloaded = transformers.AutoModelForCausalLM.from_pretrained(
            str(tmp_path / "hf_out"), local_files_only=True
        )
        assert reloaded.config.vocab_size == 512
    finally:
        PRESETS.pop("tiny-export", None)
