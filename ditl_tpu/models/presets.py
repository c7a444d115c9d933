"""Named model presets for the BASELINE.json target configs.

Preset definitions are part of checkpoint provenance: serving or exporting a
checkpoint under a preset whose architecture/RoPE fields changed since
training silently changes the math (RoPE scaling and context length are not
stored in the param tree, so restore cannot detect it). Treat existing preset
names as frozen — new variants get NEW names (e.g. llama31-8b vs llama3-8b).
"""

from __future__ import annotations

from dataclasses import replace

from ditl_tpu.config import ModelConfig

PRESETS: dict[str, ModelConfig] = {
    # Debug/test model: small but architecturally identical to Llama-3.1.
    "tiny-llama": ModelConfig(),
    "tiny-moe": ModelConfig(
        name="tiny-moe", num_experts=8, num_experts_per_tok=2, intermediate_size=344
    ),
    "llama3-8b": ModelConfig(
        name="llama3-8b",
        vocab_size=128256,
        hidden_size=4096,
        intermediate_size=14336,
        num_layers=32,
        num_heads=32,
        num_kv_heads=8,
        head_dim=128,
        max_seq_len=8192,
        rope_theta=500000.0,
    ),
    "llama3-70b": ModelConfig(
        name="llama3-70b",
        vocab_size=128256,
        hidden_size=8192,
        intermediate_size=28672,
        num_layers=80,
        num_heads=64,
        num_kv_heads=8,
        head_dim=128,
        max_seq_len=8192,
        rope_theta=500000.0,
    ),
    # Llama-3.1: long context via NTK rope scaling (separate names so
    # checkpoints trained under the 3.0-style presets keep their RoPE).
    "llama31-8b": ModelConfig(
        name="llama31-8b",
        vocab_size=128256,
        hidden_size=4096,
        intermediate_size=14336,
        num_layers=32,
        num_heads=32,
        num_kv_heads=8,
        head_dim=128,
        max_seq_len=131072,
        rope_theta=500000.0,
        rope_scaling_factor=8.0,
    ),
    "llama31-70b": ModelConfig(
        name="llama31-70b",
        vocab_size=128256,
        hidden_size=8192,
        intermediate_size=28672,
        num_layers=80,
        num_heads=64,
        num_kv_heads=8,
        head_dim=128,
        max_seq_len=131072,
        rope_theta=500000.0,
        rope_scaling_factor=8.0,
    ),
    # Qwen2/2.5-family (q/k/v attention bias, rope 1e6; the 7B unties
    # embeddings, the 0.5B ties them).
    "qwen2-7b": ModelConfig(
        name="qwen2-7b",
        vocab_size=152064,
        hidden_size=3584,
        intermediate_size=18944,
        num_layers=28,
        num_heads=28,
        num_kv_heads=4,
        head_dim=128,
        max_seq_len=32768,
        rope_theta=1000000.0,
        rms_norm_eps=1e-6,
        attention_bias=True,
    ),
    "qwen2-0.5b": ModelConfig(
        name="qwen2-0.5b",
        vocab_size=151936,
        hidden_size=896,
        intermediate_size=4864,
        num_layers=24,
        num_heads=14,
        num_kv_heads=2,
        head_dim=64,
        max_seq_len=32768,
        rope_theta=1000000.0,
        rms_norm_eps=1e-6,
        attention_bias=True,
        tie_embeddings=True,
    ),
    "mixtral-8x7b": ModelConfig(
        name="mixtral-8x7b",
        vocab_size=32000,
        hidden_size=4096,
        intermediate_size=14336,
        num_layers=32,
        num_heads=32,
        num_kv_heads=8,
        head_dim=128,
        max_seq_len=32768,
        rope_theta=1000000.0,
        num_experts=8,
        num_experts_per_tok=2,
    ),
    # OLMoE-1B-7B (allenai, arXiv:2409.02060): 64 experts of width 1024, 8 a
    # token with their softmax weights NOT renormalised, q/k normalisation,
    # 16 kv heads (no grouped queries), untied head.
    "olmoe-1b-7b": ModelConfig(
        name="olmoe-1b-7b",
        vocab_size=50304,
        hidden_size=2048,
        intermediate_size=1024,
        num_layers=16,
        num_heads=16,
        num_kv_heads=16,
        head_dim=128,
        max_seq_len=4096,
        rope_theta=10000.0,
        rms_norm_eps=1e-5,
        qk_norm=True,
        num_experts=64,
        num_experts_per_tok=8,
        norm_topk_prob=False,
    ),
    # LongCat-Flash-Chat (meituan-longcat, 560 B parameters): 28 shortcut-
    # connected double layers, latent attention (MLA), a 768-way softmax
    # router over 512 routed experts of width 2,048 and 256 identity
    # (zero-compute) experts, 12 a token, weights 6 x the probabilities as
    # they are, a selection bias for the choice only. No chip holds a layer
    # (39.9 GB in bf16): serve a share with ``model.experts_held_first`` /
    # ``model.experts_held_count``, fewer layers and a slice of the
    # vocabulary (benchmarks/configs/longcat-flash-cut1.json).
    "longcat-flash": ModelConfig(
        name="longcat-flash",
        vocab_size=131072,
        hidden_size=6144,
        intermediate_size=12288,
        expert_ffn_hidden_size=2048,
        num_layers=28,
        num_heads=64,
        num_kv_heads=64,
        head_dim=192,  # a query's / key's: 128 without position + 64 rotary
        max_seq_len=131072,
        rope_theta=10000000.0,
        rms_norm_eps=1e-5,
        q_lora_rank=1536,
        kv_lora_rank=512,
        qk_nope_head_dim=128,
        qk_rope_head_dim=64,
        v_head_dim=128,
        mla_scale_q_lora=True,
        mla_scale_kv_lora=True,
        num_experts=512,
        zero_expert_num=256,
        num_experts_per_tok=12,
        norm_topk_prob=False,
        routed_scaling_factor=6.0,
        router_bias=True,
    ),
    # Granite-4.0-H-Micro (ibm-granite, 3.19 B parameters, model_type
    # granitemoehybrid with no routed experts in this size): 40 layers in
    # four periods of nine Mamba-2 mixers and one grouped-query attention
    # layer (indices 5, 15, 25, 35), no positional embedding, a fused gate|up
    # FFN of 8,192 after every mixer, tied head, and Granite's four scalars.
    # Whole on one chip in bfloat16 (5.94 GiB); models/ssm.py.
    "granite-4.0-h-micro": ModelConfig(
        name="granite-4.0-h-micro",
        vocab_size=100352,
        hidden_size=2048,
        intermediate_size=8192,
        num_layers=40,
        num_heads=32,
        num_kv_heads=8,
        head_dim=64,
        max_seq_len=131072,
        rope_theta=10000.0,
        rms_norm_eps=1e-5,
        tie_embeddings=True,
        fused_gate_up=True,
        layer_types="mmmmmammmm" * 4,
        ssm_heads=64,
        ssm_head_dim=64,
        ssm_state=128,
        ssm_conv=4,
        ssm_chunk=256,
        position_embedding="nope",
        residual_dtype="float32",
        embedding_multiplier=12.0,
        residual_multiplier=0.22,
        attention_multiplier=0.015625,
        logits_scaling=8.0,
    ),
    # DeepSeek-V3.2 (deepseek-ai, 671 B parameters, model_type deepseek_v32):
    # 61 layers, the first three with a dense FFN of 18,432 and the rest with
    # 256 routed experts of 2,048 (8 a token, chosen by sigmoid scores inside
    # the best 4 of 8 groups) beside one shared expert; latent attention in a
    # single pre-norm block, 128 heads, YaRN x 40 over 4,096 positions; a
    # lightning indexer (64 heads of 128) picks the 2,048 cached tokens a
    # query attends to. No chip holds a layer: served as a share
    # (``experts_held_*``, fewer layers; benchmarks/configs/
    # deepseek-v3.2-cut1.json); models/dsa.py. The multi-token-prediction
    # module (num_nextn_predict_layers 1) is not implemented.
    "deepseek-v3.2": ModelConfig(
        name="deepseek-v3.2",
        vocab_size=129280,
        hidden_size=7168,
        intermediate_size=18432,
        expert_ffn_hidden_size=2048,
        num_layers=61,
        num_heads=128,
        num_kv_heads=128,
        head_dim=192,  # a query's / key's: 128 without position + 64 rotary
        max_seq_len=163840,
        rope_theta=10000.0,
        rms_norm_eps=1e-6,
        q_lora_rank=1536,
        kv_lora_rank=512,
        qk_nope_head_dim=128,
        qk_rope_head_dim=64,
        v_head_dim=128,
        index_n_heads=64,
        index_head_dim=128,
        index_topk=2048,
        rope_yarn_factor=40.0,
        rope_yarn_original_max_len=4096,
        rope_yarn_beta_fast=32.0,
        rope_yarn_beta_slow=1.0,
        rope_yarn_mscale_all_dim=1.0,
        num_experts=256,
        num_experts_per_tok=8,
        norm_topk_prob=True,
        routed_scaling_factor=2.5,
        router_bias=True,
        scoring_func="sigmoid",
        n_group=8,
        topk_group=4,
        n_shared_experts=1,
        first_k_dense_replace=3,
        experts_held_first=0,
        experts_held_count=256,
    ),
    # Kanana-2-30B-A3B (kakaocorp/kanana-2-30b-a3b-instruct-2601, ``model_type:
    # deepseek_v3``; the cut that is TRAINED is benchmarks/configs/
    # kanana-2-30b-a3b-cut1.json); models/dsa.py without its three extras: no
    # query latent (``q_lora_rank`` null: one ``wq``), no YaRN, no indexer.
    # 48 layers, one leading dense FFN of 6,144, 128 experts of 768 (6 a token,
    # sigmoid scores renormalised x 2.448, a bias for the choice, no group
    # limiting, two shared experts: one FFN of 1,536), an untied head. The loss
    # has no auxiliary term (``topk_method: noaux_tc``).
    "kanana-2-30b-a3b": ModelConfig(
        name="kanana-2-30b-a3b",
        vocab_size=128256,
        hidden_size=2048,
        intermediate_size=6144,
        expert_ffn_hidden_size=768,
        num_layers=48,
        num_heads=32,
        num_kv_heads=32,
        head_dim=192,  # a query's / key's: 128 without position + 64 rotary
        max_seq_len=32768,
        rope_theta=1000000.0,
        rms_norm_eps=1e-6,
        kv_lora_rank=512,
        qk_nope_head_dim=128,
        qk_rope_head_dim=64,
        v_head_dim=128,
        num_experts=128,
        num_experts_per_tok=6,
        norm_topk_prob=True,
        routed_scaling_factor=2.448,
        router_bias=True,
        router_aux_coef=0.0,
        scoring_func="sigmoid",
        n_group=1,
        topk_group=1,
        n_shared_experts=2,
        first_k_dense_replace=1,
        experts_held_first=0,
        experts_held_count=128,
    ),
    # Trinity-Mini (arcee-ai/Trinity-Mini, ``model_type: afmoe``; the cut that
    # is served is benchmarks/configs/trinity-mini-cut1.json); models/swa.py.
    # Eight periods of three window-2,048 layers (rope) and one full layer
    # (no positional term), gated attention, q/k norm a head, four norms a
    # layer, two leading dense layers, 128 experts of 1,024 (8 a token, sigmoid
    # scores renormalised and scaled, a bias for the choice, one shared
    # expert), the embedding times sqrt(hidden) (``mup_enabled``).
    "trinity-mini": ModelConfig(
        name="trinity-mini",
        vocab_size=200192,
        hidden_size=2048,
        intermediate_size=6144,
        expert_ffn_hidden_size=1024,
        num_layers=32,
        num_heads=32,
        num_kv_heads=4,
        head_dim=128,
        max_seq_len=131072,
        rope_theta=10000.0,
        rms_norm_eps=1e-5,
        qk_norm=True,
        layer_types="wwwa" * 8,
        sliding_window=2048,
        position_embedding="rope_window",
        attn_gate=True,
        sandwich_norm=True,
        embedding_multiplier=2048 ** 0.5,
        num_experts=128,
        num_experts_per_tok=8,
        norm_topk_prob=True,
        routed_scaling_factor=2.826,
        router_bias=True,
        scoring_func="sigmoid",
        n_shared_experts=1,
        first_k_dense_replace=2,
        experts_held_first=0,
        experts_held_count=128,
    ),
    # Brumby-14B-Base (manifestai, ``model_type: brumby``; the cut that is
    # served is benchmarks/configs/brumby-14b-cut1.json): the Qwen3-14B block
    # (40 layers, 40 query heads in 8 groups of 128-wide heads, q/k norm a
    # head, rotation, a SwiGLU FFN of 17,408, an untied head of 151,936) with
    # gated power retention of degree 2 in place of softmax attention: no
    # keys and values, a float32 state of 8 x 128 x 9,216 a layer a sequence
    # (36 MiB); models/retention.py.
    "brumby-14b": ModelConfig(
        name="brumby-14b",
        vocab_size=151936,
        hidden_size=5120,
        intermediate_size=17408,
        num_layers=40,
        num_heads=40,
        num_kv_heads=8,
        head_dim=128,
        max_seq_len=32768,
        rope_theta=1000000.0,
        rms_norm_eps=1e-6,
        qk_norm=True,
        fused_gate_up=True,
        layer_types="r" * 40,
        ret_degree=2,
        ret_chunk=128,
        ret_eps=1e-5,
    ),
}


def get_preset(name: str, **overrides) -> ModelConfig:
    if name not in PRESETS:
        raise KeyError(f"unknown preset {name!r}; available: {sorted(PRESETS)}")
    cfg = PRESETS[name]
    return replace(cfg, **overrides) if overrides else cfg
