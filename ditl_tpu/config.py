"""Typed configuration system (L0).

The reference keeps its configuration in a git-ignored Python module exporting a
5-key dict — ``from config import CONFIG`` (ref ``src/distributed_inference.py:12``,
``.gitignore:29``, ``docs/setup_guide.md:43-46``) — with secrets stored in the
module and rendezvous info duplicated between CONFIG and launcher CLI flags
(defect #5 in SURVEY.md §2). This module replaces that with:

- frozen dataclasses per concern (runtime / mesh / model / data / train / api),
- secrets **only** from environment variables (never stored in config files),
- a single source of truth for rendezvous info (``RuntimeConfig``),
- dotted-path CLI overrides (``train.batch_size=8``) for the launcher.

Reference key mapping:
  ``MASTER_ADDR``/``MASTER_PORT`` -> ``RuntimeConfig.coordinator_address``
  ``MODEL_NAME``                  -> ``APIConfig.model_name``
  ``API_BASE``                    -> ``APIConfig.api_base``
  ``API_KEY``                     -> env ``OPENAI_API_KEY`` (read lazily, never persisted)
"""

from __future__ import annotations

import dataclasses
import json
import os
from dataclasses import dataclass, field, fields, replace
from typing import Any, Mapping, Sequence

__all__ = [
    "RuntimeConfig",
    "MeshConfig",
    "ModelConfig",
    "DataConfig",
    "TrainConfig",
    "APIConfig",
    "GatewayConfig",
    "AutoscaleConfig",
    "AdapterConfig",
    "ChaosConfig",
    "BulkConfig",
    "TelemetryConfig",
    "Config",
    "parse_overrides",
    "config_fingerprint",
]


@dataclass(frozen=True)
class RuntimeConfig:
    """Distributed-runtime bring-up (replaces NCCL env + ``setup()``, ref
    ``src/distributed_inference.py:14-18``).

    On a real TPU pod, ``jax.distributed.initialize()`` autodetects everything
    and all fields may stay ``None``. For CPU simulation or explicit multi-host
    runs, ``coordinator_address`` is the analog of ``MASTER_ADDR:MASTER_PORT``.
    """

    coordinator_address: str | None = None  # "host:port"; None => autodetect
    num_processes: int | None = None  # analog of WORLD_SIZE (ref :47)
    process_id: int | None = None  # analog of RANK (ref :46)
    simulate_devices: int = 0  # >0 => force N virtual CPU devices (tests/sim)
    distributed: bool = False  # True => call jax.distributed.initialize
    log_level: str = "INFO"
    profiler_port: int = 0  # >0 => start jax.profiler server on this port
    # Persistent XLA compilation cache, on by default: restarts, elastic
    # relaunches and repeat runs reuse compiled programs. WHERE it lives is
    # not a config value: JAX_COMPILATION_CACHE_DIR when the environment
    # sets it, else one fixed git-ignored directory inside the checkout
    # (runtime/distributed.enable_compile_cache). On CPU the cache is only
    # honored for single-device, single-process runs — XLA:CPU
    # intermittently crashes (SIGABRT/SIGSEGV) deserializing cached
    # executables under the multi-device host platform and in
    # multi-process gloo pods (see tests/conftest.py and
    # docs/troubleshooting.md §20).
    compile_cache: bool = True


@dataclass(frozen=True)
class MeshConfig:
    """Device-mesh shape. Axis sizes of 1 are kept in the mesh (harmless to
    XLA) so a single step function serves DP, FSDP, TP, SP and EP without
    rewriting — SURVEY.md §7 'hard part (b)'.

    ``data``: pure data parallelism (batch split, the reference's only strategy).
    ``fsdp``: parameter/optimizer sharding (ZeRO-3/GSPMD style) — also splits batch.
    ``stage``: GPipe-style pipeline parallelism (layer dim split, parallel/pipeline.py).
    ``sequence``: sequence/context parallelism (ring attention axis).
    ``tensor``: megatron-style tensor parallelism within a layer.
    ``expert``: MoE expert parallelism.
    A value of -1 means "absorb all remaining devices" (at most one axis).
    """

    data: int = -1
    fsdp: int = 1
    stage: int = 1
    sequence: int = 1
    tensor: int = 1
    expert: int = 1

    @property
    def axis_names(self) -> tuple[str, ...]:
        return ("data", "fsdp", "stage", "sequence", "tensor", "expert")

    def sizes(self) -> tuple[int, ...]:
        return (self.data, self.fsdp, self.stage, self.sequence, self.tensor, self.expert)

    def resolve(self, n_devices: int) -> tuple[int, ...]:
        """Resolve -1 axes against the actual device count; validate product."""
        sizes = list(self.sizes())
        wild = [i for i, s in enumerate(sizes) if s == -1]
        if len(wild) > 1:
            raise ValueError(f"at most one mesh axis may be -1, got {self}")
        fixed = 1
        for i, s in enumerate(sizes):
            if i not in wild:
                if s < 1:
                    raise ValueError(f"mesh axis sizes must be >=1 or -1, got {self}")
                fixed *= s
        if wild:
            if n_devices % fixed != 0:
                raise ValueError(
                    f"{n_devices} devices not divisible by fixed mesh product {fixed}"
                )
            sizes[wild[0]] = n_devices // fixed
        elif fixed != n_devices:
            raise ValueError(
                f"mesh {tuple(sizes)} needs {fixed} devices but {n_devices} are present"
            )
        return tuple(sizes)


@dataclass(frozen=True)
class ModelConfig:
    """Llama/Mixtral-family architecture hyperparameters.

    Defaults describe a tiny debug model; ``presets.py`` provides llama3-8b/70b
    and mixtral-8x7b shapes. ``num_experts == 0`` means dense MLP.
    """

    name: str = "tiny-llama"
    vocab_size: int = 32000
    hidden_size: int = 256
    intermediate_size: int = 688
    num_layers: int = 4
    num_heads: int = 8
    num_kv_heads: int = 4  # grouped-query attention; == num_heads => MHA
    head_dim: int = 32
    max_seq_len: int = 2048
    rope_theta: float = 500000.0
    # Llama-3.1-style NTK RoPE scaling; factor 0 disables. Matches HF's
    # "llama3" rope_scaling semantics (models/llama.py rope_frequencies).
    rope_scaling_factor: float = 0.0
    rope_scaling_low_freq_factor: float = 1.0
    rope_scaling_high_freq_factor: float = 4.0
    rope_scaling_original_max_len: int = 8192
    rms_norm_eps: float = 1e-5
    tie_embeddings: bool = False
    # Qwen2-style attention: bias on the q/k/v projections (o stays
    # bias-free, matching the family).
    attention_bias: bool = False
    # OLMoE-style q/k normalisation: an RMSNorm with its own learned scale
    # over the WHOLE projected q and k vectors (all heads together), between
    # the projections and RoPE.
    qk_norm: bool = False
    dtype: str = "bfloat16"  # activation/compute dtype
    param_dtype: str = "float32"  # master parameter dtype
    # MoE (Mixtral-style); num_experts == 0 disables.
    num_experts: int = 0
    num_experts_per_tok: int = 2
    # Whether the chosen experts' softmax weights are rescaled to sum to 1
    # (Mixtral does; OLMoE's ``norm_topk_prob: false`` keeps them as they are).
    norm_topk_prob: bool = True
    router_aux_coef: float = 0.01  # Switch-style load-balancing loss weight
    # LongCat-Flash-style expert layer (models/moe.py ``_shared_moe_block``).
    # The router has ``num_experts + zero_expert_num`` outputs: the routed
    # experts first, then ``zero_expert_num`` zero-compute experts, each the
    # identity (no weights). A per-expert selection bias joins the softmax
    # probabilities for the CHOICE only (``router_bias``); the chosen
    # probabilities, as they are, times ``routed_scaling_factor`` weigh the
    # outputs.
    zero_expert_num: int = 0
    routed_scaling_factor: float = 1.0
    router_bias: bool = False
    # The width of one routed expert where it differs from the dense FFN's
    # ``intermediate_size`` (a double layer has both); 0 = the same.
    expert_ffn_hidden_size: int = 0
    # The experts held HERE: ``experts_held_count`` routed experts from
    # index ``experts_held_first`` on (0 = all of them). One chip's share of
    # an expert-parallel deployment: the router keeps its full width, held
    # experts are computed, the others' part of the sum is left out (it is
    # another chip's), zero-compute experts are computed everywhere.
    experts_held_first: int = 0
    experts_held_count: int = 0
    # Multi-head latent attention (DeepSeek-V2 / LongCat-Flash): active when
    # ``kv_lora_rank > 0``. Queries go through a ``q_lora_rank`` bottleneck
    # with its own norm; keys and values are up-projected from ONE latent
    # vector a token (``kv_lora_rank`` wide, normed) plus a rotary key of
    # ``qk_rope_head_dim`` shared by all heads: what the cache stores. Each
    # head's query/key is ``qk_nope_head_dim + qk_rope_head_dim`` wide, its
    # value ``v_head_dim``. ``mla_scale_*``: the normed latents times
    # sqrt(hidden_size / rank). Rotary pairs are neighbouring elements (2i,
    # 2i+1), not the two halves. Two blocks carry it, told apart by ONE
    # stated fact, ``first_k_dense_replace``: 0 is LongCat-Flash's
    # shortcut-connected double layer (models/mla.py: two attention sublayers
    # and two dense FFNs a layer, and one expert block that reads the first
    # post-attention norm and joins at the layer's end; always a query
    # latent); > 0 is the single pre-norm block behind leading dense layers
    # (models/dsa.py; DeepSeek-V3's family), whose query latent
    # (``q_lora_rank``, 0 = one ``wq``), YaRN (``rope_yarn_factor``) and
    # lightning indexer (``index_topk``) are each optional (``double_layer``,
    # ``dsa_layer``, ``indexed`` below).
    q_lora_rank: int = 0
    kv_lora_rank: int = 0
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0
    mla_scale_q_lora: bool = False
    mla_scale_kv_lora: bool = False
    # DeepSeek-V3.2's lightning indexer (models/dsa.py), ``index_topk > 0``:
    # every query of the single pre-norm block attends to the ``index_topk``
    # cached tokens the indexer scores highest (``index_n_heads`` heads of
    # ``index_head_dim``, fed by the query latent, so it needs one; one index
    # key a token, cached beside the latent entry). 0: attention is dense.
    index_n_heads: int = 0
    index_head_dim: int = 0
    index_topk: int = 0
    # YaRN on the rotary frequencies of that block (``rope_yarn_factor > 0``):
    # each frequency a blend of ``f`` and ``f / factor`` by a linear ramp
    # between the correction dimensions of ``beta_fast`` and ``beta_slow``
    # rotations over ``original_max_len`` positions; the softmax scale times
    # ``(0.1 * mscale_all_dim * ln(factor) + 1) ** 2`` (models/dsa.py). cos and
    # sin are never scaled: the source's ``mscale`` equals ``mscale_all_dim``,
    # whose ratio would scale them.
    rope_yarn_factor: float = 0.0
    rope_yarn_original_max_len: int = 4096
    rope_yarn_beta_fast: float = 32.0
    rope_yarn_beta_slow: float = 1.0
    rope_yarn_mscale_all_dim: float = 0.0
    # Its expert layer (models/moe.py ``_shared_moe_block``): ``scoring_func``
    # "sigmoid" scores each expert on its own (float32) where "softmax"
    # scores them against each other; with ``n_group > 1`` the experts lie
    # in ``n_group`` equal groups (1, like 0, is no group limiting: one group
    # that always stays), a group's score is the sum of its two
    # largest biased scores, and a token chooses inside its ``topk_group``
    # best groups only; ``n_shared_experts`` SwiGLU experts of the routed
    # experts' width (side by side: one FFN of n x width) see every token.
    # The first ``first_k_dense_replace`` layers have the dense FFN of
    # ``intermediate_size`` instead of experts.
    scoring_func: str = "softmax"
    n_group: int = 0
    topk_group: int = 0
    n_shared_experts: int = 0
    first_k_dense_replace: int = 0
    # A stack of UNLIKE layers (Granite-4.0-H: Mamba-2 state-space mixers
    # beside attention): one letter a layer, ``m`` a Mamba-2 mixer, ``a``
    # grouped-query attention, every layer followed by the dense FFN; "" =
    # every layer attention (every other family). ``num_layers`` letters; the
    # stack is scanned by PERIODS, the shortest prefix the string repeats
    # (``layer_period``; models/ssm.py). A mixer has ``ssm_heads`` heads of
    # ``ssm_head_dim`` (the inner width is their product), ONE group of
    # ``ssm_state`` state columns, a causal depthwise convolution of
    # ``ssm_conv`` taps with bias, and runs a whole sequence in chunks of
    # ``ssm_chunk`` tokens (ops/ssd.py).
    layer_types: str = ""
    ssm_heads: int = 0
    ssm_head_dim: int = 0
    ssm_state: int = 0
    ssm_conv: int = 4
    ssm_chunk: int = 256
    # A stack of POWER-RETENTION layers (Brumby: models/retention.py), every
    # letter of ``layer_types`` an ``r``: the dense block's projections,
    # ``qk_norm`` a head and rotation, and in place of softmax attention the
    # gated retention of degree ``ret_degree`` (2, the only even degree whose
    # state fits a chip: ops/retention.py), a gate a kv head, the output
    # divided by the sum of its weights plus ``ret_eps``; a call's tokens
    # run ``ret_chunk`` queries at a time. Such a stack keeps NO keys and
    # values: its cache entry is a state a slot and nothing else.
    ret_degree: int = 2
    ret_chunk: int = 128
    ret_eps: float = 1e-5
    # "rope" rotates queries and keys; "nope" applies no positional term at
    # all (Granite-4.0-H: position comes from the recurrence alone).
    position_embedding: str = "rope"
    # A stack with WINDOW attention layers (Trinity / afmoe: models/swa.py),
    # selected by a ``w`` in ``layer_types``: a ``w`` layer's query i sees key j
    # iff ``0 <= i - j < sliding_window`` (itself among them), an ``a`` layer's
    # every ``j <= i``. ``position_embedding = "rope_window"`` rotates queries
    # and keys in the ``w`` layers and applies no positional term in the ``a``
    # layers. In that stack ``qk_norm`` is an RMSNorm over each HEAD's values
    # (one weight of ``head_dim`` for the queries, one for the keys),
    # ``attn_gate`` multiplies the attention output, before ``wo``, by the
    # sigmoid of a projection of the sublayer's normed input (``wg``), and
    # ``sandwich_norm`` norms each sublayer's OUTPUT too, before it joins the
    # stream (four norms a layer). Its first ``first_k_dense_replace`` layers
    # have the dense FFN, the others the expert layer with a held share.
    sliding_window: int = 0
    attn_gate: bool = False
    sandwich_norm: bool = False
    # Granite's four scalars, each 1 (or 0 = the default) elsewhere: the
    # embedding's output times ``embedding_multiplier``; every sublayer's
    # output times ``residual_multiplier`` before it joins the stream;
    # attention scores times ``attention_multiplier`` in place of 1 /
    # sqrt(head_dim) (0 = that default); logits divided by ``logits_scaling``.
    # The dtype the residual stream is carried in between sublayers ("" =
    # ``dtype``, every family before Granite-4.0-H). Every norm's output and
    # so every matmul stays in ``dtype``; only the sums ``x + f(x)`` are kept
    # wider. Granite's 80 sublayers each add 0.22 f(x) to a stream several
    # times larger: in bfloat16 the stream's own rounding, 2^-9 of x at every
    # sum, is most of the forward pass's error (3.4% of the logits' rms on
    # the chip against 1.9% at mid widths with a float32 stream: PERF.md §6).
    residual_dtype: str = ""
    embedding_multiplier: float = 1.0
    residual_multiplier: float = 1.0
    attention_multiplier: float = 0.0
    logits_scaling: float = 1.0
    # LoRA; rank 0 disables.
    lora_rank: int = 0
    lora_alpha: float = 16.0
    lora_dropout: float = 0.0
    # Attention implementation: "xla" | "flash" (Pallas) | "ring" (SP ring
    # attention) | "ulysses" (SP via all-to-all head/sequence transposition)
    attention_impl: str = "xla"
    # Pallas flash-attention tile sizes. 0 = kernel default (512). The
    # backward kernels take their own sizes (0 = same as forward): the dkv
    # kernel's working set (two f32 accumulators + recomputed p) differs from
    # the forward's, so its optimum can differ — sweepable per chip.
    flash_block_q: int = 0
    flash_block_kv: int = 0
    flash_block_q_bwd: int = 0
    flash_block_kv_bwd: int = 0
    # KV-cache storage for inference: "" / "model" (compute dtype, bf16 on
    # TPU) | "int8" (symmetric per-head absmax quantization, infer/cache.py)
    kv_cache_dtype: str = ""
    # Gradient checkpointing policy for the layer scan:
    # "none" | "full" | "dots" | "dots_inputs" (dots plus the norm outputs
    # feeding the qkv/gate/up projections, so every backward GEMM reads a
    # stored operand) | "attn" (save only attention outputs, so the
    # backward never re-runs the attention kernel).
    remat: str = "full"
    # Unroll factor for the training-path layer scan: >1 lets XLA fuse and
    # overlap across consecutive layers' forward/backward at the cost of
    # code size / compile time (the fusion-boundary lever the r4 roofline
    # named). 1 = fully rolled (one layer's HLO).
    scan_unroll: int = 1
    # Store gate and up projections as ONE (D, 2F) matrix: half the MLP
    # GEMM count forward and backward (one fwd GEMM, one dgrad, one wgrad
    # instead of two each) — bigger MXU tiles, fewer kernel boundaries.
    # Same math: the fused output splits into (gate, up) before SwiGLU.
    # Tensor-parallel note: the gate|up boundary aligns with shard edges
    # only for an EVEN tensor-axis size; odd sizes insert per-layer
    # resharding around the split (correct, but erodes the fusion win).
    fused_gate_up: bool = False
    # Same trick for the attention input projections: q|k|v stored as one
    # (D, (nh + 2*nkv)*hd) matrix — one GEMM (and one dgrad/wgrad pair)
    # instead of three. Not composable with LoRA adapters (which target
    # the per-projection names). Tensor-parallel note: under GQA
    # (nkv < nh) the q|k|v boundaries generally do NOT align with
    # head-axis shard edges, so TP meshes reshard around the split —
    # prefer the unfused layout for TP serving; the fusion targets
    # single-chip / data-parallel training.
    fused_qkv: bool = False
    # Hand-written VJP for the fused-gate|up MLP block (requires
    # fused_gate_up): the whole block's backward — activation grads and
    # BOTH weight grads — is emitted as one function with explicit
    # einsum contractions instead of autodiff transposes. An instrument
    # against the backward-scheduling residual (a builders' null result
    # from before this round); leave it off.
    mlp_custom_vjp: bool = False
    # MLP backward implementation behind the custom-VJP seam: "xla"
    # (explicit einsums, scheduled by XLA — the r5 null) | "pallas"
    # (hand-tiled Mosaic kernels, ops/mlp_bwd.py — the schedule is pinned
    # by the grid). "pallas" requires fused_gate_up and routes through the
    # custom VJP even when mlp_custom_vjp is off. Shapes the kernels
    # cannot tile raise on the TPU backend (size mlp_bwd_block_* to the
    # model); in interpret mode they give way to the einsum spelling.
    mlp_bwd_impl: str = "xla"
    # Pallas MLP-backward tile sizes (0 = kernel defaults, sized for the
    # 1b3 shapes on v5e): token tile, intermediate-dim tile (pass 1),
    # hidden-dim tile (pass 2). Sweepable per chip like the flash blocks.
    mlp_bwd_block_n: int = 0
    mlp_bwd_block_f: int = 0
    mlp_bwd_block_d: int = 0
    # Attention-projection (qkv/out) backward: "xla" | "pallas"
    # (ops/projection.py — dx and the wgrad emitted from one kernel with a
    # shared cotangent read). Targets the ~33 ms attn-proj wgrad residual
    # of the r4 roofline. Plain float weights only (reject-don't-drop at
    # the projection site, like mlp_custom_vjp).
    proj_bwd_impl: str = "xla"
    proj_bwd_block_n: int = 0
    proj_bwd_block_d: int = 0
    # Loss head: "naive" materializes (B, S, V) f32 logits; "fused" computes
    # the lm-head matmul + cross-entropy blockwise (ops/fused_ce.py) so peak
    # logits memory is loss_block_tokens x V instead of B*S*V, and forms both
    # gradients in the same pass over the blocks (a custom VJP: three head
    # matmuls a block, one loop).
    loss_impl: str = "naive"
    loss_block_tokens: int = 1024
    # Pipeline parallelism (active when the mesh's "stage" axis > 1):
    # microbatches per pipeline flush; 0 => one per stage.
    pipeline_microbatches: int = 0

    @property
    def double_layer(self) -> bool:
        """Whether the layers are LongCat-Flash's double layer (models/mla.py):
        latent attention with no leading dense layers. Derived, not a field:
        the single pre-norm block (``dsa_layer``) is told apart by its leading
        dense layers."""
        return self.kv_lora_rank > 0 and self.first_k_dense_replace == 0

    @property
    def dsa_layer(self) -> bool:
        """Whether the layers are the single pre-norm latent block
        (models/dsa.py): latent attention behind ``first_k_dense_replace``
        leading dense layers, with or without a query latent, YaRN and a
        lightning indexer (``indexed``)."""
        return self.kv_lora_rank > 0 and self.first_k_dense_replace > 0

    @property
    def indexed(self) -> bool:
        """Whether that block selects by DeepSeek-V3.2's lightning indexer."""
        return self.index_topk > 0

    @property
    def window_layer(self) -> bool:
        """Whether the stack has window attention layers (models/swa.py)."""
        return "w" in self.layer_types

    @property
    def retention_layer(self) -> bool:
        """Whether the stack is power-retention layers (models/retention.py)."""
        return "r" in self.layer_types

    @property
    def layer_period(self) -> str:
        """One period of ``layer_types``: its shortest prefix that, repeated,
        gives the whole string ("" for a stack of identical layers)."""
        t = self.layer_types
        return next((t[:n] for n in range(1, len(t) + 1)
                     if len(t) % n == 0 and t[:n] * (len(t) // n) == t), "")

    def _check_window_stack(self) -> None:
        """What a stack with window layers (models/swa.py) can run; everything
        else is refused by name."""
        if self.sliding_window <= 0:
            raise ValueError(
                "a window attention layer ('w' in layer_types) needs "
                "sliding_window > 0")
        if "m" in self.layer_types:
            raise ValueError(
                "layer_types mixes state-space ('m') and window ('w') layers: "
                "models/swa.py carries attention layers only")
        if not 0 <= self.first_k_dense_replace < self.num_layers:
            raise ValueError(
                f"first_k_dense_replace {self.first_k_dense_replace} must leave an "
                f"expert layer among the {self.num_layers}")
        if not (self.num_experts > 0 and self.experts_held_count > 0):
            raise ValueError(
                "a stack with window layers has the expert layer with a held share "
                "behind its leading dense layers (num_experts, experts_held_count)")
        for what, on in (("latent attention (kv_lora_rank)", self.kv_lora_rank > 0),
                         ("LoRA (lora_rank)", self.lora_rank > 0),
                         ("fused_qkv", self.fused_qkv),
                         ("attention_bias", self.attention_bias),
                         ("zero-compute experts (zero_expert_num)", self.zero_expert_num > 0),
                         ("residual_dtype", bool(self.residual_dtype)),
                         ("attention_multiplier / residual_multiplier",
                          bool(self.attention_multiplier) or self.residual_multiplier != 1.0),
                         ("attention_impl other than xla or flash",
                          self.attention_impl not in ("xla", "flash")),
                         ("tie_embeddings", self.tie_embeddings)):
            if on:
                raise ValueError(
                    f"a stack with window layers (models/swa.py) does not carry {what}")

    def _check_retention_stack(self) -> None:
        """What a stack of retention layers (models/retention.py) can run;
        everything else is refused by name."""
        if set(self.layer_types) != {"r"}:
            raise ValueError(
                f"layer_types {self.layer_types!r} mixes retention ('r') with other "
                "layers: models/retention.py carries a stack of retention layers alone")
        if self.ret_degree != 2 or self.ret_chunk <= 0 or self.ret_eps <= 0:
            raise ValueError(
                "a retention layer ('r' in layer_types) has ret_degree 2 (degree 4 of "
                "a 128-wide head is a state of 11.7 M x 128 values a head), "
                "ret_chunk > 0 and ret_eps > 0")
        for what, on in (("experts (num_experts)", self.num_experts > 0),
                         ("latent attention (kv_lora_rank)", self.kv_lora_rank > 0),
                         ("LoRA (lora_rank)", self.lora_rank > 0),
                         ("attention_bias", self.attention_bias),
                         ("position_embedding other than rope",
                          self.position_embedding != "rope"),
                         ("attention_multiplier / residual_multiplier",
                          bool(self.attention_multiplier) or self.residual_multiplier != 1.0),
                         ("an unfused gate and up (fused_gate_up=False) or fused_qkv",
                          not self.fused_gate_up or self.fused_qkv)):
            if on:
                raise ValueError(
                    f"a stack of retention layers (models/retention.py) does not carry {what}")

    def __post_init__(self):
        if self.layer_types:
            if len(self.layer_types) != self.num_layers or set(self.layer_types) - {
                    "m", "a", "w", "r"}:
                raise ValueError(
                    f"layer_types {self.layer_types!r} must be num_layers "
                    f"({self.num_layers}) letters, each 'm', 'a', 'w' or 'r'")
            if "m" in self.layer_types and not (
                    self.ssm_heads > 0 and self.ssm_head_dim > 0 and self.ssm_state > 0
                    and self.ssm_conv > 1 and self.ssm_chunk > 0):
                raise ValueError(
                    "a state-space layer ('m' in layer_types) needs ssm_heads, "
                    "ssm_head_dim, ssm_state, ssm_conv and ssm_chunk set")
            if self.retention_layer:
                self._check_retention_stack()
            elif self.window_layer:
                self._check_window_stack()
            elif self.num_experts > 0 or self.kv_lora_rank > 0 or self.lora_rank > 0 or (
                    not self.fused_gate_up or self.fused_qkv):
                raise ValueError(
                    "layer_types (models/ssm.py) carries dense layers with "
                    "fused_gate_up and unfused q/k/v only: no experts, no "
                    "latent attention, no LoRA")
        if self.residual_dtype and self.kv_lora_rank > 0:
            raise ValueError(
                "residual_dtype is not carried by the double layer (models/mla.py)")
        if self.position_embedding not in ("rope", "nope", "rope_window"):
            raise ValueError(
                f"unknown position_embedding {self.position_embedding!r} "
                "(rope|nope|rope_window)")
        if not self.window_layer and (
                self.sliding_window or self.attn_gate or self.sandwich_norm
                or self.position_embedding == "rope_window"):
            raise ValueError(
                "sliding_window, attn_gate, sandwich_norm and position_embedding="
                "'rope_window' belong to a stack with window attention layers (a "
                "'w' in layer_types, models/swa.py): every other block would "
                "ignore them")
        # Reject-don't-drop: the MoE block has no fused gate|up layout, so
        # these flags would be silently ignored (an A/B would measure
        # byte-identical programs) — the same failure mode the dense-path
        # guard in models/llama.py exists to prevent.
        if self.num_experts > 0 and (
            self.fused_gate_up or self.mlp_custom_vjp
            or self.mlp_bwd_impl != "xla"
        ):
            raise ValueError(
                "fused_gate_up/mlp_custom_vjp/mlp_bwd_impl target the dense "
                f"MLP path and do not apply to MoE models (num_experts="
                f"{self.num_experts}); unset them rather than measuring a "
                "silently unfused program"
            )
        if self.experts_held_first < 0 or self.experts_held_count < 0 or (
            self.experts_held_first + self.experts_held_count > self.num_experts
        ):
            raise ValueError(
                f"experts held {self.experts_held_first}+"
                f"{self.experts_held_count} lie outside the {self.num_experts} "
                "routed experts"
            )
        if (self.router_bias or self.routed_scaling_factor != 1.0) and not (
            self.zero_expert_num or self.experts_held_count
        ):
            raise ValueError(
                "router_bias and routed_scaling_factor belong to the expert "
                "layer with zero-compute experts or a held share "
                "(zero_expert_num, experts_held_count): the layer that holds "
                "every expert would ignore them"
            )
        if self.kv_lora_rank > 0 and not (
            self.num_experts > 0 and self.qk_nope_head_dim > 0
            and self.qk_rope_head_dim > 0 and self.v_head_dim > 0
        ):
            raise ValueError(
                "latent attention (kv_lora_rank > 0) is implemented inside "
                "LongCat-Flash's double layer and the single pre-norm block "
                "(models/dsa.py) only: it needs an expert layer and "
                "qk_nope_head_dim, qk_rope_head_dim and v_head_dim set"
            )
        if self.double_layer and self.q_lora_rank <= 0:
            raise ValueError(
                "the double layer (latent attention without leading dense layers, "
                "models/mla.py) always has a query latent: it needs q_lora_rank > 0; "
                "one wq is the single pre-norm block's (first_k_dense_replace > 0)"
            )
        if self.index_topk > 0 and not (
            self.kv_lora_rank > 0 and self.index_n_heads > 0 and self.q_lora_rank > 0
            and 0 < self.qk_rope_head_dim <= self.index_head_dim
            and 0 < self.first_k_dense_replace < self.num_layers
        ):
            raise ValueError(
                "a lightning indexer (index_topk > 0) selects inside "
                "the single pre-norm latent block (models/dsa.py): it needs latent "
                "attention (kv_lora_rank) with a query latent (q_lora_rank), "
                "index_n_heads, an index_head_dim of at least qk_rope_head_dim, "
                "and leading dense layers (0 < first_k_dense_replace < num_layers)"
            )
        if self.dsa_layer and not (
            self.first_k_dense_replace < self.num_layers
            and self.experts_held_count > 0
            and not (self.mla_scale_q_lora or self.mla_scale_kv_lora
                     or self.zero_expert_num)
        ):
            raise ValueError(
                "the single pre-norm latent block (kv_lora_rank > 0 behind "
                "first_k_dense_replace leading dense layers, models/dsa.py) needs "
                "an expert layer with a held share behind them "
                "(first_k_dense_replace < num_layers, experts_held_count), and "
                "none of the double layer's mla_scale_* and zero_expert_num"
            )
        if self.index_topk == 0 and (self.index_n_heads or self.index_head_dim):
            raise ValueError(
                "index_n_heads and index_head_dim belong to the lightning indexer "
                "(index_topk > 0): every other block would ignore them"
            )
        if not self.dsa_layer and (
            self.rope_yarn_factor
            or (self.first_k_dense_replace and not self.window_layer)
        ):
            raise ValueError(
                "rope_yarn_* belongs to the single pre-norm latent block "
                "(kv_lora_rank > 0, models/dsa.py), first_k_dense_replace to it and "
                "to a stack with window layers: every other block would ignore them"
            )
        if self.scoring_func not in ("softmax", "sigmoid"):
            raise ValueError(
                f"unknown scoring_func {self.scoring_func!r} (softmax|sigmoid)")
        # one group that always stays is no group limiting (n_group 1)
        grouped = self.n_group > 1 or self.topk_group > 1
        if (self.scoring_func != "softmax" or grouped or self.n_shared_experts) and not (
            self.experts_held_count
        ):
            raise ValueError(
                "scoring_func, n_group / topk_group and n_shared_experts belong "
                "to the expert layer with a held share (experts_held_count): "
                "the layer that holds every expert would ignore them"
            )
        if grouped and not (
            0 < self.topk_group <= self.n_group and self.zero_expert_num == 0
            and self.num_experts % self.n_group == 0
            and self.num_experts // self.n_group >= 2
            and self.topk_group * (self.num_experts // self.n_group)
            >= self.num_experts_per_tok
        ):
            raise ValueError(
                f"n_group {self.n_group} / topk_group {self.topk_group}: the "
                f"{self.num_experts} routed experts must lie in equal groups of "
                "at least two, and the chosen groups must hold the "
                f"{self.num_experts_per_tok} choices"
            )
        if self.mlp_bwd_impl not in ("xla", "pallas"):
            raise ValueError(
                f"unknown mlp_bwd_impl {self.mlp_bwd_impl!r} (xla|pallas)"
            )
        if self.proj_bwd_impl not in ("xla", "pallas"):
            raise ValueError(
                f"unknown proj_bwd_impl {self.proj_bwd_impl!r} (xla|pallas)"
            )
        for blk in ("mlp_bwd_block_n", "mlp_bwd_block_f", "mlp_bwd_block_d",
                    "proj_bwd_block_n", "proj_bwd_block_d"):
            if getattr(self, blk) < 0:
                # Negative blocks sneak through the kernels' divisibility
                # checks (Python modulo) into a cryptic Mosaic error —
                # reject at config time like every other knob.
                raise ValueError(f"{blk} must be >= 0 (0 = kernel default), "
                                 f"got {getattr(self, blk)}")
        if self.mlp_bwd_impl == "pallas" and not self.fused_gate_up:
            # Reject-don't-drop: the Pallas backward targets the fused w_gu
            # layout; silently ignoring the flag on the unfused layout would
            # make an A/B measure byte-identical programs.
            raise ValueError(
                "mlp_bwd_impl='pallas' requires fused_gate_up=True (the "
                "kernels target the fused w_gu layout)"
            )


@dataclass(frozen=True)
class DataConfig:
    """Data pipeline. Parity surface: HF ``load_dataset('imdb','train[:1%]')``
    + DistributedSampler + DataLoader(batch_size=4) (ref
    ``src/distributed_inference.py:56-59``)."""

    dataset_name: str = "imdb"
    dataset_split: str = "train[:1%]"
    text_column: str = "text"
    label_column: str = "label"
    batch_size: int = 4  # GLOBAL batch size (split across the data/fsdp axes)
    seq_len: int = 512
    shuffle: bool = True
    seed: int = 0
    drop_last: bool = False
    # Fraction of the dataset held out for local validation-loss eval
    # (train.val_every); 0 disables. Deterministic tail split.
    eval_fraction: float = 0.0
    num_epochs: int = 3  # ref :61
    tokenizer: str = "byte"  # "byte" | HF tokenizer name
    pack_sequences: bool = True
    prefetch: int = 2  # device prefetch depth (double buffering)
    synthetic: bool = False  # True => generated data, no HF hub (hermetic tests)
    synthetic_examples: int = 256
    # The synthetic corpus's documents at FIXED token lengths (bos and eos
    # counted, byte tokenizer), comma-separated, repeated in this order:
    # "4096,2048,1024,512,256,128,64,64" with ``shuffle=false`` packs every
    # row of 8,192 with the same eight documents, whatever ``seed`` draws as
    # their content. "" = lengths drawn with the content (16-96 words).
    synthetic_doc_tokens: str = ""
    # Max seconds the consumer may block waiting for the prefetch producer
    # before raising a diagnosable DataStallError (data/loader.py) instead
    # of hanging the step loop forever behind a wedged pipeline (hub stall,
    # injected hang). 0 = wait forever (the historical behavior).
    data_wait_timeout_s: float = 0.0


@dataclass(frozen=True)
class TrainConfig:
    # "adamw" | "adafactor" (factored second moment — the TPU-lineage
    # memory-efficient choice: O(rows+cols) stats instead of O(params)) |
    # "lion" (sign-momentum, one bf16-able moment) | "sgd" (momentum=beta1)
    optimizer: str = "adamw"
    learning_rate: float = 3e-4
    warmup_steps: int = 10
    total_steps: int = 100
    weight_decay: float = 0.01
    beta1: float = 0.9
    beta2: float = 0.95
    grad_clip_norm: float = 1.0
    grad_accum_steps: int = 1
    # Adam first-moment storage dtype ("float32" | "bfloat16"): bf16 halves
    # the moment's HBM footprint; variance always stays float32.
    adam_mu_dtype: str = "float32"
    # Optimizer steps per compiled call (lax.scan window; train/step.py
    # make_multi_step). >1 removes host dispatch overhead between steps.
    steps_per_call: int = 1
    # Seed of the initial parameters where it is not ``seed`` (-1): a job whose
    # every run starts from ONE draw (a published checkpoint's stand-in) while
    # ``seed`` and ``data.seed`` still vary the run.
    init_seed: int = -1
    # Leaves that do not train, by the name of a key on their path, comma-
    # separated ("router": a fine-tune that leaves the routers as the
    # checkpoint has them, as expert-specialised fine-tuning does): no
    # optimizer update, no weight decay, no optimizer state. "" = all train.
    frozen: str = ""
    log_every: int = 10
    metrics_file: str = ""  # "" => no JSONL scalar stream (metrics.py)
    eval_every: int = 0  # 0 => no API eval loop
    eval_samples: int = 8
    # Local validation: every N steps run the compiled eval step over
    # val_batches batches of the held-out split (data.eval_fraction).
    val_every: int = 0
    val_batches: int = 8
    checkpoint_dir: str = ""  # "" => checkpointing disabled
    checkpoint_every: int = 0
    keep_checkpoints: int = 3
    resume: bool = True  # resume from latest checkpoint if present
    # Elastic recovery (launch.run_supervised — the torchrun --max_restarts
    # analog the reference never configured, SURVEY.md §5 'failure
    # detection'): on an unhandled training exception, re-enter train() up to
    # this many times, resuming from the latest checkpoint. 0 => fail fast.
    max_restarts: int = 0
    # Fault injection for drilling the recovery path: raise at this global
    # step on the FIRST run (never after a resume). 0 => off. Pick a step
    # past checkpoint_every so the restart has something to resume from.
    fault_inject_step: int = 0
    # Harsher drill: SIGKILL our own process at this global step on the
    # FIRST run — uncatchable, like a host crash/OOM-kill. Only an
    # OUT-OF-PROCESS supervisor (launch --supervise, or k8s restartPolicy)
    # can recover from it. 0 => off.
    fault_kill_step: int = 0
    # Anomaly-plane drill (ISSUE 10): inject NaN into the step's reported
    # loss metric at this global step (train/step.py), so the non-finite
    # detector path — flight-ring dump, incident bundle, THEN crash — is
    # drillable without engineering a real divergence. The injection rides
    # the compiled metrics (a real device NaN reaching the host flush),
    # touching only the reported loss, never the gradients. 0 => off.
    fault_nan_step: int = 0
    # Which process index fault_kill_step applies to: -1 => every process
    # (the single-host drill), >= 0 => only that worker dies — the pod-level
    # drill (runtime/elastic.py), where the SURVIVORS are left wedged in a
    # collective and the pod controller must tear them down and relaunch.
    fault_kill_process: int = -1
    # Elastic pod liveness (launch --supervise [--pod N], runtime/elastic.py):
    # each process touches {heartbeat_dir}/worker-{process_index}.heartbeat
    # every step window (path derived from the process index so the config
    # stays identical pod-wide for the consistency check). "" => no
    # heartbeats. The controller treats a heartbeat older than
    # heartbeat_timeout_s as a dead worker (0 => exit-code liveness only).
    # Heartbeats are emitted at HOST boundaries — once per steps_per_call
    # window (a >1 window runs entirely on-device; nothing can emit
    # mid-program) and again after a validation / API-eval pass — so size
    # the timeout above worst-case first-step compile, one full window's
    # wall time, AND one validation or eval pass, or a healthy slow
    # boundary reads as a stall.
    heartbeat_dir: str = ""
    heartbeat_timeout_s: float = 0.0
    # Straggler escalation (runtime/elastic.py): a worker whose heartbeat
    # STEP trails the pod median by more than this many steps is flagged
    # (journaled `pod.straggler`) — the slow-not-dead failure class the
    # dead-or-silent liveness checks cannot see. 0 = off. Requires
    # heartbeat_dir (steps ride the heartbeat files).
    straggler_lag_steps: int = 0
    # Escalate a flagged straggler to a pod relaunch (same teardown +
    # fresh-port relaunch path as a death; consumes the restart budget).
    # False = journal-and-log only.
    straggler_relaunch: bool = False
    # Telemetry event journal (ditl_tpu/telemetry/journal.py): each process
    # appends typed lifecycle/progress events to
    # {telemetry_dir}/events-worker-{process_index}.jsonl, and the elastic
    # pod controller adds its own events-controller.jsonl plus a merged
    # pod_timeline.jsonl at the end of a supervised run. Also the source for
    # restart lost-work attribution in the goodput report. "" => no journal
    # (goodput/phase accounting stays on; it needs no files).
    telemetry_dir: str = ""

    def __post_init__(self):
        if self.heartbeat_timeout_s > 0 and not self.heartbeat_dir:
            # Reject-don't-drop: a timeout without a heartbeat dir would
            # silently disarm the stall watchdog the operator asked for.
            raise ValueError(
                "heartbeat_timeout_s requires heartbeat_dir (without it no "
                "heartbeats are emitted and stall detection is silently off)"
            )
        if self.straggler_lag_steps > 0 and not self.heartbeat_dir:
            # Same reject-don't-drop rule: straggler detection reads step
            # progress off the heartbeat files.
            raise ValueError(
                "straggler_lag_steps requires heartbeat_dir (step progress "
                "rides the heartbeat files; without them straggler "
                "detection is silently off)"
            )
    # Path to a local HF checkpoint directory (transformers format) to
    # initialize parameters from instead of random init (models/convert.py).
    init_from_hf: str = ""
    seed: int = 42
    # Step-window trace capture (utils/profiling.py); "" => disabled.
    profile_dir: str = ""
    profile_start_step: int = 2  # skip the compile step
    profile_num_steps: int = 3


@dataclass(frozen=True)
class APIConfig:
    """Remote-LLM (OpenAI-compatible) client config — the LiteLLM-parity
    surface (ref ``src/distributed_inference.py:34-41,53-54``). The API key is
    *never* stored here; ``api_key()`` reads the env at call time."""

    model_name: str = "meta-llama/Meta-Llama-3.1-70B-Instruct"
    api_base: str = "http://localhost:4000/v1"
    api_key_env: str = "OPENAI_API_KEY"
    timeout_s: float = 60.0
    max_retries: int = 5
    backoff_base_s: float = 0.5  # exponential backoff, doc'd-but-unimplemented
    backoff_max_s: float = 30.0  # in the reference (troubleshooting.md:42-51)
    # Hard wall-clock bound over the WHOLE retry loop (one logical call):
    # without it, max_retries x (timeout_s + backoff_max_s) can stall an
    # eval loop for minutes behind one dead endpoint. Per-attempt timeouts
    # are clamped to the remaining budget and backoff never sleeps past
    # the deadline. 0 = unbounded (the historical behavior).
    total_timeout_s: float = 0.0
    max_concurrency: int = 8  # async client fan-out (vs ref's serial loop)

    def api_key(self) -> str:
        return os.environ.get(self.api_key_env, "")


@dataclass(frozen=True)
class GatewayConfig:
    """Serving-gateway fleet config (ditl_tpu/gateway/, ISSUE 4): one
    OpenAI-compatible endpoint over N engine replicas, with routing,
    supervision, and per-tenant admission knobs. Launched via
    ``python -m ditl_tpu.launch gateway`` (subprocess replicas) and
    overridable with the usual dotted syntax (``gateway.router=affinity``).
    """

    host: str = "127.0.0.1"
    port: int = 8400
    replicas: int = 2  # fleet size when the launcher spawns the replicas
    # Routing policy: "round_robin" | "least_outstanding" | "affinity"
    # (consistent hashing over session_id / the prompt's leading tokens,
    # spilling to least-loaded when the home replica is saturated).
    router: str = "affinity"
    # How many leading (whitespace) prompt tokens form the affinity key.
    affinity_prefix_tokens: int = 32
    # Supervision: health-poll cadence, consecutive failures before a
    # replica is declared dead (died -> drain -> relaunch -> re-admit),
    # and how long a relaunch may take to become healthy.
    health_interval_s: float = 0.5
    fail_threshold: int = 3
    probe_timeout_s: float = 2.0
    restart_timeout_s: float = 300.0
    drain_timeout_s: float = 60.0
    # Proxying: attempts across distinct replicas per request (retries are
    # idempotent-safe — nothing has been relayed when a retry fires),
    # upstream timeout, and optional tail-latency hedging (0 = off).
    max_attempts: int = 3
    request_timeout_s: float = 300.0
    hedge_after_s: float = 0.0
    # Per-tenant admission (keyed on the request's Bearer token): token-
    # bucket rate (requests/s; 0 = unlimited), burst (0 = max(1, rate)),
    # and concurrent-request cap (0 = unlimited).
    tenant_rate: float = 0.0
    tenant_burst: float = 0.0
    tenant_max_concurrent: int = 0
    # Default SLO-class pin applied to every admission-managed tenant
    # ("" = no pin): the gateway stamps X-SLO-Class on each relay, which
    # overrides the request payload at the replica — scheduling-priority
    # enforcement at the front door (ISSUE 8). Programmatic per-tenant pins
    # ride TenantAdmission(per_tenant={"name": {"slo_class": ...}}).
    tenant_slo_class: str = ""
    # Disaggregated prefill/decode fleets (ISSUE 9): comma-separated role
    # per launcher-spawned replica ("prefill_heavy,decode_heavy,..."), each
    # of gateway/roles.ROLES; shorter specs pad with "hybrid", "" = a
    # homogeneous (all-hybrid) fleet. The launcher derives each replica's
    # engine knobs (slots / prefill chunk / token budget / pages) from its
    # role via gateway.roles.role_knobs.
    replica_roles: str = ""
    # Steer requests by SLO class across replica roles (interactive ->
    # decode_heavy/hybrid, long-prompt batch/best_effort -> prefill_heavy/
    # hybrid) before the routing policy picks. A no-op on homogeneous
    # fleets; False disables steering even on heterogeneous ones.
    role_routing: bool = True
    # Whitespace-token threshold above which a batch/best_effort prompt
    # counts as "long" for prefill-heavy steering; 0 = every batch/
    # best_effort request steers regardless of prompt size.
    long_prompt_tokens: int = 0
    # Upstream keep-alive connection pool (gateway/pool.py, ISSUE 14):
    # how many idle kept-alive connections the gateway parks per replica
    # (0 disables pooling — every relay/poll/probe connects fresh, the
    # --serve-gateway-overhead A/B leg), and how old a parked connection
    # may grow before checkout discards it instead of reusing it.
    pool_max_idle_per_replica: int = 8
    pool_max_age_s: float = 30.0
    # Journal directory for replica lifecycle events
    # (events-gateway.jsonl via telemetry/journal.py); "" = no journal.
    journal_dir: str = ""
    # Data plane (ISSUE 17): "evloop" (default) serves client I/O from a
    # single-threaded selectors event loop — SSE relays fan through the
    # loop without a parked thread, so open-stream concurrency is bounded
    # by fds, not thread stacks. "threaded" keeps the legacy
    # thread-per-connection ThreadingHTTPServer for one release as the
    # fallback. Control-plane semantics are identical on both.
    data_plane: str = "evloop"
    # Evloop dispatch pool (gateway/evloop.py): control-plane handling
    # (admission, routing, retries, hedging, non-stream relays) runs on
    # this many worker threads; streams detach back to the loop after
    # their first upstream chunk. The default keeps the whole data plane
    # (loop + workers) comfortably under the 16-thread pin the bench
    # records. Non-stream relays park a worker for the upstream duration,
    # so this also caps concurrent non-stream relays.
    evloop_offload_workers: int = 12
    # Idle keep-alive client connections are closed after this long with
    # no request (parity with the threaded handler's 120 s socket
    # timeout). Streams are exempt — their bound is the upstream read
    # timeout.
    evloop_idle_timeout_s: float = 120.0
    # Accept cap: beyond this many open client connections the loop
    # accepts-and-closes (counted as ditl_gateway_loop_accept_backlog
    # _drops) instead of growing without bound. 0 = unlimited (the
    # process fd limit is then the only cap).
    evloop_max_connections: int = 0
    # Crash recovery (gateway/recovery.py, ISSUE 20). A recovering
    # gateway reclaims its predecessor's port while kernel TIME_WAIT
    # entries from severed connections linger: bind EADDRINUSE is
    # retried up to recovery_bind_retries times, recovery_bind_wait_s
    # apart (0 retries = fail fast, the pre-recovery behavior).
    recovery_bind_retries: int = 5
    recovery_bind_wait_s: float = 0.5
    # How long the --recover path waits for an adopted replica's /health
    # cross-check before giving up on adoption and relaunching it on a
    # fresh port (pid liveness alone never adopts — a recycled pid or a
    # rebound port must not alias).
    recovery_adopt_timeout_s: float = 5.0

    def __post_init__(self):
        if self.data_plane not in ("threaded", "evloop"):
            raise ValueError(
                f"unknown gateway.data_plane {self.data_plane!r} "
                "(threaded|evloop)"
            )
        if self.evloop_offload_workers < 1:
            raise ValueError(
                f"gateway.evloop_offload_workers must be >= 1, got "
                f"{self.evloop_offload_workers}"
            )
        if self.evloop_idle_timeout_s <= 0:
            raise ValueError(
                f"gateway.evloop_idle_timeout_s must be > 0, got "
                f"{self.evloop_idle_timeout_s}"
            )
        if self.evloop_max_connections < 0:
            raise ValueError(
                f"gateway.evloop_max_connections must be >= 0, got "
                f"{self.evloop_max_connections}"
            )
        if self.recovery_bind_retries < 0:
            raise ValueError(
                f"gateway.recovery_bind_retries must be >= 0, got "
                f"{self.recovery_bind_retries}"
            )
        if self.recovery_bind_wait_s <= 0:
            raise ValueError(
                f"gateway.recovery_bind_wait_s must be > 0, got "
                f"{self.recovery_bind_wait_s}"
            )
        if self.recovery_adopt_timeout_s <= 0:
            raise ValueError(
                f"gateway.recovery_adopt_timeout_s must be > 0, got "
                f"{self.recovery_adopt_timeout_s}"
            )
        if self.router not in ("round_robin", "least_outstanding",
                               "affinity"):
            raise ValueError(
                f"unknown gateway.router {self.router!r} "
                "(round_robin|least_outstanding|affinity)"
            )
        if self.replicas < 1:
            raise ValueError(f"gateway.replicas must be >= 1, got "
                             f"{self.replicas}")
        if self.max_attempts < 1:
            raise ValueError(f"gateway.max_attempts must be >= 1, got "
                             f"{self.max_attempts}")
        if self.tenant_slo_class:
            # Reject-don't-drop at config time: a typo'd class would 400
            # every relayed request at the replica. Lazy import keeps the
            # single source of truth (the gateway package is stdlib-only,
            # so this never drags jax into config loading).
            from ditl_tpu.gateway.admission import SLO_CLASS_NAMES

            if self.tenant_slo_class not in SLO_CLASS_NAMES:
                raise ValueError(
                    f"unknown gateway.tenant_slo_class "
                    f"{self.tenant_slo_class!r} "
                    f"(one of {SLO_CLASS_NAMES}, or empty for no pin)"
                )
        if self.long_prompt_tokens < 0:
            raise ValueError(
                f"gateway.long_prompt_tokens must be >= 0, got "
                f"{self.long_prompt_tokens}"
            )
        if self.pool_max_idle_per_replica < 0:
            raise ValueError(
                f"gateway.pool_max_idle_per_replica must be >= 0, got "
                f"{self.pool_max_idle_per_replica}"
            )
        if self.pool_max_age_s <= 0:
            raise ValueError(
                f"gateway.pool_max_age_s must be > 0, got "
                f"{self.pool_max_age_s}"
            )
        if self.replica_roles:
            # Same reject-don't-drop rule: a typo'd role must fail the
            # launch, not silently serve a hybrid.
            from ditl_tpu.gateway.roles import parse_roles

            parse_roles(self.replica_roles, self.replicas)


@dataclass(frozen=True)
class AutoscaleConfig:
    """Actuation plane (ditl_tpu/gateway/autoscale.py, ISSUE 12):
    demand-driven replica scale-up/down plus detector-triggered remediation
    over the gateway's FleetSupervisor. Disabled by default — the planner
    never runs and the fleet behaves exactly as before. Every planned/
    executed/refused/failed action is journaled (``action.*`` events with
    the triggering signal snapshot inline), recorded into the ACTION flight
    ring, span-traced (``gateway.action``), counted on /metrics, and
    listable at the gateway's ``/actions`` endpoint."""

    enabled: bool = False
    # Fleet-size bounds for ordinary demand scaling: scale_down never goes
    # below min_replicas (the idle scale-to-zero path below is the one
    # exception, and it must be armed separately).
    min_replicas: int = 1
    # Demand signals: mean active_slots/capacity across live replicas
    # above scale_up_pressure (or mean queued+outstanding per live replica
    # at/above scale_up_queue) asks for one more replica; pressure below
    # scale_down_pressure with empty queues asks for one fewer.
    scale_up_pressure: float = 0.75
    scale_down_pressure: float = 0.25
    scale_up_queue: float = 2.0
    # Hysteresis: the up/down signal must hold for this many consecutive
    # planner polls before an action is planned (asymmetric on purpose —
    # adding capacity is cheap and urgent, removing it is neither).
    up_hysteresis_polls: int = 1
    hysteresis_polls: int = 3
    # Cooldown after any EXECUTED scale action before the next scale action
    # may plan (remediation and scale-to-zero wake are exempt: draining a
    # storm or answering demand must not wait out a scale cooldown).
    cooldown_s: float = 15.0
    # How long a scale-down/drain waits for the gateway's own in-flight
    # proxies to clear before stopping the replica.
    drain_wait_s: float = 10.0
    # Scale-to-zero: with every active replica idle (zero pressure, zero
    # queue) for idle_to_zero_s, deactivate below min_replicas down to 0.
    # Demand arriving against an empty fleet answers 429 with a measured
    # wake-up budget as Retry-After and wakes a replica immediately.
    scale_to_zero: bool = False
    idle_to_zero_s: float = 60.0
    # Wake-up budget = wake_budget_factor x the largest MEASURED replica
    # cold start (time-to-first-ready stamped on /health, compile cache
    # included); default_cold_start_s is only the bootstrap estimate used
    # before any replica has ever reported one.
    default_cold_start_s: float = 30.0
    wake_budget_factor: float = 2.0
    # Remediation: a live replica whose health-polled TPOT p95 exceeds
    # tpot_storm_factor x the median of its peers AND tpot_storm_min_s
    # (the absolute floor keeps sub-millisecond noise from reading as a
    # storm) is drained and restarted; a replica that dies
    # quarantine_deaths times within quarantine_window_s is quarantined
    # (stopped, excluded from supervision — the crash-loop breaker).
    # remedy_cooldown_s rate-limits remediation PER REPLICA, so a
    # sustained storm is one drain, not one per planner poll.
    tpot_storm_factor: float = 4.0
    tpot_storm_min_s: float = 0.25
    quarantine_deaths: int = 3
    quarantine_window_s: float = 60.0
    remedy_cooldown_s: float = 300.0
    # Plan-but-log: actions journal/count/trace as planned and are then
    # recorded with outcome "dry_run" instead of executing.
    dry_run: bool = False
    # Bounded in-memory action log served at the gateway's /actions.
    action_log: int = 256
    # Bulk-lane coupling (ISSUE 19): pending bulk work items at/above this
    # depth count as a scale-up signal (soak the backlog with more decode
    # capacity), and ANY bulk backlog vetoes the idle scale-down/
    # scale-to-zero paths — the lane exists to fill valleys, so an "idle"
    # fleet with bulk work pending is not idle. 0 disables the coupling
    # entirely: bulk never asks for capacity and never blocks parking.
    bulk_scale_up_backlog: int = 0

    def __post_init__(self):
        if self.min_replicas < 0:
            raise ValueError(
                f"autoscale.min_replicas must be >= 0, got "
                f"{self.min_replicas}"
            )
        for name in ("scale_up_pressure", "scale_down_pressure"):
            v = getattr(self, name)
            if not 0.0 < v <= 1.0:
                raise ValueError(
                    f"autoscale.{name} must be in (0, 1], got {v}"
                )
        if self.scale_down_pressure >= self.scale_up_pressure:
            raise ValueError(
                "autoscale.scale_down_pressure must be below "
                f"scale_up_pressure, got {self.scale_down_pressure} >= "
                f"{self.scale_up_pressure}"
            )
        for name in ("up_hysteresis_polls", "hysteresis_polls",
                     "quarantine_deaths", "action_log"):
            if getattr(self, name) < 1:
                raise ValueError(
                    f"autoscale.{name} must be >= 1, got "
                    f"{getattr(self, name)}"
                )
        if self.scale_up_queue <= 0:
            # 0 would make the queue signal PERMANENTLY hot (mean queued
            # >= 0 always holds) — an idle fleet would read as overloaded
            # and oscillate against the idle scale-down path. There is no
            # "disable" spelling for this knob; set it high instead.
            raise ValueError(
                f"autoscale.scale_up_queue must be > 0, got "
                f"{self.scale_up_queue}"
            )
        for name in ("cooldown_s", "drain_wait_s",
                     "idle_to_zero_s", "remedy_cooldown_s",
                     "quarantine_window_s"):
            if getattr(self, name) < 0:
                raise ValueError(
                    f"autoscale.{name} must be >= 0, got "
                    f"{getattr(self, name)}"
                )
        for name in ("default_cold_start_s", "wake_budget_factor",
                     "tpot_storm_factor", "tpot_storm_min_s"):
            if getattr(self, name) <= 0:
                raise ValueError(
                    f"autoscale.{name} must be > 0, got "
                    f"{getattr(self, name)}"
                )
        if self.bulk_scale_up_backlog < 0:
            raise ValueError(
                f"autoscale.bulk_scale_up_backlog must be >= 0 (0 = "
                f"decoupled), got {self.bulk_scale_up_backlog}"
            )


@dataclass(frozen=True)
class KVTierConfig:
    """KV movement plane (ISSUE 13): the host-RAM prefix-cache tier
    (infer/host_tier.py — LRU-evicted published pages spill to pinned host
    memory and swap back in on admission miss) and the prefill->decode KV
    handoff (infer/kv_transfer.py + the gateway orchestration — a
    ``prefill_heavy`` replica's finished prefill ships to the decode
    replica the router already chose, gated by a measured transfer-cost
    model). Both are off by default: the fleet behaves exactly as before
    until armed."""

    # Host-RAM tier capacity in MiB per replica engine (0 = off). Sizes
    # the effective shared-prefix working set BEYOND the HBM page pool —
    # the knob that used to be a hardware constant.
    host_tier_mb: int = 0
    # Per-tick cap on pages moved device->host by the spill batch (bounds
    # the one batched device_get a tick pays; the remainder carries over).
    spill_max_pages_per_tick: int = 32
    # Arm prefill->decode KV handoff on the gateway's relay leg.
    handoff: bool = False
    # Cost-model floors. Prompts below handoff_min_prompt_tokens never
    # handoff (re-prefill wins for short prompts and the model must say
    # so); the bandwidth/throughput floors seed the model before any
    # replica has MEASURED device_put MB/s (/health kv_put_mbps) or
    # prefill tok/s (/health prefill_tok_per_s); handoff_overhead_s is the
    # per-handoff fixed cost (two intra-host HTTP hops + serialize).
    handoff_min_prompt_tokens: int = 256
    put_bw_floor_mbps: float = 100.0
    prefill_tps_floor: float = 500.0
    handoff_overhead_s: float = 0.01
    # The gateway cannot tokenize (it is jax- and tokenizer-free), but the
    # floors above are denominated in MODEL tokens: its estimate is
    # max(whitespace words, prompt chars / est_chars_per_token). ~4 fits
    # BPE-style subword vocabularies; byte-level tokenizers want 1.0 (one
    # token per char). Calibrate against the decision journal's estimates
    # vs the replicas' measured /health numbers (troubleshooting §31).
    est_chars_per_token: float = 4.0
    # Wall-clock bound on each handoff leg (prefill export + import POST);
    # past it the gateway falls back to plain relay (re-prefill).
    handoff_timeout_s: float = 120.0

    def __post_init__(self):
        if self.host_tier_mb < 0:
            raise ValueError(
                f"kvtier.host_tier_mb must be >= 0, got {self.host_tier_mb}"
            )
        if self.spill_max_pages_per_tick < 1:
            raise ValueError(
                f"kvtier.spill_max_pages_per_tick must be >= 1, got "
                f"{self.spill_max_pages_per_tick}"
            )
        if self.handoff_min_prompt_tokens < 1:
            raise ValueError(
                f"kvtier.handoff_min_prompt_tokens must be >= 1, got "
                f"{self.handoff_min_prompt_tokens}"
            )
        for name in ("put_bw_floor_mbps", "prefill_tps_floor",
                     "handoff_timeout_s", "est_chars_per_token"):
            if getattr(self, name) <= 0:
                raise ValueError(
                    f"kvtier.{name} must be > 0, got {getattr(self, name)}"
                )
        if self.handoff_overhead_s < 0:
            raise ValueError(
                f"kvtier.handoff_overhead_s must be >= 0, got "
                f"{self.handoff_overhead_s}"
            )


@dataclass(frozen=True)
class UsageConfig:
    """Per-tenant usage metering & cost attribution (ISSUE 15,
    telemetry/usage.py): the in-memory per-tenant meter behind the
    ``/usage`` endpoints + ``ditl_usage_*`` families, the crash-consistent
    JSONL usage ledger, and the noisy-neighbor conviction thresholds the
    serving anomaly monitor applies when a TPOT/TTFT storm fires."""

    # Arm the in-memory meter on continuous-engine replicas (per-tenant
    # rollups at /usage, bounded ditl_usage_* families on /metrics, the
    # windowed accounting convictions read). Off = the engine keeps zero
    # per-tenant state — the bench A/B's unmetered leg.
    metering: bool = True
    # Directory for the crash-consistent usage ledger ("" = no ledger;
    # the meter still serves /usage). Each process writes its own
    # usage-<source>.jsonl, rotated under telemetry.journal_max_mb;
    # aggregate with python -m ditl_tpu.telemetry.usage --dir DIR.
    ledger_dir: str = ""
    # Distinct per-tenant metric-family sets (and rollup/window entries)
    # before new tenants fold into the "other" label — the bounded-
    # families rule GatewayMetrics already applies.
    max_tenant_families: int = 32
    # Noisy-neighbor conviction: when a TPOT/TTFT storm fires, the tenant
    # holding at least conviction_share of the window's prefill tokens is
    # named in the incident bundle — provided the window moved at least
    # conviction_min_tokens prompt tokens (thin windows convict nobody).
    # Tuning both is troubleshooting §33.
    conviction_share: float = 0.6
    conviction_min_tokens: int = 256

    def __post_init__(self):
        if not 0.0 < self.conviction_share <= 1.0:
            raise ValueError(
                f"usage.conviction_share must be in (0, 1], got "
                f"{self.conviction_share}"
            )
        for name in ("max_tenant_families", "conviction_min_tokens"):
            if getattr(self, name) < 1:
                raise ValueError(
                    f"usage.{name} must be >= 1, got {getattr(self, name)}"
                )


@dataclass(frozen=True)
class AdapterConfig:
    """Adapter plane (ISSUE 16, infer/adapters.py + gateway/publish.py):
    per-tenant multi-LoRA serving with hot load/evict and live
    train->serve weight publication. Disarmed by default — a server
    without a stacked adapter pool pays nothing."""

    # Spare all-zeros rows appended to the serving stack at launch
    # (infer/server.py --adapter-pool): the free rows hot loads and
    # publications land in. 0 = the stack holds exactly the launch-time
    # adapters and nothing can be hot-loaded.
    pool: int = 0
    # Trainer-side publication (train/adapter_export.py): every
    # publish_every optimizer steps the train loop commits an
    # adapter-only checkpoint (npz + crc manifest + atomic LATEST
    # pointer) under publish_dir/<publish_name>/. publish_every=0 or an
    # empty publish_dir = no exports.
    publish_dir: str = ""
    publish_every: int = 0
    publish_name: str = "adapter"
    # How long an evict/publish waits for in-flight requests on the old
    # row to drain before freeing it (the row never frees under traffic —
    # a timeout fails the evict, it does not tear the row).
    drain_timeout_s: float = 30.0

    def __post_init__(self):
        if self.pool < 0:
            raise ValueError(f"adapter.pool must be >= 0, got {self.pool}")
        if self.publish_every < 0:
            raise ValueError(
                f"adapter.publish_every must be >= 0, got "
                f"{self.publish_every}")
        if self.drain_timeout_s <= 0:
            raise ValueError(
                f"adapter.drain_timeout_s must be > 0, got "
                f"{self.drain_timeout_s}")


@dataclass(frozen=True)
class ChaosConfig:
    """Fault-injection plane (ditl_tpu/chaos/, ISSUE 5). ``rules`` is the
    compact spec string ``site:action[@k=v,...];...`` (see
    ``chaos.parse_rules``); empty = disarmed. The same ``seed`` replays
    the identical fault sequence — drills assert journal-diff equality.
    Armed by the trainer (``launch.py``); every
    worker of a pod receives the identical rules (the config fingerprint
    covers this section), with per-worker targeting via the rule's
    ``proc=N`` option."""

    seed: int = 0
    rules: str = ""
    # Chaos events journal + persisted fire-count state ("" = ride the
    # caller's journal / train.telemetry_dir). Fire counts persist across
    # relaunches so `max=N` caps survive the kills they inject.
    journal_dir: str = ""

    def __post_init__(self):
        if self.rules:
            # Validate at config time (reject-don't-drop): a typo'd site or
            # action must fail the launch, not silently never fire.
            from ditl_tpu.chaos.plane import parse_rules

            parse_rules(self.rules)


@dataclass(frozen=True)
class BulkConfig:
    """Offline bulk-inference lane (ditl_tpu/gateway/bulk.py, ISSUE 19):
    a crash-consistent job manager behind the gateway's ``/v1/bulk/jobs``
    endpoints, decomposing each job into per-prompt work items dispatched
    through the ordinary relay path pinned to ``best_effort`` — so
    interactive and batch traffic preempt bulk token-by-token at the
    engine and the interactive stall bound (ISSUE 8) holds unchanged.
    Disarmed by default: with ``dir`` empty the gateway serves no bulk
    endpoints and behaves exactly as before."""

    # The lane's durable state directory: job specs, per-job item/result
    # JSONL files, and the segment-rotated ``bulk-<source>.jsonl``
    # journal the resume scan replays. "" = lane disarmed.
    dir: str = ""
    # Per-JOB in-flight dispatch window: how many items one job may have
    # riding the relay at once. Also the crash-loss bound — a SIGKILLed
    # gateway re-dispatches at most this many already-attempted items on
    # resume (their terminal journal rows had not landed yet).
    max_in_flight: int = 4
    # Per-tenant quotas enforced by TenantAdmission at submit with typed
    # 429s (0 = unlimited): concurrently queued/running jobs, and total
    # not-yet-terminal items across those jobs.
    max_jobs_per_tenant: int = 4
    max_queued_items_per_tenant: int = 10000
    # Per-job item cap — a submit above it is a 400, not a quota 429
    # (reject-don't-drop: the job is malformed, not merely early).
    max_items_per_job: int = 10000
    # Decode budget per item when the job spec does not set max_new.
    default_max_new: int = 64
    # Outer retry budget per item for transient outcomes (429/503/504/
    # transport error) ON TOP of the relay's own idempotent-safe
    # in-attempt retries; exhausting it marks the item failed.
    retry_limit: int = 8
    # Backlog-stall detector: with a non-empty backlog, NO item reaching
    # a terminal outcome for this long while the fleet's live replicas
    # sit idle raises the ``bulk.backlog_stall`` anomaly (one incident
    # bundle via the fingerprint cooldown).
    stall_after_s: float = 30.0
    # Dispatch-loop poll cadence (cancel checks, stall checks, gauge
    # refresh) — the latency floor for noticing a cancel, not a
    # throughput knob.
    poll_interval_s: float = 0.5

    def __post_init__(self):
        for name in ("max_in_flight", "max_items_per_job",
                     "default_max_new", "retry_limit"):
            if getattr(self, name) < 1:
                raise ValueError(
                    f"bulk.{name} must be >= 1, got {getattr(self, name)}"
                )
        for name in ("max_jobs_per_tenant", "max_queued_items_per_tenant"):
            if getattr(self, name) < 0:
                raise ValueError(
                    f"bulk.{name} must be >= 0 (0 = unlimited), got "
                    f"{getattr(self, name)}"
                )
        for name in ("stall_after_s", "poll_interval_s"):
            if getattr(self, name) <= 0:
                raise ValueError(
                    f"bulk.{name} must be > 0, got {getattr(self, name)}"
                )


@dataclass(frozen=True)
class TelemetryConfig:
    """Observability knobs shared by the serving and training legs
    (ditl_tpu/telemetry/, ISSUE 6): journal size control, and the SLO
    objectives the ``/slo`` burn-rate endpoints grade against. Latency
    thresholds snap DOWN to the histogram bucket ladders
    (telemetry/registry.py) — the effective bound is reported in the
    ``/slo`` body so nobody grades against a number that was silently
    rounded."""

    # Per-process JSONL journal rotation cap in MiB (0 = unbounded, the
    # historical behavior). With tracing armed, span records arrive per
    # request and tick spans per scheduler tick — a long serving run
    # must not grow its journal without bound. Total footprint stays
    # ~this cap (telemetry/journal.py keeps the newest segments only).
    journal_max_mb: float = 0.0
    # HBM accounting (telemetry/memwatch.py, ISSUE 7): sample per-device
    # allocator stats whenever the step counter crosses a multiple of N
    # (host-only reads, zero device syncs; with steps_per_call > 1 that is
    # at most once per window; 0 disables sampling). Backends without
    # memory_stats (CPU) degrade to no gauges, never a crash.
    memory_sample_every: int = 1
    # How many live buffers the OOM post-mortem dump records
    # (shape/dtype/sharding/nbytes, largest first).
    memory_topk: int = 8
    # Server (replica) SLOs: TTFT / TPOT latency objectives over the
    # engine's harvest-observed histograms, plus availability.
    slo_ttft_s: float = 2.5
    slo_ttft_target: float = 0.95
    slo_tpot_s: float = 0.25
    slo_tpot_target: float = 0.95
    slo_availability_target: float = 0.999
    # Gateway SLOs: end-to-end relay latency + fleet availability.
    slo_gateway_e2e_s: float = 10.0
    slo_gateway_e2e_target: float = 0.95
    # Multi-window burn-rate evaluation: the alert fires only when BOTH
    # windows burn the error budget faster than slo_burn_alert (fast window
    # for responsiveness, slow window to de-flap).
    slo_fast_window_s: float = 300.0
    slo_slow_window_s: float = 3600.0
    slo_burn_alert: float = 1.0
    # -- Flight recorder + anomaly plane (ISSUE 10) ----------------------
    # Rows each always-on flight ring keeps (telemetry/flight.py): the
    # black-box horizon an incident bundle dumps. Bounded memory; zero
    # device syncs; dumped only on trigger.
    flight_ring_size: int = 512
    # Incident bundle directory (telemetry/incident.py); "" = anomaly
    # detectors may still run (journaled anomaly.detected events) but no
    # bundles are assembled.
    incident_dir: str = ""
    # Fingerprint cooldown: triggers for the same anomaly fingerprint
    # within this window only bump the suppressed counter — a sustained
    # storm is ONE bundle.
    incident_cooldown_s: float = 300.0
    # Bundle-dir retention (oldest-first GC, journal-rotation spirit).
    incident_max_bundles: int = 16
    incident_max_mb: float = 64.0
    # Bundle contents: last-N merged journal events, and the trace-slice
    # half-window (seconds before the trigger) exported to Chrome-trace.
    incident_journal_tail: int = 200
    incident_trace_window_s: float = 30.0
    # Serving detectors (telemetry/anomaly.py): observe cadence in
    # scheduler ticks, per-window storm threshold (deadline expiries /
    # 429s / preemptions / gateway spills), queue-depth growth limit,
    # latency-jump factor vs the rolling windowed-p95 baseline (with a
    # minimum sample count), and the prefix-hit-ratio collapse floor.
    anomaly_check_every_ticks: int = 32
    anomaly_storm_threshold: int = 8
    anomaly_queue_depth: int = 64
    anomaly_latency_factor: float = 3.0
    anomaly_min_samples: int = 16
    anomaly_hit_ratio_floor: float = 0.5
    # Training detectors: rolling window length, spike factor over the
    # rolling loss median, explosion factor over the rolling grad-norm
    # median (non-finite loss/grad always fires — not a knob).
    anomaly_window: int = 32
    anomaly_loss_spike_factor: float = 4.0
    anomaly_grad_explosion_factor: float = 10.0
    # -- Continuous profiling & stall attribution (ISSUE 18) -------------
    # Wall-clock sampling profiler hertz (telemetry/prof.py): 0 disarms;
    # > 0 arms a continuous sampler across the trainer's step loop and on
    # a profiling-armed gateway (the bench A/B leg gates its overhead
    # inside the perf_compare noise floor, so leaving it on is priced).
    prof_hz: float = 0.0
    # Distinct collapsed stacks the sampler holds before oldest-first
    # eviction — the profiler's hard memory cap.
    prof_max_stacks: int = 2048
    # Event-loop lag watchdog (evloop data plane only): busy heartbeat
    # age past this threshold is a stall — burst-sampled into a
    # convicting stack, journaled as loop.stall, and fed to the incident
    # plane. 0 disarms the watchdog.
    loop_stall_threshold_s: float = 0.0
    # Burst-sampling rate while a stall is in progress (high on purpose:
    # the burst lasts only for the stall's duration).
    loop_stall_burst_hz: float = 200.0

    def __post_init__(self):
        if self.journal_max_mb < 0:
            raise ValueError(
                f"telemetry.journal_max_mb must be >= 0 (0 = unbounded), "
                f"got {self.journal_max_mb}"
            )
        if self.memory_sample_every < 0:
            raise ValueError(
                f"telemetry.memory_sample_every must be >= 0 (0 = off), "
                f"got {self.memory_sample_every}"
            )
        if self.memory_topk < 1:
            raise ValueError(
                f"telemetry.memory_topk must be >= 1, got {self.memory_topk}"
            )
        for name in ("slo_ttft_target", "slo_tpot_target",
                     "slo_availability_target", "slo_gateway_e2e_target"):
            v = getattr(self, name)
            if not 0.0 < v < 1.0:
                # target == 1.0 has zero error budget: burn rate divides
                # by it — reject at config time, not at the first scrape.
                raise ValueError(
                    f"telemetry.{name} must be in (0, 1), got {v}"
                )
        for name in ("slo_ttft_s", "slo_tpot_s", "slo_gateway_e2e_s",
                     "slo_fast_window_s", "slo_slow_window_s",
                     "slo_burn_alert"):
            if getattr(self, name) <= 0:
                raise ValueError(
                    f"telemetry.{name} must be > 0, got {getattr(self, name)}"
                )
        if self.slo_fast_window_s >= self.slo_slow_window_s:
            raise ValueError(
                "telemetry.slo_fast_window_s must be shorter than "
                f"slo_slow_window_s, got {self.slo_fast_window_s} >= "
                f"{self.slo_slow_window_s}"
            )
        for name in ("flight_ring_size", "incident_max_bundles",
                     "anomaly_check_every_ticks", "anomaly_storm_threshold",
                     "anomaly_queue_depth", "anomaly_min_samples",
                     "anomaly_window"):
            if getattr(self, name) < 1:
                raise ValueError(
                    f"telemetry.{name} must be >= 1, got "
                    f"{getattr(self, name)}"
                )
        for name in ("incident_cooldown_s", "incident_max_mb",
                     "incident_trace_window_s", "anomaly_latency_factor",
                     "anomaly_loss_spike_factor",
                     "anomaly_grad_explosion_factor"):
            if getattr(self, name) <= 0:
                raise ValueError(
                    f"telemetry.{name} must be > 0, got {getattr(self, name)}"
                )
        if self.incident_journal_tail < 0:
            raise ValueError(
                f"telemetry.incident_journal_tail must be >= 0, got "
                f"{self.incident_journal_tail}"
            )
        if not 0.0 < self.anomaly_hit_ratio_floor < 1.0:
            raise ValueError(
                "telemetry.anomaly_hit_ratio_floor must be in (0, 1), got "
                f"{self.anomaly_hit_ratio_floor}"
            )
        for name in ("prof_hz", "loop_stall_threshold_s"):
            if getattr(self, name) < 0:
                raise ValueError(
                    f"telemetry.{name} must be >= 0 (0 = disarmed), got "
                    f"{getattr(self, name)}"
                )
        if self.prof_max_stacks < 1:
            raise ValueError(
                f"telemetry.prof_max_stacks must be >= 1, got "
                f"{self.prof_max_stacks}"
            )
        if self.loop_stall_burst_hz <= 0:
            raise ValueError(
                f"telemetry.loop_stall_burst_hz must be > 0, got "
                f"{self.loop_stall_burst_hz}"
            )

    def journal_max_bytes(self) -> int | None:
        """The journal rotation cap in bytes (None = unbounded) —
        the form ``EventJournal(max_bytes=...)`` takes."""
        return int(self.journal_max_mb * 1048576) or None

    def slo_windows(self) -> tuple[float, float]:
        return (self.slo_fast_window_s, self.slo_slow_window_s)

    def serving_slo_kwargs(self) -> dict:
        """Keyword form of the server objectives — exactly what
        ``telemetry.slo.serving_slo`` takes."""
        return dict(
            ttft_s=self.slo_ttft_s,
            ttft_target=self.slo_ttft_target,
            tpot_s=self.slo_tpot_s,
            tpot_target=self.slo_tpot_target,
            availability_target=self.slo_availability_target,
            windows=self.slo_windows(),
            burn_alert=self.slo_burn_alert,
        )

    def gateway_slo_kwargs(self) -> dict:
        """Keyword form of the gateway objectives — exactly what
        ``telemetry.slo.gateway_slo`` takes."""
        return dict(
            e2e_s=self.slo_gateway_e2e_s,
            e2e_target=self.slo_gateway_e2e_target,
            availability_target=self.slo_availability_target,
            windows=self.slo_windows(),
            burn_alert=self.slo_burn_alert,
        )

    def incident_kwargs(self) -> dict:
        """Keyword form of the bundle-hygiene knobs — exactly what
        ``telemetry.incident.IncidentManager`` takes."""
        return dict(
            cooldown_s=self.incident_cooldown_s,
            max_bundles=self.incident_max_bundles,
            max_total_mb=self.incident_max_mb,
            journal_tail=self.incident_journal_tail,
            trace_window_s=self.incident_trace_window_s,
        )

    def watchdog_kwargs(self) -> dict:
        """Keyword form of the loop-stall watchdog knobs — exactly what
        ``telemetry.prof.LoopWatchdog`` takes. Callers gate on
        ``loop_stall_threshold_s > 0`` before building one (0 =
        disarmed, and the watchdog itself rejects it)."""
        return dict(
            threshold_s=self.loop_stall_threshold_s,
            burst_hz=self.loop_stall_burst_hz,
        )

    def serving_detector_kwargs(self) -> dict:
        """Keyword form of the serving detector thresholds
        (``telemetry.anomaly.ServingDetector``)."""
        return dict(
            storm_threshold=self.anomaly_storm_threshold,
            queue_depth_limit=self.anomaly_queue_depth,
            latency_factor=self.anomaly_latency_factor,
            min_samples=self.anomaly_min_samples,
            hit_ratio_floor=self.anomaly_hit_ratio_floor,
        )

    def training_detector_kwargs(self) -> dict:
        """Keyword form of the training detector thresholds
        (``telemetry.anomaly.TrainingDetector``)."""
        return dict(
            window=self.anomaly_window,
            loss_spike_factor=self.anomaly_loss_spike_factor,
            grad_explosion_factor=self.anomaly_grad_explosion_factor,
        )


@dataclass(frozen=True)
class Config:
    runtime: RuntimeConfig = field(default_factory=RuntimeConfig)
    mesh: MeshConfig = field(default_factory=MeshConfig)
    model: ModelConfig = field(default_factory=ModelConfig)
    data: DataConfig = field(default_factory=DataConfig)
    train: TrainConfig = field(default_factory=TrainConfig)
    api: APIConfig = field(default_factory=APIConfig)
    gateway: GatewayConfig = field(default_factory=GatewayConfig)
    autoscale: AutoscaleConfig = field(default_factory=AutoscaleConfig)
    kvtier: KVTierConfig = field(default_factory=KVTierConfig)
    usage: UsageConfig = field(default_factory=UsageConfig)
    adapter: AdapterConfig = field(default_factory=AdapterConfig)
    chaos: ChaosConfig = field(default_factory=ChaosConfig)
    bulk: BulkConfig = field(default_factory=BulkConfig)
    telemetry: TelemetryConfig = field(default_factory=TelemetryConfig)

    def to_dict(self) -> dict[str, Any]:
        return dataclasses.asdict(self)

    def to_json(self) -> str:
        return json.dumps(self.to_dict(), indent=2, sort_keys=True)

    @classmethod
    def from_dict(cls, d: Mapping[str, Any]) -> "Config":
        kwargs: dict[str, Any] = {}
        for f in fields(cls):
            if f.name in d:
                sub = d[f.name]
                sub_cls = f.default_factory  # type: ignore[misc]
                if isinstance(sub, Mapping):
                    kwargs[f.name] = sub_cls(**sub)
                else:
                    kwargs[f.name] = sub
        return cls(**kwargs)


def _coerce(value: str, target_type: Any) -> Any:
    """Coerce a CLI string to the dataclass field's type."""
    if target_type in ("bool", bool):
        if value.lower() in ("1", "true", "yes", "on"):
            return True
        if value.lower() in ("0", "false", "no", "off"):
            return False
        raise ValueError(f"not a bool: {value!r}")
    for caster in (int, float):
        if target_type in (caster.__name__, caster):
            return caster(value)
    if value.lower() == "none":
        return None
    # Optional[int] style annotations arrive as strings like "int | None".
    if isinstance(target_type, str) and "int" in target_type:
        return int(value)
    if isinstance(target_type, str) and "float" in target_type:
        return float(value)
    return value


def parse_overrides(config: Config, overrides: Sequence[str]) -> Config:
    """Apply ``section.key=value`` overrides, e.g. ``mesh.fsdp=8``.

    Overrides are staged and applied ONCE per section, so ``__post_init__``
    validation sees only the final combination — `model.fused_gate_up=true
    model.num_experts=0` is legal regardless of CLI order, while a finally
    invalid combination still fails."""
    staged: dict[str, dict[str, Any]] = {}
    for item in overrides:
        if "=" not in item:
            raise ValueError(f"override must be section.key=value, got {item!r}")
        path, value = item.split("=", 1)
        parts = path.split(".")
        if len(parts) != 2:
            raise ValueError(f"override path must be section.key, got {path!r}")
        section_name, key = parts
        if not hasattr(config, section_name):
            raise ValueError(f"unknown config section {section_name!r}")
        section = getattr(config, section_name)
        matching = [f for f in fields(section) if f.name == key]
        if not matching:
            raise ValueError(f"unknown key {key!r} in section {section_name!r}")
        staged.setdefault(section_name, {})[key] = _coerce(value, matching[0].type)
    for section_name, kv in staged.items():
        config = replace(
            config, **{section_name: replace(getattr(config, section_name), **kv)}
        )
    return config


def config_fingerprint(config: Config) -> int:
    """Deterministic 63-bit fingerprint of the full config, used by the
    cross-host consistency check (runtime/consistency.py) to turn the
    reference's 'Nodes out of sync' doc advice (troubleshooting.md:53-63) into
    an executed startup assertion."""
    import hashlib

    digest = hashlib.sha256(config.to_json().encode("utf-8")).digest()
    return int.from_bytes(digest[:8], "big") >> 1
