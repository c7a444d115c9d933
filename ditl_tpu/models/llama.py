"""Llama-3.1-family decoder-only transformer (L1), TPU-first.

The reference never instantiates a model — its 70B Llama lives behind an HTTP
API (ref ``src/distributed_inference.py:34-41``) and the on-device compute is a
char-ordinal mean (ref ``src/utils.py:25-28``). This module is the real local
model the BASELINE.json north star calls for, designed for XLA/TPU:

- **Pure functional**: parameters are a pytree of arrays; ``init`` / ``forward``
  are plain functions, trivially composable with jit/grad/shard.
- **Scanned layers**: all decoder layers are stacked along a leading ``layers``
  dim and traversed with ``lax.scan`` — one layer's HLO compiled once instead
  of L times (compile-time and code-size win XLA can't get from unrolled
  Python loops).
- **Rematerialization**: ``jax.checkpoint`` around the scanned layer trades
  FLOPs for HBM (``ModelConfig.remat``).
- **bf16 compute / f32 masters**: matmuls run in ``cfg.dtype`` on the MXU with
  float32 accumulation; norms/softmax/logits in float32.
- **Logical sharding**: ``param_logical_axes`` mirrors the param tree with
  logical axis names; parallel/sharding.py maps them to the mesh (DP / FSDP /
  TP / SP / EP without touching this file).
- GQA (``num_kv_heads < num_heads``), RoPE (``rope_theta``), RMSNorm, SwiGLU —
  the Llama-3.1 architecture; Mixtral-style MoE via ``num_experts > 0``
  (models/moe.py); LoRA adapters via ``lora_rank > 0`` (models/lora.py).
"""

from __future__ import annotations

import math
from typing import Any

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from ditl_tpu.config import ModelConfig
from ditl_tpu.ops.attention import dot_product_attention

Params = dict[str, Any]

__all__ = ["init_params", "param_logical_axes", "forward", "num_params"]


def _dtype(name: str):
    return jnp.dtype(name)


# ---------------------------------------------------------------------------
# Initialization
# ---------------------------------------------------------------------------


def init_params(rng: jax.Array, cfg: ModelConfig) -> Params:
    """Initialize the full parameter pytree (layers stacked on axis 0)."""
    pd = _dtype(cfg.param_dtype)
    d, hd = cfg.hidden_size, cfg.head_dim
    nh, nkv, f, L = cfg.num_heads, cfg.num_kv_heads, cfg.intermediate_size, cfg.num_layers
    if nh % nkv:
        raise ValueError(f"num_heads {nh} must be divisible by num_kv_heads {nkv}")

    keys = iter(jax.random.split(rng, 16))

    def dense(key, shape, fan_in):
        return (jax.random.normal(key, shape) * (1.0 / math.sqrt(fan_in))).astype(pd)

    if cfg.double_layer:
        # LongCat-Flash (models/mla.py): a tree of its own under "layers",
        # its leaves drawn in ``param_dtype`` so that 9.6 GiB of bfloat16
        # weights are built on a 16 GB chip; the presets that came before
        # keep the eager float32 draws below, and their numbers.
        from ditl_tpu.models.mla import init_double_layer_params

        if cfg.tie_embeddings or cfg.lora_rank > 0:
            raise ValueError("the double layer has an untied head and no LoRA")
        return {
            "embed": {"embedding": (
                jax.random.normal(next(keys), (cfg.vocab_size, d)) * 0.02).astype(pd)},
            "layers": init_double_layer_params(next(keys), cfg),
            "final_norm": {"scale": jnp.ones((d,), pd)},
            "lm_head": {"kernel": dense(next(keys), (d, cfg.vocab_size), d)},
        }

    if cfg.dsa_layer:
        # DeepSeek-V3.2 (models/dsa.py): a leading dense stack and an expert
        # stack under "layers", drawn in ``param_dtype`` like the double layer
        from ditl_tpu.models.dsa import init_dsa_params

        if cfg.tie_embeddings or cfg.lora_rank > 0:
            raise ValueError("DeepSeek-V3.2's block has an untied head and no LoRA")
        return {
            "embed": {"embedding": (
                jax.random.normal(next(keys), (cfg.vocab_size, d)) * 0.02).astype(pd)},
            "layers": init_dsa_params(next(keys), cfg),
            "final_norm": {"scale": jnp.ones((d,), pd)},
            "lm_head": {"kernel": dense(next(keys), (d, cfg.vocab_size), d)},
        }

    if cfg.window_layer:
        # Trinity (models/swa.py): a leading dense stack and an expert stack
        # of window and full attention layers, drawn in ``param_dtype``
        from ditl_tpu.models.swa import init_swa_params

        return {
            "embed": {"embedding": (
                jax.random.normal(next(keys), (cfg.vocab_size, d)) * 0.02).astype(pd)},
            "layers": init_swa_params(next(keys), cfg),
            "final_norm": {"scale": jnp.ones((d,), pd)},
            "lm_head": {"kernel": dense(next(keys), (d, cfg.vocab_size), d)},
        }

    if cfg.layer_types:
        # Granite-4.0-H (models/ssm.py): a subtree a position of the period
        from ditl_tpu.models.ssm import init_hybrid_params

        tree = {
            "embed": {"embedding": (
                jax.random.normal(next(keys), (cfg.vocab_size, d)) * 0.02).astype(pd)},
            "layers": init_hybrid_params(next(keys), cfg),
            "final_norm": {"scale": jnp.ones((d,), pd)},
        }
        if not cfg.tie_embeddings:
            tree["lm_head"] = {"kernel": dense(next(keys), (d, cfg.vocab_size), d)}
        return tree

    params: Params = {
        "embed": {
            "embedding": (jax.random.normal(next(keys), (cfg.vocab_size, d)) * 0.02).astype(pd)
        },
        "layers": {
            "attn_norm": {"scale": jnp.ones((L, d), pd)},
            "attn": (
                {
                    "w_qkv": dense(
                        next(keys), (L, d, (nh + 2 * nkv) * hd), d
                    ),
                    "wo": dense(next(keys), (L, nh * hd, d), nh * hd),
                }
                if cfg.fused_qkv else
                {
                    "wq": dense(next(keys), (L, d, nh * hd), d),
                    "wk": dense(next(keys), (L, d, nkv * hd), d),
                    "wv": dense(next(keys), (L, d, nkv * hd), d),
                    "wo": dense(next(keys), (L, nh * hd, d), nh * hd),
                }
            ),
            "mlp_norm": {"scale": jnp.ones((L, d), pd)},
        },
        "final_norm": {"scale": jnp.ones((d,), pd)},
    }
    if cfg.attention_bias:  # Qwen2-family: bias on q/k/v only
        params["layers"]["attn"].update({
            "bq": jnp.zeros((L, nh * hd), pd),
            "bk": jnp.zeros((L, nkv * hd), pd),
            "bv": jnp.zeros((L, nkv * hd), pd),
        })
    if cfg.qk_norm:  # OLMoE-family: RMSNorm over the whole q and k vectors
        params["layers"]["attn"].update({
            "q_norm": jnp.ones((L, nh * hd), pd),
            "k_norm": jnp.ones((L, nkv * hd), pd),
        })
    if cfg.num_experts > 0:
        from ditl_tpu.models.moe import init_moe_params

        params["layers"]["moe"] = init_moe_params(next(keys), cfg)
    elif cfg.fused_gate_up:
        params["layers"]["mlp"] = {
            "w_gu": dense(next(keys), (L, d, 2 * f), d),
            "w_down": dense(next(keys), (L, f, d), f),
        }
    else:
        params["layers"]["mlp"] = {
            "w_gate": dense(next(keys), (L, d, f), d),
            "w_up": dense(next(keys), (L, d, f), d),
            "w_down": dense(next(keys), (L, f, d), f),
        }
    if not cfg.tie_embeddings:
        params["lm_head"] = {"kernel": dense(next(keys), (d, cfg.vocab_size), d)}
    if cfg.lora_rank > 0:
        if cfg.fused_qkv:
            raise ValueError(
                "fused_qkv does not compose with LoRA adapters (deltas "
                "target the per-projection names wq/wk/wv)"
            )
        from ditl_tpu.models.lora import init_lora_params

        params["layers"]["lora"] = init_lora_params(next(keys), cfg)
    return params


def param_logical_axes(cfg: ModelConfig) -> Params:
    """Same structure as ``init_params``, leaves are logical-axis tuples."""
    if cfg.double_layer:
        from ditl_tpu.models.mla import double_layer_logical_axes

        return {
            "embed": {"embedding": ("vocab", "embed")},
            "layers": double_layer_logical_axes(cfg),
            "final_norm": {"scale": ("norm",)},
            "lm_head": {"kernel": ("embed", "vocab")},
        }
    if cfg.dsa_layer:
        from ditl_tpu.models.dsa import dsa_logical_axes

        return {
            "embed": {"embedding": ("vocab", "embed")},
            "layers": dsa_logical_axes(cfg),
            "final_norm": {"scale": ("norm",)},
            "lm_head": {"kernel": ("embed", "vocab")},
        }
    if cfg.window_layer:
        from ditl_tpu.models.swa import swa_logical_axes

        return {
            "embed": {"embedding": ("vocab", "embed")},
            "layers": swa_logical_axes(cfg),
            "final_norm": {"scale": ("norm",)},
            "lm_head": {"kernel": ("embed", "vocab")},
        }
    if cfg.layer_types:
        from ditl_tpu.models.ssm import hybrid_logical_axes

        return {
            "embed": {"embedding": ("vocab", "embed")},
            "layers": hybrid_logical_axes(cfg),
            "final_norm": {"scale": ("norm",)},
            **({} if cfg.tie_embeddings else {"lm_head": {"kernel": ("embed", "vocab")}}),
        }
    axes: Params = {
        "embed": {"embedding": ("vocab", "embed")},
        "layers": {
            "attn_norm": {"scale": ("layers", "norm")},
            "attn": {
                **({"w_qkv": ("layers", "embed", "heads")}
                   if cfg.fused_qkv else
                   {"wq": ("layers", "embed", "heads"),
                    "wk": ("layers", "embed", "kv_heads"),
                    "wv": ("layers", "embed", "kv_heads")}),
                "wo": ("layers", "heads", "embed"),
                **({"bq": ("layers", "heads"),
                    "bk": ("layers", "kv_heads"),
                    "bv": ("layers", "kv_heads")}
                   if cfg.attention_bias else {}),
                **({"q_norm": ("layers", "heads"),
                    "k_norm": ("layers", "kv_heads")}
                   if cfg.qk_norm else {}),
            },
            "mlp_norm": {"scale": ("layers", "norm")},
        },
        "final_norm": {"scale": ("norm",)},
    }
    if cfg.num_experts > 0:
        from ditl_tpu.models.moe import moe_logical_axes

        axes["layers"]["moe"] = moe_logical_axes(cfg)
    elif cfg.fused_gate_up:
        axes["layers"]["mlp"] = {
            "w_gu": ("layers", "embed", "mlp"),
            "w_down": ("layers", "mlp", "embed"),
        }
    else:
        axes["layers"]["mlp"] = {
            "w_gate": ("layers", "embed", "mlp"),
            "w_up": ("layers", "embed", "mlp"),
            "w_down": ("layers", "mlp", "embed"),
        }
    if not cfg.tie_embeddings:
        axes["lm_head"] = {"kernel": ("embed", "vocab")}
    if cfg.lora_rank > 0:
        from ditl_tpu.models.lora import lora_logical_axes

        axes["layers"]["lora"] = lora_logical_axes(cfg)
    return axes


def num_params(params: Params) -> int:
    return sum(x.size for x in jax.tree.leaves(params))


def head_weights(params: Params, cfg: ModelConfig) -> jax.Array:
    """The (D, V) lm-head matrix (transposed embedding when tied)."""
    if cfg.tie_embeddings:
        return params["embed"]["embedding"].T
    return params["lm_head"]["kernel"]


# ---------------------------------------------------------------------------
# Building blocks
# ---------------------------------------------------------------------------


def rms_norm(x: jax.Array, scale: jax.Array, eps: float) -> jax.Array:
    """RMSNorm in float32 (norm statistics are precision-sensitive)."""
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    out = xf * jax.lax.rsqrt(var + eps)
    return (out * scale.astype(jnp.float32)).astype(x.dtype)


def rope_frequencies(
    head_dim: int, theta: float | None = None, cfg: ModelConfig | None = None
) -> jax.Array:
    """Inverse RoPE frequencies; applies Llama-3.1 NTK scaling when
    ``cfg.rope_scaling_factor > 0`` (same piecewise-by-wavelength rule as
    HF's "llama3" rope_scaling: long wavelengths divided by ``factor``,
    short ones untouched, a smooth interpolation between). With ``cfg``
    given, theta comes from the config — one source of truth for both the
    base frequencies and the scaling wavelength bands."""
    if cfg is not None:
        theta = cfg.rope_theta
    if theta is None:
        raise ValueError("rope_frequencies needs theta or cfg")
    inv_freq = 1.0 / (
        theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim)
    )
    if cfg is None or cfg.rope_scaling_factor <= 0:
        return inv_freq
    factor = cfg.rope_scaling_factor
    low_f, high_f = cfg.rope_scaling_low_freq_factor, cfg.rope_scaling_high_freq_factor
    old_len = cfg.rope_scaling_original_max_len
    wavelen = 2.0 * math.pi / inv_freq
    scaled = jnp.where(wavelen > old_len / low_f, inv_freq / factor, inv_freq)
    smooth = (old_len / wavelen - low_f) / (high_f - low_f)
    smoothed = (1.0 - smooth) * scaled / factor + smooth * scaled
    medium = (wavelen >= old_len / high_f) & (wavelen <= old_len / low_f)
    return jnp.where(medium, smoothed, scaled)


def apply_rope(
    x: jax.Array,
    positions: jax.Array,
    theta: float | None = None,
    cfg: ModelConfig | None = None,
) -> jax.Array:
    """Rotary position embedding. x: (B, S, H, D); positions: (B, S).
    Pass ``cfg`` (theta + scaling from config) or a bare ``theta``."""
    freqs = rope_frequencies(x.shape[-1], theta, cfg)  # (D/2,)
    angles = positions[..., None].astype(jnp.float32) * freqs  # (B, S, D/2)
    cos = jnp.cos(angles)[:, :, None, :]
    sin = jnp.sin(angles)[:, :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


def _constrain(x: jax.Array, logical_axes, mesh, rules):
    if mesh is None:
        return x
    from jax.sharding import NamedSharding

    from ditl_tpu.parallel.sharding import logical_to_spec

    return jax.lax.with_sharding_constraint(
        x, NamedSharding(mesh, logical_to_spec(logical_axes, rules))
    )


def _apply_remat(layer_fn, cfg: ModelConfig):
    """Wrap a layer body with the configured rematerialization policy."""
    if cfg.remat == "full":
        return jax.checkpoint(layer_fn)
    if cfg.remat == "dots":
        return jax.checkpoint(
            layer_fn,
            policy=jax.checkpoint_policies.checkpoint_dots_with_no_batch_dims,
        )
    if cfg.remat == "dots_inputs":
        # "dots" plus the two norm outputs (attn_in/mlp_in): the backward's
        # weight-gradient GEMMs read stored operands instead of a recompute
        # chain. Deliberately does NOT save the flash attention output —
        # measured on v5e (r5): adding attn_out REGRESSED the step by
        # ~45 ms (the recompute overlaps fine; the extra resident buffers
        # push XLA into worse layouts), while attn_in+mlp_in combined with
        # fused_gate_up is -20 ms. ~64MB/layer extra HBM over "dots".
        return jax.checkpoint(
            layer_fn,
            policy=jax.checkpoint_policies.save_from_both_policies(
                jax.checkpoint_policies.checkpoint_dots_with_no_batch_dims,
                jax.checkpoint_policies.save_only_these_names(
                    "attn_in", "mlp_in"
                ),
            ),
        )
    if cfg.remat == "attn":
        # Save only the per-layer attention outputs; recompute the rest.
        return jax.checkpoint(
            layer_fn,
            policy=jax.checkpoint_policies.save_only_these_names("attn_out"),
        )
    if cfg.remat != "none":
        raise ValueError(
            f"unknown remat policy {cfg.remat!r} "
            "(none|full|dots|dots_inputs|attn)"
        )
    return layer_fn


# ---------------------------------------------------------------------------
# Forward
# ---------------------------------------------------------------------------


def dense_mlp(mlp: dict, h: jax.Array, *, cfg: ModelConfig, mesh, rules) -> jax.Array:
    """The dense SwiGLU FFN on the normed input ``h`` (B, S, D), before the
    residual: ``_decoder_layer``'s, and each of the two a double layer has
    (models/mla.py). ``mlp``: ``w_gate`` and ``w_up``, or the fused ``w_gu``,
    and ``w_down``, plain or quantized."""
    from ditl_tpu.ops.quant import is_quantized_leaf, weight_einsum

    cd = jnp.dtype(cfg.dtype)
    use_custom_vjp = cfg.mlp_custom_vjp or cfg.mlp_bwd_impl == "pallas"
    if use_custom_vjp and "w_gu" not in mlp:
        # Reject-don't-drop: silently falling back to autodiff would
        # make an A/B of the flag measure byte-identical programs.
        raise ValueError(
            "mlp_custom_vjp/mlp_bwd_impl='pallas' require "
            "fused_gate_up=True (the hand-written backward targets the "
            "fused w_gu layout)"
        )
    if "w_gu" in mlp and use_custom_vjp:
        if is_quantized_leaf(mlp["w_gu"]) or is_quantized_leaf(mlp["w_down"]):
            raise ValueError(
                "mlp_custom_vjp/mlp_bwd_impl need plain float weights "
                "(quantized serving never differentiates — leave it off)"
            )
        from ditl_tpu.ops.mlp import mlp_block

        return mlp_block(
            lambda t: _constrain(t, ("batch", "seq", "act_mlp"), mesh, rules),
            h, mlp["w_gu"].astype(cd), mlp["w_down"].astype(cd),
            bwd_impl=cfg.mlp_bwd_impl,
            bwd_blocks=(cfg.mlp_bwd_block_n, cfg.mlp_bwd_block_f,
                        cfg.mlp_bwd_block_d),
            mesh=mesh, rules=rules,
        )
    if "w_gu" in mlp:
        # fused_gate_up: one (D, 2F) GEMM replaces the gate/up
        # pair — and one dgrad/wgrad pair replaces two in the
        # backward.
        gu = weight_einsum("bsd,df->bsf", h, mlp["w_gu"], compute_dtype=cd)
        gate, up = jnp.split(gu, 2, axis=-1)
    else:
        gate = weight_einsum("bsd,df->bsf", h, mlp["w_gate"], compute_dtype=cd)
        up = weight_einsum("bsd,df->bsf", h, mlp["w_up"], compute_dtype=cd)
    inner = jax.nn.silu(gate) * up
    inner = _constrain(inner, ("batch", "seq", "act_mlp"), mesh, rules)
    # Named so remat policies CAN save it (w_down's wgrad
    # operand); no shipped policy does — measured
    # neutral-to-negative on v5e.
    inner = checkpoint_name(inner, "mlp_inner")
    return weight_einsum("bsf,fd->bsd", inner, mlp["w_down"], compute_dtype=cd)


def _decoder_layer(
    layer_params: Params,
    x: jax.Array,
    *,
    cfg: ModelConfig,
    positions: jax.Array,
    segment_ids: jax.Array | None,
    mesh,
    rules,
    layer_cache: dict | None = None,
    cache_index: jax.Array | None = None,
    attn_mask: jax.Array | None = None,
    adapter_ids: jax.Array | None = None,
    paged: dict | None = None,
    prefill_causal: bool = False,
    token_mask: jax.Array | None = None,
    with_moe_counts: bool = False,
    moe_stack: dict | None = None,
    layer_index: jax.Array | None = None,
    pools: dict | None = None,
) -> tuple:
    """One decoder block: ``(x, aux)``, then ``new_kv`` with a cache, then
    (``with_moe_counts``) the (E,) assignments each expert got from the tokens
    ``token_mask`` (B, S) marks live — all of them when None; the router's
    load-balancing term ``aux`` sees the same tokens. ``moe_stack`` /
    ``layer_index``: every layer's expert weights and which layer this is
    (models/moe.py ``experts_in_place``); ``layer_params["moe"]`` then holds
    only this layer's router.

    With ``layer_cache`` (this layer's slice of the KV
    cache pytree, values shaped (B, Smax, K, D) — plus scales when int8,
    infer/cache.py), the chunk's keys/values are written at slot
    ``cache_index`` and attention runs against the whole cache under
    ``attn_mask`` — the KV-cache prefill/decode path (infer/engine.py).

    With ``pools`` this is the paged decode step. ``pools`` holds the page
    pools of ALL layers, whole (``{"kp", "vp"}``, plus the int8 scales
    ``{"ks", "vs"}``), layers and pages flattened into one leading axis:
    (L * n_pages, K, page_size, D) — kv-heads before page slots, the Mosaic
    trailing-dim layout of ops/paged_attention.py. They are NOT sliced by
    layer: ``forward`` keeps them outside its layer loop, and ``paged``'s
    ``table`` (B, maxp) already names this layer's pages inside them
    (``forward`` adds ``layer * n_pages``), so no layer's pool is ever copied
    in front of the kernel. ``layer_cache`` then holds only this layer's tail
    buffers (``{"tk", "tv"}``, (B, K, T, D)) and ``paged`` the rest of the
    tick metadata — ``starts``/``lengths`` (B,), the scan column ``t``
    (or, multi-query verify, the per-row tail offsets ``off``) and, where the
    program built it, ``steps``: the kernel's work list, which holds rows and
    steps and no page, so it is every layer's (``ops/paged_attention.py``
    ``decode_steps``). The token's
    K/V land in the tail (returned as this layer's new_kv; the pools are
    never re-emitted) and attention runs through the page table plus the
    tail."""
    b, s, d = x.shape
    nh, nkv, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    cd = _dtype(cfg.dtype)
    attn = layer_params["attn"]
    lora = layer_params.get("lora")

    from ditl_tpu.ops.quant import is_quantized_leaf, weight_einsum

    def base_proj(t, w):
        """The attention projections' base matmul — the proj_bwd_impl seam.
        The Pallas variant (ops/projection.py) keeps the forward
        bit-identical and swaps only the backward's spelling."""
        if cfg.proj_bwd_impl == "pallas":
            if is_quantized_leaf(w):
                # Reject-don't-drop (same failure mode as mlp_custom_vjp):
                # quantized serving never differentiates — leave it off.
                raise ValueError(
                    "proj_bwd_impl='pallas' needs plain float weights "
                    "(quantized serving never differentiates — leave it off)"
                )
            from ditl_tpu.ops.projection import projection

            return projection(
                t, w.astype(cd), bwd_impl="pallas",
                blocks=(cfg.proj_bwd_block_n, cfg.proj_bwd_block_d),
                mesh=mesh, rules=rules,
            )
        return weight_einsum("bsd,df->bsf", t, w, compute_dtype=cd)

    def proj(h, w, name):
        out = base_proj(h, w)
        if lora is not None and name in lora:
            from ditl_tpu.models.lora import lora_delta

            out = out + lora_delta(lora[name], h, cfg, adapter_ids=adapter_ids)
        return out

    # Attention block. The scopes are the step's stable names (ops/names.py).
    with jax.named_scope("attn_qkv"):
        h = rms_norm(x, layer_params["attn_norm"]["scale"], cfg.rms_norm_eps).astype(cd)
        # Named for remat="dots_inputs": h is the qkv projections' WGRAD
        # operand — saving it keeps the backward's weight-gradient GEMMs fed
        # from a stored buffer instead of a recompute chain (r5 ablation:
        # in-step wgrads ran at ~2x their isolated cost under remat="dots").
        h = checkpoint_name(h, "attn_in")

        def _bias(t, name):
            # Qwen2-family q/k/v bias (o stays bias-free).
            return t + attn[name].astype(t.dtype) if name in attn else t

        def _heads(t, n, bias, norm=None):
            # (B, S, n * hd) -> (B, S, n, hd), after the Qwen2-family bias
            # and the OLMoE-family norm: an RMSNorm with its own scale over
            # the WHOLE projected vector (all heads together).
            t = _bias(t, bias)
            if norm in attn:
                t = rms_norm(t, attn[norm], cfg.rms_norm_eps)
            return t.reshape(b, s, n, hd)

        if "w_qkv" in attn:
            # fused_qkv: one (D, (nh+2*nkv)*hd) GEMM replaces the q/k/v trio —
            # and one dgrad/wgrad pair replaces three each in the backward.
            if lora is not None:
                # init_params guards config-time; this closes the runtime hole
                # (adapters attached post-init by the serving path or a loaded
                # tree) — silently dropping deltas would serve base outputs.
                raise ValueError(
                    "fused_qkv does not compose with LoRA adapters (deltas "
                    "target the per-projection names wq/wk/wv)"
                )
            qkv = base_proj(h, attn["w_qkv"])
            q, k, v = jnp.split(
                qkv, (nh * hd, (nh + nkv) * hd), axis=-1
            )
            q = _heads(q, nh, "bq", "q_norm")
            k = _heads(k, nkv, "bk", "k_norm")
            v = _heads(v, nkv, "bv")
        else:
            q = _heads(proj(h, attn["wq"], "wq"), nh, "bq", "q_norm")
            k = _heads(proj(h, attn["wk"], "wk"), nkv, "bk", "k_norm")
            v = _heads(proj(h, attn["wv"], "wv"), nkv, "bv")
        if cfg.position_embedding == "rope":
            q = apply_rope(q, positions, cfg=cfg)
            k = apply_rope(k, positions, cfg=cfg)
        # The width the cache stores a head at: ``head_dim``, or whole lanes
        # of 128 where the engine pads a narrower head's pages with zeros
        # (infer/page_format.py ``KVPages.head_dim``). Zeros add nothing to a
        # score and come back as zero columns of the output, cut off below.
        width = hd
        if pools is not None:
            width = pools["kp"].shape[-1]
        elif layer_cache is not None:
            width = layer_cache["k"].shape[-1]
        if cfg.attention_multiplier or width != hd:
            # every attention path scales its scores by 1 / sqrt(the width it
            # sees): the query carries the ratio to the configured scale
            # (Granite's 1 / 64 over 128 stored lanes: 0.177; over its own 64:
            # 1 / 8, exact in any float)
            scale = cfg.attention_multiplier or hd ** -0.5
            q = (q.astype(jnp.float32) * (scale * math.sqrt(width))).astype(q.dtype)
        if width != hd:
            lanes = [(0, 0)] * 3 + [(0, width - hd)]
            q, k, v = jnp.pad(q, lanes), jnp.pad(k, lanes), jnp.pad(v, lanes)
        q = _constrain(q, ("batch", "seq", "act_heads", "head_dim"), mesh, rules)
        k = _constrain(k, ("batch", "seq", "act_kv_heads", "head_dim"), mesh, rules)
    new_kv = None
    with jax.named_scope("attn_core"):
        if pools is not None:
            from ditl_tpu.ops.paged_attention import paged_attention

            # Deferred flush: the chunk's K/V go into the tick's small TAIL
            # buffer (per-token writes into the big page pool inside the decode
            # scan cost ~7 ms/step on v5e); the kernel reads pages + tail, and
            # the engine flushes the tail into pages once per tick.
            tdt = layer_cache["tk"].dtype
            k_tok = jnp.swapaxes(k, 1, 2).astype(tdt)  # (B, K, S, D)
            v_tok = jnp.swapaxes(v, 1, 2).astype(tdt)
            if s == 1:
                # Plain decode tick: every live slot writes tail column
                # ``paged["t"]`` (the scan step — slots advance in lock-step
                # within a tick, each at its own global position).
                with jax.named_scope("kv_write"):
                    tk = jax.lax.dynamic_update_slice(
                        layer_cache["tk"], k_tok, (0, 0, paged["t"], 0)
                    )
                    tv = jax.lax.dynamic_update_slice(
                        layer_cache["tv"], v_tok, (0, 0, paged["t"], 0)
                    )
            else:
                # Speculative verify: K+1 tokens land at per-row tail offsets
                # ``paged["off"]`` (= pos - starts; slots advance by their own
                # acceptance, so depths diverge within the tick).
                from ditl_tpu.infer.cache import scatter_tail

                tk = scatter_tail(layer_cache["tk"], k_tok, paged["off"])
                tv = scatter_tail(layer_cache["tv"], v_tok, paged["off"])
            new_kv = {"tk": tk, "tv": tv}
            attn_out = paged_attention(
                q[:, 0] if s == 1 else q,
                pools["kp"], pools["vp"], paged["table"],
                paged["lengths"], tail_k=tk, tail_v=tv, starts=paged["starts"],
                k_scale=pools.get("ks"), v_scale=pools.get("vs"),
                steps=paged.get("steps"), mesh=mesh, rules=rules,
            )
            if s == 1:
                attn_out = attn_out[:, None]
        elif layer_cache is not None and prefill_causal:
            from ditl_tpu.infer.cache import write_kv

            # Full prefill from an EMPTY cache (offset 0): every query attends
            # only chunk positions — pure causal self-attention, so the Pallas
            # flash kernel applies (the O(S²) score tensor never hits HBM;
            # 3.4× faster at 8k context than the masked cache read, BASELINE).
            # Validity (right-padding) rides segment_ids; the cache write is
            # unchanged.
            new_kv = write_kv(layer_cache, k, v, cache_index)
            attn_out = dot_product_attention(
                q, k, v, causal=True, segment_ids=segment_ids,
                impl=cfg.attention_impl, mesh=mesh, rules=rules,
                block_sizes=(cfg.flash_block_q, cfg.flash_block_kv,
                             cfg.flash_block_q_bwd, cfg.flash_block_kv_bwd),
            )
        elif layer_cache is not None:
            from ditl_tpu.infer.cache import read_kv, write_kv

            new_kv = write_kv(layer_cache, k, v, cache_index)
            if "k_scale" in new_kv:
                # int8 cache: hand the raw int8 values + scales to attention so
                # the dequant fuses into the dots (HBM reads stay int8-sized).
                attn_out = dot_product_attention(
                    q, new_kv["k"], new_kv["v"], causal=False, mask=attn_mask,
                    impl=cfg.attention_impl, mesh=mesh, rules=rules,
                    k_scale=new_kv["k_scale"], v_scale=new_kv["v_scale"],
                )
            else:
                k_full, v_full = read_kv(new_kv, cd)
                attn_out = dot_product_attention(
                    q, k_full, v_full, causal=False, mask=attn_mask,
                    impl=cfg.attention_impl, mesh=mesh, rules=rules,
                )
        else:
            attn_out = dot_product_attention(
                q, k, v, causal=True, segment_ids=segment_ids, impl=cfg.attention_impl,
                mesh=mesh, rules=rules,
                block_sizes=(cfg.flash_block_q, cfg.flash_block_kv,
                             cfg.flash_block_q_bwd, cfg.flash_block_kv_bwd),
            )
    if width != hd:
        attn_out = attn_out.reshape(b, s, nh, width)[..., :hd]
    attn_out = attn_out.reshape(b, s, nh * hd)
    # Named for the remat="attn" policy: saving this one activation means the
    # backward pass never re-runs the attention kernel itself (its recompute
    # is the expensive part of full remat), while everything else (norms,
    # projections, SwiGLU) is still rematerialized.
    attn_out = checkpoint_name(attn_out, "attn_out")
    res = cfg.residual_multiplier  # Granite's; 1 leaves the sums as they were
    with jax.named_scope("attn_out"):
        out = proj(attn_out, attn["wo"], "wo")
        x = x + (out if res == 1.0 else res * out)
        x = _constrain(x, ("batch", "seq", "act_embed"), mesh, rules)

    # MLP / MoE block
    with jax.named_scope("mlp"):
        h = rms_norm(x, layer_params["mlp_norm"]["scale"], cfg.rms_norm_eps).astype(cd)
        h = checkpoint_name(h, "mlp_in")  # gate/up wgrad operand (see attn_in)
        aux = jnp.zeros((), jnp.float32)
        moe_counts = None
        if "moe" in layer_params:
            from ditl_tpu.models.moe import moe_block

            mlp_out, aux, moe_counts = moe_block(
                {**layer_params["moe"], **(moe_stack or {})}, h, cfg,
                token_mask=token_mask, mesh=mesh,
                layer=layer_index if moe_stack else None,
            )
        else:
            mlp_out = dense_mlp(layer_params["mlp"], h, cfg=cfg, mesh=mesh, rules=rules)
        x = x + (mlp_out if res == 1.0 else res * mlp_out)
        x = _constrain(x, ("batch", "seq", "act_embed"), mesh, rules)
    out = (x, aux) if new_kv is None else (x, aux, new_kv)
    if with_moe_counts:
        if moe_counts is None:
            raise ValueError("with_moe_counts needs a model with experts")
        out += (moe_counts,)
    return out


def forward(
    params: Params,
    input_ids: jax.Array,
    cfg: ModelConfig,
    *,
    positions: jax.Array | None = None,
    segment_ids: jax.Array | None = None,
    mesh=None,
    rules=None,
    with_aux: bool = False,
    cache: dict[str, jax.Array] | None = None,
    cache_index: jax.Array | None = None,
    attn_mask: jax.Array | None = None,
    return_hidden: bool = False,
    adapter_ids: jax.Array | None = None,
    paged: dict | None = None,
    prefill_causal: bool = False,
    token_mask: jax.Array | None = None,
    with_moe_counts: bool = False,
) -> Any:
    """Token ids (B, S) -> logits (B, S, V) in float32.

    ``token_mask`` (B, S), for a model with experts: the tokens that count
    (unmasked training tokens; a decode tick's live slots; a prefill bucket's
    real tokens). The router's load-balancing term and the assignment counts
    see only these; None counts all. ``with_moe_counts=True`` returns, last,
    the (L, E) int32 assignments each layer's experts got from them (not
    under pipeline parallelism).

    ``return_hidden=True`` skips the lm-head projection and returns the
    final-normed hidden states (B, S, D) instead of logits — the fused
    blockwise cross-entropy (ops/fused_ce.py) applies the head itself so the
    full logits tensor is never materialized.

    ``with_aux=True`` additionally returns the summed per-layer auxiliary loss
    (MoE router load balancing; zero for dense models; the loss averages it
    over the layers, train/step.py).

    ``cache`` (``{"k": (L,B,Smax,K,D), "v": ...}``, see infer/cache.py) turns
    this into the incremental-decode forward: the chunk's K/V are written into
    the cache at ``cache_index`` and attention uses ``attn_mask`` (B, S, Smax)
    instead of the causal mask. Returns ``(logits, new_cache)`` (plus aux when
    requested). No remat in this mode — there is no backward pass.

    A ``cache`` that holds page pools (``{"kp", "vp"}`` (L, n_pages, K,
    page_size, D), int8 pools with their scales ``{"ks", "vs"}``, beside the
    tick's tails ``{"tk", "tv"}`` (L, B, K, T, D)) with ``paged`` (the tick
    metadata, see ``_decoder_layer``) makes this a paged decode step. The
    pools are read in place: they stay out of the layer loop, whole, and every
    layer addresses its own pages inside them. Only the tails are scanned and
    returned (``new_cache`` is ``{"tk", "tv"}``); the caller scatters them into
    the pools once a tick (infer/page_format.py ``flush``).

    ``prefill_causal=True`` (with ``cache``): the chunk prefills an EMPTY
    cache from offset 0, so attention is pure causal self-attention over
    the chunk (validity via ``segment_ids``) and routes through the flash
    kernel instead of a masked full-cache read — the long-prompt serving
    prefill path."""
    cd = _dtype(cfg.dtype)
    b, s = input_ids.shape
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (b, s))

    # Embedding lookup. The stored table is (vocab->tensor, embed->fsdp)
    # sharded; gathering straight from it leaves the output embed-sharded in a
    # permuted device order that GSPMD cannot reshard to the batch-sharded
    # activation layout without an "involuntary full rematerialization"
    # (replicate-then-repartition) — in both the forward gather and the
    # backward scatter-add. Constraining the table to vocab-sharded /
    # embed-replicated for the lookup makes XLA use its sharded-vocab gather
    # (mask out-of-shard ids + psum over the tensor axis), whose output is
    # already batch-sharded; the embed-axis all-gather this implies is the
    # same per-use weight all-gather FSDP performs everywhere else.
    with jax.named_scope("embed"):
        table = _constrain(
            params["embed"]["embedding"].astype(cd), ("vocab", None), mesh, rules
        )
        x = table[input_ids]
        if cfg.embedding_multiplier != 1.0:
            x = x * cfg.embedding_multiplier
        if cfg.residual_dtype:
            # the stream alone; every norm's output goes back to ``dtype``
            x = x.astype(_dtype(cfg.residual_dtype))
        x = _constrain(x, ("batch", "seq", "act_embed"), mesh, rules)

    block, n_scan = _decoder_layer, cfg.num_layers
    if cfg.double_layer:
        # LongCat-Flash: the same scans carry its double layer (models/mla.py)
        from ditl_tpu.models.mla import double_layer as block
    rec = None  # a hybrid stack's recurrent state, carried beside the stream
    if cfg.layer_types and not cfg.window_layer:
        # Granite-4.0-H: one scan step a PERIOD of unlike layers (models/ssm.py)
        from ditl_tpu.models.ssm import hybrid_period as block
        from ditl_tpu.models.ssm import period_counts, state_axes, tick_leaves

        n_scan, _, attn_per = period_counts(cfg)
        if cache is not None:
            if set(state_axes(cfg)) - set(cache):
                raise ValueError(
                    "a cached forward of a hybrid stack needs the sequences' "
                    f"recurrent state beside the keys and values ({list(state_axes(cfg))} "
                    "in cache: models/ssm.py init_state)")
            # the state, and what a decode tick holds beside it (retention's
            # held tokens: ``page_format.StateSlots.tails0``)
            rec = {k: cache[k] for k in (*state_axes(cfg), *tick_leaves(cfg)) if k in cache}
            cache = {k: v for k, v in cache.items() if k not in rec}

    if cfg.dsa_layer:
        # DeepSeek-V3.2: a leading dense stack and an expert stack, each a
        # scan of its own (models/dsa.py), cached or not
        from ditl_tpu.models.dsa import stack

        if adapter_ids is not None:
            raise ValueError("LoRA adapters are not implemented for DeepSeek-V3.2's block")
        x, layer_aux, new_cache, *moe_counts = stack(
            params["layers"], x, cfg=cfg, positions=positions, segment_ids=segment_ids,
            mesh=mesh, rules=rules, cache=cache, cache_index=cache_index,
            attn_mask=attn_mask, paged=paged, prefill_causal=prefill_causal,
            token_mask=token_mask)
        if not with_moe_counts:  # the experts' counts, then the tokens selected
            moe_counts = []
    elif cfg.window_layer:
        # Trinity: window and full attention layers in two stacks, each a scan
        # of its own (models/swa.py), cached or not
        from ditl_tpu.models.swa import stack

        if adapter_ids is not None:
            raise ValueError("LoRA adapters are not implemented for a stack with "
                             "window layers")
        x, layer_aux, new_cache, counts = stack(
            params["layers"], x, cfg=cfg, positions=positions, segment_ids=segment_ids,
            mesh=mesh, rules=rules, cache=cache, cache_index=cache_index, paged=paged,
            token_mask=token_mask)
        moe_counts = [counts] if with_moe_counts else []
    elif cache is not None:
        layers, moe_stack = params["layers"], None
        if "moe" in layers:
            from ditl_tpu.models.moe import experts_in_place, grouped_rows

            if experts_in_place(layers["moe"], grouped_rows(cfg, b * s), mesh):
                # the loop slices only the router; the kernel addresses the
                # layer's experts inside the stack
                in_loop = ("router", "router_bias")
                moe_stack = {k: v for k, v in layers["moe"].items() if k not in in_loop}
                layers = {**layers, "moe": {k: v for k, v in layers["moe"].items()
                                            if k in in_loop}}

        pools = None
        tails = ("tk", "tv", "tc")
        if "kp" in cache or "cp" in cache:
            # A paged decode. The pools stay whole and OUTSIDE the loop (the
            # kernel is a custom call: a scanned pool would be copied out of
            # the stack, layer by layer, every step): layers and pages become
            # one axis (a bitcast) and each layer's page table is offset to
            # its own pages. Only the small tails are scanned. A latent pool
            # (``cp``: (2 L, n_pages, ps, Dl), one set of pages an attention
            # SUBLAYER) is addressed the same way, two sets a layer.
            n_pages = cache["cp" if "cp" in cache else "kp"].shape[1]
            pools = {k: v.reshape(-1, *v.shape[2:]) for k, v in cache.items()
                     if k not in tails}
            cache = {k: v for k, v in cache.items() if k in tails}
            if "cp" in pools:
                paged = {**paged, "n_pages": n_pages}
                n_pages *= 2
            elif rec is not None:
                paged = {**paged, "n_pages": n_pages}
                n_pages *= attn_per
        if rec is not None:
            # keys and values are the attention layers': (periods, a period's
            # attention layers, ...) for the scan, and back after it
            cache = {k: v.reshape(n_scan, attn_per, *v.shape[1:])
                     for k, v in cache.items()}

        def cached_layer_fn(carry, xs):
            layer_params, layer_cache, layer_index = xs
            layer_paged = paged
            if pools is not None:
                layer_paged = {
                    **paged, "table": paged["table"] + layer_index * n_pages}
            extra = {}
            if rec is not None:
                carry, extra["rec"] = carry
            y, aux, new_kv, *counts = block(
                layer_params,
                carry,
                cfg=cfg,
                positions=positions,
                segment_ids=segment_ids,
                mesh=mesh,
                rules=rules,
                layer_cache=layer_cache,
                cache_index=cache_index,
                attn_mask=attn_mask,
                adapter_ids=adapter_ids,
                paged=layer_paged,
                prefill_causal=prefill_causal,
                token_mask=token_mask,
                with_moe_counts=with_moe_counts,
                moe_stack=moe_stack,
                layer_index=layer_index,
                pools=pools,
                **extra,
            )
            if rec is not None:
                *counts, new_rec = counts
                y = (y, new_rec)
            return y, (aux, new_kv, *counts)

        # Every layer part has a scope of its own, so what is left to this
        # one is what the scan itself does: slicing each layer's weights and
        # cache (a paged decode: its tails) out of the stacked arrays and
        # stacking the new K/V.
        with jax.named_scope("layer_scan"):
            x, (layer_aux, new_cache, *moe_counts) = jax.lax.scan(
                cached_layer_fn, x if rec is None else (x, rec),
                (layers, cache, jnp.arange(n_scan, dtype=jnp.int32)),
            )
        if rec is not None:
            x, rec = x
            new_cache = {k: v.reshape(-1, *v.shape[2:])
                         for k, v in new_cache.items()} | rec
    elif mesh is not None and mesh.shape.get("stage", 1) > 1:
        # Pipeline parallelism: layers are stage-sharded; microbatches flow
        # through the stages via ppermute (parallel/pipeline.py). Layer bodies
        # run inside shard_map, so no GSPMD constraints (mesh=None).
        from ditl_tpu.parallel.pipeline import pipeline_apply

        if with_moe_counts:
            raise ValueError("with_moe_counts is not carried through the "
                             "pipeline schedule")

        def pipe_layer(h, layer_params, ex):
            pos, seg, tmask = ex
            return block(
                layer_params, h, cfg=cfg, positions=pos, segment_ids=seg,
                mesh=None, rules=None, token_mask=tmask,
            )

        pipe_layer = _apply_remat(pipe_layer, cfg)
        with jax.named_scope("layer_scan"):
            x, layer_aux = pipeline_apply(
                pipe_layer,
                params["layers"],
                x,
                (positions, segment_ids, token_mask),
                mesh=mesh,
                rules=rules,
                n_microbatches=cfg.pipeline_microbatches or None,
            )
        new_cache, moe_counts = None, []
    else:
        def layer_fn(carry, layer_params):
            y, *ys = block(
                layer_params,
                carry,
                cfg=cfg,
                positions=positions,
                segment_ids=segment_ids,
                mesh=mesh,
                rules=rules,
                adapter_ids=adapter_ids,
                token_mask=token_mask,
                with_moe_counts=with_moe_counts,
            )
            return y, tuple(ys)

        layer_fn = _apply_remat(layer_fn, cfg)
        with jax.named_scope("layer_scan"):
            x, (layer_aux, *moe_counts) = jax.lax.scan(
                layer_fn, x, params["layers"], unroll=cfg.scan_unroll
            )
        new_cache = None

    with jax.named_scope("lm_head"):
        x = rms_norm(x, params["final_norm"]["scale"], cfg.rms_norm_eps)
        if cfg.logits_scaling != 1.0:
            # the head is linear: dividing its input divides the logits, for
            # the fused loss (which applies the head itself) as for this one
            x = x / cfg.logits_scaling
        x = x.astype(cd)  # the head's input, whatever the stream was kept in
    # what follows the logits (or the hidden states), in this order
    tail = ((jnp.sum(layer_aux),) if with_aux else ()) + (
        (new_cache,) if cache is not None else ()) + tuple(moe_counts)
    if return_hidden:
        out = (x,)
        out = out + tail
        return out if len(out) > 1 else x
    from ditl_tpu.ops.quant import weight_einsum

    with jax.named_scope("lm_head"):
        logits = weight_einsum(
            "bsd,dv->bsv", x, head_weights(params, cfg),
            compute_dtype=cd, preferred=jnp.float32,
        )
        logits = _constrain(logits, ("batch", "seq", "act_vocab"), mesh, rules)
    out = (logits,) + tail
    return out if len(out) > 1 else logits
