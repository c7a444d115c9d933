"""HuggingFace checkpoint import: torch Llama/Qwen2/Mixtral/OLMoE/LongCat-Flash/Granite-4.0-H/Trinity weights -> param pytree.

The reference never loads weights at all — its Llama-3.1-70B lives behind an
HTTP API (ref ``src/distributed_inference.py:34-41``, ``MODEL_NAME`` in
``config.py``). For this framework to fine-tune/serve those same models
locally on TPU, real checkpoints must come in from the HF ecosystem. This
module maps a ``transformers`` state dict onto the stacked-layer param tree
(models/llama.py) with pure numpy host-side work:

- torch ``Linear.weight`` is (out, in) — transposed here to the (in, out)
  einsum layout the model uses;
- per-layer tensors are stacked along the leading ``layers`` axis (the
  ``lax.scan`` layout, one HLO per layer);
- nothing touches a device: outputs are numpy, so the caller can shard them
  straight to the mesh with ``jax.device_put`` / ``make_array_from_callback``
  without first materializing the whole model on one chip.

RoPE/RMSNorm/SwiGLU conventions match HF's Llama exactly (same rotate-half
frequency layout, same eps placement); verified by the logits-parity test
against a randomly initialized ``LlamaForCausalLM`` (tests/test_convert.py).
"""

from __future__ import annotations

from typing import Any, Mapping

import numpy as np

from ditl_tpu.config import ModelConfig

__all__ = [
    "config_from_hf",
    "params_from_state_dict",
    "state_dict_from_params",
    "load_hf_model",
    "export_hf_model",
]


def config_from_hf(hf_config: Any, **overrides) -> ModelConfig:
    """Derive a ModelConfig from a ``transformers`` Llama/Mixtral config."""
    num_heads = hf_config.num_attention_heads
    head_dim = getattr(hf_config, "head_dim", None) or (
        hf_config.hidden_size // num_heads
    )
    kwargs: dict[str, Any] = dict(
        name=getattr(hf_config, "name_or_path", "") or hf_config.model_type,
        vocab_size=hf_config.vocab_size,
        hidden_size=hf_config.hidden_size,
        intermediate_size=hf_config.intermediate_size,
        num_layers=hf_config.num_hidden_layers,
        num_heads=num_heads,
        num_kv_heads=getattr(hf_config, "num_key_value_heads", num_heads),
        head_dim=head_dim,
        max_seq_len=hf_config.max_position_embeddings,
        rope_theta=getattr(hf_config, "rope_theta", 10000.0),
        rms_norm_eps=hf_config.rms_norm_eps,
        tie_embeddings=getattr(hf_config, "tie_word_embeddings", False),
        # Qwen2-family: q/k/v bias. HF Llama configs carry an explicit
        # attention_bias flag; Qwen2Config implies it by architecture.
        attention_bias=bool(
            getattr(hf_config, "attention_bias", False)
            or getattr(hf_config, "model_type", "") == "qwen2"
        ),
    )
    if getattr(hf_config, "num_local_experts", 0):  # Mixtral
        kwargs["num_experts"] = hf_config.num_local_experts
        kwargs["num_experts_per_tok"] = hf_config.num_experts_per_tok
    elif getattr(hf_config, "model_type", "") == "olmoe":
        # q/k normalisation is fixed by the architecture (no config key);
        # intermediate_size is the width of ONE expert.
        kwargs["num_experts"] = hf_config.num_experts
        kwargs["num_experts_per_tok"] = hf_config.num_experts_per_tok
        kwargs["norm_topk_prob"] = bool(hf_config.norm_topk_prob)
        kwargs["router_aux_coef"] = hf_config.router_aux_loss_coef
        kwargs["qk_norm"] = True
        if getattr(hf_config, "clip_qkv", None) is not None:
            raise ValueError("OLMoE's clip_qkv is not implemented (the "
                             "published 1B-7B configs leave it null)")
    elif getattr(hf_config, "model_type", "") == "brumby":
        # every layer a retention layer; what the config has no key for (the
        # degree, the chunk, eps) is ModelConfig's default (models/retention.py)
        kwargs.update(layer_types="r" * hf_config.num_hidden_layers, qk_norm=True,
                      fused_gate_up=True)
    scaling = getattr(hf_config, "rope_scaling", None)
    if scaling and scaling.get("rope_type", scaling.get("type")) == "llama3":
        kwargs["rope_scaling_factor"] = scaling["factor"]
        kwargs["rope_scaling_low_freq_factor"] = scaling["low_freq_factor"]
        kwargs["rope_scaling_high_freq_factor"] = scaling["high_freq_factor"]
        kwargs["rope_scaling_original_max_len"] = scaling[
            "original_max_position_embeddings"
        ]
    elif scaling:
        raise ValueError(
            f"unsupported rope_scaling type {scaling!r} (only 'llama3' NTK "
            "scaling is implemented)"
        )
    kwargs.update(overrides)
    return ModelConfig(**kwargs)


def _moe_names(cfg: ModelConfig) -> tuple[str, str, str, str]:
    """HF's names of an expert layer: (block, gate, up, down). Mixtral:
    ``block_sparse_moe.gate`` and ``.experts.{j}.w1 / w3 / w2``; OLMoE (the
    family with q/k normalisation): ``mlp.gate`` and ``.experts.{j}.gate_proj
    / up_proj / down_proj``."""
    if cfg.qk_norm:
        return "mlp", "gate_proj", "up_proj", "down_proj"
    return "block_sparse_moe", "w1", "w3", "w2"


def _np(t) -> np.ndarray:
    """torch tensor (any dtype/device) -> float32 numpy without torch deps
    leaking into the signature."""
    if hasattr(t, "detach"):
        t = t.detach().to("cpu").float().numpy()
    return np.asarray(t, np.float32)


def _stack(sd: Mapping[str, Any], template: str, n_layers: int, transpose: bool) -> np.ndarray:
    mats = []
    for i in range(n_layers):
        w = _np(sd[template.format(i=i)])
        mats.append(w.T if transpose else w)
    return np.stack(mats, axis=0)


# LongCat-Flash's double layer (models/mla.py): our leaf -> HF's module path
# under ``model.layers.{i}`` (``modeling_longcat_flash.py`` as recalled: two
# attention modules, two dense MLPs and four norms a layer in ModuleLists
# indexed by the half ``{j}``, one expert block ``mlp`` whose router holds a
# ``classifier`` and the selection bias as a buffer). Matrices transpose.
_DOUBLE_ATTN = {
    "w_qa": "self_attn.{j}.q_a_proj.weight", "q_norm": "self_attn.{j}.q_a_layernorm.weight",
    "w_qb": "self_attn.{j}.q_b_proj.weight", "w_kva": "self_attn.{j}.kv_a_proj_with_mqa.weight",
    "kv_norm": "self_attn.{j}.kv_a_layernorm.weight", "w_kvb": "self_attn.{j}.kv_b_proj.weight",
    "wo": "self_attn.{j}.o_proj.weight",
}
_DOUBLE_MLP = {"w_gate": "mlps.{j}.gate_proj.weight", "w_up": "mlps.{j}.up_proj.weight",
               "w_down": "mlps.{j}.down_proj.weight"}
_DOUBLE_NORMS = {"attn_norm": "input_layernorm.{j}.weight",
                 "mlp_norm": "post_attention_layernorm.{j}.weight"}
_DOUBLE_EXPERT = {"w_gate": "gate_proj", "w_up": "up_proj", "w_down": "down_proj"}


def _double_layer_items(cfg: ModelConfig):
    """(path in our ``layers`` tree, HF template with ``{i}``, transpose?) of
    every leaf of the double layer; an expert leaf's template keeps ``{e}``
    for the HELD experts' published indices."""
    for j in range(2):
        for tree, table in (("attn", _DOUBLE_ATTN), ("mlp", _DOUBLE_MLP)):
            for ours, theirs in table.items():
                yield ((tree, f"sub{j}", ours), "model.layers.{i}." + theirs.format(j=j),
                       not ours.endswith("norm"))
    yield ("moe", "router"), "model.layers.{i}.mlp.router.classifier.weight", True
    if cfg.router_bias:
        yield (("moe", "router_bias"), "model.layers.{i}.mlp.router.e_score_correction_bias",
               False)
    for ours, theirs in _DOUBLE_EXPERT.items():
        yield ("moe", ours), "model.layers.{i}.mlp.experts.{e}." + theirs + ".weight", True


def _double_layer_from_state_dict(sd, cfg: ModelConfig, cast) -> dict[str, Any]:
    from ditl_tpu.models.moe import held_experts

    L = cfg.num_layers
    first, count = held_experts(cfg)
    layers: dict[str, Any] = {
        ours: {"scale": cast(np.stack([
            _stack(sd, "model.layers.{i}." + theirs.format(j=j), L, False)
            for j in range(2)], axis=1))}
        for ours, theirs in _DOUBLE_NORMS.items()
    }
    for path, template, transpose in _double_layer_items(cfg):
        if "{e}" in template:
            leaf = np.stack([_stack(sd, template.replace("{e}", str(first + e)), L, transpose)
                             for e in range(count)], axis=1)
        else:
            leaf = _stack(sd, template, L, transpose)
        node = layers
        for key in path[:-1]:
            node = node.setdefault(key, {})
        node[path[-1]] = leaf.astype(np.float32) if path[-1] == "router_bias" else cast(leaf)
    return layers


def _double_layer_state_dict(layers, cfg: ModelConfig, host) -> dict[str, np.ndarray]:
    from ditl_tpu.models.moe import held_experts

    first, count = held_experts(cfg)
    sd: dict[str, np.ndarray] = {}
    for i in range(cfg.num_layers):
        for ours, theirs in _DOUBLE_NORMS.items():
            for j in range(2):
                sd[f"model.layers.{i}." + theirs.format(j=j)] = host(layers[ours]["scale"][i, j])
        for path, template, transpose in _double_layer_items(cfg):
            leaf = layers
            for key in path:
                leaf = leaf[key]
            for e in range(count if "{e}" in template else 1):
                w = host(leaf[i, e] if "{e}" in template else leaf[i])
                sd[template.format(i=i, e=first + e)] = w.T if transpose else w
    return sd


# Granite-4.0-H's hybrid stack (models/ssm.py): our leaf under a position's
# subtree -> the ``granitemoehybrid`` checkpoint's name under
# ``model.layers.{i}`` (``modeling_granitemoehybrid.py`` as recalled, never
# held against a published checkpoint: the mixer is ``mamba`` with
# ``in_proj`` [z | xBC | dt], a depthwise ``conv1d`` whose weight is (C, 1,
# K), ``dt_bias``, ``A_log``, ``D``, the gated ``norm`` and ``out_proj``; the
# FFN is ``shared_mlp`` with a fused gate|up ``input_linear`` and
# ``output_linear``; attention layers are ``self_attn`` as Llama's). Layer
# ``i`` is position ``i % period`` of period ``i // period``.
_HYBRID_MIXER = {"dt_bias": "mamba.dt_bias", "A_log": "mamba.A_log", "D": "mamba.D",
                 "norm": "mamba.norm.weight", "conv_b": "mamba.conv1d.bias"}
_HYBRID_ATTN = {"wq": "q_proj", "wk": "k_proj", "wv": "v_proj", "wo": "o_proj"}


# Brumby's retention layer (models/retention.py; ``model_type: brumby``): the
# Qwen3 block's names (``self_attn`` with q/k/v/o, a ``q_norm`` / ``k_norm`` a
# head; ``mlp`` with ``gate_proj`` / ``up_proj`` / ``down_proj``, fused here
# into ``w_gu``) and the retention's gate a kv head, ``self_attn.gate_proj``
# with its bias (the gate's name is this repository's: recalled from no
# published checkpoint).
_RET_MATRICES = {**_HYBRID_ATTN, "wg": "gate_proj"}
_RET_VECTORS = {"q_norm": "q_norm.weight", "k_norm": "k_norm.weight", "bg": "gate_proj.bias"}


def _retention_layer_from_state_dict(sd, i: int) -> dict[str, Any]:
    p = f"model.layers.{i}."
    return {
        "attn_norm": {"scale": _np(sd[p + "input_layernorm.weight"])},
        "mlp_norm": {"scale": _np(sd[p + "post_attention_layernorm.weight"])},
        "mlp": {"w_gu": np.concatenate([_np(sd[p + "mlp.gate_proj.weight"]).T,
                                        _np(sd[p + "mlp.up_proj.weight"]).T], axis=1),
                "w_down": _np(sd[p + "mlp.down_proj.weight"]).T},
        "ret": {**{ours: _np(sd[p + f"self_attn.{theirs}.weight"]).T
                   for ours, theirs in _RET_MATRICES.items()},
                **{ours: _np(sd[p + f"self_attn.{theirs}"])
                   for ours, theirs in _RET_VECTORS.items()}},
    }


def _retention_layer_state_dict(sub, n: int, p: str, host) -> dict[str, np.ndarray]:
    gate, up = np.split(host(sub["mlp"]["w_gu"][n]), 2, axis=1)
    return {
        p + "input_layernorm.weight": host(sub["attn_norm"]["scale"][n]),
        p + "post_attention_layernorm.weight": host(sub["mlp_norm"]["scale"][n]),
        p + "mlp.gate_proj.weight": gate.T, p + "mlp.up_proj.weight": up.T,
        p + "mlp.down_proj.weight": host(sub["mlp"]["w_down"][n]).T,
        **{p + f"self_attn.{theirs}.weight": host(sub["ret"][ours][n]).T
           for ours, theirs in _RET_MATRICES.items()},
        **{p + f"self_attn.{theirs}": host(sub["ret"][ours][n])
           for ours, theirs in _RET_VECTORS.items()},
    }


def _hybrid_layer_from_state_dict(sd, cfg: ModelConfig, i: int) -> dict[str, Any]:
    if cfg.layer_types[i] == "r":
        return _retention_layer_from_state_dict(sd, i)
    p = f"model.layers.{i}."
    inner = cfg.ssm_heads * cfg.ssm_head_dim
    layer: dict[str, Any] = {
        "attn_norm": {"scale": _np(sd[p + "input_layernorm.weight"])},
        "mlp_norm": {"scale": _np(sd[p + "post_attention_layernorm.weight"])},
        "mlp": {"w_gu": _np(sd[p + "shared_mlp.input_linear.weight"]).T,
                "w_down": _np(sd[p + "shared_mlp.output_linear.weight"]).T},
    }
    if cfg.layer_types[i] == "a":
        layer["attn"] = {ours: _np(sd[p + f"self_attn.{theirs}.weight"]).T
                         for ours, theirs in _HYBRID_ATTN.items()}
        return layer
    w_in = _np(sd[p + "mamba.in_proj.weight"]).T  # (D, z | xBC | dt)
    layer["ssm"] = {
        "w_in": w_in[:, :-cfg.ssm_heads], "w_dt": w_in[:, -cfg.ssm_heads:],
        "conv_w": _np(sd[p + "mamba.conv1d.weight"])[:, 0, :].T,  # (C, 1, K) -> (K, C)
        "w_out": _np(sd[p + "mamba.out_proj.weight"]).T,
        **{ours: _np(sd[p + theirs]) for ours, theirs in _HYBRID_MIXER.items()},
    }
    assert w_in.shape[1] == 2 * inner + 2 * cfg.ssm_state + cfg.ssm_heads
    return layer


def _hybrid_from_state_dict(sd, cfg: ModelConfig, cast) -> dict[str, Any]:
    import jax

    period = len(cfg.layer_period)
    return {
        f"sub{j}": jax.tree.map(
            lambda *leaves: cast(np.stack(leaves)),
            *[_hybrid_layer_from_state_dict(sd, cfg, i)
              for i in range(j, cfg.num_layers, period)])
        for j in range(period)
    }


def _hybrid_state_dict(layers, cfg: ModelConfig, host) -> dict[str, np.ndarray]:
    period = len(cfg.layer_period)
    sd: dict[str, np.ndarray] = {}
    for i in range(cfg.num_layers):
        sub, n, p = layers[f"sub{i % period}"], i // period, f"model.layers.{i}."
        if "ret" in sub:
            sd.update(_retention_layer_state_dict(sub, n, p, host))
            continue
        sd[p + "input_layernorm.weight"] = host(sub["attn_norm"]["scale"][n])
        sd[p + "post_attention_layernorm.weight"] = host(sub["mlp_norm"]["scale"][n])
        sd[p + "shared_mlp.input_linear.weight"] = host(sub["mlp"]["w_gu"][n]).T
        sd[p + "shared_mlp.output_linear.weight"] = host(sub["mlp"]["w_down"][n]).T
        if "attn" in sub:
            for ours, theirs in _HYBRID_ATTN.items():
                sd[p + f"self_attn.{theirs}.weight"] = host(sub["attn"][ours][n]).T
            continue
        m = sub["ssm"]
        sd[p + "mamba.in_proj.weight"] = np.concatenate(
            [host(m["w_in"][n]), host(m["w_dt"][n])], axis=1).T
        sd[p + "mamba.conv1d.weight"] = host(m["conv_w"][n]).T[:, None, :]
        sd[p + "mamba.out_proj.weight"] = host(m["w_out"][n]).T
        for ours, theirs in _HYBRID_MIXER.items():
            sd[p + theirs] = host(m[ours][n])
    return sd


# Trinity (``model_type: afmoe``; models/swa.py), as the family's public
# modelling code names its tensors (recalled: this sandbox has no network, and
# no checkpoint is fetched). A layer has four norms, ``self_attn`` with a
# ``gate_proj`` beside q/k/v/o and a ``q_norm`` / ``k_norm`` a head, and
# ``mlp``: the dense SwiGLU in the leading layers, else ``router.gate``,
# ``expert_bias``, ``shared_experts`` and ``experts.{e}``. A share loads only
# the experts it holds (``experts_held_first`` on).
_AFMOE_NORMS = {"attn_norm": "input_layernorm", "attn_post_norm": "post_attention_layernorm",
                "mlp_norm": "pre_mlp_layernorm", "mlp_post_norm": "post_mlp_layernorm"}
_AFMOE_ATTN = {"wq": "q_proj", "wk": "k_proj", "wv": "v_proj", "wo": "o_proj", "wg": "gate_proj"}
_AFMOE_MLP = {"w_gate": "gate_proj", "w_up": "up_proj", "w_down": "down_proj"}


def _afmoe_layer_from_state_dict(sd, cfg: ModelConfig, i: int) -> dict[str, Any]:
    from ditl_tpu.models.moe import held_experts

    p = f"model.layers.{i}."
    layer: dict[str, Any] = {
        ours: {"scale": _np(sd[p + f"{theirs}.weight"])} for ours, theirs in _AFMOE_NORMS.items()}
    layer["attn"] = {ours: _np(sd[p + f"self_attn.{theirs}.weight"]).T
                     for ours, theirs in _AFMOE_ATTN.items()}
    layer["attn"].update(q_norm=_np(sd[p + "self_attn.q_norm.weight"]),
                         k_norm=_np(sd[p + "self_attn.k_norm.weight"]))
    if i < cfg.first_k_dense_replace:
        layer["mlp"] = {ours: _np(sd[p + f"mlp.{theirs}.weight"]).T
                        for ours, theirs in _AFMOE_MLP.items()}
        return layer
    first, count = held_experts(cfg)
    layer["moe"] = {
        "router": _np(sd[p + "mlp.router.gate.weight"]).T,
        "router_bias": _np(sd[p + "mlp.expert_bias"]).astype(np.float32),
        "shared": {ours: _np(sd[p + f"mlp.shared_experts.{theirs}.weight"]).T
                   for ours, theirs in _AFMOE_MLP.items()},
        **{ours: np.stack([_np(sd[p + f"mlp.experts.{e}.{theirs}.weight"]).T
                           for e in range(first, first + count)])
           for ours, theirs in _AFMOE_MLP.items()},
    }
    return layer


def _afmoe_from_state_dict(sd, cfg: ModelConfig, cast) -> dict[str, Any]:
    import jax

    def keep(path, x):  # the bias for the choice stays float32, like the scores
        return x if path[-1].key == "router_bias" else cast(x)

    n_dense = cfg.first_k_dense_replace
    out = {}
    for kind, layers in (("dense", range(n_dense)), ("sparse", range(n_dense, cfg.num_layers))):
        if len(layers):
            out[kind] = jax.tree_util.tree_map_with_path(
                lambda path, *leaves: keep(path, np.stack(leaves)),
                *[_afmoe_layer_from_state_dict(sd, cfg, i) for i in layers])
    return out


def _afmoe_state_dict(layers, cfg: ModelConfig, host) -> dict[str, np.ndarray]:
    from ditl_tpu.models.moe import held_experts

    first, count = held_experts(cfg)
    sd: dict[str, np.ndarray] = {}
    for i in range(cfg.num_layers):
        dense = i < cfg.first_k_dense_replace
        stack = layers["dense" if dense else "sparse"]
        n, p = (i if dense else i - cfg.first_k_dense_replace), f"model.layers.{i}."
        for ours, theirs in _AFMOE_NORMS.items():
            sd[p + f"{theirs}.weight"] = host(stack[ours]["scale"][n])
        for ours, theirs in _AFMOE_ATTN.items():
            sd[p + f"self_attn.{theirs}.weight"] = host(stack["attn"][ours][n]).T
        sd[p + "self_attn.q_norm.weight"] = host(stack["attn"]["q_norm"][n])
        sd[p + "self_attn.k_norm.weight"] = host(stack["attn"]["k_norm"][n])
        if dense:
            for ours, theirs in _AFMOE_MLP.items():
                sd[p + f"mlp.{theirs}.weight"] = host(stack["mlp"][ours][n]).T
            continue
        moe = stack["moe"]
        sd[p + "mlp.router.gate.weight"] = host(moe["router"][n]).T
        sd[p + "mlp.expert_bias"] = host(moe["router_bias"][n])
        for ours, theirs in _AFMOE_MLP.items():
            sd[p + f"mlp.shared_experts.{theirs}.weight"] = host(moe["shared"][ours][n]).T
            for e in range(count):
                sd[p + f"mlp.experts.{first + e}.{theirs}.weight"] = host(moe[ours][n, e]).T
    return sd


def params_from_state_dict(
    sd: Mapping[str, Any], cfg: ModelConfig, dtype: str | None = None
) -> dict[str, Any]:
    """HF Llama/Qwen2/Mixtral/OLMoE state dict -> this framework's param pytree (numpy).

    ``dtype`` defaults to ``cfg.param_dtype``. Keys follow HF's
    ``model.layers.{i}.*`` naming; both dense (Llama) and sparse (Mixtral)
    MLPs are handled according to ``cfg.num_experts``.
    """
    pd = np.dtype(dtype or cfg.param_dtype)
    L = cfg.num_layers

    def cast(x: np.ndarray) -> np.ndarray:
        return x.astype(pd)

    if cfg.window_layer:  # Trinity (afmoe): a leading dense stack and an expert stack
        return {
            "embed": {"embedding": cast(_np(sd["model.embed_tokens.weight"]))},
            "layers": _afmoe_from_state_dict(sd, cfg, cast),
            "final_norm": {"scale": cast(_np(sd["model.norm.weight"]))},
            "lm_head": {"kernel": cast(_np(sd["lm_head.weight"]).T)},
        }
    if cfg.layer_types:  # Granite-4.0-H: a subtree a position of the period
        tree = {
            "embed": {"embedding": cast(_np(sd["model.embed_tokens.weight"]))},
            "layers": _hybrid_from_state_dict(sd, cfg, cast),
            "final_norm": {"scale": cast(_np(sd["model.norm.weight"]))},
        }
        if not cfg.tie_embeddings:
            tree["lm_head"] = {"kernel": cast(_np(sd["lm_head.weight"]).T)}
        return tree
    if cfg.double_layer:  # LongCat-Flash; a share loads only the experts it holds
        return {
            "embed": {"embedding": cast(_np(sd["model.embed_tokens.weight"]))},
            "layers": _double_layer_from_state_dict(sd, cfg, cast),
            "final_norm": {"scale": cast(_np(sd["model.norm.weight"]))},
            "lm_head": {"kernel": cast(_np(sd["lm_head.weight"]).T)},
        }
    params: dict[str, Any] = {
        "embed": {"embedding": cast(_np(sd["model.embed_tokens.weight"]))},
        "layers": {
            "attn_norm": {
                "scale": cast(
                    _stack(sd, "model.layers.{i}.input_layernorm.weight", L, False)
                )
            },
            "attn": (
                {
                    "w_qkv": cast(np.concatenate([
                        _stack(sd, "model.layers.{i}.self_attn.q_proj.weight", L, True),
                        _stack(sd, "model.layers.{i}.self_attn.k_proj.weight", L, True),
                        _stack(sd, "model.layers.{i}.self_attn.v_proj.weight", L, True),
                    ], axis=-1)),
                    "wo": cast(_stack(sd, "model.layers.{i}.self_attn.o_proj.weight", L, True)),
                }
                if cfg.fused_qkv else
                {
                    "wq": cast(_stack(sd, "model.layers.{i}.self_attn.q_proj.weight", L, True)),
                    "wk": cast(_stack(sd, "model.layers.{i}.self_attn.k_proj.weight", L, True)),
                    "wv": cast(_stack(sd, "model.layers.{i}.self_attn.v_proj.weight", L, True)),
                    "wo": cast(_stack(sd, "model.layers.{i}.self_attn.o_proj.weight", L, True)),
                }
            ),
            "mlp_norm": {
                "scale": cast(
                    _stack(
                        sd, "model.layers.{i}.post_attention_layernorm.weight", L, False
                    )
                )
            },
        },
        "final_norm": {"scale": cast(_np(sd["model.norm.weight"]))},
    }
    if cfg.attention_bias:  # Qwen2-family q/k/v bias (1-D: no transpose)
        params["layers"]["attn"].update({
            "bq": cast(_stack(sd, "model.layers.{i}.self_attn.q_proj.bias", L, False)),
            "bk": cast(_stack(sd, "model.layers.{i}.self_attn.k_proj.bias", L, False)),
            "bv": cast(_stack(sd, "model.layers.{i}.self_attn.v_proj.bias", L, False)),
        })
    if cfg.qk_norm:  # OLMoE-family norms over the whole q and k vectors
        params["layers"]["attn"].update({
            "q_norm": cast(_stack(sd, "model.layers.{i}.self_attn.q_norm.weight", L, False)),
            "k_norm": cast(_stack(sd, "model.layers.{i}.self_attn.k_norm.weight", L, False)),
        })
    if cfg.num_experts > 0:  # sparse MLP (Mixtral's or OLMoE's names)
        e = cfg.num_experts
        block, gate_name, up_name, down_name = _moe_names(cfg)
        router = _stack(sd, "model.layers.{i}." + block + ".gate.weight", L, True)

        def experts(w_name: str, transpose: bool) -> np.ndarray:
            return np.stack(
                [
                    np.stack(
                        [
                            (lambda w: w.T if transpose else w)(
                                _np(
                                    sd[
                                        f"model.layers.{i}.{block}."
                                        f"experts.{j}.{w_name}.weight"
                                    ]
                                )
                            )
                            for j in range(e)
                        ],
                        axis=0,
                    )
                    for i in range(L)
                ],
                axis=0,
            )  # (L, E, ..., ...)

        params["layers"]["moe"] = {
            "router": cast(router),
            "w_gate": cast(experts(gate_name, True)),
            "w_up": cast(experts(up_name, True)),
            "w_down": cast(experts(down_name, True)),
        }
    elif cfg.fused_gate_up:
        params["layers"]["mlp"] = {
            "w_gu": cast(np.concatenate([
                _stack(sd, "model.layers.{i}.mlp.gate_proj.weight", L, True),
                _stack(sd, "model.layers.{i}.mlp.up_proj.weight", L, True),
            ], axis=-1)),
            "w_down": cast(_stack(sd, "model.layers.{i}.mlp.down_proj.weight", L, True)),
        }
    else:
        params["layers"]["mlp"] = {
            "w_gate": cast(_stack(sd, "model.layers.{i}.mlp.gate_proj.weight", L, True)),
            "w_up": cast(_stack(sd, "model.layers.{i}.mlp.up_proj.weight", L, True)),
            "w_down": cast(_stack(sd, "model.layers.{i}.mlp.down_proj.weight", L, True)),
        }
    if not cfg.tie_embeddings:
        params["lm_head"] = {"kernel": cast(_np(sd["lm_head.weight"]).T)}
    return params


def state_dict_from_params(params: Mapping[str, Any], cfg: ModelConfig) -> dict[str, np.ndarray]:
    """Inverse of ``params_from_state_dict``: param pytree -> HF state dict
    (numpy, f32) — so a TPU fine-tune can be served by any HF-stack consumer.
    LoRA adapters, if present, must be merged into the base weights first
    (models/lora.py ``merge_lora``); they have no HF-side representation here."""

    def host(x) -> np.ndarray:
        return np.asarray(x, np.float32)

    L = cfg.num_layers
    layers = params["layers"]
    if "lora" in layers:
        raise ValueError(
            "param tree still carries LoRA adapters — exporting would silently "
            "drop the fine-tune (base weights are frozen under LoRA). Call "
            "models.lora.merge_lora(params, cfg) first."
        )
    sd: dict[str, np.ndarray] = {
        "model.embed_tokens.weight": host(params["embed"]["embedding"]),
        "model.norm.weight": host(params["final_norm"]["scale"]),
    }
    if cfg.window_layer:
        sd.update(_afmoe_state_dict(layers, cfg, host))
        sd["lm_head.weight"] = host(params["lm_head"]["kernel"]).T
        return sd
    if cfg.layer_types:
        sd.update(_hybrid_state_dict(layers, cfg, host))
        if not cfg.tie_embeddings:
            sd["lm_head.weight"] = host(params["lm_head"]["kernel"]).T
        return sd
    if cfg.double_layer:
        sd.update(_double_layer_state_dict(layers, cfg, host))
        sd["lm_head.weight"] = host(params["lm_head"]["kernel"]).T
        return sd
    for i in range(L):
        p = f"model.layers.{i}"
        sd[f"{p}.input_layernorm.weight"] = host(layers["attn_norm"]["scale"][i])
        sd[f"{p}.post_attention_layernorm.weight"] = host(layers["mlp_norm"]["scale"][i])
        if "w_qkv" in layers["attn"]:
            nq = cfg.num_heads * cfg.head_dim
            nk = cfg.num_kv_heads * cfg.head_dim
            w = layers["attn"]["w_qkv"][i]
            sd[f"{p}.self_attn.q_proj.weight"] = host(w[:, :nq]).T
            sd[f"{p}.self_attn.k_proj.weight"] = host(w[:, nq:nq + nk]).T
            sd[f"{p}.self_attn.v_proj.weight"] = host(w[:, nq + nk:]).T
            sd[f"{p}.self_attn.o_proj.weight"] = host(layers["attn"]["wo"][i]).T
        else:
            for ours, theirs in (("wq", "q_proj"), ("wk", "k_proj"), ("wv", "v_proj"), ("wo", "o_proj")):
                sd[f"{p}.self_attn.{theirs}.weight"] = host(layers["attn"][ours][i]).T
        if cfg.attention_bias:
            for ours, theirs in (("bq", "q_proj"), ("bk", "k_proj"), ("bv", "v_proj")):
                sd[f"{p}.self_attn.{theirs}.bias"] = host(layers["attn"][ours][i])
        if cfg.qk_norm:
            for name in ("q_norm", "k_norm"):
                sd[f"{p}.self_attn.{name}.weight"] = host(layers["attn"][name][i])
        if cfg.num_experts > 0:
            moe = layers["moe"]
            block, gate_name, up_name, down_name = _moe_names(cfg)
            sd[f"{p}.{block}.gate.weight"] = host(moe["router"][i]).T
            for j in range(cfg.num_experts):
                q = f"{p}.{block}.experts.{j}"
                sd[f"{q}.{gate_name}.weight"] = host(moe["w_gate"][i, j]).T
                sd[f"{q}.{up_name}.weight"] = host(moe["w_up"][i, j]).T
                sd[f"{q}.{down_name}.weight"] = host(moe["w_down"][i, j]).T
        elif "w_gu" in layers["mlp"]:
            mlp = layers["mlp"]
            f = cfg.intermediate_size
            sd[f"{p}.mlp.gate_proj.weight"] = host(mlp["w_gu"][i, :, :f]).T
            sd[f"{p}.mlp.up_proj.weight"] = host(mlp["w_gu"][i, :, f:]).T
            sd[f"{p}.mlp.down_proj.weight"] = host(mlp["w_down"][i]).T
        else:
            mlp = layers["mlp"]
            sd[f"{p}.mlp.gate_proj.weight"] = host(mlp["w_gate"][i]).T
            sd[f"{p}.mlp.up_proj.weight"] = host(mlp["w_up"][i]).T
            sd[f"{p}.mlp.down_proj.weight"] = host(mlp["w_down"][i]).T
    if not cfg.tie_embeddings:
        sd["lm_head.weight"] = host(params["lm_head"]["kernel"]).T
    return sd


def export_hf_model(params: Mapping[str, Any], cfg: ModelConfig, path: str) -> None:
    """Write a ``transformers``-loadable checkpoint directory from a param
    pytree (the serve-anywhere exit path the reference's API-only design never
    needed — its model lived behind someone else's server)."""
    import torch
    from transformers import LlamaConfig, LlamaForCausalLM, MixtralConfig, MixtralForCausalLM

    common = dict(
        vocab_size=cfg.vocab_size,
        hidden_size=cfg.hidden_size,
        intermediate_size=cfg.intermediate_size,
        num_hidden_layers=cfg.num_layers,
        num_attention_heads=cfg.num_heads,
        num_key_value_heads=cfg.num_kv_heads,
        head_dim=cfg.head_dim,
        max_position_embeddings=cfg.max_seq_len,
        rms_norm_eps=cfg.rms_norm_eps,
        rope_theta=cfg.rope_theta,
        tie_word_embeddings=cfg.tie_embeddings,
    )
    if cfg.rope_scaling_factor > 0:
        # Round-trip the Llama-3.1 NTK scaling — omitting it would make the
        # exported model compute different (unscaled) RoPE than this one.
        common["rope_scaling"] = {
            "rope_type": "llama3",
            "factor": cfg.rope_scaling_factor,
            "low_freq_factor": cfg.rope_scaling_low_freq_factor,
            "high_freq_factor": cfg.rope_scaling_high_freq_factor,
            "original_max_position_embeddings": cfg.rope_scaling_original_max_len,
        }
    if cfg.num_experts > 0 and cfg.qk_norm:
        from transformers import OlmoeConfig, OlmoeForCausalLM

        common.pop("head_dim", None)  # OlmoeConfig derives it
        hf_cfg = OlmoeConfig(
            num_experts=cfg.num_experts,
            num_experts_per_tok=cfg.num_experts_per_tok,
            norm_topk_prob=cfg.norm_topk_prob,
            router_aux_loss_coef=cfg.router_aux_coef,
            **common,
        )
        model = OlmoeForCausalLM(hf_cfg)
    elif cfg.num_experts > 0:
        hf_cfg = MixtralConfig(
            num_local_experts=cfg.num_experts,
            num_experts_per_tok=cfg.num_experts_per_tok,
            **common,
        )
        model = MixtralForCausalLM(hf_cfg)
    elif cfg.attention_bias:
        # Qwen2-family (q/k/v bias): export as a native Qwen2 checkpoint.
        from transformers import Qwen2Config, Qwen2ForCausalLM

        common.pop("head_dim", None)  # Qwen2Config derives it
        if cfg.head_dim * cfg.num_heads != cfg.hidden_size:
            raise ValueError(
                "Qwen2 export needs head_dim * num_heads == hidden_size "
                f"({cfg.head_dim} * {cfg.num_heads} != {cfg.hidden_size})"
            )
        hf_cfg = Qwen2Config(**common)
        model = Qwen2ForCausalLM(hf_cfg)
    else:
        hf_cfg = LlamaConfig(attention_bias=False, mlp_bias=False, **common)
        model = LlamaForCausalLM(hf_cfg)
    sd = {k: torch.from_numpy(v) for k, v in state_dict_from_params(params, cfg).items()}
    missing, unexpected = model.load_state_dict(sd, strict=False)
    # Tied-embedding models have no lm_head entry; anything else missing is a bug.
    real_missing = [m for m in missing if not (cfg.tie_embeddings and "lm_head" in m)]
    if real_missing or unexpected:
        raise ValueError(
            f"state dict mismatch exporting to HF: missing={real_missing} "
            f"unexpected={unexpected}"
        )
    model.save_pretrained(path)


def main(argv: list[str] | None = None) -> int:
    """CLI: convert an Orbax training checkpoint to a HF checkpoint dir.

        python -m ditl_tpu.models.convert \\
            --checkpoint-dir /mnt/ckpt --preset llama3-8b --out /mnt/hf_export

    LoRA runs are merged automatically (models/lora.py) before export.
    """
    import argparse

    import jax

    from ditl_tpu.models import llama
    from ditl_tpu.models.presets import get_preset
    from ditl_tpu.train.checkpoint import CheckpointManager
    from ditl_tpu.utils.logging import get_logger, setup_logging

    setup_logging()
    logger = get_logger(__name__)
    parser = argparse.ArgumentParser(prog="ditl_tpu.models.convert")
    parser.add_argument("--checkpoint-dir", required=True)
    parser.add_argument("--preset", required=True)
    parser.add_argument("--out", required=True)
    parser.add_argument("--lora-rank", type=int, default=0,
                        help="set if the checkpoint was a LoRA fine-tune")
    parser.add_argument("--lora-alpha", type=float, default=16.0,
                        help="must match the training run's model.lora_alpha "
                        "(the merge scale is alpha/rank)")
    args = parser.parse_args(argv)

    cfg = get_preset(args.preset, lora_rank=args.lora_rank,
                     lora_alpha=args.lora_alpha)
    abstract = jax.eval_shape(lambda: llama.init_params(jax.random.key(0), cfg))
    mgr = CheckpointManager(args.checkpoint_dir)
    params = mgr.restore_latest_params(abstract)
    mgr.close()
    if params is None:
        raise SystemExit(f"no checkpoint found in {args.checkpoint_dir}")
    if cfg.lora_rank > 0:
        from ditl_tpu.models.lora import merge_lora

        logger.info("merging LoRA adapters (rank %d) into base weights", cfg.lora_rank)
        params = merge_lora(params, cfg)
        import dataclasses

        cfg = dataclasses.replace(cfg, lora_rank=0)
    export_hf_model(params, cfg, args.out)
    logger.info("exported HF checkpoint to %s", args.out)
    return 0


def load_hf_model(model_or_path: Any, **config_overrides):
    """Convenience: a ``transformers`` model instance *or* a local checkpoint
    path -> ``(params, ModelConfig)``. Network access is never attempted for
    instances; for paths, ``local_files_only=True`` keeps it hermetic."""
    if isinstance(model_or_path, str):
        from transformers import AutoModelForCausalLM

        # torch_dtype="auto" keeps the checkpoint's storage dtype (bf16 for
        # modern Llama releases) — loading a 70B as f32 would double host RAM
        # before conversion even starts. _np upcasts per-tensor only.
        model = AutoModelForCausalLM.from_pretrained(
            model_or_path, local_files_only=True, torch_dtype="auto"
        )
    else:
        model = model_or_path
    cfg = config_from_hf(model.config, **config_overrides)
    params = params_from_state_dict(model.state_dict(), cfg)
    return params, cfg


if __name__ == "__main__":
    import sys

    sys.exit(main())
