"""Operations a Qwen2-style decoder needs per token, from its sizes: the
benchmark's copy of ``bench._model_flops_per_token`` (PERF.md section 7 lists
the original for deletion), with one change: attention is counted at the
context the tokens really attend to, not at half the sequence. Packed
documents attend only within themselves, so the traffic file states the mean
causal context (``attention_context_mean``, counted once from the loader's
own positions); half the sequence would count operations nobody needs.

A family whose count is not this one (sparse experts, latent attention)
exports ``forward_flops_per_token(config, context_mean)`` from its reference
module, ``reference/<config["reference"]>.py``, and that one is used.
"""

from __future__ import annotations

import os

from harness import HERE, load_module


def family_count(config: dict):
    """The reference module's own ``forward_flops_per_token``, or None."""
    path = os.path.join(HERE, "reference", f"{config['reference']}.py")
    return getattr(load_module(path), "forward_flops_per_token", None)


def forward_flops_per_token(config: dict, context_mean: float) -> float:
    """Matmul FLOPs (2 per multiply-add) of one forward pass, per token.
    ``config``: the published config.json keys of the configuration file.
    ``context_mean``: mean number of keys a query attends to."""
    own = family_count(config)
    if own is not None:
        return own(config, context_mean)
    d = config["hidden_size"]
    nh, nkv = config["num_attention_heads"], config["num_key_value_heads"]
    hd = d // nh
    f = config["intermediate_size"]
    qkvo = 2 * d * (nh * hd) * 2 + 2 * d * (nkv * hd) * 2  # wq + wo, wk + wv
    attn = 4 * context_mean * (nh * hd)  # q.k^T and p.v
    mlp = 3 * 2 * d * f
    head = 2 * d * config["vocab_size"]
    return config["num_hidden_layers"] * (qkvo + attn + mlp) + head


def train_flops_per_token(config: dict, context_mean: float) -> float:
    """Forward plus backward (twice the forward). Recomputed operations are
    not counted: utilization is of the operations the step requires."""
    return 3.0 * forward_flops_per_token(config, context_mean)
