"""Weight-only int8 quantization for inference.

Decoding at small batch is weight-bandwidth-bound: every generated token
re-reads every weight from HBM while the MXU idles. Symmetric per-channel
int8 halves those bytes versus bf16. The matmul consumes the int8 tensor
directly (converted on the fly in-register); the per-output-channel scale is
applied to the matmul *output* — valid because a column scale commutes
through the contraction: ``h @ (q · s_col) == (h @ q) · s_col``. So HBM sees
int8, the MXU sees its native bf16, and accuracy loss is per-channel-bounded.

Quantized leaves are ``{"q": int8 (..., d_in, d_out), "scale": f32
(..., 1, d_out)}`` dicts; ``models/llama.py``'s projection helper detects
them, so the same forward serves float and quantized params (training always
uses float — this is an inference-side transform, applied after
fine-tuning/merging).
"""

from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp

__all__ = ["quantize_weights", "is_quantized_leaf", "weight_einsum"]

# Param-tree leaves that are (…, d_in, d_out) matmul weights.
_QUANT_KEYS = ("wq", "wk", "wv", "w_qkv", "wo", "w_gate", "w_up", "w_gu", "w_down", "kernel",
               # latent attention's projections (models/mla.py); ``w_kvb`` stays
               # float: its halves are folded into the query and the output
               "w_qa", "w_qb", "w_kva",
               # a state-space mixer's projections (models/ssm.py)
               "w_in", "w_dt", "w_out",
               # the attention output gate's projection (models/swa.py)
               "wg")


def is_quantized_leaf(w: Any) -> bool:
    return isinstance(w, dict) and set(w) == {"q", "scale"}


@jax.jit
def _quantize_matrix(w: jax.Array) -> dict[str, jax.Array]:
    """Symmetric per-output-channel int8 over the input (contraction) dim.
    One compiled program a shape: written op by op it holds three float32
    copies of the leaf at once, which a stack of experts does not leave room
    for beside the weights (PERF.md section 6, PR 44)."""
    wf = jnp.asarray(w, jnp.float32)
    amax = jnp.max(jnp.abs(wf), axis=-2, keepdims=True)  # (..., 1, d_out)
    scale = jnp.where(amax > 0, amax / 127.0, 1.0)
    q = jnp.clip(jnp.round(wf / scale), -127, 127).astype(jnp.int8)
    return {"q": q, "scale": scale.astype(jnp.float32)}


def weight_einsum(
    pattern: str,
    x: jax.Array,
    w: Any,
    *,
    compute_dtype,
    preferred=None,
) -> jax.Array:
    """``einsum(pattern, x, w)`` where ``w`` is a float matrix OR a quantized
    ``{"q", "scale"}`` leaf. The int8 tensor feeds the matmul directly (HBM
    reads stay int8); the per-output-channel scale multiplies the output.
    Works for any pattern whose last output dim is the weight's ``d_out``
    (scale shape (..., 1, d_out) broadcasts from the right)."""
    if is_quantized_leaf(w):
        out = jnp.einsum(
            pattern,
            x,
            w["q"].astype(compute_dtype),
            preferred_element_type=preferred or compute_dtype,
        )
        return out * w["scale"].astype(out.dtype)
    return jnp.einsum(
        pattern, x, w.astype(compute_dtype),
        preferred_element_type=preferred or compute_dtype,
    )


def quantize_weights(params: Any) -> Any:
    """Quantize the projection/MLP/lm-head weights of a (dense) param tree.

    Norm scales and the embedding table stay float (the embedding is a
    gather, not a matmul; norms are tiny and precision-sensitive). LoRA
    trees must be merged first (models/lora.py) — adapters train in float.
    """
    if "lora" in params.get("layers", {}):
        raise ValueError(
            "merge LoRA adapters before quantizing (models.lora.merge_lora)"
        )

    def walk(tree: Any) -> Any:
        if isinstance(tree, dict):
            return {
                k: _quantize_matrix(v)
                if k in _QUANT_KEYS and not isinstance(v, dict)
                else walk(v)
                for k, v in tree.items()
            }
        return tree

    return walk(params)
