"""Sparse Mixture-of-Experts block: top-k of E SwiGLU experts per token
(Mixtral, OLMoE), token-exact.

Every one of the ``T * k`` (token, choice) pairs is computed: there is no
capacity and no drop, so a token's output does not depend on what else is in
the batch (a served answer does not change with its neighbours in the tick,
and a plain reference can agree with it).

1. ``moe_router``: router logits -> softmax over all E experts (float32;
   routing is precision-sensitive).
2. ``moe_dispatch``: the k largest gates and their experts; the weights are
   those gates, rescaled to sum to 1 only where ``cfg.norm_topk_prob``
   (Mixtral yes, OLMoE no). The pairs are sorted by expert (a stable argsort
   of ``T * k`` small integers) and each pair's token row is gathered, so
   every expert's rows are contiguous.
3. ``moe_experts``: three grouped matmuls over the ragged groups. On one
   TPU chip they are the Mosaic kernel ``jax.experimental.pallas.ops.tpu.
   megablox.gmm``, which visits only the experts
   that have rows; everywhere else (the CPU tests; a mesh, where XLA has to
   partition the matmul itself; a row count the kernel's 128-row tile does
   not divide) ``jax.lax.ragged_dot``, the same product spelled by XLA. Why
   the kernel and these tiles: ``GMM_TILING``. In a serving forward pass the
   kernel is handed every layer's experts at once and finds its layer's
   inside them (``experts_in_place``), so that the layer loop does not copy a
   layer's experts in front of it. Weight-only int8 experts feed either as
   they are, the column scales applied to each row's output by its expert.
4. ``moe_combine``: the rows go back to pair order (the inverse permutation,
   a gather: no scatter-add, so the sum over a token's k experts is in one
   fixed order), times the gate weights, summed over k in float32.

Rows that are not live (a decode tick's dead slots, a prefill bucket's
padding: ``token_mask`` false) ARE dispatched like any other row: their
output is discarded by the caller as it always was, token-exactness keeps
them from touching a live row, and leaving them in keeps one static shape
and no ragged tail whose contents the kernel would leave undefined. They are
kept out of what is COUNTED: the load-balancing term and the per-expert
assignment counts see live rows only.
"""

from __future__ import annotations

import math
from typing import Any

import jax
import jax.numpy as jnp

from ditl_tpu.config import ModelConfig
from ditl_tpu.ops.backend import interpret_default

__all__ = ["init_moe_params", "moe_logical_axes", "moe_block", "load_balancing_loss"]


def init_moe_params(rng: jax.Array, cfg: ModelConfig) -> dict[str, Any]:
    pd = jnp.dtype(cfg.param_dtype)
    d, f, L, E = cfg.hidden_size, cfg.intermediate_size, cfg.num_layers, cfg.num_experts
    k1, k2, k3, k4 = jax.random.split(rng, 4)

    def dense(key, shape, fan_in):
        return (jax.random.normal(key, shape) * (1.0 / math.sqrt(fan_in))).astype(pd)

    return {
        "router": dense(k1, (L, d, E), d),
        "w_gate": dense(k2, (L, E, d, f), d),
        "w_up": dense(k3, (L, E, d, f), d),
        "w_down": dense(k4, (L, E, f, d), f),
    }


def moe_logical_axes(cfg: ModelConfig) -> dict[str, Any]:
    return {
        "router": ("layers", "embed", None),
        "w_gate": ("layers", "expert", "embed", "mlp"),
        "w_up": ("layers", "expert", "embed", "mlp"),
        "w_down": ("layers", "expert", "mlp", "embed"),
    }


def load_balancing_loss(gates: jax.Array, counts: jax.Array,
                        token_mask: jax.Array) -> jax.Array:
    """Switch-Transformer load-balancing term of one layer, ``E * sum_e f_e *
    P_e``: ``f_e`` the share of the live tokens' ``T * k`` assignments that
    went to expert ``e`` (``counts``, (E,)), ``P_e`` the mean router
    probability of ``e`` over the live tokens (``gates`` (T, E), ``token_mask``
    (T,) float). 1 when both are uniform."""
    e = gates.shape[-1]
    f = counts / jnp.maximum(counts.sum(), 1.0)
    p = (gates * token_mask[:, None]).sum(axis=0) / jnp.maximum(token_mask.sum(), 1.0)
    return e * jnp.sum(f * p)


# Tiles (rows, contraction, output columns) of the grouped-matmul kernel, each
# clipped to the matrix. Measured on a v5e at OLMoE's widths (64 experts of
# 2048 x 1024, bf16; PERF.md section 6, PR 26), the three matmuls of one
# layer, this kernel against ``jax.lax.ragged_dot`` (also a Mosaic kernel on
# the TPU, with tiles of XLA's choosing): 0.80 against 1.67 ms on a decode
# step's 512 rows over 40 experts (77% against 37% of what HBM needs to
# deliver those experts), 1.45 against 2.78 ms on a 256-token prefill, 3.38
# against 4.05 ms on a 2,048-token one. 512-row tiles were slower at every
# size (an expert has 8 to 256 rows here). A whole-matrix contraction tile
# (2,048) is 0-15% faster forward, but the kernel's transposed product in the
# backward pass then asks for 16.4 MB of the 16 MB of scoped VMEM and does not
# compile (found by compiling for a described v5e, no chip); these compile
# both ways, so training and serving share them.
GMM_TILING = (128, 1024, 1024)


def _use_gmm(rows: int, mesh) -> bool:
    """One TPU chip and whole row tiles: the kernel. A mesh needs a matmul
    XLA can partition, and off the TPU there is no Mosaic."""
    return (not interpret_default() and rows % GMM_TILING[0] == 0
            and (mesh is None or mesh.size == 1))


def experts_in_place(moe: dict[str, Any], rows: int, mesh) -> bool:
    """Whether a cached (serving) forward pass may hand ``moe_block`` the
    STACKED expert weights and a layer index instead of letting the layer
    loop slice the layer's experts out first. The kernel takes its weights
    as a custom call's operand, so a slice cannot fuse into it: the loop
    would copy all of a layer's experts (805 MB at OLMoE's widths, 2.45 ms)
    in front of matmuls that read them once (1.07 ms; seen on the chip,
    PERF.md section 6, PR 26). The kernel visits only groups that have rows,
    so it can address the layer's experts inside the whole stack instead.
    Only where the kernel runs, and on plain float weights."""
    from ditl_tpu.ops.quant import is_quantized_leaf

    return _use_gmm(rows, mesh) and not any(
        is_quantized_leaf(moe[k]) for k in ("w_gate", "w_up", "w_down"))


def _grouped(x: jax.Array, w: Any, sizes: jax.Array, row_expert: jax.Array, cd, mesh,
             layer=None):
    """``x[rows of group e] @ w[e]`` for every expert: ``x`` (M, d_in) sorted
    by expert, ``w`` (E, d_in, d_out) float or a weight-only int8 leaf. With
    ``layer`` (``experts_in_place``) ``w`` is the whole stack (L, E, d_in,
    d_out) and the layer's experts are groups ``layer * E ...`` of ``L * E``,
    every other group empty."""
    from ditl_tpu.ops.quant import is_quantized_leaf

    scale = None
    if is_quantized_leaf(w):
        w, scale = w["q"], w["scale"][row_expert, 0].astype(cd)
    w = w.astype(cd)
    if layer is not None:
        n_layers, e = w.shape[:2]
        w = w.reshape(n_layers * e, *w.shape[2:])
        sizes = jax.lax.dynamic_update_slice(
            jnp.zeros((n_layers * e,), sizes.dtype), sizes, (layer * e,))
    if layer is not None or _use_gmm(x.shape[0], mesh):
        from jax.experimental.pallas.ops.tpu.megablox import gmm

        tm, tk, tn = GMM_TILING
        out = gmm(x, w, sizes, cd, (tm, min(tk, w.shape[1]), min(tn, w.shape[2])),
                  interpret=interpret_default())
    else:
        out = jax.lax.ragged_dot(x, w, sizes, preferred_element_type=cd)
    return out if scale is None else out * scale


def moe_block(
    moe: dict[str, Any],
    h: jax.Array,
    cfg: ModelConfig,
    *,
    token_mask: jax.Array | None = None,
    mesh=None,
    layer=None,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """(B, S, D) -> ((B, S, D), aux, counts) through top-k routed experts.

    ``aux`` is this layer's load-balancing term (``load_balancing_loss``),
    weighted into the total loss by ``ModelConfig.router_aux_coef``
    (train/step.py); ``counts`` (E,) int32 the assignments each expert got.
    Both see only the tokens ``token_mask`` (B, S) marks live (all, when
    None). ``mesh``: the mesh the step is partitioned over, if any (it
    decides which grouped matmul runs, see ``_use_gmm``). ``layer``: the
    index of this layer where ``moe``'s expert weights are the whole stack
    (``experts_in_place``; the router is this layer's own)."""
    b, s, d = h.shape
    e, k = cfg.num_experts, cfg.num_experts_per_tok
    cd = h.dtype
    t = b * s
    x = h.reshape(t, d)
    live = (jnp.ones((t,), jnp.float32) if token_mask is None
            else token_mask.reshape(t).astype(jnp.float32))

    with jax.named_scope("moe_router"):
        gates = jax.nn.softmax(
            jnp.einsum("td,de->te", x.astype(jnp.float32),
                       moe["router"].astype(jnp.float32)),
            axis=-1,
        )  # (T, E) f32

    with jax.named_scope("moe_dispatch"):
        top_w, top_idx = jax.lax.top_k(gates, k)  # (T, k)
        if cfg.norm_topk_prob:
            top_w = top_w / jnp.maximum(top_w.sum(axis=-1, keepdims=True), 1e-9)
        pair_expert = top_idx.reshape(t * k)  # token-major
        chosen = pair_expert[:, None] == jnp.arange(e, dtype=pair_expert.dtype)
        sizes = chosen.sum(axis=0, dtype=jnp.int32)  # (E,) rows of each group
        counts = (chosen * jnp.repeat(live, k)[:, None]).sum(axis=0)  # live only
        order = jnp.argsort(pair_expert, stable=True)  # (TK,) pairs by expert
        row_expert = pair_expert[order]
        xs = x[order // k]  # (TK, D): each pair's token row

    with jax.named_scope("moe_experts"):
        gate = _grouped(xs, moe["w_gate"], sizes, row_expert, cd, mesh, layer)
        up = _grouped(xs, moe["w_up"], sizes, row_expert, cd, mesh, layer)
        ys = _grouped(jax.nn.silu(gate) * up, moe["w_down"], sizes, row_expert, cd, mesh,
                      layer)

    with jax.named_scope("moe_combine"):
        back = jnp.argsort(order)  # the inverse permutation
        pairs = ys[back].reshape(t, k, d).astype(jnp.float32)
        out = (pairs * top_w[..., None]).sum(axis=1).astype(cd)

    aux = load_balancing_loss(gates, counts, live)
    return out.reshape(b, s, d), aux, counts.astype(jnp.int32)
