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

**A share of a wider expert layer** (LongCat-Flash; ``shares_experts``). The
router there has ``num_experts + zero_expert_num`` outputs and this chip
holds ``experts_held_count`` of the routed experts, one rank's share of an
expert-parallel deployment. A (token, choice) pair is then one of three
kinds: its expert is HELD (computed here, by the same grouped matmuls),
ZERO-COMPUTE (the identity: the token's own row times the weight, no gather,
no matmul; ``moe_zero``) or ABSENT (another chip's: its part of the sum is
left out here, in program and reference alike, and nothing stands in for the
chips that are not there). Only held pairs of LIVE rows are gathered: they
are sorted to the front and the grouped matmuls run over a buffer of
``held_rows`` rows, an eighth of ``T * k`` (2% of the pairs are held on
random weights at 16 of 512), once for every such buffer the held pairs
fill: no pair is dropped however the router skews, and a step whose rows
chose no held expert runs no matmul at all. The rows come back by a
scatter-add over tokens in float32. ``counts`` then has ``n_held + 2``
entries: each held expert's assignments, then the zero-compute experts'
and the absent ones' totals (``split_counts``).

DeepSeek-V3.2's expert layer is such a share too, with three things of its
own (``ModelConfig.scoring_func``, ``n_group``, ``n_shared_experts``): the
scores are SIGMOIDS of the router's logits, each expert on its own; the
choice (on scores plus bias) is group-limited: a group's score is the sum of
its two largest, the ``topk_group`` best groups stay and the k largest inside
them are chosen; the chosen scores (without the bias), renormalised where
``norm_topk_prob``, times ``routed_scaling_factor`` weigh the outputs; and a
SHARED expert, a dense SwiGLU FFN every token passes through, joins the sum
(scope ``moe_shared``; every chip of the deployment holds it whole).
"""

from __future__ import annotations

import functools
import math
from typing import Any

import jax
import jax.numpy as jnp

from ditl_tpu.config import ModelConfig
from ditl_tpu.ops.backend import interpret_default

__all__ = ["init_moe_params", "moe_logical_axes", "moe_block", "load_balancing_loss",
           "shares_experts", "held_experts", "count_width", "split_counts",
           "grouped_rows", "lean_dense"]


def shares_experts(cfg: ModelConfig) -> bool:
    """Whether the expert layer is LongCat-Flash's kind (module docstring):
    zero-compute experts or a held share; its selection bias and scaled
    weights exist only with one of them (``ModelConfig`` refuses them
    otherwise). False for Mixtral and OLMoE, whose path is the one above."""
    return bool(cfg.zero_expert_num or cfg.experts_held_count)


def held_experts(cfg: ModelConfig) -> tuple[int, int]:
    """(first index, count) of the routed experts whose weights live here."""
    if cfg.experts_held_count:
        return cfg.experts_held_first, cfg.experts_held_count
    return 0, cfg.num_experts


def count_width(cfg: ModelConfig) -> int:
    """Entries of ``moe_block``'s ``counts``: one an expert, or, for a share,
    one a held expert and the zero-compute and absent totals."""
    return held_experts(cfg)[1] + 2 if shares_experts(cfg) else cfg.num_experts


def split_counts(counts, cfg: ModelConfig):
    """``counts`` (..., count_width) -> (per held expert (..., n_held),
    zero-compute total (...), absent total (...)); a layer that holds every
    expert and has no zero-compute ones reads (counts, 0, 0)."""
    if not shares_experts(cfg):
        return counts, counts[..., 0] * 0, counts[..., 0] * 0
    return counts[..., :-2], counts[..., -2], counts[..., -1]


def grouped_rows(cfg: ModelConfig, tokens: int) -> int:
    """Rows of the buffer the grouped matmuls see for ``tokens`` tokens: all
    ``T * k`` pairs, or a share's ``held_rows``."""
    pairs = tokens * cfg.num_experts_per_tok
    return held_rows(pairs) if shares_experts(cfg) else pairs


def held_rows(pairs: int) -> int:
    """A share's buffer: an eighth of the pairs (four times what 16 of 512
    experts draw on random weights), in whole row tiles of the kernel."""
    tile = GMM_TILING[0]
    return max(tile, -(-(pairs // 8) // tile) * tile)


def lean_dense(key, shape, fan_in: int, pd) -> jax.Array:
    """A seeded normal leaf of standard deviation ``1 / sqrt(fan_in)`` drawn
    and cast inside ONE program, so that no float32 copy of it is ever
    resident: a 9.6 GiB bfloat16 tree is built under a 16 GB chip (the eager
    draw-then-cast of ``llama.init_params`` holds a leaf three times over in
    float32, which the OLMoE cell's peak of 16.65 GB shows). Not the same
    numbers as that eager draw's, so the presets that had it keep it."""
    std = 1.0 / math.sqrt(fan_in)
    return jax.jit(
        lambda k: (jax.random.normal(k, shape, jnp.float32) * std).astype(pd))(key)


def init_moe_params(rng: jax.Array, cfg: ModelConfig,
                    n_layers: int | None = None) -> dict[str, Any]:
    """``n_layers``: the depth of the stack where not every layer has experts
    (models/dsa.py); ``cfg.num_layers`` otherwise."""
    pd = jnp.dtype(cfg.param_dtype)
    d, f, E = cfg.hidden_size, cfg.intermediate_size, cfg.num_experts
    L = cfg.num_layers if n_layers is None else n_layers
    k1, k2, k3, k4 = jax.random.split(rng, 4)
    if shares_experts(cfg):
        f = cfg.expert_ffn_hidden_size or f
        n_held = held_experts(cfg)[1]
        out = {
            "router": lean_dense(k1, (L, d, E + cfg.zero_expert_num), d, pd),
            "w_gate": lean_dense(k2, (L, n_held, d, f), d, pd),
            "w_up": lean_dense(k3, (L, n_held, d, f), d, pd),
            "w_down": lean_dense(k4, (L, n_held, f, d), f, pd),
        }
        if cfg.router_bias:  # float32 like the probabilities it joins
            out["router_bias"] = jnp.zeros((L, E + cfg.zero_expert_num), jnp.float32)
        if cfg.n_shared_experts:
            fs = cfg.n_shared_experts * f
            k5, k6, k7 = jax.random.split(k4, 3)
            out["shared"] = {
                "w_gate": lean_dense(k5, (L, d, fs), d, pd),
                "w_up": lean_dense(k6, (L, d, fs), d, pd),
                "w_down": lean_dense(k7, (L, fs, d), fs, pd),
            }
        return out

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
        **({"router_bias": ("layers", None)} if cfg.router_bias else {}),
        **({"shared": {"w_gate": ("layers", "embed", "mlp"),
                       "w_up": ("layers", "embed", "mlp"),
                       "w_down": ("layers", "mlp", "embed")}}
           if cfg.n_shared_experts else {}),
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
    static_buffers: bool = False,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """(B, S, D) -> ((B, S, D), aux, counts) through top-k routed experts.

    ``aux`` is this layer's load-balancing term (``load_balancing_loss``),
    weighted into the total loss by ``ModelConfig.router_aux_coef``
    (train/step.py); ``counts`` (E,) int32 the assignments each expert got.
    Both see only the tokens ``token_mask`` (B, S) marks live (all, when
    None). ``mesh``: the mesh the step is partitioned over, if any (it
    decides which grouped matmul runs, see ``_use_gmm``). ``layer``: the
    index of this layer where ``moe``'s expert weights are the whole stack
    (``experts_in_place``; the router is this layer's own).
    ``static_buffers``: a share walks a STATIC number of buffers of held pairs
    (``_shared_moe_block``), so that the pass can be differentiated."""
    if shares_experts(cfg):
        return _shared_moe_block(moe, h, cfg, token_mask=token_mask, mesh=mesh,
                                 layer=layer, static_buffers=static_buffers)
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


def _shared_moe_block(moe, h, cfg: ModelConfig, *, token_mask, mesh, layer,
                      static_buffers: bool = False):
    """``moe_block`` for a share of a wider expert layer (module docstring):
    (B, S, D) -> ((B, S, D), aux, counts (n_held + 2,)).

    The buffers of held pairs are walked by a loop whose trip count is data
    (a serving pass: as many buffers as the held pairs fill) or, with
    ``static_buffers`` (a pass that may be differentiated: reverse mode does
    not cross a data trip count), by ``ceil(T k / m)`` buffers unrolled, all
    the pairs there can be at any skew. Each is a ``jax.lax.cond`` on whether
    it holds a pair, so an empty one runs no grouped matmul, forward or
    backward, and passes the sum through."""
    b, s, d = h.shape
    e, k = cfg.num_experts, cfg.num_experts_per_tok
    first, n_held = held_experts(cfg)
    cd = h.dtype
    t = b * s
    x = h.reshape(t, d)
    live = (jnp.ones((t,), bool) if token_mask is None
            else token_mask.reshape(t).astype(bool))

    with jax.named_scope("moe_router"):
        logits = jnp.einsum("td,de->te", x.astype(jnp.float32),
                            moe["router"].astype(jnp.float32))
        gates = (jax.nn.sigmoid(logits) if cfg.scoring_func == "sigmoid"
                 else jax.nn.softmax(logits, axis=-1))  # (T, E + Z) f32

    with jax.named_scope("moe_dispatch"):
        # a buffer, not a parameter: it only chooses, and no gradient reaches it
        choose = (gates + jax.lax.stop_gradient(moe["router_bias"])
                  if "router_bias" in moe else gates)
        if cfg.n_group > 1:  # one group that always stays limits nothing
            choose = group_limited(choose, cfg.n_group, cfg.topk_group)
        _, top_idx = jax.lax.top_k(choose, k)  # the bias chooses, and only chooses
        if "choice" in moe:
            # a check that holds the choice still (benchmarks/train_grad_check.py
            # gives the plain reference's, so that a rounding's flipped sixth
            # choice is told from an error): (T, k) expert ids in place of ours
            top_idx = moe["choice"].reshape(t, k).astype(top_idx.dtype)
        top_w = jnp.take_along_axis(gates, top_idx, axis=-1)
        if cfg.norm_topk_prob:
            top_w = top_w / jnp.maximum(top_w.sum(axis=-1, keepdims=True), 1e-9)
        top_w = top_w * cfg.routed_scaling_factor
        pair_expert = top_idx.reshape(t * k)  # token-major
        pair_live = jnp.repeat(live, k)
        chosen = (pair_expert[:, None] == jnp.arange(e + cfg.zero_expert_num,
                                                     dtype=pair_expert.dtype)
                  ) & pair_live[:, None]
        per_expert = chosen.sum(axis=0, dtype=jnp.int32)  # live rows only
        sizes = per_expert[first:first + n_held]  # rows of each held group
        n_zero = per_expert[e:].sum()
        counts = jnp.concatenate([
            sizes, jnp.stack([n_zero, per_expert.sum() - sizes.sum() - n_zero])])
        # held pairs of live rows first, by expert; everything else behind
        local = pair_expert - first
        key = jnp.where((local >= 0) & (local < n_held) & pair_live, local, n_held)
        m = held_rows(t * k)
        order = jnp.argsort(key, stable=True)
        order = jnp.concatenate([order, jnp.zeros((m,), order.dtype)])
        ends = jnp.cumsum(sizes)
        n_rows = ends[-1]
        pair_w = top_w.reshape(t * k)

    def buffer_rows(i, x, experts, pair_w):
        """Rows [i * m, (i + 1) * m) of the sorted held pairs through the
        experts: (their tokens (m,), their weighted outputs (m, D) float32,
        zero where the buffer ends before the row)."""
        with jax.named_scope("moe_dispatch"):
            at = i * m + jnp.arange(m, dtype=jnp.int32)
            valid = at < n_rows
            pair = jax.lax.dynamic_slice(order, (i * m,), (m,))
            tok = pair // k
            xs = x[tok]
            if static_buffers:
                # the kernel's transposed products leave the rows of no group
                # as they were, and the gather's backward would add them to
                # their tokens: a select, so that no cotangent reaches them
                xs = jnp.where(valid[:, None], xs, 0)
            part = jnp.clip(jnp.minimum(ends, (i + 1) * m)
                            - jnp.maximum(ends - sizes, i * m), 0, m)
            row_expert = jnp.minimum(key[pair], n_held - 1)
        with jax.named_scope("moe_experts"):
            gate = _grouped(xs, experts["w_gate"], part, row_expert, cd, mesh, layer)
            up = _grouped(xs, experts["w_up"], part, row_expert, cd, mesh, layer)
            ys = _grouped(jax.nn.silu(gate) * up, experts["w_down"], part, row_expert, cd,
                          mesh, layer)
        with jax.named_scope("moe_combine"):
            # rows past the held pairs belong to no group: the kernel leaves
            # them as they were, so they are masked, not weighted by zero
            rows = jnp.where(valid[:, None], ys.astype(jnp.float32), 0.0)
            return tok, rows * pair_w[pair][:, None]

    experts = {n: moe[n] for n in ("w_gate", "w_up", "w_down")}
    # a differentiated buffer keeps its inputs alone and runs its rows again in
    # the backward pass: the layer's own remat no longer needs them, so the
    # grouped matmuls run as often as without, and eight buffers' rows
    # (0.4 GiB each at 24,576 pairs of 2,048) are never resident together
    rows_of = jax.checkpoint(buffer_rows, static_argnums=0) if static_buffers else buffer_rows

    def one_buffer(i, acc):
        tok, rows = rows_of(i, x, experts, pair_w)
        with jax.named_scope("moe_combine"):
            return acc.at[tok].add(rows)

    out = jnp.zeros((t, d), jnp.float32)
    if static_buffers:
        for i in range(-(-(t * k) // m)):
            out = jax.lax.cond(i * m < n_rows, functools.partial(one_buffer, i),
                               lambda acc: acc, out)
    else:
        out = jax.lax.fori_loop(0, (n_rows + m - 1) // m, one_buffer, out)
    with jax.named_scope("moe_combine"):
        if cfg.zero_expert_num or not static_buffers:
            with jax.named_scope("moe_zero"):
                # the identities: the token's own row, once, times their weights
                # (a differentiated pass without zero-compute experts leaves the
                # term out: a float32 copy of the stream times zero, kept for
                # the backward pass)
                w_zero = (top_w * (top_idx >= e)).sum(axis=-1)
                out = out + x.astype(jnp.float32) * w_zero[:, None]
        out = out.astype(cd)
    if "shared" in moe:
        from ditl_tpu.ops.quant import weight_einsum

        with jax.named_scope("moe_shared"):
            sh = moe["shared"]
            act = jax.nn.silu(
                weight_einsum("td,df->tf", x, sh["w_gate"], compute_dtype=cd)
            ) * weight_einsum("td,df->tf", x, sh["w_up"], compute_dtype=cd)
            out = out + weight_einsum("tf,fd->td", act, sh["w_down"], compute_dtype=cd)

    if cfg.scoring_func == "sigmoid":  # the term is defined on shares of 1
        gates = gates / jnp.maximum(gates.sum(axis=-1, keepdims=True), 1e-9)
    aux = load_balancing_loss(gates, per_expert.astype(jnp.float32),
                              live.astype(jnp.float32))
    return out.reshape(b, s, d), aux, counts.astype(jnp.int32)


def group_limited(choose: jax.Array, n_group: int, topk_group: int) -> jax.Array:
    """``choose`` (T, E) with every expert outside the token's ``topk_group``
    best groups at -inf. The experts lie in ``n_group`` equal consecutive
    groups; a group's score is the sum of its two largest entries."""
    t, e = choose.shape
    best2 = jax.lax.top_k(choose.reshape(t, n_group, e // n_group), 2)[0].sum(axis=-1)
    _, kept = jax.lax.top_k(best2, topk_group)  # (T, topk_group)
    keep = (kept[..., None] == jnp.arange(n_group)).any(axis=1)  # (T, n_group)
    return jnp.where(jnp.repeat(keep, e // n_group, axis=1), choose, -jnp.inf)
