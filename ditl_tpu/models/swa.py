"""A stack of WINDOW and FULL attention layers (Trinity / ``afmoe``): one
letter a layer in ``ModelConfig.layer_types``, ``w`` a layer whose query i
sees key j iff ``0 <= i - j < sliding_window``, ``a`` a layer that sees every
``j <= i``.

The layer (``_block``), with ``N(.)`` an RMSNorm with its own learned scale::

    h = N_in(x);  q, k, v, g = h Wq, h Wk, h Wv, h Wg
    q, k = N_q(q), N_k(k)            over each head's values   (qk_norm)
    q, k rotated in a ``w`` layer, not at all in an ``a`` layer (rope_window)
    a = softmax(q k^T / sqrt(head_dim), masked) v
    a = a * sigmoid(g)                                          (attn_gate)
    x = x + N_post_attn(a Wo)                                   (sandwich_norm)
    x = x + N_post_mlp(F(N_pre_mlp(x)))

``F`` is the dense SwiGLU FFN in the first ``first_k_dense_replace`` layers
and the expert layer with a held share behind them (models/moe.py
``_shared_moe_block``: sigmoid scores, a bias for the choice only,
renormalised top-k, a scale, a shared expert).

Every layer has the same parameter shapes whatever its kind, so each of the
two stacks (``params["layers"]["dense"]``, ``["sparse"]``) is ONE scan, and a
layer's kind is data: ``lax.cond`` takes the window or the full branch. What
differs by kind is the mask and WHERE the cache is. A paged engine
(infer/page_format.py ``WindowKVPages``) keeps two page pools, the full
layers' ``kp`` / ``vp`` (full layers, P_full, K, ps, D) and the window
layers' ``wkp`` / ``wvp`` (window layers, P_win, K, ps, D), page ids of
their own, each with its page table (``table``, ``wtable``) and its decode
work list (``steps``, ``wsteps``: ops/paged_attention.py ``decode_steps``
with and without a window), all built once a program in front of the scan. A
prefill reads the context it needs from two transient rows (every cached
token for the full layers, the last ``ceil(window / page_size)`` pages for
the window layers) and returns the CHUNK's keys and values, every layer's;
the format writes them into both pools.

Scopes (ops/names.py ``SWA_SCOPES``), inside ``attn_core``: ``attn_window``
and ``attn_full``, a layer's attention by its kind, the gate's product
among it, in prefill and in decode.
"""

from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from ditl_tpu.config import ModelConfig

__all__ = ["init_swa_params", "swa_logical_axes", "stack", "layer_kinds", "Q_BLOCK"]

Q_BLOCK = 128  # queries a block where a prefill's scores would not fit whole


def _depths(cfg: ModelConfig) -> dict[str, int]:
    """The two stacks of ``params["layers"]`` and their depths."""
    return {"dense": cfg.first_k_dense_replace,
            "sparse": cfg.num_layers - cfg.first_k_dense_replace}


def layer_kinds(cfg: ModelConfig) -> tuple[np.ndarray, np.ndarray]:
    """``(is_window (L,) bool, index (L,) int32)``: each layer's kind and its
    index among the layers of its kind (its place in that kind's pool)."""
    is_w = np.array([c == "w" for c in cfg.layer_types], bool)
    index = np.where(is_w, np.cumsum(is_w) - 1, np.cumsum(~is_w) - 1)
    return is_w, index.astype(np.int32)


def init_swa_params(rng: jax.Array, cfg: ModelConfig) -> dict[str, Any]:
    """``{"dense": ..., "sparse": ...}`` (a stack of depth 0 is left out), each
    leaf stacked over its stack's layers and drawn in ``param_dtype``."""
    from ditl_tpu.models.moe import init_moe_params, lean_dense

    pd = jnp.dtype(cfg.param_dtype)
    d, f, hd = cfg.hidden_size, cfg.intermediate_size, cfg.head_dim
    nh, nkv = cfg.num_heads, cfg.num_kv_heads

    def one(rng, n, dense_ffn):
        keys = iter(jax.random.split(rng, 16))

        def dense(shape, fan_in):
            return lean_dense(next(keys), (n,) + shape, fan_in, pd)

        attn = {"wq": dense((d, nh * hd), d), "wk": dense((d, nkv * hd), d),
                "wv": dense((d, nkv * hd), d), "wo": dense((nh * hd, d), nh * hd)}
        if cfg.attn_gate:
            attn["wg"] = dense((d, nh * hd), d)
        if cfg.qk_norm:
            attn.update(q_norm=jnp.ones((n, hd), pd), k_norm=jnp.ones((n, hd), pd))
        out = {"attn_norm": {"scale": jnp.ones((n, d), pd)}, "attn": attn,
               "mlp_norm": {"scale": jnp.ones((n, d), pd)}}
        if cfg.sandwich_norm:
            out["attn_post_norm"] = {"scale": jnp.ones((n, d), pd)}
            out["mlp_post_norm"] = {"scale": jnp.ones((n, d), pd)}
        if dense_ffn:
            out["mlp"] = {"w_gate": dense((d, f), d), "w_up": dense((d, f), d),
                          "w_down": dense((f, d), f)}
        else:
            out["moe"] = init_moe_params(next(keys), cfg, n_layers=n)
        return out

    k_dense, k_sparse = jax.random.split(rng)
    return {kind: one(key, n, kind == "dense")
            for (kind, n), key in zip(_depths(cfg).items(), (k_dense, k_sparse)) if n}


def swa_logical_axes(cfg: ModelConfig) -> dict[str, Any]:
    from ditl_tpu.models.moe import moe_logical_axes

    norm = {"scale": ("layers", "norm")}

    def one(dense_ffn):
        attn = {"wq": ("layers", "embed", "heads"), "wk": ("layers", "embed", "kv_heads"),
                "wv": ("layers", "embed", "kv_heads"), "wo": ("layers", "heads", "embed")}
        if cfg.attn_gate:
            attn["wg"] = ("layers", "embed", "heads")
        if cfg.qk_norm:
            attn.update(q_norm=("layers", "norm"), k_norm=("layers", "norm"))
        out = {"attn_norm": norm, "attn": attn, "mlp_norm": norm}
        if cfg.sandwich_norm:
            out.update(attn_post_norm=norm, mlp_post_norm=norm)
        if dense_ffn:
            out["mlp"] = {"w_gate": ("layers", "embed", "mlp"),
                          "w_up": ("layers", "embed", "mlp"),
                          "w_down": ("layers", "mlp", "embed")}
        else:
            out["moe"] = moe_logical_axes(cfg)
        return out

    return {kind: one(kind == "dense") for kind, n in _depths(cfg).items() if n}


# ---------------------------------------------------------------------------
# Attention under a mask made of positions
# ---------------------------------------------------------------------------


def _attend(q, k, v, q_pos, k_pos, k_ok, window: int | None, same_doc=None):
    """``q`` (B, S, H, D) against ``k`` / ``v`` (B, Skv, K, D): query i at
    position ``q_pos[b, i]`` sees key j iff ``k_ok[b, j]``, ``k_pos[b, j] <=
    q_pos[b, i]`` and, with a ``window``, ``q_pos - k_pos < window``;
    ``same_doc`` (B, S, Skv) joins where rows are packed. In blocks of
    ``Q_BLOCK`` queries where the scores of all of them are large (a prefill
    chunk over tens of thousands of cached tokens)."""
    from ditl_tpu.ops.attention import _xla_attention

    def allowed(qp, doc):
        ok = k_ok[:, None, :] & (k_pos[:, None, :] <= qp[:, :, None])
        if window is not None:
            ok = ok & (qp[:, :, None] - k_pos[:, None, :] < window)
        return ok if doc is None else ok & doc

    b, s = q.shape[:2]
    blocks = s // Q_BLOCK
    if blocks < 2 or s % Q_BLOCK or same_doc is not None or s * k.shape[1] < (1 << 22):
        return _xla_attention(q, k, v, causal=False, segment_ids=None,
                              mask=allowed(q_pos, same_doc))

    def block(xs):
        qb, qp = xs  # (B, Q_BLOCK, H, D), (B, Q_BLOCK)
        return _xla_attention(qb, k, v, causal=False, segment_ids=None,
                              mask=allowed(qp, None))

    out = jax.lax.map(block, (
        jnp.moveaxis(q.reshape(b, blocks, Q_BLOCK, *q.shape[2:]), 1, 0),
        jnp.moveaxis(q_pos.reshape(b, blocks, Q_BLOCK), 1, 0)))
    return jnp.moveaxis(out, 0, 1).reshape(q.shape)


def _block(lp, x, *, cfg: ModelConfig, positions, segment_ids, mesh, rules, is_w, kidx,
           layer_cache, rows, cache_index, paged, pools, token_mask, moe_stack,
           layer_index):
    """One layer: ``(x, aux, new cache or None, expert counts or None)``.
    ``is_w`` / ``kidx``: traced scalars, the layer's kind and its index among
    its kind. ``rows``: a prefill's context, whole and read only; ``pools``: a
    decode step's page pools, whole; ``layer_cache``: that step's tails of
    this layer."""
    from ditl_tpu.models.llama import _constrain, apply_rope, dense_mlp, rms_norm
    from ditl_tpu.models.moe import moe_block
    from ditl_tpu.ops.quant import weight_einsum

    b, s, _ = x.shape
    nh, nkv, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    cd, eps, w = jnp.dtype(cfg.dtype), cfg.rms_norm_eps, cfg.sliding_window
    a = lp["attn"]
    with jax.named_scope("attn_qkv"):
        h = rms_norm(x, lp["attn_norm"]["scale"], eps).astype(cd)

        def heads(name, n, norm):
            t = weight_einsum("bsd,df->bsf", h, a[name], compute_dtype=cd).reshape(b, s, n, hd)
            return rms_norm(t, a[norm], eps) if norm in a else t

        q, k = heads("wq", nh, "q_norm"), heads("wk", nkv, "k_norm")
        v = heads("wv", nkv, "")
        if cfg.position_embedding == "rope":
            q, k = apply_rope(q, positions, cfg=cfg), apply_rope(k, positions, cfg=cfg)
        elif cfg.position_embedding == "rope_window":
            q = jnp.where(is_w, apply_rope(q, positions, cfg=cfg), q)
            k = jnp.where(is_w, apply_rope(k, positions, cfg=cfg), k)
        gate = (jax.nn.sigmoid(weight_einsum("bsd,df->bsf", h, a["wg"], compute_dtype=cd)
                               .astype(jnp.float32)) if "wg" in a else None)
        q = _constrain(q, ("batch", "seq", "act_heads", "head_dim"), mesh, rules)
        k = _constrain(k, ("batch", "seq", "act_kv_heads", "head_dim"), mesh, rules)

    def gated(out):  # (B, S, H, D) -> (B, S, H * D), times the gate
        out = out.reshape(b, s, nh * hd)
        return out if gate is None else (out.astype(jnp.float32) * gate).astype(cd)

    new_cache = None
    with jax.named_scope("attn_core"):
        if pools is not None:
            from ditl_tpu.ops.paged_attention import paged_attention

            tdt = layer_cache["tk"].dtype
            with jax.named_scope("kv_write"):
                at = (0, 0, paged["t"], 0)
                tk = jax.lax.dynamic_update_slice(
                    layer_cache["tk"], jnp.swapaxes(k, 1, 2).astype(tdt), at)
                tv = jax.lax.dynamic_update_slice(
                    layer_cache["tv"], jnp.swapaxes(v, 1, 2).astype(tdt), at)
            new_cache = {"tk": tk, "tv": tv}

            def decode(kind: str, window):
                pre = "w" if window else ""
                n_pages = pools[pre + "kp"].shape[0] // max(
                    1, cfg.layer_types.count("w" if window else "a"))

                def run():
                    with jax.named_scope(kind):
                        return gated(paged_attention(
                            q[:, 0], pools[pre + "kp"], pools[pre + "vp"],
                            paged[pre + "table"] + kidx * n_pages, paged["lengths"],
                            tail_k=tk, tail_v=tv, starts=paged["starts"],
                            steps=paged[pre + "steps"], window=window)[:, None])
                return run

            attn_out = jax.lax.cond(is_w, decode("attn_window", w), decode("attn_full", None))
        else:
            if rows is not None:
                new_cache = {"k": k.astype(rows["k"].dtype), "v": v.astype(rows["v"].dtype)}

            def prefill(kind: str, window):
                pre = "w" if window else ""

                def run():
                    keys, vals, k_pos, k_ok = k, v, positions, jnp.ones((b, s), bool)
                    if rows is not None:
                        ctx_k = jax.lax.dynamic_index_in_dim(rows[pre + "k"], kidx, 0, False)
                        ctx_v = jax.lax.dynamic_index_in_dim(rows[pre + "v"], kidx, 0, False)
                        n_ctx = ctx_k.shape[1]
                        # the full layers' row starts at position 0; the window
                        # layers' at the chunk's start less the pages it holds
                        first = cache_index - n_ctx if window else 0
                        at = first + jnp.arange(n_ctx, dtype=jnp.int32)
                        ctx_ok = (at >= 0) & (at < cache_index)
                        keys = jnp.concatenate([ctx_k.astype(k.dtype), k], axis=1)
                        vals = jnp.concatenate([ctx_v.astype(v.dtype), v], axis=1)
                        k_pos = jnp.concatenate(
                            [jnp.broadcast_to(at, (b, n_ctx)), positions], axis=1)
                        k_ok = jnp.concatenate(
                            [jnp.broadcast_to(ctx_ok, (b, n_ctx)), k_ok], axis=1)
                    doc = None
                    if segment_ids is not None and rows is None:
                        doc = segment_ids[:, :, None] == segment_ids[:, None, :]
                    with jax.named_scope(kind):
                        if rows is None and cfg.attention_impl == "flash":
                            # a whole sequence: the kernel skips the blocks
                            # behind the window (forward only)
                            from ditl_tpu.ops.attention import dot_product_attention

                            return gated(dot_product_attention(
                                q, k, v, causal=True, segment_ids=segment_ids, impl="flash",
                                window=window, block_sizes=(
                                    cfg.flash_block_q, cfg.flash_block_kv, 0, 0)))
                        return gated(_attend(q, keys, vals, positions, k_pos, k_ok, window, doc))
                return run

            attn_out = jax.lax.cond(is_w, prefill("attn_window", w), prefill("attn_full", None))
    with jax.named_scope("attn_out"):
        out = weight_einsum("bsf,fd->bsd", attn_out, a["wo"], compute_dtype=cd)
        if "attn_post_norm" in lp:
            out = rms_norm(out, lp["attn_post_norm"]["scale"], eps)
        x = _constrain(x + out, ("batch", "seq", "act_embed"), mesh, rules)
    with jax.named_scope("mlp"):
        u = rms_norm(x, lp["mlp_norm"]["scale"], eps).astype(cd)
        if "mlp" in lp:
            y = dense_mlp(lp["mlp"], u, cfg=cfg, mesh=mesh, rules=rules)
            aux, counts = jnp.zeros((), jnp.float32), None
        else:
            y, aux, counts = moe_block(
                {**lp["moe"], **(moe_stack or {})}, u, cfg, token_mask=token_mask,
                mesh=mesh, layer=layer_index if moe_stack else None)
        if "mlp_post_norm" in lp:
            y = rms_norm(y, lp["mlp_post_norm"]["scale"], eps)
        x = _constrain(x + y, ("batch", "seq", "act_embed"), mesh, rules)
    return x, aux, new_cache, counts


def stack(layers, x, *, cfg: ModelConfig, positions, segment_ids, mesh, rules,
          cache=None, cache_index=None, paged=None, token_mask=None):
    """All layers: the leading dense stack, then the expert stack, each a
    scan. -> ``(x, layer_aux (L,), new cache or None, expert counts (expert
    layers, count_width))``.

    ``cache``: None (a whole sequence, causal, ``segment_ids`` its documents);
    a prefill's context rows ``{"k", "v": (full layers, B, ctx, K, D), "wk",
    "wv": (window layers, B, window ctx, K, D)}`` with ``cache_index`` the
    chunk's first position (back come the chunk's own ``{"k", "v": (L, B, S,
    K, D)}``, every layer's); or the page pools ``{"kp", "vp", "wkp", "wvp"}``
    beside a decode step's tails ``{"tk", "tv": (L, B, K, T, D)}`` with
    ``paged`` (back come the tails)."""
    from ditl_tpu.models.llama import _apply_remat
    from ditl_tpu.models.moe import experts_in_place, grouped_rows

    if mesh is not None and mesh.shape.get("stage", 1) > 1:
        raise ValueError("pipeline parallelism does not carry a stack with window "
                         "layers (models/swa.py)")
    b, s, _ = x.shape
    is_w, kidx = (jnp.asarray(t) for t in layer_kinds(cfg))
    pools = rows = tails = None
    if cache is not None and "kp" in cache:
        pools = {n: cache[n].reshape(-1, *cache[n].shape[2:])
                 for n in ("kp", "vp", "wkp", "wvp")}
        tails = {n: cache[n] for n in ("tk", "tv")}
    elif cache is not None:
        rows = cache

    outs, first = [], 0
    for kind, depth in _depths(cfg).items():
        if not depth:
            continue
        lp_stack, moe_stack = layers[kind], None
        if cache is not None and kind == "sparse" and experts_in_place(
                lp_stack["moe"], grouped_rows(cfg, b * s), mesh):
            # the scan slices only the router and the shared expert; the
            # kernel addresses the layer's experts inside the stack
            in_loop = ("router", "router_bias", "shared")
            moe_stack = {n: t for n, t in lp_stack["moe"].items() if n not in in_loop}
            lp_stack = {**lp_stack, "moe": {n: t for n, t in lp_stack["moe"].items()
                                            if n in in_loop}}

        def layer_fn(carry, xs, first=first, moe_stack=moe_stack):
            lp, layer_cache, i = xs
            y, *ys = _block(
                lp, carry, cfg=cfg, positions=positions, segment_ids=segment_ids,
                mesh=mesh, rules=rules, is_w=is_w[first + i], kidx=kidx[first + i],
                layer_cache=layer_cache, rows=rows, cache_index=cache_index, paged=paged,
                pools=pools, token_mask=token_mask, moe_stack=moe_stack, layer_index=i)
            return y, tuple(ys)

        if cache is None:
            layer_fn = _apply_remat(layer_fn, cfg)
        part = None if tails is None else {
            n: t[first:first + depth] for n, t in tails.items()}
        with jax.named_scope("layer_scan"):
            x, ys = jax.lax.scan(
                layer_fn, x, (lp_stack, part, jnp.arange(depth, dtype=jnp.int32)))
        outs.append(ys)
        first += depth

    def joined(parts):  # the two stacks' outputs, one after the other
        parts = [p for p in parts if p is not None]
        return jax.tree.map(lambda *leaves: jnp.concatenate(leaves), *parts) if parts else None

    aux, new_cache, counts = (joined(parts) for parts in zip(*outs))
    return x, aux, new_cache, counts
