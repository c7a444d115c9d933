"""LongCat-Flash's block: a shortcut-connected DOUBLE layer whose attention
is multi-head latent attention (MLA). ``llama.forward``'s layer scan carries
it in place of ``_decoder_layer`` when ``cfg.double_layer`` (every layer of
that model is alike, so one stack suffices). Latent attention in a SINGLE
pre-norm block, behind an indexer, is DeepSeek-V3.2's (models/dsa.py), which
shares ``latent_width`` and ``rope_interleaved`` with this file.

With ``n`` an RMSNorm with its own scale, for layer input ``x``::

    h1 = x  + MLA_0(n_in0(x));      u  = n_post0(h1)
    s  = MoE(u)                     # the shortcut: from u, added at the end
    h2 = h1 + FFN_0(u)              # SwiGLU, hidden -> intermediate -> hidden
    h3 = h2 + MLA_1(n_in1(h2))
    y  = h3 + FFN_1(n_post1(h3)) + s

    MLA(z): cq = n_q(z Wqa);  q = (cq Wqb) * sqrt(hidden / q_lora_rank)
            [c | kr] = z Wkva;  c = n_kv(c) * sqrt(hidden / kv_lora_rank)
            [k_nope | v] = c Wkvb;  kr is shared by all heads
            scores = (q_nope . k_nope + rope(q_rope) . rope(kr)) / sqrt(nope + rope)
            out = (softmax(scores) v) Wo

A token's cache entry, one an attention sublayer, is the LATENT ``(c,
rope(kr))``: ``kv_lora_rank + qk_rope_head_dim`` values (576), stored padded
with zeros to whole lanes (``latent_width``: 640), so that a page's block is
a whole number of (8, 128) tiles, which is also what the device's tiled
layout would make of 576; the padding is stored, and counted as stored.

Two forms of one attention:

- DECOMPRESSED (a forward without cache, a prefill): ``k_nope`` and ``v`` are
  made from ``c`` through ``Wkvb`` for every key position (a paged prefill's
  context pages are gathered as latents and decompressed after the gather),
  and attention runs per head over 192-wide queries and keys and 128-wide
  values. It runs on the XLA path in blocks of ``PREFILL_Q_BLOCK`` queries
  (the flash kernel assumes one width for q, k and v; padding v to 192 was
  the alternative), so the score tensor of a 2,048-token prefill is
  (64, 512, 2048) at a time and not (64, 2048, 2048).
- ABSORBED (a paged decode step): ``Wkvb``'s key half is folded into the
  query (``q_lat = q_nope Wkvb_k``: 512 wide) and its value half into the
  output, so attention runs over the stored entries themselves, one per
  token for all 64 heads: scores ``[q_lat | q_rope] . [c | kr]``, values
  ``c``; ``ops/mla_attention.py``.

Scopes (``ops/names.py`` ``MLA_SCOPES``), each INSIDE the scope of
``SCOPES`` it refines: ``mla_q`` and ``mla_kv`` inside ``attn_qkv`` (and the
value half of the absorption inside ``attn_out``), ``mla_attn`` inside
``attn_core``; the latent's write into the tick's tail inside ``kv_write``.

Serving only as far as the cache goes: latent pages (``{"cp"}``, a tick's
tail ``{"tc"}``) and a prefill's transient row (``{"c"}``). The contiguous
cache, int8 pools, speculative ticks, the host tier, the handoff and a mesh
refuse at engine construction (infer/page_format.py).
"""

from __future__ import annotations

import math
from typing import Any

import jax
import jax.numpy as jnp

from ditl_tpu.config import ModelConfig

__all__ = ["init_double_layer_params", "double_layer_logical_axes", "double_layer",
           "latent_width", "PREFILL_Q_BLOCK"]

SUBLAYERS = 2  # attention sublayers (and dense FFNs) a double layer
PREFILL_Q_BLOCK = 512  # queries a block of the decompressed attention


def latent_width(cfg: ModelConfig) -> int:
    """Values of one stored cache entry: ``[c | rope(kr) | zeros]``, whole
    lanes of 128."""
    return -(-(cfg.kv_lora_rank + cfg.qk_rope_head_dim) // 128) * 128


def init_double_layer_params(rng: jax.Array, cfg: ModelConfig) -> dict[str, Any]:
    """The ``layers`` subtree: the norms' scales (L, 2, D) for the two halves
    together, the two halves' matrices as trees of their own (``attn`` /
    ``mlp`` -> ``sub0``, ``sub1``, every leaf (L, ...)), the expert block
    (one a layer) as ``models/moe.py`` builds it. The matrices are apart
    because the layer loop slices a layer out of each stacked leaf: a slice
    (1, d_in, d_out) fuses into the matmul that reads it, a slice (1, 2,
    d_in, d_out) of both halves was materialised first, 302 MB a dense FFN
    matrix, every layer of every decode step (seen in the HLO compiled for a
    described v5e). Leaves are drawn in ``param_dtype`` (``moe.lean_dense``)."""
    from ditl_tpu.models.moe import init_moe_params, lean_dense

    pd = jnp.dtype(cfg.param_dtype)
    d, f, L, nh = cfg.hidden_size, cfg.intermediate_size, cfg.num_layers, cfg.num_heads
    qr, kr = cfg.q_lora_rank, cfg.kv_lora_rank
    nope, rope, vd = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    keys = iter(jax.random.split(rng, 24))

    def dense(shape, fan_in):
        return lean_dense(next(keys), (L,) + shape, fan_in, pd)

    # The up-projections start at 1 / sqrt(fan_in) DIVIDED by the scale the
    # forward pass puts on their normed latent (``mla_scale_*``: sqrt(hidden
    # / rank), so 1 / sqrt(hidden)): queries, keys and values then start at
    # unit scale as every other family's do. At 1 / sqrt(rank) the scores of
    # seeded random weights have a standard deviation of 5.7, every head of
    # every sublayer is nearly an argmax, and a bfloat16 rounding that flips
    # one changes a token's stream by a tenth: the forward pass then differs
    # from its own float32 self by 30% over four layers (seen on the chip at
    # the published widths; 1% with this start), which no trained model does.
    q_fan = d if cfg.mla_scale_q_lora else qr
    kv_fan = d if cfg.mla_scale_kv_lora else kr

    def attn():
        return {
            "w_qa": dense((d, qr), d),
            "q_norm": jnp.ones((L, qr), pd),
            "w_qb": dense((qr, nh * (nope + rope)), q_fan),
            "w_kva": dense((d, kr + rope), d),
            "kv_norm": jnp.ones((L, kr), pd),
            "w_kvb": dense((kr, nh * (nope + vd)), kv_fan),
            "wo": dense((nh * vd, d), nh * vd),
        }

    def mlp():
        return {
            "w_gate": dense((d, f), d),
            "w_up": dense((d, f), d),
            "w_down": dense((f, d), f),
        }

    halves = [f"sub{j}" for j in range(SUBLAYERS)]
    return {
        "attn_norm": {"scale": jnp.ones((L, SUBLAYERS, d), pd)},
        "attn": {h: attn() for h in halves},
        "mlp_norm": {"scale": jnp.ones((L, SUBLAYERS, d), pd)},
        "mlp": {h: mlp() for h in halves},
        "moe": init_moe_params(next(keys), cfg),
    }


def double_layer_logical_axes(cfg: ModelConfig) -> dict[str, Any]:
    from ditl_tpu.models.moe import moe_logical_axes

    attn = {
        "w_qa": ("layers", "embed", None),
        "q_norm": ("layers", "norm"),
        "w_qb": ("layers", None, "heads"),
        "w_kva": ("layers", "embed", None),
        "kv_norm": ("layers", "norm"),
        "w_kvb": ("layers", None, "heads"),
        "wo": ("layers", "heads", "embed"),
    }
    mlp = {
        "w_gate": ("layers", "embed", "mlp"),
        "w_up": ("layers", "embed", "mlp"),
        "w_down": ("layers", "mlp", "embed"),
    }
    halves = [f"sub{j}" for j in range(SUBLAYERS)]
    return {
        "attn_norm": {"scale": ("layers", None, "norm")},
        "attn": {h: dict(attn) for h in halves},
        "mlp_norm": {"scale": ("layers", None, "norm")},
        "mlp": {h: dict(mlp) for h in halves},
        "moe": moe_logical_axes(cfg),
    }


def rope_interleaved(x: jax.Array, positions: jax.Array, theta: float,
                     inv_freq: jax.Array | None = None) -> jax.Array:
    """Rotary embedding over neighbouring pairs (2i, 2i+1). x: (B, S, H, D);
    positions: (B, S). ``inv_freq`` (D / 2,): the frequencies where they are
    not ``theta``'s own (models/dsa.py: YaRN)."""
    d = x.shape[-1]
    inv = inv_freq
    if inv is None:
        inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = positions[..., None].astype(jnp.float32) * inv  # (B, S, D/2)
    cos, sin = jnp.cos(ang)[:, :, None, :], jnp.sin(ang)[:, :, None, :]
    xf = x.astype(jnp.float32).reshape(*x.shape[:-1], d // 2, 2)
    a, b = xf[..., 0], xf[..., 1]
    out = jnp.stack([a * cos - b * sin, b * cos + a * sin], axis=-1)
    return out.reshape(x.shape).astype(x.dtype)


def _decompressed_attention(q, k, v, allowed) -> jax.Array:
    """Per-head softmax attention with keys wider than values. q: (B, Sq, H,
    Dk), k: (B, Sk, H, Dk), v: (B, Sk, H, Dv), ``allowed`` (B, Sq, Sk) bool
    -> (B, Sq, H, Dv). Blocks of ``PREFILL_Q_BLOCK`` queries (module
    docstring); float32 scores and softmax."""
    from ditl_tpu.ops.attention import NEG_INF

    b, sq, nh, dk = q.shape
    scale = dk ** -0.5

    def block(qb, ab):
        scores = jnp.einsum("bqhd,bkhd->bhqk", qb, k,
                            preferred_element_type=jnp.float32) * scale
        scores = jnp.where(ab[:, None], scores, NEG_INF)
        probs = jax.nn.softmax(scores, axis=-1).astype(v.dtype)
        return jnp.einsum("bhqk,bkhd->bqhd", probs, v)

    n = PREFILL_Q_BLOCK
    if sq <= n or sq % n:
        return block(q, allowed)
    qs = jnp.moveaxis(q.reshape(b, sq // n, n, nh, dk), 1, 0)
    al = jnp.moveaxis(allowed.reshape(b, sq // n, n, -1), 1, 0)
    out = jax.lax.map(lambda xs: block(*xs), (qs, al))  # (nb, B, n, H, Dv)
    return jnp.moveaxis(out, 0, 1).reshape(b, sq, nh, v.shape[-1])


def _mla_sublayer(a, h, *, cfg: ModelConfig, positions, allowed, cache, cache_index,
                  paged, pool, cd):
    """One attention sublayer on the normed input ``h`` (B, S, D): ``(out
    (B, S, D) before the residual, new cache or None)``. ``a``: this
    sublayer's weights. ``cache``: None; a prefill's row ``(B, Smax, Dl)``
    (written at ``cache_index``, all of it attended under ``allowed`` (B, S,
    Smax)); or, with ``pool``, this sublayer's tail ``(B, T, Dl)`` of a paged
    decode step. ``allowed`` without a cache is (B, S, S)."""
    from ditl_tpu.models.llama import rms_norm
    from ditl_tpu.ops.quant import weight_einsum

    b, s, d = h.shape
    nh, eps = cfg.num_heads, cfg.rms_norm_eps
    r = cfg.kv_lora_rank
    nope, rope, vd = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    w_kvb = a["w_kvb"].astype(cd).reshape(r, nh, nope + vd)
    absorbed = pool is not None

    with jax.named_scope("attn_qkv"):
        with jax.named_scope("mla_q"):
            cq = rms_norm(weight_einsum("bsd,dr->bsr", h, a["w_qa"], compute_dtype=cd),
                          a["q_norm"], eps)
            q = weight_einsum("bsr,rf->bsf", cq, a["w_qb"], compute_dtype=cd)
            if cfg.mla_scale_q_lora:
                q = q * math.sqrt(cfg.hidden_size / cfg.q_lora_rank)
            q = q.reshape(b, s, nh, nope + rope)
            q_nope = q[..., :nope]
            q_rope = rope_interleaved(q[..., nope:], positions, cfg.rope_theta)
            if absorbed:
                # Wkvb's key half folded into the query: 512 wide, against c
                q_nope = jnp.einsum("bshn,rhn->bshr", q_nope, w_kvb[..., :nope])
        with jax.named_scope("mla_kv"):
            ckr = weight_einsum("bsd,df->bsf", h, a["w_kva"], compute_dtype=cd)
            c = rms_norm(ckr[..., :r], a["kv_norm"], eps)
            if cfg.mla_scale_kv_lora:
                c = c * math.sqrt(cfg.hidden_size / r)
            kr = rope_interleaved(ckr[..., None, r:], positions, cfg.rope_theta)[:, :, 0]
            pad = latent_width(cfg) - r - rope
            entry = jnp.concatenate(
                [c, kr, jnp.zeros((b, s, pad), c.dtype)], axis=-1)  # (B, S, Dl)
            if not absorbed:
                src, new_cache = entry, None
                if cache is not None:  # a prefill's row: all of it is context
                    new_cache = jax.lax.dynamic_update_slice(
                        cache, entry.astype(cache.dtype), (0, cache_index, 0))
                    src = new_cache.astype(cd)
                kv = jnp.einsum("bkr,rhf->bkhf", src[..., :r], w_kvb)
                k = jnp.concatenate(
                    [kv[..., :nope],
                     jnp.broadcast_to(src[:, :, None, r:r + rope],
                                      (*kv.shape[:3], rope))], axis=-1)
                v = kv[..., nope:]

    with jax.named_scope("attn_core"):
        if absorbed:
            from ditl_tpu.ops.mla_attention import mla_paged_attention

            with jax.named_scope("kv_write"):
                new_cache = jax.lax.dynamic_update_slice(
                    cache, entry.astype(cache.dtype), (0, paged["t"], 0))
            with jax.named_scope("mla_attn"):
                q_full = jnp.concatenate(
                    [q_nope, q_rope, jnp.zeros((b, s, nh, pad), q_nope.dtype)],
                    axis=-1)[:, 0]  # (B, H, Dl)
                lat = mla_paged_attention(
                    q_full, pool, paged["table"], paged["lengths"], tail=new_cache,
                    starts=paged["starts"], value_width=r,
                    scale=(nope + rope) ** -0.5, steps=paged.get("steps"))  # (B, H, r)
        else:
            attn = _decompressed_attention(
                jnp.concatenate([q_nope, q_rope], axis=-1), k, v, allowed)
    with jax.named_scope("attn_out"):
        if absorbed:
            with jax.named_scope("mla_kv"):
                # Wkvb's value half, folded behind the attention
                attn = jnp.einsum("bhr,rhv->bhv", lat.astype(cd),
                                  w_kvb[..., nope:])[:, None]
        out = weight_einsum("bsf,fd->bsd", attn.reshape(b, s, nh * vd), a["wo"],
                            compute_dtype=cd)
    return out, new_cache


def double_layer(
    layer_params: dict[str, Any],
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
    paged: dict | None = None,
    prefill_causal: bool = False,
    token_mask: jax.Array | None = None,
    with_moe_counts: bool = False,
    moe_stack: dict | None = None,
    layer_index: jax.Array | None = None,
    pools: dict | None = None,
    adapter_ids: jax.Array | None = None,
) -> tuple:
    """One double layer, with ``_decoder_layer``'s protocol: ``(x, aux)``,
    then ``new_kv`` with a cache, then (``with_moe_counts``) the expert
    block's counts. ``layer_cache``: ``{"c": (2, B, Smax, Dl)}`` (a prefill's
    row, written at ``cache_index``) or, with ``pools`` (``{"cp": (L * 2 *
    n_pages, ps, Dl)}``, whole, every sublayer's pages in one axis), the
    tick's tails ``{"tc": (2, B, T, Dl)}``; ``paged["table"]`` then names this
    LAYER's first sublayer's pages and ``paged["n_pages"]`` is the stride to
    the second's."""
    from ditl_tpu.models.llama import _constrain, dense_mlp, rms_norm
    from ditl_tpu.models.moe import moe_block

    if adapter_ids is not None or "lora" in layer_params:
        raise ValueError("LoRA adapters are not implemented for the double layer")
    b, s, _ = x.shape
    cd = jnp.dtype(cfg.dtype)
    eps = cfg.rms_norm_eps
    caches = None
    if layer_cache is not None:
        caches = layer_cache["tc" if pools is not None else "c"]
    if pools is not None:
        allowed = None
    elif caches is not None and not prefill_causal:
        allowed = attn_mask  # (B, S, Smax), the engine's
    else:
        idx = jnp.arange(s)
        allowed = jnp.broadcast_to(idx[None, :, None] >= idx[None, None, :], (b, s, s))
        if segment_ids is not None:
            allowed = allowed & (segment_ids[:, :, None] == segment_ids[:, None, :])
        if caches is not None:
            # a prefill of an EMPTY row from offset 0: the chunk attends to
            # itself, wherever it sits in the row
            smax = caches.shape[2]
            allowed = jax.lax.dynamic_update_slice(
                jnp.zeros((b, s, smax), bool), allowed, (0, 0, cache_index))

    new = []
    shortcut = aux = moe_counts = None
    for j in range(SUBLAYERS):
        sub_paged = paged
        if pools is not None:
            sub_paged = {**paged, "table": paged["table"] + j * paged["n_pages"]}
        with jax.named_scope("attn_qkv"):
            h = rms_norm(x, layer_params["attn_norm"]["scale"][j], eps)
        out, new_cache = _mla_sublayer(
            layer_params["attn"][f"sub{j}"], h, cfg=cfg, positions=positions,
            allowed=allowed, cache=None if caches is None else caches[j],
            cache_index=cache_index, paged=sub_paged,
            pool=None if pools is None else pools["cp"], cd=cd)
        new.append(new_cache)
        with jax.named_scope("attn_out"):
            x = _constrain(x + out, ("batch", "seq", "act_embed"), mesh, rules)
        with jax.named_scope("mlp"):
            u = rms_norm(x, layer_params["mlp_norm"]["scale"][j], eps)
            if j == 0:
                # the shortcut: the experts read u beside the dense FFN
                shortcut, aux, moe_counts = moe_block(
                    {**layer_params["moe"], **(moe_stack or {})}, u, cfg,
                    token_mask=token_mask, mesh=mesh,
                    layer=layer_index if moe_stack else None)
            x = x + dense_mlp(layer_params["mlp"][f"sub{j}"], u, cfg=cfg, mesh=mesh,
                              rules=rules)
            if j == SUBLAYERS - 1:
                x = x + shortcut
            x = _constrain(x, ("batch", "seq", "act_embed"), mesh, rules)

    out = (x, aux)
    if caches is not None:
        out += ({"tc" if pools is not None else "c": jnp.stack(new)},)
    if with_moe_counts:
        out += (moe_counts,)
    return out
