"""Attention implementations (L1).

The reference contains no attention code at all (SURVEY.md §5 'long-context':
the 70B model lives behind an HTTP API). Here attention is a first-class op
with four interchangeable implementations selected by
``ModelConfig.attention_impl``:

- ``"xla"``:   einsum + softmax, fully fused by XLA. Correctness reference.
- ``"flash"``: Pallas (Mosaic) blockwise FlashAttention kernel — O(S) memory,
               tiles sized for MXU/VMEM (ops/flash_attention.py).
- ``"ring"``:  ring attention over the ``sequence`` mesh axis for contexts
               longer than one chip's HBM (ops/ring_attention.py).
- ``"ulysses"``: all-to-all sequence parallelism over the same axis — heads
               re-sharded instead of KV rotated (ops/ulysses.py).

All take GQA-layout tensors: q ``(B, S, H, D)``, k/v ``(B, S, K, D)`` with
``H % K == 0``; softmax is computed in float32 regardless of input dtype. On
the xla and flash paths v (and so the output) may have a width of its own
(latent attention decompressed: 192 / 128).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from ditl_tpu.ops.backend import refuse_on_tpu

__all__ = ["dot_product_attention"]

NEG_INF = -2.3819763e38  # most-negative bf16-representable; avoids bf16 NaNs


def _xla_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool,
    segment_ids: jax.Array | None,
    mask: jax.Array | None = None,
    k_scale: jax.Array | None = None,
    v_scale: jax.Array | None = None,
    window: int | None = None,
) -> jax.Array:
    """``window`` (with ``causal``): query i sees only the keys ``i - j <
    window``. ``k_scale``/``v_scale`` (B, Skv, K) mark k/v as int8-quantized
    (infer/cache.py). The scales are factored OUT of the dots: the score
    matmul consumes raw int8 K (the int8->bf16 convert fuses into the dot's
    operand read, so HBM traffic stays int8-sized) and the per-slot scale
    multiplies the (B,K,G,Sq,Skv) score tile afterwards; likewise V's scale
    folds into the probabilities. Dequantizing before the dot instead would
    materialize a full bf16 cache copy in HBM and forfeit the bandwidth win."""
    b, s_q, h, d = q.shape
    _, s_kv, kv_heads, _ = k.shape
    groups = h // kv_heads
    qg = q.reshape(b, s_q, kv_heads, groups, d)
    scale = d**-0.5
    if k_scale is not None:
        k = k.astype(q.dtype)
        v = v.astype(q.dtype)
    # (B, K, G, Sq, Skv) scores; accumulate in f32 on the MXU.
    scores = jnp.einsum(
        "bqkgd,bskd->bkgqs", qg, k, preferred_element_type=jnp.float32
    ) * scale
    if k_scale is not None:
        scores = scores * jnp.moveaxis(k_scale, 1, 2)[:, :, None, None, :]
    if causal:
        causal_mask = jnp.tril(jnp.ones((s_q, s_kv), dtype=bool))
        if window is not None:
            causal_mask = causal_mask & ~jnp.tril(jnp.ones((s_q, s_kv), dtype=bool), -window)
        scores = jnp.where(causal_mask[None, None, None], scores, NEG_INF)
    if segment_ids is not None:
        seg_mask = segment_ids[:, :, None] == segment_ids[:, None, :]  # (B,Sq,Skv)
        scores = jnp.where(seg_mask[:, None, None], scores, NEG_INF)
    if mask is not None:  # explicit (B, Sq, Skv) mask — KV-cache decode path
        scores = jnp.where(mask[:, None, None], scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    if v_scale is not None:
        probs = probs * jnp.moveaxis(v_scale, 1, 2)[:, :, None, None, :]
    probs = probs.astype(v.dtype)
    out = jnp.einsum("bkgqs,bskd->bqkgd", probs, v)
    return out.reshape(b, s_q, h, v.shape[-1])  # the values' width, not q's


def _mesh_axes_size(mesh, axes) -> int:
    """Product of mesh-axis sizes for a rules value (str, tuple, or None).
    Canonical definition lives in parallel/sharding.mesh_axes_size; this
    alias keeps the op module's historical import surface."""
    from ditl_tpu.parallel.sharding import mesh_axes_size

    return mesh_axes_size(mesh, axes)


def _seq_sharded_decode(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    mask: jax.Array,
    *,
    mesh,
    rules,
    k_scale: jax.Array | None = None,
    v_scale: jax.Array | None = None,
) -> jax.Array:
    """Flash-decoding over ICI: the KV cache's CONTEXT dim is sharded over
    the ``sequence`` mesh axis ("cache_seq" rule), each device computes
    partial attention over its context shard with online-softmax stats
    (m, l, unnormalized o), and the shards merge with one pmax + two psums
    — the standard log-sum-exp combine, so the result equals the unsharded
    softmax up to float addition order. Context capacity then scales with
    the mesh instead of one chip's HBM, and per-device attention reads
    drop by the shard factor. int8 KV composes: scales are per-position
    and shard with their positions."""
    from ditl_tpu.parallel.sharding import logical_to_spec

    seq_axes = rules.get("cache_seq")
    seq_axes = (seq_axes,) if isinstance(seq_axes, str) else tuple(seq_axes)
    scale = q.shape[-1] ** -0.5

    def local(q_, k_, v_, mask_, ks_, vs_):
        b, s_q, h, d = q_.shape
        kh = k_.shape[2]
        g = h // kh
        qg = q_.reshape(b, s_q, kh, g, d)
        kk, vv = k_, v_
        if ks_ is not None:
            kk = kk.astype(q_.dtype)
            vv = vv.astype(q_.dtype)
        scores = jnp.einsum(
            "bqkgd,bskd->bkgqs", qg, kk, preferred_element_type=jnp.float32
        ) * scale
        if ks_ is not None:
            scores = scores * jnp.moveaxis(ks_, 1, 2)[:, :, None, None, :]
        scores = jnp.where(mask_[:, None, None], scores, NEG_INF)
        m = jnp.max(scores, axis=-1)  # (B, K, G, Sq)
        p = jnp.exp(scores - m[..., None])
        l = jnp.sum(p, axis=-1)
        if vs_ is not None:
            p = p * jnp.moveaxis(vs_, 1, 2)[:, :, None, None, :]
        o = jnp.einsum("bkgqs,bskd->bqkgd", p.astype(vv.dtype), vv)
        # log-sum-exp merge across context shards
        m_g = m
        for ax in seq_axes:
            m_g = jax.lax.pmax(m_g, ax)
        corr = jnp.exp(m - m_g)  # (B, K, G, Sq)
        l_g = jax.lax.psum(l * corr, seq_axes)
        o_g = jax.lax.psum(
            o.astype(jnp.float32)
            * jnp.transpose(corr, (0, 3, 1, 2))[..., None],
            seq_axes,
        )
        l_t = jnp.transpose(jnp.maximum(l_g, 1e-30), (0, 3, 1, 2))[..., None]
        out = o_g / l_t  # (B, Sq, K, G, D)
        return out.reshape(b, s_q, h, d).astype(q_.dtype)

    q_spec = logical_to_spec(("batch", None, "act_heads", None), rules)
    kv_spec = logical_to_spec(("batch", "cache_seq", "act_kv_heads", None), rules)
    mask_spec = logical_to_spec(("batch", None, "cache_seq"), rules)
    scale_spec = logical_to_spec(("batch", "cache_seq", "act_kv_heads"), rules)

    if k_scale is None:
        def local4(q_, k_, v_, mask_):
            return local(q_, k_, v_, mask_, None, None)

        return jax.shard_map(
            local4, mesh=mesh,
            in_specs=(q_spec, kv_spec, kv_spec, mask_spec),
            out_specs=q_spec, check_vma=False,
        )(q, k, v, mask)
    return jax.shard_map(
        local, mesh=mesh,
        in_specs=(q_spec, kv_spec, kv_spec, mask_spec, scale_spec, scale_spec),
        out_specs=q_spec, check_vma=False,
    )(q, k, v, mask, k_scale, v_scale)


@jax.named_scope("attn_core")
def dot_product_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = True,
    segment_ids: jax.Array | None = None,
    mask: jax.Array | None = None,
    impl: str = "xla",
    mesh=None,
    rules=None,
    k_scale: jax.Array | None = None,
    v_scale: jax.Array | None = None,
    block_sizes: tuple[int, int, int, int] | None = None,
    window: int | None = None,
) -> jax.Array:
    """Grouped-query attention. ``window`` (static; the xla and flash paths,
    causal, no explicit mask, no mesh): a window attention layer, query i sees
    key j iff ``0 <= i - j < window``. ``segment_ids`` (B, S) int32 restricts
    attention to tokens of the same segment (sequence packing / padding:
    give pad tokens a segment id of -1-ish sentinel distinct from real ones).
    ``mask`` is an explicit (B, Sq, Skv) boolean mask (True = attend), used by
    the KV-cache decode path where validity is per-slot, not causal.

    ``rules`` is the logical-axis table (parallel/sharding.py) used to derive
    shard_map specs for the flash and ring paths — the same single source of
    truth the rest of the model uses for its sharding constraints.

    ``block_sizes`` is ``(block_q, block_kv, block_q_bwd, block_kv_bwd)`` for
    the flash kernels; zeros mean kernel defaults (ModelConfig.flash_block_*)."""
    if q.shape[2] % k.shape[2]:
        raise ValueError(f"q heads {q.shape[2]} not divisible by kv heads {k.shape[2]}")
    if k_scale is not None and mask is None:
        raise ValueError("quantized K/V (k_scale/v_scale) require the mask path")
    if window is not None and (mask is not None or not causal or mesh is not None
                               or impl not in ("xla", "flash")):
        raise ValueError(
            "a window is a clause of the causal mask on the xla and flash paths, on "
            "one chip: with an explicit mask put it into the mask")
    if mask is not None:
        # Explicit-mask (decode) path: bandwidth-bound, XLA fuses it fine; the
        # flash/ring kernels are for long training chunks, not 1-token queries.
        if mesh is not None:
            from ditl_tpu.parallel.sharding import (
                DEFAULT_RULES,
                mesh_axes_size,
                seq_shards,
            )

            r = rules if rules is not None else DEFAULT_RULES
            seq_n = seq_shards(mesh, r)
            dp = mesh_axes_size(mesh, r.get("batch"))
            tp = mesh_axes_size(mesh, r.get("act_kv_heads"))
            if (seq_n > 1 and k.shape[1] % seq_n == 0
                    and q.shape[0] % dp == 0 and k.shape[2] % tp == 0
                    and q.shape[2] % max(tp, 1) == 0):
                # Context (KV sequence) sharded over the mesh:
                # flash-decoding-style partial-softmax merge over ICI.
                return _seq_sharded_decode(
                    q, k, v, mask, mesh=mesh, rules=r,
                    k_scale=k_scale, v_scale=v_scale,
                )
        return _xla_attention(
            q, k, v, causal=causal, segment_ids=segment_ids, mask=mask,
            k_scale=k_scale, v_scale=v_scale,
        )
    if impl == "xla":
        return _xla_attention(q, k, v, causal=causal, segment_ids=segment_ids,
                              window=window)
    if impl == "ring":
        from ditl_tpu.ops.ring_attention import ring_attention

        return ring_attention(
            q, k, v, causal=causal, segment_ids=segment_ids, mesh=mesh, rules=rules
        )
    if impl == "ulysses":
        from ditl_tpu.ops.ulysses import ulysses_attention

        return ulysses_attention(
            q, k, v, causal=causal, segment_ids=segment_ids, mesh=mesh, rules=rules
        )
    if impl == "flash":
        from ditl_tpu.ops import flash_attention as fa
        from ditl_tpu.parallel.sharding import DEFAULT_RULES, logical_to_spec

        rules = rules if rules is not None else DEFAULT_RULES
        if mesh is not None and _mesh_axes_size(mesh, rules.get("seq")) > 1:
            # Sequence-sharded activations: ring attention IS the flash path
            # for context parallelism (blockwise kernel distributed over the
            # ring instead of the Pallas grid).
            from ditl_tpu.ops.ring_attention import ring_attention

            return ring_attention(
                q, k, v, causal=causal, segment_ids=segment_ids, mesh=mesh,
                rules=rules,
            )
        bq, bkv, bqb, bkvb = block_sizes or (0, 0, 0, 0)
        bq, bkv = bq or 512, bkv or 512
        if not (fa.supports(q.shape[1], k.shape[1], q.shape[3], bq, bkv, v.shape[3])
                and fa.supports(q.shape[1], k.shape[1], q.shape[3],
                                bqb or bq, bkvb or bkv, v.shape[3])):
            # Shapes the kernel can't tile: an error on the TPU; in
            # interpret mode (tiny tests, odd seq lens) XLA.
            refuse_on_tpu(
                "attention_impl='flash'",
                f"cannot tile Sq={q.shape[1]} Skv={k.shape[1]} "
                f"D={q.shape[3]} Dv={v.shape[3]} (block_q={bq}, block_kv={bkv}, "
                f"bwd {bqb or bq}/{bkvb or bkv})",
            )
            return _xla_attention(q, k, v, causal=causal, segment_ids=segment_ids,
                                  window=window)
        if mesh is None:
            return fa.flash_attention(
                q, k, v, causal=causal, segment_ids=segment_ids,
                block_q=bq, block_kv=bkv, block_q_bwd=bqb, block_kv_bwd=bkvb,
                window=window,
            )
        # Pallas calls carry no GSPMD partitioning rules — under pjit they
        # must be explicitly mapped over the mesh. Batch splits over the
        # batch axes and heads over the heads axis; attention is independent
        # along both, so no collectives are induced.
        dp = _mesh_axes_size(mesh, rules.get("batch"))
        tp = _mesh_axes_size(mesh, rules.get("act_heads"))
        if q.shape[0] % dp or q.shape[2] % tp or k.shape[2] % tp:
            # Mesh doesn't divide batch/heads: the shard_map would fail at
            # trace time. An error on the TPU; in interpret mode the
            # GSPMD-partitionable XLA path.
            refuse_on_tpu(
                "attention_impl='flash'",
                f"mesh batch axes ({dp}) / heads axis ({tp}) do not divide "
                f"batch={q.shape[0]} heads={q.shape[2]} "
                f"kv_heads={k.shape[2]}",
            )
            return _xla_attention(q, k, v, causal=causal, segment_ids=segment_ids)
        qkv_spec = logical_to_spec(("batch", None, "act_heads", None), rules)
        args = [q, k, v]
        in_specs = [qkv_spec, qkv_spec, qkv_spec]
        if segment_ids is not None:
            args.append(segment_ids)
            in_specs.append(logical_to_spec(("batch", None), rules))

        def local(q_, k_, v_, seg_=None):
            return fa.flash_attention(
                q_, k_, v_, causal=causal, segment_ids=seg_,
                block_q=bq, block_kv=bkv, block_q_bwd=bqb, block_kv_bwd=bkvb,
            )

        return jax.shard_map(
            local,
            mesh=mesh,
            in_specs=tuple(in_specs),
            out_specs=qkv_spec,
            check_vma=False,
        )(*args)
    raise ValueError(f"unknown attention impl {impl!r}")
