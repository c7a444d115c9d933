"""Ring attention: exact causal attention over a sequence-sharded mesh axis.

The reference has no long-context machinery at all — sequence length is never
even a variable there (SURVEY.md §5 'long-context'; prompts go unchunked to an
HTTP API, ref ``src/distributed_inference.py:65,69``). This module is the
TPU-native long-context path: the sequence dimension is sharded over the
``sequence`` mesh axis, each device holds S/n query and KV chunks, and KV
chunks rotate around the ring via ``lax.ppermute`` (XLA lowers neighbor
permutes to ICI sends) while an online-softmax accumulator merges partial
attention results. HBM per device is O(S/n · S/n) for the score tile and
O(S/n · D) for the output — sequences n× longer than one chip's HBM fit.

Semantics match ``ops.attention._xla_attention`` exactly (GQA, causal,
segment-id packing masks) — tested against it on the 8-device CPU mesh.
With causal masking, chunk pairs strictly above the diagonal are skipped with
``lax.cond`` (the ppermute still runs — the ring must keep rotating — but the
score/pv einsums are not computed), saving ~half the attention FLOPs.

The algorithm is blockwise-parallel exact attention (Liu et al., "Ring
Attention with Blockwise Transformers"; see PAPERS.md) — log-sum-exp merging
identical to the flash kernel's, with the block loop distributed over chips
instead of over the Pallas grid.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from ditl_tpu.ops.attention import NEG_INF

__all__ = ["ring_attention"]


def _masked_scores(
    q: jax.Array,  # (B, Sq, K, G, D) f32, pre-scaled
    k: jax.Array,  # (B, Skv, K, D)
    q_pos: jax.Array,  # (Sq,) global positions of the query chunk
    kv_pos: jax.Array,  # (Skv,) global positions of the kv chunk
    q_seg: jax.Array | None,  # (B, Sq)
    kv_seg: jax.Array | None,  # (B, Skv)
    *,
    causal: bool,
) -> jax.Array:
    """Masked score tile (B, K, G, Sq, Skv) in f32 for one chunk pair."""
    s = jnp.einsum(
        "bqkgd,bskd->bkgqs", q, k.astype(jnp.float32),
        preferred_element_type=jnp.float32,
    )
    if causal:
        mask = q_pos[:, None] >= kv_pos[None, :]  # (Sq, Skv)
        s = jnp.where(mask[None, None, None], s, NEG_INF)
    if q_seg is not None:
        seg = q_seg[:, :, None] == kv_seg[:, None, :]  # (B, Sq, Skv)
        s = jnp.where(seg[:, None, None], s, NEG_INF)
    return s


def _ring_body(axis_name: str, causal: bool, n: int, carry, _):
    (k_cur, v_cur, kv_seg_cur, src, m, l, acc, q, q_pos, q_seg) = carry
    s_local = k_cur.shape[1]
    my = jax.lax.axis_index(axis_name)

    def merge(operand):
        k_c, v_c, kv_seg_c, src_, m_, l_, acc_ = operand
        kv_pos = src_ * s_local + jnp.arange(s_local, dtype=jnp.int32)
        s = _masked_scores(
            q, k_c, q_pos, kv_pos, q_seg, kv_seg_c, causal=causal
        )  # (B, K, G, Sq, Skv)
        m_chunk = jnp.max(s, axis=-1)  # (B, K, G, Sq)
        m_new = jnp.maximum(m_, m_chunk)
        # Fully-masked rows leave m at NEG_INF; exp(NEG_INF - NEG_INF) would
        # be exp(0)=1 on garbage rows — clamp the shift so they stay zero.
        shift = jnp.where(m_new == NEG_INF, 0.0, m_new)
        p = jnp.exp(s - shift[..., None])
        p = jnp.where(s == NEG_INF, 0.0, p)
        alpha = jnp.exp(jnp.where(m_ == NEG_INF, NEG_INF, m_ - shift))
        l_ = alpha * l_ + jnp.sum(p, axis=-1)
        pv = jnp.einsum(
            "bkgqs,bskd->bqkgd", p, v_c.astype(jnp.float32),
            preferred_element_type=jnp.float32,
        )
        acc_ = acc_ * jnp.moveaxis(alpha, -1, 1)[..., None] + pv
        return m_new, l_, acc_

    operand = (k_cur, v_cur, kv_seg_cur, src, m, l, acc)
    if causal:
        # Chunks are contiguous position ranges, so a KV chunk from a later
        # device (src > my) is entirely in the future: skip its compute.
        m, l, acc = jax.lax.cond(
            src <= my, merge, lambda op: (op[4], op[5], op[6]), operand
        )
    else:
        m, l, acc = merge(operand)

    # Rotate: send our current KV chunk to the next device in the ring; after
    # n-1 rotations every device has seen every chunk.
    perm = [(i, (i + 1) % n) for i in range(n)]
    k_cur = jax.lax.ppermute(k_cur, axis_name, perm)
    v_cur = jax.lax.ppermute(v_cur, axis_name, perm)
    if kv_seg_cur is not None:
        kv_seg_cur = jax.lax.ppermute(kv_seg_cur, axis_name, perm)
    src = (src - 1) % n
    return (k_cur, v_cur, kv_seg_cur, src, m, l, acc, q, q_pos, q_seg), None


def _ring_attention_local(
    q: jax.Array,  # (B, S_local, H, D) — this device's query chunk
    k: jax.Array,  # (B, S_local, K, D)
    v: jax.Array,
    segment_ids: jax.Array | None,  # (B, S_local)
    *,
    axis_name: str,
    causal: bool,
) -> jax.Array:
    b, s_local, h, d = q.shape
    kv_heads = k.shape[2]
    groups = h // kv_heads
    n = jax.lax.axis_size(axis_name)
    my = jax.lax.axis_index(axis_name)

    qg = (q.astype(jnp.float32) * d**-0.5).reshape(b, s_local, kv_heads, groups, d)
    q_pos = my * s_local + jnp.arange(s_local, dtype=jnp.int32)

    m = jnp.full((b, kv_heads, groups, s_local), NEG_INF, jnp.float32)
    l = jnp.zeros((b, kv_heads, groups, s_local), jnp.float32)
    acc = jnp.zeros((b, s_local, kv_heads, groups, d), jnp.float32)

    carry = (k, v, segment_ids, my, m, l, acc, qg, q_pos, segment_ids)
    body = functools.partial(_ring_body, axis_name, causal, n)
    carry, _ = jax.lax.scan(body, carry, None, length=n)
    _, _, _, _, m, l, acc, _, _, _ = carry

    l_safe = jnp.where(l == 0.0, 1.0, l)  # fully-masked rows -> 0 not NaN
    out = acc / jnp.moveaxis(l_safe, -1, 1)[..., None]
    return out.reshape(b, s_local, h, d).astype(q.dtype)


def ring_attention(
    q: jax.Array,  # (B, S, H, D) global
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = True,
    segment_ids: jax.Array | None = None,
    mesh: jax.sharding.Mesh | None = None,
    rules=None,
) -> jax.Array:
    """Exact attention with the sequence dimension sharded over the mesh axis
    named by ``rules['seq']`` (default: ``sequence``).

    Specs are derived from the same logical-axis rule table the rest of the
    model uses (parallel/sharding.py), so batch/head layouts stay consistent
    with the surrounding sharding constraints. Falls back to the XLA
    implementation when there is no mesh or the sequence axis has size 1.
    """
    from ditl_tpu.ops.attention import _mesh_axes_size, _xla_attention
    from ditl_tpu.parallel.sharding import DEFAULT_RULES, logical_to_spec

    rules = rules if rules is not None else DEFAULT_RULES
    axis_name = rules.get("seq")
    if (
        mesh is None
        or not isinstance(axis_name, str)
        or axis_name not in mesh.shape
        or mesh.shape[axis_name] == 1
    ):
        return _xla_attention(q, k, v, causal=causal, segment_ids=segment_ids)
    dp = _mesh_axes_size(mesh, rules.get("batch"))
    tp = _mesh_axes_size(mesh, rules.get("act_heads"))
    if (
        q.shape[0] % dp
        or q.shape[2] % tp
        or k.shape[2] % tp
        or q.shape[1] % mesh.shape[axis_name]
    ):
        # Batch/heads/seq don't divide the mesh: shard_map would fail at trace
        # time. XLA's GSPMD attention partitions any layout (at more comms).
        return _xla_attention(q, k, v, causal=causal, segment_ids=segment_ids)

    qkv_spec = logical_to_spec(("batch", "seq", "act_heads", None), rules)
    args = [q, k, v]
    in_specs = [qkv_spec, qkv_spec, qkv_spec]
    if segment_ids is not None:
        args.append(segment_ids)
        in_specs.append(logical_to_spec(("batch", "seq"), rules))

    def local(q_, k_, v_, seg_=None):
        return _ring_attention_local(
            q_, k_, v_, seg_, axis_name=axis_name, causal=causal
        )

    return jax.shard_map(
        local,
        mesh=mesh,
        in_specs=tuple(in_specs),
        out_specs=qkv_spec,
        check_vma=False,
    )(*args)
