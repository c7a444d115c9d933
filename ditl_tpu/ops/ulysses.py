"""Ulysses (all-to-all) sequence parallelism: the second context-parallel path.

The reference has no long-context machinery at all (SURVEY.md §5
'long-context' — sequence length is never even a variable there). This module
complements ring attention (ops/ring_attention.py) with the DeepSpeed-Ulysses
scheme (Jacobs et al.; see PAPERS.md): instead of rotating KV chunks around a
ring, two ``all_to_all`` collectives re-shard the activations from
sequence-sharded to head-sharded and back:

    (B, S/n, H, D) --all_to_all--> (B, S, H/n, D)   # full sequence, 1/n heads
        -> exact local attention (Pallas flash kernel when shapes allow)
    (B, S, H/n, D) --all_to_all--> (B, S/n, H, D)

Trade-off vs ring: Ulysses moves O(S·H·D/n) bytes in two dense all-to-alls
(ICI-friendly, overlappable, and the attention itself is a single unsplit
kernel — better MXU utilization), while ring moves the KV pair n-1 times but
never needs the head dim divisible by n. Hence the dispatch rule here: heads
and KV heads must both divide by the sequence-axis size or we fall back to
ring attention, which handles every GQA layout.

Semantics match ``ops.attention._xla_attention`` exactly (GQA, causal,
segment-id packing masks) — tested against it on the 8-device CPU mesh,
including gradients through both all-to-alls.
"""

from __future__ import annotations

import jax

__all__ = ["ulysses_attention"]


def _local_attention(q, k, v, *, causal, segment_ids):
    """Full-sequence attention on this device's head slice: Pallas flash
    kernel when the shapes tile, XLA einsum otherwise (tiny tests, odd lens)."""
    from ditl_tpu.ops import flash_attention as fa
    from ditl_tpu.ops.attention import _xla_attention

    if fa.supports(q.shape[1], k.shape[1], q.shape[3]):
        return fa.flash_attention(q, k, v, causal=causal, segment_ids=segment_ids)
    return _xla_attention(q, k, v, causal=causal, segment_ids=segment_ids)


def ulysses_attention(
    q: jax.Array,  # (B, S, H, D) global
    k: jax.Array,  # (B, S, K, D)
    v: jax.Array,
    *,
    causal: bool = True,
    segment_ids: jax.Array | None = None,
    mesh: jax.sharding.Mesh | None = None,
    rules=None,
) -> jax.Array:
    """Exact attention with the sequence dim sharded over ``rules['seq']``,
    implemented with all-to-all head/sequence transposition.

    Falls back to (a) plain XLA attention when there is no mesh or the
    sequence axis has size 1, (b) ring attention when the per-device head
    counts don't divide by the sequence-axis size (GQA with few KV heads).
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

    sp = mesh.shape[axis_name]
    tp = _mesh_axes_size(mesh, rules.get("act_heads"))
    h_local, kv_local = q.shape[2] // tp, k.shape[2] // tp
    if (
        q.shape[2] % tp
        or k.shape[2] % tp
        or not kv_local
        or h_local % sp
        or kv_local % sp
        or q.shape[1] % sp
        or q.shape[0] % _mesh_axes_size(mesh, rules.get("batch"))
    ):
        # Head slice per device would be fractional (or batch/seq don't
        # divide): ring attention handles every layout, at more KV traffic.
        from ditl_tpu.ops.ring_attention import ring_attention

        return ring_attention(
            q, k, v, causal=causal, segment_ids=segment_ids, mesh=mesh, rules=rules
        )

    qkv_spec = logical_to_spec(("batch", "seq", "act_heads", None), rules)
    args = [q, k, v]
    in_specs = [qkv_spec, qkv_spec, qkv_spec]
    if segment_ids is not None:
        args.append(segment_ids)
        in_specs.append(logical_to_spec(("batch", "seq"), rules))

    def local(q_, k_, v_, seg_=None):
        # Sequence-sharded -> head-sharded: each device receives every other
        # device's sequence chunk for its 1/sp slice of the heads. Chunks
        # concatenate in ring order == contiguous global order, so global
        # positions are simply 0..S-1 and the causal mask is the plain tril.
        to_heads = lambda x: jax.lax.all_to_all(
            x, axis_name, split_axis=2, concat_axis=1, tiled=True
        )
        q_g, k_g, v_g = to_heads(q_), to_heads(k_), to_heads(v_)
        seg_g = (
            jax.lax.all_gather(seg_, axis_name, axis=1, tiled=True)
            if seg_ is not None
            else None
        )
        out = _local_attention(q_g, k_g, v_g, causal=causal, segment_ids=seg_g)
        # Head-sharded -> sequence-sharded: the inverse transposition.
        return jax.lax.all_to_all(
            out, axis_name, split_axis=1, concat_axis=2, tiled=True
        )

    return jax.shard_map(
        local,
        mesh=mesh,
        in_specs=tuple(in_specs),
        out_specs=qkv_spec,
        check_vma=False,
    )(*args)
