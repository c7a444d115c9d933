"""Paged decode attention over a LATENT page pool (multi-head latent
attention in its absorbed form, models/mla.py): one stored entry a token
serves every query head as key (all of it) and as value (its first
``value_width`` values).

    scores[b, h, t] = scale * q[b, h] . entry[b, t]          (Dl wide)
    out[b, h]       = softmax_t(scores) @ entry[b, :, :value_width]

``q`` is ``[q_nope Wkvb_k | rope(q_rope) | 0]`` and an entry ``[c | rope(kr)
| 0]``, both padded to whole lanes; the zeros add nothing to a score. Tokens
``[0, starts)`` of a slot live in its pages, ``[starts, lengths)`` in the
tick's tail (the deferred flush of ops/paged_attention.py).

- ``mla_paged_attention_xla``: gather the pages, then masked attention; the
  oracle of the tests.
- ``mla_paged_attention`` (Pallas/Mosaic, kernel name
  ``mla_paged_attention``): a one-axis grid over ``paged_attention``'s work
  list (``decode_steps``: each live row's flushed pages, then its tail; its
  traced count is the grid's length), the lists and the page table on the
  scalar-prefetch channel; each page is fetched ONCE and used as key and as
  value, bfloat16 dots with float32 accumulation, online softmax over a
  row's pages, the tail as the row's last step. A row with ``lengths == 0``
  comes out exactly zero, listed or not (selected behind the call: a row
  the walk never visits is memory nobody wrote).
  At LongCat-Flash's widths 64 heads x 2 x (576 + 512) = 139,264 operations
  a context token over 1,280 stored bytes (benchmarks/mla_counts.py): 109
  FLOP/B against the v5e's 240, memory-bound with little to spare.

The pool is whatever the table addresses: the engine hands over every
sublayer's pages as one pool ``(L * 2 * n_pages, ps, Dl)`` and a table offset
to the sublayer's own (models/llama.py ``forward``), so no sublayer's pool is
sliced out in front of the custom call.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ditl_tpu.ops.attention import NEG_INF
from ditl_tpu.ops.backend import interpret_default
from ditl_tpu.ops.flash_attention import NUM_LANES, _lane_tile
from ditl_tpu.ops.paged_attention import decode_steps, walk_length, walk_maps, zero_dead_rows

__all__ = ["mla_paged_attention", "mla_paged_attention_xla"]


def mla_paged_attention_xla(
    q: jax.Array,  # (B, H, Dl)
    pool: jax.Array,  # (P, ps, Dl)
    page_table: jax.Array,  # (B, maxp) int32
    lengths: jax.Array,  # (B,) int32; 0 = dead slot -> zeros
    *,
    tail: jax.Array,  # (B, T, Dl)
    starts: jax.Array,  # (B,) tokens resident in pages
    value_width: int,
    scale: float,
) -> jax.Array:
    b, maxp = page_table.shape
    ps = pool.shape[1]
    ctx = pool[page_table].reshape(b, maxp * ps, pool.shape[-1])
    t = tail.shape[1]
    entries = jnp.concatenate([ctx, tail.astype(ctx.dtype)], axis=1)
    valid = jnp.concatenate([
        jnp.arange(maxp * ps, dtype=jnp.int32)[None, :]
        < jnp.minimum(starts, lengths)[:, None],
        starts[:, None] + jnp.arange(t, dtype=jnp.int32)[None, :] < lengths[:, None],
    ], axis=1)  # (B, S)
    scores = jnp.einsum("bhd,bsd->bhs", q, entries,
                        preferred_element_type=jnp.float32) * scale
    scores = jnp.where(valid[:, None, :], scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    probs = jnp.where(lengths[:, None, None] > 0, probs, 0.0)
    return jnp.einsum("bhs,bsd->bhd", probs.astype(entries.dtype),
                      entries[..., :value_width]).astype(q.dtype)


def _accumulate(q_ref, kv_ref, m_scr, l_scr, acc_scr, *, scale, base, limit):
    """One block of entries, columns ``[base, base + width)``, masked to
    ``< limit``, into the online softmax of all heads."""
    width = kv_ref.shape[1]
    heads = q_ref.shape[1]
    vw = acc_scr.shape[-1]
    kv = kv_ref[0]  # (width, Dl), read once: key and value
    s = jax.lax.dot_general(
        q_ref[0], kv, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    ) * scale  # (H, width)
    cols = base + jax.lax.broadcasted_iota(jnp.int32, (heads, width), 1)
    s = jnp.where(cols < limit, s, NEG_INF)
    m_prev, l_prev = m_scr[...], l_scr[...]  # (H, NUM_LANES) lane-replicated
    m_next = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
    alpha = jnp.exp(m_prev - m_next)
    p = jnp.exp(s - _lane_tile(m_next, width))
    l_scr[...] = alpha * l_prev + jnp.sum(p, axis=1, keepdims=True)
    m_scr[...] = m_next
    pv = jax.lax.dot_general(
        p.astype(kv.dtype), kv[:, :vw], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )  # (H, vw)
    acc_scr[...] = acc_scr[...] * _lane_tile(alpha, vw) + pv


def _mla_kernel(rows_ref, ks_ref, table_ref, lengths_ref, starts_ref, q_ref, pool_ref,
                tail_ref, o_ref, m_scr, l_scr, acc_scr, *, scale: float, page_size: int):
    del table_ref  # the index maps' alone
    i = pl.program_id(0)
    b, p = rows_ref[i], ks_ref[i]  # step i of the work list: row b's p-th

    @pl.when(p == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    length, start = lengths_ref[b], starts_ref[b]
    n_pages = pl.cdiv(start, page_size)  # the row's page steps; then its tail
    page_limit = jnp.minimum(start, length)
    base = p * page_size

    @pl.when((p < n_pages) & (base < page_limit))
    def _pages():
        _accumulate(q_ref, pool_ref, m_scr, l_scr, acc_scr, scale=scale, base=base,
                    limit=page_limit)

    @pl.when((p == n_pages) & (length > start))
    def _tail():
        _accumulate(q_ref, tail_ref, m_scr, l_scr, acc_scr, scale=scale, base=start,
                    limit=length)

    @pl.when(p == n_pages)
    def _finalize():
        l = l_scr[...]
        l_safe = jnp.where(l == 0.0, 1.0, l)  # a dead slot: zeros, not NaN
        o_ref[0] = (acc_scr[...] / _lane_tile(l_safe, acc_scr.shape[-1])).astype(o_ref.dtype)


def mla_paged_attention(
    q: jax.Array,  # (B, H, Dl)
    pool: jax.Array,  # (P, ps, Dl)
    page_table: jax.Array,  # (B, maxp) int32
    lengths: jax.Array,  # (B,) int32
    *,
    tail: jax.Array,  # (B, T, Dl): the tick's unflushed entries
    starts: jax.Array,  # (B,) tokens resident in pages
    value_width: int,
    scale: float,
    steps: dict[str, jax.Array] | None = None,  # ``decode_steps``' list
    interpret: bool | None = None,
) -> jax.Array:
    """(B, H, value_width): see the module docstring. ``steps`` has to name
    every row with ``lengths > 0``; left out, it is built here from
    ``lengths > 0``. Off the TPU, unless ``interpret`` is asked for, this is
    ``mla_paged_attention_xla``: the interpreted kernel walks its steps
    through a loop that carries the whole pool (seconds a decode tick on the
    CPU at test sizes, against 0.15 s), so the engine's CPU runs take the
    gather and ``tests/test_longcat.py`` holds the interpreted kernel to it."""
    if interpret is None:
        if interpret_default():
            return mla_paged_attention_xla(
                q, pool, page_table, lengths, tail=tail, starts=starts,
                value_width=value_width, scale=scale)
        interpret = False
    b, heads, dl = q.shape
    _, ps, _ = pool.shape
    maxp = page_table.shape[1]
    tail = tail.astype(pool.dtype)

    if steps is None:
        steps = decode_steps(starts, lengths > 0, page_size=ps, max_pages=maxp)
    slot_map, (page_map,) = walk_maps(ps, maxp, trailing=2)  # one page a step
    out = pl.pallas_call(
        functools.partial(_mla_kernel, scale=scale, page_size=ps),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=5,
            grid=(walk_length(steps),),
            in_specs=[
                pl.BlockSpec((1, heads, dl), slot_map),
                pl.BlockSpec((1, ps, dl), page_map),
                pl.BlockSpec((1, tail.shape[1], dl), slot_map),
            ],
            out_specs=pl.BlockSpec((1, heads, value_width), slot_map),
            scratch_shapes=[
                pltpu.VMEM((heads, NUM_LANES), jnp.float32),  # m
                pltpu.VMEM((heads, NUM_LANES), jnp.float32),  # l
                pltpu.VMEM((heads, value_width), jnp.float32),  # acc
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((b, heads, value_width), q.dtype),
        compiler_params=pltpu.CompilerParams(dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name="mla_paged_attention",
    )(steps["rows"], steps["ks"], page_table, lengths, starts, q.astype(pool.dtype), pool,
      tail)
    return zero_dead_rows(out, lengths)
