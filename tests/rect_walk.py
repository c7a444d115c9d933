"""The decode attention kernels as they walked before PR 42, kept as the
tests' oracle: a grid of ``(B, maxp + 1)``, every slot by every page-table
position and the tail, with the step bodies the kernels still use
(``_accumulate_block`` / ``_finalize_out``, ``_accumulate``). The work-list
kernels have to give a live row these numbers to the bit."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ditl_tpu.ops import mla_attention as mla
from ditl_tpu.ops import paged_attention as pa
from ditl_tpu.ops.attention import NEG_INF
from ditl_tpu.ops.flash_attention import NUM_LANES, _lane_tile


def _init(m_scr, l_scr, acc_scr):
    m_scr[...] = jnp.full_like(m_scr, NEG_INF)
    l_scr[...] = jnp.zeros_like(l_scr)
    acc_scr[...] = jnp.zeros_like(acc_scr)


def _maps(maxp, ps, trailing):
    zeros = (0,) * trailing

    def page_map(ib, ip, tab, lens, st):
        pi = jnp.minimum(ip, maxp - 1)
        live = (ip < maxp) & (pi * ps < jnp.minimum(st[ib], lens[ib]))
        return (jnp.where(live, tab[ib, pi], 0), *zeros)

    def slot_map(ib, ip, tab, lens, st):
        return (ib, *zeros)

    return slot_map, page_map


def _paged_kernel(table_ref, lengths_ref, starts_ref, q_ref, k_ref, v_ref, *rest,
                  scale, page_size, n_pages, quantized, q_groups):
    if quantized:
        ks_ref, vs_ref, tk_ref, tv_ref, o_ref, m_scr, l_scr, acc_scr = rest
        scales = {"ks_refs": (ks_ref,), "vs_refs": (vs_ref,)}
    else:
        scales = {}
        tk_ref, tv_ref, o_ref, m_scr, l_scr, acc_scr = rest
    b, p = pl.program_id(0), pl.program_id(1)
    pl.when(p == 0)(lambda: _init(m_scr, l_scr, acc_scr))
    length, start = lengths_ref[b], starts_ref[b]
    page_limit = jnp.minimum(start, length)
    base = p * page_size

    @pl.when((p < n_pages) & (base < page_limit))
    def _pages():
        pa._accumulate_block(
            q_ref, (k_ref,), (v_ref,), m_scr, l_scr, acc_scr, scale=scale, base=base,
            width=page_size, limit=page_limit, **scales)

    @pl.when((p == n_pages) & (length > start))
    def _tail():
        pa._accumulate_block(
            q_ref, (tk_ref,), (tv_ref,), m_scr, l_scr, acc_scr, scale=scale, base=start,
            width=tk_ref.shape[2], limit=length, q_groups=q_groups)

    pl.when(p == n_pages)(lambda: pa._finalize_out(o_ref, m_scr, l_scr, acc_scr))


def paged_attention_rect(q, k_pages, v_pages, page_table, lengths, *, tail_k, tail_v, starts,
                         k_scale=None, v_scale=None):
    """``paged_attention``'s tail path on the rectangle, interpreted."""
    multi_q = q.ndim == 4
    b, nq, h, d = q.shape if multi_q else (q.shape[0], 1, *q.shape[1:])
    _, kv_heads, ps, _ = k_pages.shape
    maxp = page_table.shape[1]
    groups = h // kv_heads
    qg_rows = nq * groups
    qg = (q.reshape(b, nq, kv_heads, groups, d).transpose(0, 2, 1, 3, 4)
          .reshape(b, kv_heads, qg_rows, d))
    slot_map, page_map = _maps(maxp, ps, 3)
    quantized = k_scale is not None
    page = pl.BlockSpec((1, kv_heads, ps, d), page_map)
    in_specs = [pl.BlockSpec((1, kv_heads, qg_rows, d), slot_map), page, page]
    args = [page_table, lengths, starts, qg, k_pages, v_pages]
    if quantized:
        in_specs += [pl.BlockSpec((1, kv_heads, 1, ps), page_map)] * 2
        args += [k_scale, v_scale]
    in_specs += [pl.BlockSpec((1, kv_heads, tail_k.shape[2], d), slot_map)] * 2
    out = pl.pallas_call(
        functools.partial(_paged_kernel, scale=d ** -0.5, page_size=ps, n_pages=maxp,
                          quantized=quantized, q_groups=groups if nq > 1 else None),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3, grid=(b, maxp + 1), in_specs=in_specs,
            out_specs=pl.BlockSpec((1, kv_heads, qg_rows, d), slot_map),
            scratch_shapes=pa._scratch(kv_heads, qg_rows, d)),
        out_shape=jax.ShapeDtypeStruct((b, kv_heads, qg_rows, d), q.dtype),
        interpret=True,
    )(*args, tail_k, tail_v)
    out = out.reshape(b, kv_heads, nq, groups, d).transpose(0, 2, 1, 3, 4).reshape(b, nq, h, d)
    return out if multi_q else out[:, 0]


def _mla_kernel(table_ref, lengths_ref, starts_ref, q_ref, pool_ref, tail_ref, o_ref,
                m_scr, l_scr, acc_scr, *, scale, page_size, n_pages):
    b, p = pl.program_id(0), pl.program_id(1)
    pl.when(p == 0)(lambda: _init(m_scr, l_scr, acc_scr))
    length, start = lengths_ref[b], starts_ref[b]
    page_limit = jnp.minimum(start, length)
    base = p * page_size

    @pl.when((p < n_pages) & (base < page_limit))
    def _pages():
        mla._accumulate(q_ref, pool_ref, m_scr, l_scr, acc_scr, scale=scale, base=base,
                        limit=page_limit)

    @pl.when((p == n_pages) & (length > start))
    def _tail():
        mla._accumulate(q_ref, tail_ref, m_scr, l_scr, acc_scr, scale=scale, base=start,
                        limit=length)

    @pl.when(p == n_pages)
    def _finalize():
        l = l_scr[...]
        l_safe = jnp.where(l == 0.0, 1.0, l)
        o_ref[0] = (acc_scr[...] / _lane_tile(l_safe, acc_scr.shape[-1])).astype(o_ref.dtype)


def mla_paged_attention_rect(q, pool, page_table, lengths, *, tail, starts, value_width, scale):
    """``mla_paged_attention`` on the rectangle, interpreted."""
    b, heads, dl = q.shape
    ps, maxp = pool.shape[1], page_table.shape[1]
    slot_map, page_map = _maps(maxp, ps, 2)
    return pl.pallas_call(
        functools.partial(_mla_kernel, scale=scale, page_size=ps, n_pages=maxp),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3, grid=(b, maxp + 1),
            in_specs=[pl.BlockSpec((1, heads, dl), slot_map),
                      pl.BlockSpec((1, ps, dl), page_map),
                      pl.BlockSpec((1, tail.shape[1], dl), slot_map)],
            out_specs=pl.BlockSpec((1, heads, value_width), slot_map),
            scratch_shapes=[pltpu.VMEM((heads, NUM_LANES), jnp.float32),
                            pltpu.VMEM((heads, NUM_LANES), jnp.float32),
                            pltpu.VMEM((heads, value_width), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((b, heads, value_width), q.dtype),
        interpret=True,
    )(page_table, lengths, starts, q.astype(pool.dtype), pool, tail.astype(pool.dtype))


def derive_pages_a_step(monkeypatch, pages: int, pool) -> None:
    """Make ``pages_a_step`` derive ``pages`` for ``pool`` (an array or a
    ``ShapeDtypeStruct`` of ``(..., K, ps, D)``; and every pool of its page's
    bytes): the rule's threshold moved to that many of its pages, as it lies
    at two of a 4-kv-head bf16 page of 256 on the chip."""
    kv_heads, ps, d = pool.shape[-3:]
    monkeypatch.setattr(
        pa, "STEP_BYTES", pages * 2 * kv_heads * ps * d * jnp.dtype(pool.dtype).itemsize)


# Rows by what the walk has to get right. ``starts`` are the tokens in pages
# (pages of 16, 4 a row), ``grow`` the tail columns a live row attends to
# (0: the row is dead), ``listed``: the row is in the work list (alive at the
# tick's start). A dead row keeps a stale ``starts``, as a freed slot does.
SCENARIOS = {
    "dead-rows-first": ([7, 40, 20, 33, 48, 16], [0, 0, 2, 3, 1, 1], None),
    "dead-rows-last": ([7, 40, 20, 33, 48, 16], [3, 2, 2, 1, 0, 0], None),
    "dead-rows-interleaved": ([7, 40, 20, 33, 48, 16], [0, 2, 0, 3, 0, 1], None),
    "ended-inside-the-tick": ([7, 40, 20, 33, 48, 16], [3, 0, 2, 0, 1, 0],
                              [True, True, True, True, True, False]),
    "starts-on-page-edges-and-inside": ([16, 32, 48, 17, 31, 64], [1, 2, 3, 1, 2, 3], None),
    "tail-only": ([0, 0, 0, 0, 0, 0], [1, 0, 3, 2, 0, 3], None),
    "empty-list": ([7, 40, 20, 33, 48, 16], [0, 0, 0, 0, 0, 0], None),
    "every-row-live-at-full-width": ([64, 64, 64, 64, 64, 64], [1, 2, 3, 1, 2, 3], None),
}
PAGE_SIZE, MAX_PAGES = 16, 4


def rows_of(name):
    """(starts, lengths, listed) of a scenario, as int32 / bool arrays."""
    starts, grow, listed = SCENARIOS[name]
    starts, grow = jnp.asarray(starts, jnp.int32), jnp.asarray(grow, jnp.int32)
    lengths = jnp.where(grow > 0, starts + grow, 0)
    listed = lengths > 0 if listed is None else jnp.asarray(listed)
    return starts, lengths, listed
