"""The lightning indexer's scores of a paged decode step, computed where the
index keys lie (DeepSeek-V3.2's sparse attention, models/dsa.py).

    I[b, s] = sum_j w[b, j] * relu(qI[b, j] . kI[pool[table[b, s // ps], s % ps]])

for every live row ``b`` and every position ``s`` of its flushed pages. One
index key a token (``index_head_dim`` values) is all a score needs, and a page
of the index pool is one contiguous block, so the floor is one read of each
live row's pages. Written as ``pool[table]`` and an einsum
(``models/dsa.py`` ``paged_index_scores``: the tests' oracle and the path off
the TPU) the keys are read, written as a ``(B, maxp * ps, Di)`` temporary and
read again: three passes over 263 MB a layer on the serving cell, 26% of the
floor.

``dsa_index_scores`` (Pallas/Mosaic, kernel name ``dsa_index_scores``) walks
``ops/paged_attention.py``'s work list with GROUPS of ``G`` pages for pages
(``decode_steps`` handed ``page_size = G * ps`` and ``max_pages = ceil(maxp /
G)``: a live row's groups, then one step that does nothing here, where the
attention kernels read their tail): one page a step would pay a step's fixed
part 4,000 times a call for 64 KiB each. (The K/V attention kernel groups too
where a page is under 1 MiB, ``paged_attention.pages_a_step``, PR 53: there
through the block pipeline, a pool operand a page of the group, because its
groups are 2 pages of 512 KiB and not 12 of 64.) The pool stays in HBM (``pl.ANY``);
a step starts one DMA for each LIVE page of the NEXT step's group (the row's
``ceil(min(starts, lengths) / ps)`` pages and no other: a ragged last group,
an ended row and the list's idle steps fetch nothing) into the other half of
a two-slot VMEM buffer, waits for its own, and scores them: the row's ``qI``
against the group's keys in ONE dot on the MXU (a dot a page is twice
slower), in the operands' dtype with float32 accumulation, then ReLU, the
product with ``w`` and the sum over the index heads in float32 on the vector
unit. Nothing is gathered and nothing is written but the scores.

What comes back is ``(B, maxp * ps)`` float32 of which ONLY positions ``<
min(starts, lengths)`` of listed rows were written: everything else (a row
the walk never visits, a group's columns past the row's pages, an ended
row) is memory nobody wrote, and the caller has to SELECT it away (``jnp.
where`` on the row's length, as ``models/dsa.py`` does in front of
``top_indices``) before any arithmetic.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ditl_tpu.ops.flash_attention import NUM_LANES
from ditl_tpu.ops.paged_attention import decode_steps, walk_length

__all__ = ["dsa_index_scores", "index_steps", "pages_a_step"]

# Tokens a step at most. On a v5e at the serving cell's shape (32 rows of 130
# pages of 256; DEVICE time a call, PERF.md section 6, PR 46): 4 pages a step
# 582 us, 6 481, 12 377, 22 376, 33 384, 44 396, where the gather and einsum
# take 1,219 and the keys' bytes 312; the step body's dot wants thousands of
# keys (a page a dot: 880 us at any G) and the buffer's two slots stay small.
GROUP_TOKENS = 3072


def pages_a_step(max_pages: int, page_size: int) -> int:
    """``G``, the pages a step of the walk fetches and scores: the largest
    divisor of ``max_pages`` whose group holds at most ``GROUP_TOKENS``
    tokens, or, where the divisors are all small (a prime ``max_pages``),
    as many pages as that budget holds with a ragged last group."""
    most = max(1, min(max_pages, GROUP_TOKENS // page_size))
    best = max(g for g in range(1, most + 1) if max_pages % g == 0)
    return best if 2 * best > most else most


def index_steps(starts: jax.Array, alive: jax.Array, *, page_size: int,
                max_pages: int) -> dict[str, jax.Array]:
    """The walk's work list: ``decode_steps``' with a group of ``pages_a_step``
    pages for a page. Built once a decode program, as the attention kernels'
    list is (``starts`` and the rows alive are constants in there)."""
    g = pages_a_step(max_pages, page_size)
    return decode_steps(starts, alive, page_size=g * page_size,
                        max_pages=pl.cdiv(max_pages, g))


def _kernel(n_ref, rows_ref, ks_ref, table_ref, lengths_ref, starts_ref, q_ref, w_ref,
            pool_ref, o_ref, buf, sem, *, page_size: int, group: int):
    ps = page_size
    i = pl.program_id(0)

    def copies(step, slot, do):
        """``do`` on the DMA of each live page of ``step``'s group; their count."""
        b, first = rows_ref[step], ks_ref[step] * group
        pages = pl.cdiv(jnp.minimum(starts_ref[b], lengths_ref[b]), ps)
        count = jnp.clip(pages - first, 0, group)

        def one(j, carry):
            do(pltpu.make_async_copy(
                pool_ref.at[table_ref[b, first + j]],
                buf.at[slot, pl.ds(pl.multiple_of(j * ps, ps), ps)], sem.at[slot]))
            return carry

        jax.lax.fori_loop(0, count, one, 0)
        return count

    @pl.when(i == 0)
    def _first():
        copies(0, 0, lambda dma: dma.start())

    @pl.when(i + 1 < n_ref[0])
    def _next():
        copies(i + 1, (i + 1) % 2, lambda dma: dma.start())

    slot = i % 2
    count = copies(i, slot, lambda dma: dma.wait())

    @pl.when(count > 0)
    def _score():  # the whole group in one dot: a ragged one scores stale keys too
        q = q_ref[0]  # (Hi, Di)
        s = jax.lax.dot_general(q, buf[slot].astype(q.dtype), (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)  # (Hi, G * ps)
        row = jnp.sum(jnp.maximum(s, 0.0) * w_ref[0], axis=0, keepdims=True)  # (1, G * ps)
        if o_ref.shape[1] == 1:
            o_ref[0] = row
        else:  # (G * ps / 128, 128): 128 scores a sublane, whole tiles of the output
            for c in range(o_ref.shape[1]):
                o_ref[0, pl.ds(c, 1), :] = row[:, c * NUM_LANES:(c + 1) * NUM_LANES]


def dsa_index_scores(
    qi: jax.Array,  # (B, Hi, Di)
    w: jax.Array,  # (B, Hi) float32
    pool: jax.Array,  # (P, ps, Di): the index keys' page pool
    page_table: jax.Array,  # (B, maxp) int32
    lengths: jax.Array,  # (B,) int32; 0 = a dead or ended row
    starts: jax.Array,  # (B,) tokens resident in pages
    *,
    steps: dict[str, jax.Array] | None = None,  # ``index_steps``' list
    interpret: bool = False,
) -> jax.Array:
    """(B, maxp * ps) float32: see the module docstring; written only at
    positions ``< min(starts, lengths)`` of the rows ``steps`` lists, which
    has to name every row with ``lengths > 0`` (left out, it is built here
    from ``lengths > 0``)."""
    b, hi, di = qi.shape
    ps = pool.shape[1]
    maxp = page_table.shape[1]
    g = pages_a_step(maxp, ps)
    groups = pl.cdiv(maxp, g)
    if steps is None:
        steps = index_steps(starts, lengths > 0, page_size=ps, max_pages=maxp)
    n = walk_length(steps)

    def row_map(i, n, rows, ks, tab, lens, st):
        return (rows[i], 0, 0)

    # A row's scores as (chunks, 128) where a group is whole (8, 128) tiles,
    # else as one (1, G * ps) row, whose array XLA keeps in tiles of ONE
    # sublane: the selection behind it then works on eighths of registers
    # (76 us a call at the serving cell's shape, PERF.md section 6, PR 46).
    # The row's idle last step names a group it may not have: clamped, it
    # names the last one again, which is then not written a second time.
    tiled = (g * ps) % (8 * NUM_LANES) == 0
    if tiled:
        chunks = g * ps // NUM_LANES
        block, shape = (1, chunks, NUM_LANES), (b, groups * chunks, NUM_LANES)
    else:
        block, shape = (1, 1, g * ps), (b, 1, groups * g * ps)

    def out_map(i, n, rows, ks, tab, lens, st):
        k = jnp.minimum(ks[i], groups - 1)
        return (rows[i], k, 0) if tiled else (rows[i], 0, k)

    out = pl.pallas_call(
        functools.partial(_kernel, page_size=ps, group=g),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=6,
            grid=(n,),
            in_specs=[
                pl.BlockSpec((1, hi, di), row_map),
                pl.BlockSpec((1, hi, 1), row_map),
                pl.BlockSpec(memory_space=pl.ANY),
            ],
            out_specs=pl.BlockSpec(block, out_map),
            scratch_shapes=[
                pltpu.VMEM((2, g * ps, di), pool.dtype),
                pltpu.SemaphoreType.DMA((2,)),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct(shape, jnp.float32),
        compiler_params=pltpu.CompilerParams(dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name="dsa_index_scores",
    )(n.reshape(1), steps["rows"], steps["ks"], page_table, lengths, starts, qi,
      w.astype(jnp.float32)[:, :, None], pool)
    return out.reshape(b, -1)[:, :maxp * ps]
