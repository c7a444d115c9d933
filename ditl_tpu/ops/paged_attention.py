"""Paged decode attention: KV lives in a shared page pool, per-slot page
tables map logical block -> physical page (vLLM-style), TPU-first.

The reference has no serving stack at all (its model is behind an HTTP API,
ref ``src/distributed_inference.py:34-41``); this op underpins the paged
mode of the continuous-batching engine (infer/continuous.py) that replaces
it. Contiguous per-slot caches (infer/cache.py) bound capacity by
``n_slots x max_context`` and make prefix sharing whole-prefix and explicit;
a page pool bounds capacity by *total tokens resident* and shares any
common full page between slots (automatic prefix reuse, infer/paged_cache.py).

Two implementations, equal by construction (tested against each other):

- ``paged_attention_xla``: gather pages -> contiguous (B, maxp*ps, K, D) ->
  masked GQA attention. Materializes the gathered cache every step (double
  HBM traffic); used as the correctness reference and the CPU path.
- ``paged_attention`` (Pallas/Mosaic): the page table rides the
  scalar-prefetch channel so each grid step's *block index map* fetches the
  right physical page from HBM — no gathered copy is ever materialized.
  Online softmax over pages (same lane-replicated row-stat scheme as
  ops/flash_attention.py); one grid step consumes a page, or a GROUP of
  pages (below), for ALL kv heads, in ONE pass (``_accumulate_block``): a
  batched score dot and a batched value dot over the kv heads, their
  operands in the dtype the pool stores and their sums in float32. A
  bfloat16 x bfloat16 product is exact in float32, so the dots add the
  products a float32 copy of the page would give and the page is never
  copied; float32 operands (the CPU tests') run the float32 arithmetic they
  always ran.

The decode path (the tail kernel, and ``ops/mla_attention.py``'s) walks a
WORK LIST, not a rectangle. A grid of every slot by every page-table position
costs its steps whether they do anything: at 64 slots x (16 pages + the tail)
with 14 rows live on two pages each, 1,088 steps a call of which ~40 work, and
every dead ROW still moves its q, tail and output block (160-245 us a call on
a v5e where the live rows' bytes take 10-25: PERF.md section 5). So the grid
has ONE axis whose length is the traced count of the steps that exist:
``decode_steps`` lists, row by row, each live row's flushed pages ``k = 0 ..
ceil(starts / page_size) - 1`` and then its tail-and-finalize step; the lists
ride the scalar-prefetch channel beside the table and the index maps read
step ``i``'s row and page from them. A row's scratch is initialised at its
``k == 0`` and its output written at its tail step.

The list's unit is a GROUP of ``P`` pages (``pages_a_step``; this kernel
only: ``ops/mla_attention.py`` keeps one page a step, ``ops/dsa_index.py``
has a rule of its own). A step's dot -> max -> exp -> dot is a chain of
~0.75 us on a v5e however few columns it has, and the pipeline hides it
behind the fetch of ONE step's blocks: behind 1.28 or 2.56 us (Granite's 1
MiB pages, OLMoE's 2 MiB) it disappears, behind the 0.64 us of 4 kv heads x
256 x 128 bf16 it does not (0.816 us a page). So ``P`` is read off the pool's
shape and dtype, never set: the fewest pages whose keys and values are
``STEP_BYTES`` (1 MiB), at most ``MOST_PAGES`` and the table's width: 2 at 4
kv heads (0.703 us a page on a row of 130 pages; 4 gives 0.707, and on a row
of 9 pages it loses to 1: PERF.md section 6, PR 53), 1 at 8 and 16, where the
call is the one it always was. The pages of a group are no neighbours in the
pool, so the pool is handed to the call ``P`` times, each operand with the
index map of its member of the group (``walk_maps``), and the body joins the
``P`` blocks at their tile boundaries into ONE ``(K, P * ps, D)`` operand: one
score dot, one mask / max / exp / sum, one value dot and one pass over the
scratch a step, whatever ``P`` (a dot a page measures the same: the MXU
takes 512 columns in four passes either way). The bytes fetched are the
one-page walk's: a row's last group may be ragged, and the member it lacks
names the block it named a group earlier, which is not fetched again; its
columns lie at or past ``starts``, where the mask is, and hold pages of the
row itself, never the sentinel and never memory nobody wrote, so nothing a
live row's step multiplies by a zero probability can be a NaN. A window
layer's groups start at the row's first page inside the window.

The list is a function of
``starts`` and of which rows are alive, both constants inside a decode
program (a row may END inside it: its steps stay, predicated off by
``lengths == 0`` as on the rectangle), so the engine builds it once a
program, in front of the scan, and every layer of every step walks the same
one; pages are named through the table, so a layer's offset never touches it.

The dead-row contract: a row with ``lengths == 0`` comes out exactly zero. A
listed row that ended writes zeros at its tail step (the ``l == 0`` guard); a
row the walk never visits is memory nobody wrote, so the wrapper selects
zeros for every ``lengths == 0`` row behind the call; an empty list walks one
step that does nothing. The axis is ``arbitrary`` (steps of a row must follow
each other): nothing is lost on a single-TensorCore chip (v5e); a two-core
chip would want the list split by core, at a row boundary.

Layouts: q is (B, H, D) — one query token per slot (the decode tick shape);
pools are (P, K, ps, D) — kv-heads BEFORE page slots, so a Pallas block
slicing one kv head keeps (ps, D) as its trailing dims (Mosaic requires the
last two block dims divisible by (8, 128) or equal to the array's);
page_table is (B, maxp) int32; lengths (B,) counts valid tokens per slot
(0 = dead slot -> zero output).

P is whatever the caller's table addresses. The engine's pools are stacked
over layers, (L, n_pages, K, ps, D); a paged decode (models/llama.py
``forward``) hands the kernel ALL of them as one pool of P = L * n_pages
pages (a bitcast) and a table offset by ``layer * n_pages``, because a
custom call takes whole operands: a pool sliced by layer in front of it is
a copy of that layer's pool, every layer of every step. The sentinel page
that the page steps of an ended row name is then ABSOLUTE page 0 (layer 0's
first page) for every layer: it is only ever fetched, never read unmasked,
so which page it is does not matter — only that consecutive such steps name
the same one (a block index that repeats is not fetched again).
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

__all__ = ["decode_steps", "paged_attention", "paged_attention_xla", "pages_a_step"]


def paged_attention_xla(
    q: jax.Array,  # (B, H, D) or (B, Q, H, D) — multi-query verify
    k_pages: jax.Array,  # (P, K, ps, D)
    v_pages: jax.Array,
    page_table: jax.Array,  # (B, maxp) int32
    lengths: jax.Array,  # (B,) int32
    tail_k: jax.Array | None = None,  # (B, K, T, D)
    tail_v: jax.Array | None = None,
    starts: jax.Array | None = None,  # (B,) — tokens resident in pages
    k_scale: jax.Array | None = None,  # (P, K, 1, ps) — int8 pool scales
    v_scale: jax.Array | None = None,
    window: int | None = None,
) -> jax.Array:
    """Gather-based reference: correctness oracle + CPU fallback.

    ``window``: the query at position ``lengths - 1`` sees only the
    positions ``>= lengths - window`` (a window attention layer).

    With a tail (the deferred-flush decode path), tokens [0, starts) live
    in pages and [starts, lengths) in the tail buffer at columns
    [0, lengths - starts). With ``k_scale``/``v_scale`` the pools are int8
    (symmetric per-row absmax; tails stay float).

    4-D ``q`` is the speculative-verify shape: Q consecutive query tokens
    per slot at positions lengths-1 .. lengths-2+Q; query qi additionally
    sees tail columns up to ``lengths + qi`` (causal within the chunk).
    Page columns need no per-query limit — they all precede ``starts``,
    which every query's limit covers."""
    squeeze = q.ndim == 3
    if squeeze:
        q = q[:, None]
    b, nq, h, d = q.shape
    _, kv_heads, ps, _ = k_pages.shape
    maxp = page_table.shape[1]
    groups = h // kv_heads
    kg = k_pages[page_table]  # (B, maxp, K, ps, D)
    vg = v_pages[page_table]
    dtype = k_pages.dtype
    if k_scale is not None:
        kg = (kg.astype(jnp.float32)
              * jnp.swapaxes(k_scale[page_table], 3, 4))  # scales (B,maxp,K,ps,1)
        vg = (vg.astype(jnp.float32)
              * jnp.swapaxes(v_scale[page_table], 3, 4))
        dtype = q.dtype
    k = jnp.swapaxes(kg, 2, 3).reshape(b, maxp * ps, kv_heads, d).astype(dtype)
    v = jnp.swapaxes(vg, 2, 3).reshape(b, maxp * ps, kv_heads, d).astype(dtype)
    page_limit = lengths if starts is None else jnp.minimum(starts, lengths)
    qi = jnp.arange(nq, dtype=jnp.int32)
    valid = (
        jnp.arange(maxp * ps, dtype=jnp.int32)[None, None, :]
        < page_limit[:, None, None]
    )  # (B, 1, S) -> broadcast over queries
    valid = jnp.broadcast_to(valid, (b, nq, maxp * ps))
    if tail_k is not None:
        t = tail_k.shape[2]
        k = jnp.concatenate([k, jnp.swapaxes(tail_k, 1, 2)], axis=1)
        v = jnp.concatenate([v, jnp.swapaxes(tail_v, 1, 2)], axis=1)
        tail_valid = (
            starts[:, None, None] + jnp.arange(t, dtype=jnp.int32)[None, None, :]
            < (lengths[:, None] + qi[None, :])[:, :, None]
        )  # (B, Q, T)
        valid = jnp.concatenate([valid, tail_valid], axis=2)
    if window is not None:
        at = jnp.arange(maxp * ps, dtype=jnp.int32)[None, :]
        if tail_k is not None:
            at = jnp.concatenate(
                [jnp.broadcast_to(at, (b, maxp * ps)),
                 starts[:, None] + jnp.arange(t, dtype=jnp.int32)[None, :]], axis=1)
        valid = valid & (at >= (lengths - window)[:, None])[:, None, :]
    qg = q.reshape(b, nq, kv_heads, groups, d)
    scores = jnp.einsum(
        "bqkgd,bskd->bqkgs", qg, k, preferred_element_type=jnp.float32
    ) * (d**-0.5)
    scores = jnp.where(valid[:, :, None, None, :], scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    # Dead slots (length 0) have an all-masked row; emit zeros, not NaN.
    probs = jnp.where(lengths[:, None, None, None, None] > 0, probs, 0.0)
    out = jnp.einsum("bqkgs,bskd->bqkgd", probs.astype(v.dtype), v)
    out = out.reshape(b, nq, h, d)
    return out[:, 0] if squeeze else out


def _side_by_side(refs, axis: int):
    """The blocks of a step's pages as one operand: ``refs`` (one block a
    page, each with a leading 1) joined along ``axis``, at page boundaries
    that are whole tiles, so the join moves nothing."""
    blocks = [r[0] for r in refs]
    return blocks[0] if len(blocks) == 1 else jnp.concatenate(blocks, axis=axis)


def _accumulate_block(
    q_ref, k_refs, v_refs, m_scr, l_scr, acc_scr, *,
    scale, base, width, limit, ks_refs=None, vs_refs=None, q_groups=None, low=None,
):
    """Online-softmax accumulation of one (all-kv-heads) KV block whose
    columns are global positions [base, base+width), masked to < limit. The
    block is a tuple of refs, a GROUP of pages side by side (``pages_a_step``;
    one ref for the tail and for a pool of large pages): ``width`` columns in
    all, taken in ONE pass whatever their number.

    ALL kv heads in one pass: one batched score dot ``(K, G, D) x (K, width,
    D) -> (K, G, width)``, one mask, one ``max`` / ``exp`` / ``sum`` over the
    whole ``(K, G, width)`` score block, one batched value dot, and the three
    scratch arrays (``(K, G, .)``, a kv head's rows on the second axis) read
    and written whole once a step. A dot a kv head with its slice of the
    scratch between them is a chain the next head's waits behind: 1.09 us a
    page of 4 kv heads on a v5e where this pass takes 0.82 and the page's DMA
    0.64 (PERF.md section 6, PR 49). Both dots take their operands in the
    dtype they are STORED in and accumulate in float32
    (``preferred_element_type``): the product of two bfloat16 values is exact
    in float32, so a bf16 pool gets the sums a float32 copy of its page would
    give, and no copy is made; ``head_dim ** -0.5`` multiplies the float32
    scores behind the dot, as ``paged_attention_xla`` writes it. The
    probabilities enter the value dot in the values' dtype.

    ``ks_refs``/``vs_refs`` ((1, K, 1, ps) f32 a page) mark the block as int8:
    the scales factor OUT of the dots — the score matmul consumes raw int8
    K (HBM reads stay int8-sized; cast to q's dtype, exact for |x| <= 127)
    and the per-position scale multiplies the (G, width) score rows
    afterwards; V's scale folds into the probabilities before the pv matmul.
    Lane-aligned broadcasts both times (same scheme as the contiguous int8
    cache, ops/attention.py).

    ``q_groups`` (multi-query / speculative verify): the q block's rows are
    Q consecutive query tokens x ``q_groups`` GQA group members (row
    r = qi * q_groups + g), and row r's column limit is ``limit + qi`` —
    causal masking WITHIN the verify chunk at zero extra block traffic."""
    groups = q_ref.shape[2]
    d = acc_scr.shape[-1]
    tile = _lane_tile  # shared lane-replication helper (ops/flash_attention)
    cols = base + jax.lax.broadcasted_iota(jnp.int32, (1, groups, width), 2)
    if q_groups is None:
        col_mask = cols < limit
    else:
        qi = jax.lax.broadcasted_iota(jnp.int32, (1, groups, width), 1) // q_groups
        col_mask = cols < (limit + qi)
    if low is not None:  # a window layer: nothing below the window's first position
        col_mask = col_mask & (cols >= low)
    q = q_ref[0]  # (K, G, D)
    k = _side_by_side(k_refs, 1)  # (K, width, D)
    if ks_refs is not None:
        k = k.astype(q.dtype)
    s = jax.lax.dot_general(
        q, k, (((2,), (2,)), ((0,), (0,))),
        preferred_element_type=jnp.float32,
    ) * scale  # (K, G, width)
    if ks_refs is not None:
        s = s * _side_by_side(ks_refs, 2)  # (K, 1, width) broadcast over G sublanes
    s = jnp.where(col_mask, s, NEG_INF)
    m_prev = m_scr[...]  # (K, G, NUM_LANES) lane-replicated
    m_next = jnp.maximum(m_prev, jnp.max(s, axis=2, keepdims=True))
    alpha = jnp.exp(m_prev - m_next)
    ptab = jnp.exp(s - tile(m_next, width))
    l_scr[...] = alpha * l_scr[...] + jnp.sum(ptab, axis=2, keepdims=True)
    m_scr[...] = m_next
    v = _side_by_side(v_refs, 1)  # (K, width, D)
    if vs_refs is not None:
        ptab, v = ptab * _side_by_side(vs_refs, 2), v.astype(jnp.float32)
    pv = jax.lax.dot_general(
        ptab.astype(v.dtype), v, (((2,), (1,)), ((0,), (0,))),
        preferred_element_type=jnp.float32,
    )  # (K, G, D)
    acc_scr[...] = acc_scr[...] * tile(alpha, d) + pv


def _finalize_out(o_ref, m_scr, l_scr, acc_scr):
    l = l_scr[...]
    l_safe = jnp.where(l == 0.0, 1.0, l)
    o_ref[0] = (acc_scr[...] / _lane_tile(l_safe, acc_scr.shape[-1])).astype(o_ref.dtype)


def _scratch(kv_heads: int, rows: int, d: int) -> list:
    """The online softmax's state over a row's steps: running max, sum
    (lane-replicated) and the unnormalised output, a kv head's ``rows`` query
    rows on the second axis."""
    return [
        pltpu.VMEM((kv_heads, rows, NUM_LANES), jnp.float32),  # m
        pltpu.VMEM((kv_heads, rows, NUM_LANES), jnp.float32),  # l
        pltpu.VMEM((kv_heads, rows, d), jnp.float32),  # acc
    ]


def _paged_kernel(
    table_ref,  # scalar prefetch: (B, maxp) int32
    lengths_ref,  # scalar prefetch: (B,) int32
    q_ref,  # (1, K, G, D)
    k_ref,  # (1, K, ps, D)
    v_ref,
    o_ref,  # (1, K, G, D)
    m_scr,  # (K, G, NUM_LANES)
    l_scr,
    acc_scr,  # (K, G, D)
    *,
    scale: float,
    page_size: int,
    n_pages: int,
):
    """Grid (B, maxp): each step consumes one PAGE for ALL kv heads, batched
    inside the step's two dots, so the grid stays small; per-(b, h, page)
    grids are latency-bound at ~2k tiny steps on v5e. Row ``[k, g]`` of the
    stats/acc scratch belongs to (kv head k, group member g)."""
    b = pl.program_id(0)
    p = pl.program_id(1)

    @pl.when(p == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    length = lengths_ref[b]
    base = p * page_size

    @pl.when(base < length)
    def _compute():
        _accumulate_block(
            q_ref, (k_ref,), (v_ref,), m_scr, l_scr, acc_scr,
            scale=scale, base=base, width=page_size, limit=length,
        )

    @pl.when(p == n_pages - 1)
    def _finalize():
        _finalize_out(o_ref, m_scr, l_scr, acc_scr)


def window_first_page(starts, window: int, page_size: int):
    """The first page a window layer's row still attends to when its program
    starts at position ``starts``: the page of position ``starts - (window -
    1)``."""
    return jnp.maximum(starts - (window - 1), 0) // page_size


# The bytes of keys and values a step of the walk should fetch at least, and
# the most pages it may take to get there. A page step's dot -> max -> exp ->
# dot takes ~0.75 us on a v5e however few columns it has, and the pipeline
# hides it behind ONE step's fetch: behind 1.28 us (1 MiB) or 2.56 (2 MiB) it
# disappears (Granite's and OLMoE's pages: 91.9% and 92.1% of the DMA's
# floor), behind 0.64 us (Trinity-Mini's and Qwen2-7B's 4 kv heads x 256 x
# 128 bf16, 0.5 MiB) it does not (78.5%). The sweep that set the threshold is
# in PERF.md section 6, PR 53.
STEP_BYTES = 1 << 20
MOST_PAGES = 4


def pages_a_step(pool_shape: tuple[int, ...], dtype, max_pages: int) -> int:
    """``P``, the pages one step of the decode walk consumes: the fewest whose
    keys and values are ``STEP_BYTES`` together, at most ``MOST_PAGES`` and the
    page table's width. Derived from the pool's shape ``(..., K, ps, D)`` and
    dtype, never set: 2 at 4 kv heads x 256 x 128 bf16, 1 where a page is 1 MiB
    or more, whose walk is then the one-page walk it always was."""
    kv_heads, ps, d = pool_shape[-3:]
    page_bytes = 2 * kv_heads * ps * d * jnp.dtype(dtype).itemsize
    return max(1, min(pl.cdiv(STEP_BYTES, page_bytes), MOST_PAGES, max_pages))


def decode_steps(
    starts: jax.Array,  # (B,) int32 — tokens resident in pages
    alive: jax.Array,  # (B,) bool — rows that may attend during the program
    *,
    page_size: int,
    max_pages: int,
    window: int | None = None,
    group: int = 1,
) -> dict[str, jax.Array]:
    """The work list of the decode kernels (module docstring): every step
    that exists, row by row. A row with ``alive`` has ``ceil(starts /
    page_size)`` pages, so ``ceil(pages / group)`` page steps ``k = 0, 1,
    ...`` (step ``k`` is the row's pages ``k * group ..``, the last group
    ragged behind) and then its tail-and-finalize step; a row without has
    none. ``group`` is the kernel's ``pages_a_step``.

    With a ``window`` (a window attention layer's list) a row's pages are
    only those that meet ``[starts - (window - 1), starts)``: its first
    query of the program, at position ``starts``, sees nothing in front of
    them, and no later one does. Step ``k`` then starts at the row's page
    ``window_first_page + k * group`` (the kernel and its index maps add it),
    the first page masked inside below each step's own ``lengths - window``.

    ``rows`` / ``ks``: (B * (ceil(max_pages / group) + 1),) int32, step
    ``i``'s row and its ``k``; ``count`` (): the steps that exist (entries past
    it name row 0, ``k`` 0 and are never walked). ``starts <= max_pages *
    page_size``, which is what a page table that wide can address. One
    cumulative sum over the rows and one comparison a (step, row): build it
    once where ``starts`` and ``alive`` are constants (a decode program: in
    front of its scan)."""
    b = starts.shape[0]
    n_steps = pl.cdiv(starts, page_size)  # a row's page steps: its pages,
    if window is not None:  # those inside the window,
        n_steps = n_steps - window_first_page(starts, window, page_size)
    most = max_pages
    if group > 1:  # in groups
        n_steps, most = pl.cdiv(n_steps, group), pl.cdiv(max_pages, group)
    per_row = jnp.where(alive, n_steps + 1, 0).astype(jnp.int32)
    ends = jnp.cumsum(per_row)  # (B,) one past each row's last step
    i = jnp.arange(b * (most + 1), dtype=jnp.int32)
    # the rows whose steps all lie in front of step i: its row's index
    rows = jnp.minimum(jnp.sum(i[:, None] >= ends[None, :], axis=1), b - 1)
    ks = i - (ends - per_row)[rows]
    walked = i < ends[-1]
    return {
        "rows": jnp.where(walked, rows, 0).astype(jnp.int32),
        "ks": jnp.where(walked, ks, 0).astype(jnp.int32),
        "count": ends[-1],
    }


def _paged_tail_kernel(
    rows_ref,  # scalar prefetch: (S,) int32 — the work list's rows
    ks_ref,  # scalar prefetch: (S,) int32 — and their steps
    table_ref,  # scalar prefetch: (B, maxp) int32
    lengths_ref,  # scalar prefetch: (B,) int32
    starts_ref,  # scalar prefetch: (B,) int32 — tokens resident in pages
    q_ref,  # (1, K, G, D)
    *rest,  # ``group`` k blocks (1, K, ps, D; int8 when quantized), as many v
            # blocks, [as many k and v scale blocks ((1, K, 1, ps) f32)],
            # tk_ref, tv_ref, o_ref, m_scr, l_scr, acc_scr
    scale: float,
    page_size: int,
    group: int,
    quantized: bool,
    q_groups: int | None = None,
    window: int | None = None,
):
    """Deferred-flush variant: grid (n_steps,), step ``i`` of the work list
    (``decode_steps``) is ``(b, p) = (rows[i], ks[i])``. Steps ``p <
    ceil(pages / group)`` consume row b's flushed pages (positions <
    starts[b]) in order, ``group`` pages a step as ONE block of ``group *
    page_size`` columns; the row's last step consumes the hot TAIL block —
    the current decode chunk's KV, held in a small contiguous buffer until
    the per-tick flush (positions [starts, lengths)) — and writes the row's
    output. A ragged last group's missing pages are blocks of the row's own
    earlier pages (``walk_maps``), masked as columns ``>= starts``. With
    ``quantized``, the pools are int8 and their per-position scales factor
    out of the dots; the tail stays float until the flush.
    ``q_groups`` (speculative verify): the q block packs Q query tokens;
    per-query causal limits apply to the TAIL only — every page column
    precedes ``starts``, which every query's limit already covers."""
    pools, (tk_ref, tv_ref, o_ref, m_scr, l_scr, acc_scr) = rest[:-6], rest[-6:]
    k_refs, v_refs = pools[:group], pools[group:2 * group]
    kscale_refs, vscale_refs = (
        (pools[2 * group:3 * group], pools[3 * group:]) if quantized else (None, None))
    i = pl.program_id(0)
    b = rows_ref[i]
    p = ks_ref[i]

    @pl.when(p == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    length = lengths_ref[b]
    start = starts_ref[b]
    width = group * page_size
    n_steps = pl.cdiv(start, page_size)  # the row's page steps (as ``decode_steps``)
    page_limit = jnp.minimum(start, length)
    base = p * width
    low = None
    if window is not None:
        # a window layer's list starts at the row's first page inside the
        # window (``decode_steps``)
        first = window_first_page(start, window, page_size)
        n_steps = n_steps - first
        base = base + first * page_size
        low = length - window
    if group > 1:
        n_steps = pl.cdiv(n_steps, group)
    wanted = (p < n_steps) & (base < page_limit)
    if window is not None:
        # pages that have fallen wholly behind this step's window (the
        # program's later steps) are skipped
        wanted = wanted & (base + width > low)

    @pl.when(wanted)
    def _pages():
        _accumulate_block(
            q_ref, k_refs, v_refs, m_scr, l_scr, acc_scr,
            scale=scale, base=base, width=width, limit=page_limit,
            ks_refs=kscale_refs, vs_refs=vscale_refs, low=low,
        )

    @pl.when((p == n_steps) & (length > start))
    def _tail():
        _accumulate_block(
            q_ref, (tk_ref,), (tv_ref,), m_scr, l_scr, acc_scr,
            scale=scale, base=start, width=tk_ref.shape[2], limit=length,
            q_groups=q_groups, low=low,
        )

    @pl.when(p == n_steps)
    def _finalize():
        _finalize_out(o_ref, m_scr, l_scr, acc_scr)


def walk_maps(page_size: int, max_pages: int, trailing: int, window: int | None = None,
              group: int = 1):
    """The block index maps of a walk over ``decode_steps``' list, for
    operands with ``trailing`` dims behind the leading one: ``slot_map``
    names step ``i``'s ROW (q, tail, output), ``page_maps[j]`` the ``j``-th
    page of its group (pools, scales: a pool is handed to the call ``group``
    times, a map each, since the pages of a group are no neighbours in it).
    A page step names the row's page ``k * group + j`` while it holds tokens
    the row still attends to (``< min(starts, lengths)``); a row that ended
    inside the program names sentinel page 0 instead, and the tail step names
    the row's LAST group again: consecutive identical block indices are not
    fetched again, so neither costs a fetch. Nor does the page a ragged last
    group lacks: member ``j`` names what it named a group earlier (a row of
    fewer than ``j + 1`` pages its last page, the one fetch that is not a
    page's first), so every block a live row's step computes on holds pages
    of that row, and the columns of a repeated one lie at or past ``starts``,
    where the mask is."""
    zeros = (0,) * trailing

    def slot_map(i, rows, ks, tab, lens, st):
        return (rows[i], *zeros)

    def page_map(i, rows, ks, tab, lens, st, *, member):
        b = rows[i]
        pages = pl.cdiv(st[b], page_size)
        last = jnp.maximum(pages - 1, 0)
        k = ks[i]
        first = 0 if window is None else window_first_page(st[b], window, page_size)
        if group > 1:
            listed = pages - first  # the pages of the row's steps
            # the tail step names the last group again
            k = jnp.minimum(k, jnp.maximum(pl.cdiv(listed, group) - 1, 0)) * group + member
            k = jnp.where((k >= listed) & (k >= group), k - group, k)
        if window is not None:  # a window layer's list starts at the row's page first
            k = k + first
        col = jnp.minimum(jnp.minimum(k, last), max_pages - 1)
        live = col * page_size < jnp.minimum(st[b], lens[b])
        return (jnp.where(live, tab[b, col], 0), *zeros)

    return slot_map, tuple(functools.partial(page_map, member=j) for j in range(group))


def walk_length(steps: dict[str, jax.Array]) -> jax.Array:
    """The walk's grid length: the list's count, and one step where the
    list is empty (a program run with no live row), so that the compiled
    loop never has zero trips: that step is row 0's ``k`` 0, on which
    nothing accumulates because its ``lengths`` is 0."""
    return jnp.maximum(steps["count"], 1)


def zero_dead_rows(out: jax.Array, lengths: jax.Array) -> jax.Array:
    """A row the walk never visits is memory nobody wrote, and a listed row
    that ended writes zeros only at its tail step: the contract's zeros for
    every row with ``lengths == 0`` are selected here, outside the call."""
    live = (lengths > 0).reshape(-1, *(1,) * (out.ndim - 1))
    return jnp.where(live, out, jnp.zeros((), out.dtype))


def paged_attention(
    q: jax.Array,  # (B, H, D); (B, Q, H, D) = multi-query speculative verify
    k_pages: jax.Array,  # (P, K, ps, D)
    v_pages: jax.Array,
    page_table: jax.Array,  # (B, maxp) int32
    lengths: jax.Array,  # (B,) int32
    *,
    tail_k: jax.Array | None = None,  # (B, K, T, D) — unflushed chunk KV
    tail_v: jax.Array | None = None,
    starts: jax.Array | None = None,  # (B,) tokens resident in pages
    k_scale: jax.Array | None = None,  # (P, K, 1, ps) — int8 pools
    v_scale: jax.Array | None = None,
    steps: dict[str, jax.Array] | None = None,  # ``decode_steps``' list
    interpret: bool | None = None,
    mesh=None,
    rules=None,
    window: int | None = None,  # a window attention layer (static)
) -> jax.Array:
    """Pallas paged GQA decode attention (see module docstring).

    With ``tail_k/tail_v/starts`` (the deferred-flush decode path), the
    grid is a walk over ``steps``, the work list of ``decode_steps``: each
    row's flushed pages, then one step that accumulates its hot tail block
    — positions [starts, lengths) held in a small contiguous buffer — so
    per-token page writes never happen inside the decode scan. ``steps``
    has to name every row with ``lengths > 0`` (built from these ``starts``
    and an ``alive`` that covers them; a decode program builds it once, in
    front of its scan); left out, it is built here from ``lengths > 0``. A
    step of the list is ``pages_a_step(k_pages.shape, k_pages.dtype, maxp)``
    pages, which whoever builds ``steps`` hands ``decode_steps`` as ``group``.
    A row with ``lengths == 0`` comes out as zeros, in the list or not.

    4-D ``q`` (requires the tail path) is the speculative K+1-token verify:
    Q queries per slot share every page fetch — the whole point of
    speculation on a bandwidth-bound decoder — and get per-query causal
    limits on the tail block only (query qi sees tail positions
    < lengths + qi; page columns all precede ``starts``).

    With a ``mesh``, the kernel is shard_mapped over the TENSOR axis:
    pools, tails and q/output split on kv-heads (the rule table's
    ``act_kv_heads``), page table / lengths / starts replicated — heads
    are independent in attention, so no collectives are induced; the
    work list is replicated with the table (where the rule table also
    splits the batch, each shard builds the list of its own rows). The
    batch axes stay unsharded here (a paged pool is one shared resource;
    multi-host paged serving replicates the batch like the pod protocols
    do)."""
    multi_q = q.ndim == 4
    if window is not None and (multi_q or mesh is not None or tail_k is None):
        raise ValueError(
            "paged_attention with a window walks the tail path's work list, one "
            "query a row, on one chip: no speculative verify, no mesh")
    # of the WHOLE pool's pages, which is what whoever built ``steps`` saw (a
    # shard's pages under a mesh are smaller)
    group = pages_a_step(k_pages.shape, k_pages.dtype, page_table.shape[1])
    if mesh is not None:
        from ditl_tpu.ops.attention import _mesh_axes_size
        from ditl_tpu.parallel.sharding import DEFAULT_RULES, logical_to_spec

        rules = rules if rules is not None else DEFAULT_RULES
        tp = _mesh_axes_size(mesh, rules.get("act_kv_heads"))
        tp_q = _mesh_axes_size(mesh, rules.get("act_heads"))
        dp = _mesh_axes_size(mesh, rules.get("batch"))
        kv_heads = k_pages.shape[1]
        shardable = (
            (tp > 1 or dp > 1)
            # q and kv specs must resolve to the SAME head split — a rule
            # table splitting them differently would silently mispair q
            # heads with kv heads inside the map.
            and rules.get("act_heads") == rules.get("act_kv_heads")
            and tp == tp_q
            and kv_heads % tp == 0
            and q.shape[-2] % tp == 0
            and q.shape[0] % dp == 0
        )
        if shardable:
            q_axes = (
                ("batch", None, "act_heads", None) if multi_q
                else ("batch", "act_heads", None)
            )
            pool_spec = logical_to_spec((None, "act_kv_heads", None, None), rules)
            tail_spec = logical_to_spec(("batch", "act_kv_heads", None, None), rules)
            row_spec = logical_to_spec(("batch",), rules)
            in_specs = [
                logical_to_spec(q_axes, rules),  # q
                pool_spec, pool_spec,  # pools (P,K,ps,D): replicated over dp
                logical_to_spec(("batch", None), rules),  # table
                row_spec,  # lengths
            ]
            args = [q, k_pages, v_pages, page_table, lengths]
            has_tail = tail_k is not None
            has_scale = k_scale is not None
            # a list over ALL rows is no shard's list once the batch is split
            has_steps = steps is not None and has_tail and dp == 1
            if has_tail:
                in_specs += [tail_spec, tail_spec, row_spec]
                args += [tail_k, tail_v, starts]
            if has_scale:
                scale_spec = logical_to_spec(
                    (None, "act_kv_heads", None, None), rules
                )
                in_specs += [scale_spec, scale_spec]
                args += [k_scale, v_scale]
            if has_steps:
                replicated = jax.sharding.PartitionSpec()
                in_specs += [replicated] * 3
                args += [steps["rows"], steps["ks"], steps["count"]]

            def local(q_, kp_, vp_, tab_, lens_, *rest):
                tk_ = tv_ = st_ = ks_ = vs_ = steps_ = None
                if has_tail:
                    tk_, tv_, st_, *rest = rest
                if has_steps:
                    *rest, rows_, steps_ks_, count_ = rest
                    steps_ = {"rows": rows_, "ks": steps_ks_, "count": count_}
                if has_scale:
                    ks_, vs_ = rest
                return _on_one_chip(
                    q_, kp_, vp_, tab_, lens_,
                    tail_k=tk_, tail_v=tv_, starts=st_,
                    k_scale=ks_, v_scale=vs_, steps=steps_, interpret=interpret,
                    group=group,
                )

            return jax.shard_map(
                local,
                mesh=mesh,
                in_specs=tuple(in_specs),
                out_specs=logical_to_spec(q_axes, rules),
                check_vma=False,
            )(*args)
        # Mesh doesn't divide heads/batch (or no such axes): single-program
        # path under GSPMD — fall through unsharded. Warn: under GSPMD the
        # unsharded pallas_call forces the whole page pool to be
        # replicated/resharded every decode step — a large silent perf/HBM
        # cliff on exactly the configs sharding exists for (ADVICE r2).
        if tp > 1 or dp > 1:
            import warnings

            warnings.warn(
                f"paged_attention: mesh given but not shardable (kv_heads="
                f"{kv_heads} vs tp={tp}/{tp_q}, batch={q.shape[0]} vs "
                f"dp={dp}); falling back to the unsharded kernel under "
                f"GSPMD — expect per-step pool resharding",
                stacklevel=2,
            )
    return _on_one_chip(
        q, k_pages, v_pages, page_table, lengths, tail_k=tail_k, tail_v=tail_v,
        starts=starts, k_scale=k_scale, v_scale=v_scale, steps=steps,
        interpret=interpret, window=window, group=group)


def _on_one_chip(q, k_pages, v_pages, page_table, lengths, *, tail_k, tail_v, starts,
                 k_scale, v_scale, steps, interpret, group, window=None):
    """``paged_attention`` on the arrays one chip holds: the ``pallas_call``."""
    multi_q = q.ndim == 4
    if multi_q:
        b, nq, h, d = q.shape
    else:
        b, h, d = q.shape
        nq = 1
    n_pool, kv_heads, ps, _ = k_pages.shape
    maxp = page_table.shape[1]
    groups = h // kv_heads
    if h % kv_heads:
        raise ValueError(f"q heads {h} not divisible by kv heads {kv_heads}")
    if multi_q and tail_k is None:
        raise ValueError(
            "multi-query paged_attention requires the tail path (the verify "
            "chunk's own KV lives in the tail buffer)"
        )
    if interpret is None:
        interpret = interpret_default()

    # (B, K, Q*G, D): one grid step's q block is ALL kv heads of one slot —
    # rows ordered query-major within a kv head (row = qi * G + g).
    qg = (
        q.reshape(b, nq, kv_heads, groups, d)
        .transpose(0, 2, 1, 3, 4)
        .reshape(b, kv_heads, nq * groups, d)
    )
    qg_rows = nq * groups
    has_tail = tail_k is not None
    scratch = _scratch(kv_heads, qg_rows, d)
    out_shape = jax.ShapeDtypeStruct((b, kv_heads, qg_rows, d), q.dtype)

    def out_4d(o):
        o = o.reshape(b, kv_heads, nq, groups, d).transpose(0, 2, 1, 3, 4)
        o = o.reshape(b, nq, h, d)
        return o if multi_q else o[:, 0]

    if has_tail:
        if steps is None:
            steps = decode_steps(starts, lengths > 0, page_size=ps, max_pages=maxp,
                                 window=window, group=group)
        slot_map, page_maps = walk_maps(ps, maxp, trailing=3, window=window, group=group)
        quantized = k_scale is not None
        # a pool is an operand once a page of the group, each with its own map
        pages = [pl.BlockSpec((1, kv_heads, ps, d), m) for m in page_maps]
        in_specs = [pl.BlockSpec((1, kv_heads, qg_rows, d), slot_map), *pages, *pages]
        args = [steps["rows"], steps["ks"], page_table, lengths, starts,
                qg, *[k_pages] * group, *[v_pages] * group]
        if quantized:
            in_specs += 2 * [pl.BlockSpec((1, kv_heads, 1, ps), m) for m in page_maps]
            args += [*[k_scale] * group, *[v_scale] * group]
        in_specs += [
            pl.BlockSpec((1, kv_heads, tail_k.shape[2], d), slot_map),
            pl.BlockSpec((1, kv_heads, tail_k.shape[2], d), slot_map),
        ]
        args += [tail_k, tail_v]
        out = pl.pallas_call(
            functools.partial(
                _paged_tail_kernel, scale=d**-0.5, page_size=ps, group=group,
                quantized=quantized, q_groups=groups if nq > 1 else None,
                window=window,
            ),
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=5,
                grid=(walk_length(steps),),
                in_specs=in_specs,
                out_specs=pl.BlockSpec((1, kv_heads, qg_rows, d), slot_map),
                scratch_shapes=scratch,
            ),
            out_shape=out_shape,
            # one axis, in the list's order: a row's steps follow each other
            compiler_params=pltpu.CompilerParams(dimension_semantics=("arbitrary",)),
            interpret=interpret,
            name="paged_attention",
        )(*args)
        return out_4d(zero_dead_rows(out, lengths))

    if k_scale is not None:
        raise ValueError(
            "paged_attention with k_scale/v_scale requires the tail path "
            "(tail_k/tail_v/starts) — the no-tail kernel would silently "
            "attend over raw int8 values"
        )
    out = pl.pallas_call(
        functools.partial(
            _paged_kernel, scale=d**-0.5, page_size=ps, n_pages=maxp
        ),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(b, maxp),
            in_specs=[
                pl.BlockSpec(
                    (1, kv_heads, groups, d), lambda ib, ip, tab, lens: (ib, 0, 0, 0)
                ),
                # Pages at or past the slot's length are redirected to the
                # sentinel page 0 (their compute is pl.when-skipped anyway):
                # consecutive identical block indices make Mosaic skip the
                # re-fetch, so a slot whose admission reserved max_new pages
                # only pays DMA for the pages actually written so far.
                pl.BlockSpec(
                    (1, kv_heads, ps, d),
                    lambda ib, ip, tab, lens: (
                        jnp.where(ip * ps < lens[ib], tab[ib, ip], 0), 0, 0, 0
                    ),
                ),
                pl.BlockSpec(
                    (1, kv_heads, ps, d),
                    lambda ib, ip, tab, lens: (
                        jnp.where(ip * ps < lens[ib], tab[ib, ip], 0), 0, 0, 0
                    ),
                ),
            ],
            out_specs=pl.BlockSpec(
                (1, kv_heads, groups, d), lambda ib, ip, tab, lens: (ib, 0, 0, 0)
            ),
            scratch_shapes=scratch,
        ),
        out_shape=out_shape,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")
        ),
        interpret=interpret,
        name="paged_attention",
    )(page_table, lengths, qg, k_pages, v_pages)
    return out.reshape(b, h, d)
