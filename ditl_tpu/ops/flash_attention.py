"""Blockwise FlashAttention as a Pallas (Mosaic) TPU kernel.

The reference has no attention code at all (its model lives behind an HTTP
API — ref ``src/distributed_inference.py:34-41``); this kernel is part of the
TPU-native compute path that replaces the reference's device op
(``src/utils.py:25-28``) with a real transformer forward.

Design (TPU-first):
- **O(S) memory**: online softmax over KV blocks; the (S, S) score matrix is
  never materialized in HBM. Residuals for the backward pass are ``o`` and the
  per-row log-sum-exp.
- **MXU tiling**: q/k/v are consumed in (block, head_dim) tiles; both matmuls
  (``q·kᵀ`` and ``p·v``) run on the MXU with f32 accumulation; the second
  matmul feeds ``p`` in the value dtype (bf16) for MXU throughput.
- **Lane-replicated row stats**: running max ``m`` and normalizer ``l`` are
  kept as (block_q, 128) with all lanes equal — row-broadcasts become free
  elementwise ops, avoiding sublane↔lane transposes Mosaic handles poorly.
  The log-sum-exp residual is stored lane-replicated the same way.
- **GQA-native**: H query heads share H//K KV heads; the KV block index map
  divides the head index, so KV tiles are fetched once per group.
- **Block skipping**: without segment ids the grid is the rectangle of
  (query block, key block) pairs, and a step whose block lies above the
  diagonal is predicated off with ``pl.when`` (the grid still visits it;
  compute and both matmuls are skipped). With them the wrappers reduce the
  ids, once a call and outside the kernels, to a ``(min, max)`` range for
  every query block and every key block (forward and backward tiles apart):
  a block is needed when it is causally reachable AND the two ranges meet
  (``q_min <= kv_max and kv_min <= q_max``). On packed rows as the loader
  makes them (ids 1-based and non-decreasing along a row) two blocks share a
  document exactly when their ranges meet, so the test is exact; for any
  other layout it is conservative, and a block it lets through is still
  masked element by element by ``_block_mask``. Rows of documents a tenth as
  long as the row keep 71% of the causal 512 x 512 blocks at 2,048 tokens and
  43% at 4,096; a row of 4,096 / 2,048 / 1,024 / 512 / 256 / 128 / 64 / 64
  tokens keeps 51 of 136 at 8,192.
- **The grid walks a work list**: with segment ids the needed blocks of the
  WHOLE call are laid out as one list (``_work_list``), built once a call
  outside the kernels and handed over as four scalar-prefetch operands
  (SMEM): an entry's row, its outer block, its inner block and three flags.
  The grid is ``(heads, entries)``, the second a dynamic bound, and every
  step computes: ``flash_fwd`` and ``flash_bwd_dq`` walk the needed (query
  block, key block) pairs row by row in row-major order, all of a query
  block's key blocks together and ascending; ``flash_bwd_dkv`` walks the
  transposed list, a key block's needed query blocks once for every query
  head of its GQA group. The index maps read a step's blocks from the list;
  a kernel clears its scratch on an entry flagged the FIRST of its outer
  block and writes its output on the LAST, so the output's block changes
  only between runs and is written once. An outer block that needs nothing
  (separate query and key ids only, below) keeps one entry that computes
  nothing, so its output is still written. One list over the batch has no
  padding: rows that need different counts cost what they need. The lists
  are a few hundred entries (``B x`` the causally reachable blocks at most,
  ``x`` the group for ``flash_bwd_dkv``: 4 x 136 = 544 at four rows of 8,192
  tokens, 16 x 10 x 7 = 1,120 at sixteen of 2,048 in groups of 7; 4 arrays
  of int32: 8.5 and 17.5 KiB of SMEM).
  History (PERF.md section 6, PR 40 and PR 59): the predicate alone took 7%
  off a layer's two forwards and backward at the 2,048-token cell's shapes;
  what a skipped step cost on the v5e was its copies and the copy it left
  exposed in front of the next walk. PR 40 walked the HULL of every outer
  block's needed blocks on an inner axis as long as the call's widest hull
  (-40 to -46%), the steps in front of a narrower hull visited and skipped
  at ~0.3 us each; one long document in a row of short ones made three steps
  in five such steps, which is what the list removes.
- **The same numbers**: a skipped block contributed exactly nothing. In both
  backward kernels ``p = exp(NEG_INF - lse)`` is 0; in the forward whatever a
  fully masked block adds to ``m``, ``l`` and ``acc`` is wiped by ``alpha =
  exp(m_prev - m_next) = 0`` at the row's first real key, and every row has
  one when queries and keys carry ONE id array (its own position). So
  outputs, ``lse`` and all three gradients are bit-equal to kernels that skip
  on causality alone. The one input on which they would differ is separate
  query and key ids under which some query row shares a segment with NO key:
  there the unskipped forward returns a mean of V and this one 0. The public
  ``flash_attention`` cannot reach it.
- **Two widths**: queries and keys are ``D`` wide, values and the output
  ``Dv`` (one width everywhere but latent attention decompressed: 192 / 128).
  ``D`` only ever meets itself in a contraction (``q.k^T``, ``ds.k``,
  ``ds^T.q``); the row statistics are tiled to ``Dv``. Nothing is padded.
- **Custom VJP**: backward runs two Pallas kernels — one accumulating dq over
  KV blocks, one accumulating dk/dv over (group × query) blocks — both
  recomputing p from the saved log-sum-exp (FlashAttention-2 style).

Layouts are (B, H, S, D) inside the kernels (callers pass (B, S, H, D); the
wrapper transposes — XLA fuses the transpose into neighboring ops).
Automatically runs in interpreter mode off-TPU so the same tests run on CPU.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["flash_attention"]

from ditl_tpu.ops.attention import NEG_INF  # single source of the mask value
from ditl_tpu.ops.backend import interpret_default

NUM_LANES = 128
NUM_SUBLANES = 8


class BlockSizes(NamedTuple):
    block_q: int
    block_kv: int


def _pick_blocks(s_q: int, s_kv: int, block_q: int, block_kv: int) -> BlockSizes:
    return BlockSizes(min(block_q, s_q), min(block_kv, s_kv))


def supports(s_q: int, s_kv: int, head_dim: int, block_q: int = 512,
             block_kv: int = 512, v_dim: int | None = None) -> bool:
    """True if the kernel can handle these shapes (off the TPU,
    ``ops.attention`` gives way to XLA otherwise; on it, it raises).
    ``head_dim`` is the queries' and keys' width, ``v_dim`` the values' and
    the output's where it differs (latent attention decompressed: 192 / 128)."""
    bq, bkv = _pick_blocks(s_q, s_kv, block_q, block_kv)
    v_dim = head_dim if v_dim is None else v_dim
    return (
        s_q % bq == 0
        and s_kv % bkv == 0
        and bkv % NUM_LANES == 0
        and bq % NUM_SUBLANES == 0
        # _lane_tile can slice (64) or tile whole lanes (128k), nothing else:
        # the width the row statistics are tiled to is the values'.
        and (v_dim == 64 or v_dim % NUM_LANES == 0)
        # q and k only meet in a contraction: whole or half lanes of 128
        and (head_dim == v_dim or head_dim % (NUM_LANES // 2) == 0)
    )


def _lane_tile(x: jax.Array, width: int) -> jax.Array:
    """Tile a lane-replicated (..., rows, 128) array to (..., rows, width)."""
    if width == NUM_LANES:
        return x
    if width < NUM_LANES:
        return x[..., :width]
    return jnp.tile(x, (1,) * (x.ndim - 1) + (width // NUM_LANES,))


def _block_mask(
    s: jax.Array,
    *,
    iq: jax.Array,
    ikv: jax.Array,
    block_q: int,
    block_kv: int,
    causal: bool,
    q_seg: jax.Array | None,
    kv_seg: jax.Array | None,
    window: int | None = None,
) -> jax.Array:
    """Apply causal + segment (+ window) masking to a (block_q, block_kv)
    score tile."""
    mask = None
    if causal:
        rows = iq * block_q + jax.lax.broadcasted_iota(
            jnp.int32, s.shape, dimension=0
        )
        cols = ikv * block_kv + jax.lax.broadcasted_iota(
            jnp.int32, s.shape, dimension=1
        )
        mask = rows >= cols
        if window is not None:  # a window layer: the last ``window`` keys only
            mask = jnp.logical_and(mask, rows - cols < window)
    if q_seg is not None:
        # q_seg: (block_q, 128) lane-replicated; kv_seg: (8, block_kv)
        # sublane-replicated. Tile q over lanes, slice kv's first sublane row
        # via broadcasting: both end up (block_q, block_kv).
        qs = _lane_tile(q_seg, s.shape[1])
        ks = kv_seg[:1, :]
        seg = qs == ks
        mask = seg if mask is None else jnp.logical_and(mask, seg)
    if mask is None:
        return s
    return jnp.where(mask, s, NEG_INF)


# ---------------------------------------------------------------------------
# Which blocks a packed row needs
# ---------------------------------------------------------------------------


def _block_ranges(seg: jax.Array, block: int) -> tuple[jax.Array, jax.Array]:
    """``(B, S)`` segment ids -> the (min, max) id of every ``block`` tokens,
    each ``(B, S // block)`` int32."""
    b, s = seg.shape
    tiles = seg.astype(jnp.int32).reshape(b, s // block, block)
    return tiles.min(axis=-1), tiles.max(axis=-1)


def _reachable(n_q: int, n_kv: int, blocks: BlockSizes, causal: bool,
               window: int | None = None) -> np.ndarray:
    """``(n_q, n_kv)`` bool, a constant of the shapes: the blocks causality
    leaves (all, if not causal); with a ``window``, of those the blocks that
    are not wholly behind it (the block's first query is nearer than
    ``window`` to the block's last key)."""
    iq = np.arange(n_q)[:, None]
    ikv = np.arange(n_kv)[None, :]
    reach = (iq + 1) * blocks.block_q - 1 >= ikv * blocks.block_kv
    if window is not None:
        reach &= iq * blocks.block_q - ((ikv + 1) * blocks.block_kv - 1) < window
    return reach if causal else np.ones_like(reach)


def _needed_blocks(q_rng, kv_rng, blocks: BlockSizes, causal: bool,
                   window: int | None = None) -> jax.Array:
    """``(B, n_q, n_kv)`` bool, the block predicate laid out whole: causally
    reachable, and the two blocks' id ranges meet."""
    (q_lo, q_hi), (kv_lo, kv_hi) = q_rng, kv_rng
    meet = jnp.logical_and(q_lo[:, :, None] <= kv_hi[:, None, :],
                           kv_lo[:, None, :] <= q_hi[:, :, None])
    reach = _reachable(q_lo.shape[1], kv_lo.shape[1], blocks, causal, window)
    return jnp.logical_and(meet, reach[None])


def _steps_walked(needed: jax.Array) -> jax.Array:
    """How many entries the work list of ``needed`` ``(B, outer, inner)`` has
    (int32 scalar): an outer block's needed inner blocks, and one for an
    outer block that needs none."""
    return jnp.sum(jnp.maximum(jnp.sum(needed, axis=-1, dtype=jnp.int32), 1))


def block_counts(
    segment_ids: jax.Array,  # (B, S)
    *,
    causal: bool = True,
    block_q: int = 512,
    block_kv: int = 512,
    window: int | None = None,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """``(reachable, needed, walked)``: how many blocks of the (query block,
    key block) rectangle causality leaves for this batch, a head; how many
    of those the predicate keeps; and how many grid steps the forward kernel
    takes over the batch, a head, which is the length of its work list (int32
    scalars). The arithmetic the kernels' operands are made with, for the
    trainer's counters: ``walked == needed`` wherever every query block needs
    a block, and under one id array for queries and keys each needs its own."""
    s = segment_ids.shape[1]
    blocks = _pick_blocks(s, s, block_q, block_kv)
    needed = _needed_blocks(
        _block_ranges(segment_ids, blocks.block_q),
        _block_ranges(segment_ids, blocks.block_kv), blocks, causal, window)
    reach = _reachable(*needed.shape[1:], blocks, causal)
    return (jnp.int32(segment_ids.shape[0] * int(reach.sum())),
            jnp.sum(needed, dtype=jnp.int32), _steps_walked(needed))


# What an entry of a work list says of itself (``flags``): it is the first or
# the last of its outer block's run, and its block is needed.
FIRST, LAST, NEEDED = 1, 2, 4


def _work_list(needed: jax.Array, size: int, folds: int = 1):
    """``(B, outer, inner)`` bool -> the work list of a walk over it and how
    many entries it has. The list is four int32 arrays ``(size,)``, an
    entry's ``rows``, ``outer`` and ``inner`` index and its ``flags``: the
    needed blocks in row-major order, so that an outer block's entries are
    adjacent and ascend. With ``folds`` an outer block's run is walked that
    many times over, fold ``f`` naming inner index ``f * n_inner + block``. An
    outer block that needs nothing keeps one entry a fold (inner block 0)
    without ``NEEDED``, so that its output is written. ``size`` is a static
    bound on the count; entries past the count are never walked.

    Dense arithmetic over small arrays, no sort, gather or scatter: a
    cumulative sum over the outer blocks' run lengths places every entry in
    its run (one comparison an entry and outer block), a cumulative sum
    along each outer block's inner axis finds the entry's block in it."""
    b, n_outer, n_inner = needed.shape
    blocks = needed.reshape(b * n_outer, n_inner)
    some = blocks.any(axis=1, keepdims=True)
    listed = jnp.concatenate([blocks[:, :1] | ~some, blocks[:, 1:]], axis=1)
    nth = jnp.cumsum(listed, axis=1, dtype=jnp.int32)
    ends = jnp.cumsum(folds * nth[:, -1])  # one past each run's last entry
    count = ends[-1]
    t = jnp.arange(size, dtype=jnp.int32)
    run = jnp.sum(ends[None, :] <= t[:, None], axis=1, dtype=jnp.int32)  # runs in front of t
    # the entry's run's own numbers: where it starts, whether it needs
    # anything, its inner blocks' ranks (the last is a fold's length)
    table = jnp.concatenate([(ends - folds * nth[:, -1])[:, None], some, nth], axis=1)
    mine = run[:, None] == jnp.arange(b * n_outer, dtype=jnp.int32)[None, :]
    start, some, nth = jnp.split(
        jnp.sum(jnp.where(mine[:, :, None], table[None], 0), axis=1), [1, 2], axis=1)
    length = nth[:, -1:]
    at = t[:, None] - start  # the entry's place in its run, and in its fold
    fold = jax.lax.div(at, jnp.maximum(length, 1))
    block = jnp.sum(nth <= at - fold * length, axis=1, dtype=jnp.int32)
    flags = FIRST * (at == 0) + LAST * (at == folds * length - 1) + NEEDED * some
    walked = t < count
    return tuple(jnp.where(walked, x, 0) for x in (
        jax.lax.div(run, n_outer), jax.lax.rem(run, n_outer),
        fold[:, 0] * n_inner + block, flags[:, 0])), count


def _work_lists(q_seg, kv_seg, blocks: BlockSizes, causal: bool,
                window: int | None = None, groups: int | None = None):
    """The kernels' scalar-prefetch operands and grid bounds: the list of
    the needed (query block, key block) pairs for the kernels that
    accumulate over key blocks (``flash_fwd``, ``flash_bwd_dq``) and, with
    ``groups``, the transposed list for ``flash_bwd_dkv`` behind it, whose
    outer block is a key block and whose inner index is ``group * n_q +
    iq``: a key block's needed query blocks once for each of the ``groups``
    query heads that share its kv head, the plain grid's folded axis with
    the unneeded steps left out."""
    needed = _needed_blocks(_block_ranges(q_seg, blocks.block_q),
                            _block_ranges(kv_seg, blocks.block_kv), blocks, causal, window)
    b, n_q, n_kv = needed.shape
    reach = _reachable(n_q, n_kv, blocks, causal, window)
    # no outer block lists more than it can reach, nor fewer than one
    over_kv, over_q = (b * int(np.maximum(reach.sum(axis), 1).sum()) for axis in (1, 0))
    lists = (_work_list(needed, over_kv),)
    if groups is not None:
        lists += (_work_list(jnp.swapaxes(needed, 1, 2), groups * over_q, groups),)
    return lists


def _grid_step(work, n_inner: int, *, causal: bool, block_q: int, block_kv: int,
               fold: int | None = None):
    """Where a grid step stands: ``(iq, ikv, first, last, needed)``, the
    blocks it names, whether it opens or closes its outer block's run, and
    its predicate (compute and both matmuls are skipped without it). Without
    a ``work`` list the grid is ``(B, H, outer, n_inner)``, every block of
    the rectangle is visited and, with causal masking, those strictly above
    the diagonal are not needed; with one it is ``(H, entries)``, the list
    says, and it holds needed blocks only (but the one entry of an outer
    block that needs none). ``fold`` as in ``_index_maps``."""
    if work is None:
        outer, inner = pl.program_id(2), pl.program_id(3)
        first, last, needed = inner == 0, inner == n_inner - 1, None
    else:
        _, outers, inners, flags = work
        t = pl.program_id(1)
        outer, inner, flag = outers[t], inners[t], flags[t]
        first, last, needed = flag & FIRST != 0, flag & LAST != 0, flag & NEEDED != 0
    iq, ikv = (outer, inner) if fold is None else (jax.lax.rem(inner, fold), outer)
    if needed is None:
        needed = (iq + 1) * block_q - 1 >= ikv * block_kv if causal else True
    return iq, ikv, first, last, needed


def _index_maps(work, groups: int, fold: int | None = None):
    """The index maps of a kernel's blocks: ``(q, kv, q ids, kv ids)``, for
    arrays laid out as q ``(B, H, S, .)``, k ``(B, K, S, .)`` and the
    broadcast ids. The grid's coordinates are ``(ib, head, outer, inner)``
    or, with a ``work`` list, ``(head, entry)`` and the list's refs behind
    them. Without ``fold`` the head is a query head, the outer block a query
    block and the inner a key block; with it (``flash_bwd_dkv``: the number
    of query blocks) the head is a kv head, the outer block a key block and
    the inner index ``group * fold + iq``."""

    def entry(*ids):
        if work is None:
            return ids
        head, t, rows, outers, inners, _ = ids
        return rows[t], head, outers[t], inners[t]

    def q_at(ib, head, outer, inner):
        if fold is None:
            return ib, head, outer
        return ib, head * groups + jax.lax.div(inner, fold), jax.lax.rem(inner, fold)

    def kv_at(ib, head, outer, inner):
        if fold is None:
            return ib, jax.lax.div(head, groups), inner
        return ib, head, outer

    def q_map(*ids):
        ib, ih, iq = q_at(*entry(*ids))
        return ib, ih, iq, 0

    def kv_map(*ids):
        ib, ikh, ikv = kv_at(*entry(*ids))
        return ib, ikh, ikv, 0

    def q_seg_map(*ids):
        ib, _, iq = q_at(*entry(*ids))
        return ib, iq, 0

    def kv_seg_map(*ids):
        ib, _, ikv = kv_at(*entry(*ids))
        return ib, 0, ikv

    return q_map, kv_map, q_seg_map, kv_seg_map


def _pallas(kernel, work, *, grid, in_specs, out_specs, scratch_shapes, **kw):
    """``pl.pallas_call``; with a ``work`` list, through a scalar-prefetch
    grid spec: its arrays reach the index maps as trailing arguments and the
    kernel as leading refs."""
    if work is None:
        return pl.pallas_call(
            kernel, grid=grid, in_specs=in_specs, out_specs=out_specs,
            scratch_shapes=scratch_shapes, **kw)

    def with_work(*refs):
        kernel(*refs[len(work):], work=refs[:len(work)])

    call = pl.pallas_call(
        with_work,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(work), grid=grid, in_specs=in_specs,
            out_specs=out_specs, scratch_shapes=scratch_shapes),
        **kw)
    return functools.partial(call, *work)


# ---------------------------------------------------------------------------
# Forward kernel
# ---------------------------------------------------------------------------


def _fwd_kernel(
    q_ref,
    k_ref,
    v_ref,
    q_seg_ref,
    kv_seg_ref,
    o_ref,
    lse_ref,
    m_scr,
    l_scr,
    acc_scr,
    *,
    scale: float,
    causal: bool,
    block_q: int,
    block_kv: int,
    n_kv: int,
    work=None,
    window: int | None = None,
):
    iq, ikv, first, last, needed = _grid_step(
        work, n_kv, causal=causal, block_q=block_q, block_kv=block_kv)

    @pl.when(first)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    @pl.when(needed)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32) * scale  # (block_q, D)
        k = k_ref[0, 0]  # (block_kv, D)
        s = jax.lax.dot_general(
            q,
            k.astype(jnp.float32),
            (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # (block_q, block_kv)
        s = _block_mask(
            s,
            iq=iq,
            ikv=ikv,
            block_q=block_q,
            block_kv=block_kv,
            causal=causal,
            q_seg=q_seg_ref[0] if q_seg_ref is not None else None,
            kv_seg=kv_seg_ref[0] if kv_seg_ref is not None else None,
            window=window,
        )

        m_prev = m_scr[...]  # (block_q, 128) lane-replicated
        l_prev = l_scr[...]
        m_cur = jnp.max(s, axis=1, keepdims=True)  # (block_q, 1)
        m_next = jnp.maximum(m_prev, m_cur)  # lane-replicated again
        alpha = jnp.exp(m_prev - m_next)
        p = jnp.exp(s - _lane_tile(m_next, block_kv))
        l_scr[...] = alpha * l_prev + jnp.sum(p, axis=1, keepdims=True)
        m_scr[...] = m_next

        v = v_ref[0, 0]  # (block_kv, D)
        pv = jax.lax.dot_general(
            p.astype(v.dtype),
            v,
            (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # (block_q, D)
        acc_scr[...] = acc_scr[...] * _lane_tile(alpha, acc_scr.shape[-1]) + pv

    @pl.when(last)
    def _finalize():
        l = l_scr[...]
        # Fully-masked rows have l == 0; emit 0 there instead of NaN.
        l_safe = jnp.where(l == 0.0, 1.0, l)
        o_ref[0, 0] = (
            acc_scr[...] / _lane_tile(l_safe, acc_scr.shape[-1])
        ).astype(o_ref.dtype)
        lse_ref[0, 0] = m_scr[...] + jnp.log(l_safe)


def _seg_operands(q_seg, kv_seg, blocks: BlockSizes, q_seg_map, kv_seg_map):
    """The ids as the kernels' masks read them, ``(specs, arrays)``: the
    queries' lane-replicated, the keys' sublane-replicated; a pair of Nones
    without ids."""
    if q_seg is None:
        return [None, None], [None, None]
    (b, s_q), s_kv = q_seg.shape, kv_seg.shape[1]
    return (
        [pl.BlockSpec((1, blocks.block_q, NUM_LANES), q_seg_map),
         pl.BlockSpec((1, NUM_SUBLANES, blocks.block_kv), kv_seg_map)],
        [jax.lax.broadcast_in_dim(q_seg, (b, s_q, NUM_LANES), (0, 1)),
         jax.lax.broadcast_in_dim(kv_seg, (b, NUM_SUBLANES, s_kv), (0, 2))],
    )


def _semantics(work):
    """Every axis parallel but the last, along which a kernel accumulates."""
    axes = 4 if work is None else 2
    return pltpu.CompilerParams(
        dimension_semantics=("parallel",) * (axes - 1) + ("arbitrary",))


def _fwd(
    q: jax.Array,  # (B, H, Sq, D)
    k: jax.Array,  # (B, K, Skv, D)
    v: jax.Array,
    q_seg: jax.Array | None,  # (B, Sq)
    kv_seg: jax.Array | None,  # (B, Skv)
    *,
    causal: bool,
    scale: float,
    blocks: BlockSizes,
    interpret: bool,
    window: int | None = None,
) -> tuple[jax.Array, jax.Array]:
    b, h, s_q, d = q.shape
    _, kv_heads, s_kv, _ = k.shape
    dv = v.shape[-1]  # the values' and the output's width; d is q's and k's
    groups = h // kv_heads
    bq, bkv = blocks
    n_q, n_kv = s_q // bq, s_kv // bkv
    if window is not None and q_seg is None:
        # the window's clause rides the work list: one document a row lists
        # the blocks that meet the window and no others
        q_seg = jnp.ones((b, s_q), jnp.int32)
        kv_seg = jnp.ones((b, s_kv), jnp.int32)
    work, grid = None, (b, h, n_q, n_kv)
    if q_seg is not None:
        ((work, count),) = _work_lists(q_seg, kv_seg, blocks, causal, window)
        grid = (h, count)
    q_map, kv_map, q_seg_map, kv_seg_map = _index_maps(work, groups)
    seg_specs, seg_args = _seg_operands(q_seg, kv_seg, blocks, q_seg_map, kv_seg_map)

    kernel = functools.partial(
        _fwd_kernel,
        scale=scale,
        causal=causal,
        block_q=bq,
        block_kv=bkv,
        n_kv=n_kv,
        window=window,
    )
    o, lse = _pallas(
        kernel,
        work,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, bq, d), q_map),
            pl.BlockSpec((1, 1, bkv, d), kv_map),
            pl.BlockSpec((1, 1, bkv, dv), kv_map),
            *seg_specs,
        ],
        out_specs=(
            pl.BlockSpec((1, 1, bq, dv), q_map),
            pl.BlockSpec((1, 1, bq, NUM_LANES), q_map),
        ),
        out_shape=(
            jax.ShapeDtypeStruct((b, h, s_q, dv), q.dtype),
            jax.ShapeDtypeStruct((b, h, s_q, NUM_LANES), jnp.float32),
        ),
        scratch_shapes=[
            pltpu.VMEM((bq, NUM_LANES), jnp.float32),  # m
            pltpu.VMEM((bq, NUM_LANES), jnp.float32),  # l
            pltpu.VMEM((bq, dv), jnp.float32),  # acc
        ],
        compiler_params=_semantics(work),
        interpret=interpret,
        name="flash_fwd",
    )(q, k, v, *seg_args)
    return o, lse


# ---------------------------------------------------------------------------
# Backward kernels
# ---------------------------------------------------------------------------


def _dq_kernel(
    q_ref,
    k_ref,
    v_ref,
    do_ref,
    lse_ref,
    delta_ref,
    q_seg_ref,
    kv_seg_ref,
    dq_ref,
    dq_scr,
    *,
    scale: float,
    causal: bool,
    block_q: int,
    block_kv: int,
    n_kv: int,
    work=None,
):
    iq, ikv, first, last, needed = _grid_step(
        work, n_kv, causal=causal, block_q=block_q, block_kv=block_kv)

    @pl.when(first)
    def _init():
        dq_scr[...] = jnp.zeros_like(dq_scr)

    @pl.when(needed)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32) * scale
        k = k_ref[0, 0].astype(jnp.float32)
        v = v_ref[0, 0].astype(jnp.float32)
        do = do_ref[0, 0].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )
        s = _block_mask(
            s,
            iq=iq,
            ikv=ikv,
            block_q=block_q,
            block_kv=block_kv,
            causal=causal,
            q_seg=q_seg_ref[0] if q_seg_ref is not None else None,
            kv_seg=kv_seg_ref[0] if kv_seg_ref is not None else None,
        )
        p = jnp.exp(s - _lane_tile(lse_ref[0, 0], block_kv))
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )
        ds = p * (dp - _lane_tile(delta_ref[0, 0], block_kv))
        dq_scr[...] += scale * jax.lax.dot_general(
            ds, k, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )

    @pl.when(last)
    def _finalize():
        dq_ref[0, 0] = dq_scr[...].astype(dq_ref.dtype)


def _dkv_kernel(
    q_ref,
    k_ref,
    v_ref,
    do_ref,
    lse_ref,
    delta_ref,
    q_seg_ref,
    kv_seg_ref,
    dk_ref,
    dv_ref,
    dk_scr,
    dv_scr,
    *,
    scale: float,
    causal: bool,
    block_q: int,
    block_kv: int,
    n_q: int,
    n_inner: int,
    work=None,
):
    """Grid (B, K, n_kv, groups * n_q), or (K, entries) of a work list whose
    inner index is the first grid's: the innermost (sequential) dim folds
    the GQA group loop into the q loop so dk/dv accumulation is race-free."""
    iq, ikv, first, last, needed = _grid_step(
        work, n_inner, causal=causal, block_q=block_q, block_kv=block_kv, fold=n_q)

    @pl.when(first)
    def _init():
        dk_scr[...] = jnp.zeros_like(dk_scr)
        dv_scr[...] = jnp.zeros_like(dv_scr)

    @pl.when(needed)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32) * scale
        k = k_ref[0, 0].astype(jnp.float32)
        v = v_ref[0, 0].astype(jnp.float32)
        do = do_ref[0, 0].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )
        s = _block_mask(
            s,
            iq=iq,
            ikv=ikv,
            block_q=block_q,
            block_kv=block_kv,
            causal=causal,
            q_seg=q_seg_ref[0] if q_seg_ref is not None else None,
            kv_seg=kv_seg_ref[0] if kv_seg_ref is not None else None,
        )
        p = jnp.exp(s - _lane_tile(lse_ref[0, 0], block_kv))
        # dv += pᵀ @ do
        dv_scr[...] += jax.lax.dot_general(
            p, do, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )
        ds = p * (dp - _lane_tile(delta_ref[0, 0], block_kv))
        # dk = scale·dsᵀ@q_unscaled = dsᵀ@q_scaled (q was pre-scaled above).
        dk_scr[...] += jax.lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )

    @pl.when(last)
    def _finalize():
        dk_ref[0, 0] = dk_scr[...].astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_scr[...].astype(dv_ref.dtype)


def _bwd_impl(
    q,
    k,
    v,
    q_seg,
    kv_seg,
    o,
    lse,
    do,
    *,
    causal: bool,
    scale: float,
    blocks: BlockSizes,
    interpret: bool,
):
    b, h, s_q, d = q.shape
    _, kv_heads, s_kv, _ = k.shape
    dv = v.shape[-1]  # v, o, do and dv; d is q, k, dq and dk
    groups = h // kv_heads
    bq, bkv = blocks
    n_q, n_kv = s_q // bq, s_kv // bkv
    n_inner = groups * n_q  # the dk/dv grid's folded axis

    # delta_i = rowsum(do ⊙ o): cheap elementwise+reduce, XLA fuses it.
    delta = jnp.sum(
        do.astype(jnp.float32) * o.astype(jnp.float32), axis=-1
    )  # (B, H, Sq)
    delta = jax.lax.broadcast_in_dim(
        delta, (b, h, s_q, NUM_LANES), (0, 1, 2)
    )

    work = work_dkv = None
    grid, grid_dkv = (b, h, n_q, n_kv), (b, kv_heads, n_kv, n_inner)
    if q_seg is not None:
        (work, count), (work_dkv, count_dkv) = _work_lists(
            q_seg, kv_seg, blocks, causal, groups=groups)
        grid, grid_dkv = (h, count), (kv_heads, count_dkv)

    # dk = scale·dsᵀq_unscaled = dsᵀ(scale·q): pre-scaling q once inside the
    # kernels folds the scale into both s and dk, so no post-multiply needed.

    def operands(work, fold=None):
        """Both kernels read the same eight arrays; q, dO, lse and delta in
        query blocks, k and v in key blocks."""
        q_map, kv_map, q_seg_map, kv_seg_map = _index_maps(work, groups, fold)
        seg_specs, seg_args = _seg_operands(q_seg, kv_seg, blocks, q_seg_map, kv_seg_map)
        specs = [
            pl.BlockSpec((1, 1, bq, d), q_map),
            pl.BlockSpec((1, 1, bkv, d), kv_map),
            pl.BlockSpec((1, 1, bkv, dv), kv_map),
            pl.BlockSpec((1, 1, bq, dv), q_map),
            pl.BlockSpec((1, 1, bq, NUM_LANES), q_map),
            pl.BlockSpec((1, 1, bq, NUM_LANES), q_map),
            *seg_specs,
        ]
        return specs, (q, k, v, do, lse, delta, *seg_args), q_map, kv_map

    # ---- dq: accumulate over kv blocks ----
    specs, args, q_map, _ = operands(work)
    dq = _pallas(
        functools.partial(
            _dq_kernel,
            scale=scale,
            causal=causal,
            block_q=bq,
            block_kv=bkv,
            n_kv=n_kv,
        ),
        work,
        grid=grid,
        in_specs=specs,
        out_specs=pl.BlockSpec((1, 1, bq, d), q_map),
        out_shape=jax.ShapeDtypeStruct((b, h, s_q, d), q.dtype),
        scratch_shapes=[pltpu.VMEM((bq, d), jnp.float32)],
        compiler_params=_semantics(work),
        interpret=interpret,
        name="flash_bwd_dq",
    )(*args)

    # ---- dk/dv: accumulate over (group, q block) ----
    specs, args, _, kv_map = operands(work_dkv, fold=n_q)
    dk, dv = _pallas(
        functools.partial(
            _dkv_kernel,
            scale=scale,
            causal=causal,
            block_q=bq,
            block_kv=bkv,
            n_q=n_q,
            n_inner=n_inner,
        ),
        work_dkv,
        grid=grid_dkv,
        in_specs=specs,
        out_specs=(
            pl.BlockSpec((1, 1, bkv, d), kv_map),
            pl.BlockSpec((1, 1, bkv, dv), kv_map),
        ),
        out_shape=(
            jax.ShapeDtypeStruct((b, kv_heads, s_kv, d), k.dtype),
            jax.ShapeDtypeStruct((b, kv_heads, s_kv, dv), v.dtype),
        ),
        scratch_shapes=[
            pltpu.VMEM((bkv, d), jnp.float32),
            pltpu.VMEM((bkv, dv), jnp.float32),
        ],
        interpret=interpret,
        name="flash_bwd_dkv",
    )(*args)
    return dq, dk, dv


# ---------------------------------------------------------------------------
# custom_vjp plumbing (on (B, H, S, D) layouts)
# ---------------------------------------------------------------------------


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8, 9))
def _flash_bhsd(q, k, v, q_seg, kv_seg, causal, scale, blocks, blocks_bwd,
                interpret):
    o, _ = _fwd(
        q, k, v, q_seg, kv_seg,
        causal=causal, scale=scale, blocks=blocks, interpret=interpret,
    )
    return o


def _flash_bhsd_fwd(q, k, v, q_seg, kv_seg, causal, scale, blocks, blocks_bwd,
                    interpret):
    o, lse = _fwd(
        q, k, v, q_seg, kv_seg,
        causal=causal, scale=scale, blocks=blocks, interpret=interpret,
    )
    return o, (q, k, v, q_seg, kv_seg, o, lse)


def _flash_bhsd_bwd(causal, scale, blocks, blocks_bwd, interpret, residuals, do):
    q, k, v, q_seg, kv_seg, o, lse = residuals
    dq, dk, dv = _bwd_impl(
        q, k, v, q_seg, kv_seg, o, lse, do,
        causal=causal, scale=scale, blocks=blocks_bwd, interpret=interpret,
    )
    return dq, dk, dv, None, None


_flash_bhsd.defvjp(_flash_bhsd_fwd, _flash_bhsd_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8))
def _flash_window(q, k, v, q_seg, kv_seg, window, scale, blocks, interpret):
    """The forward kernel with a window clause (a serving prefill, a forward
    pass that nobody differentiates)."""
    return _fwd(q, k, v, q_seg, kv_seg, causal=True, scale=scale, blocks=blocks,
                interpret=interpret, window=window)[0]


def _flash_window_fwd(q, k, v, q_seg, kv_seg, window, scale, blocks, interpret):
    return _flash_window(q, k, v, q_seg, kv_seg, window, scale, blocks, interpret), None


def _flash_window_bwd(window, scale, blocks, interpret, residuals, do):
    raise NotImplementedError(
        "flash attention's backward kernels have no window clause "
        "(ops/flash_attention.py): train a stack with window attention layers "
        "under attention_impl='xla'")


_flash_window.defvjp(_flash_window_fwd, _flash_window_bwd)


def flash_attention(
    q: jax.Array,  # (B, S, H, D)
    k: jax.Array,  # (B, S, K, D)
    v: jax.Array,  # (B, S, K, Dv); Dv may differ from D (192 / 128)
    *,
    causal: bool = True,
    segment_ids: jax.Array | None = None,  # (B, S) int32
    block_q: int = 512,
    block_kv: int = 512,
    block_q_bwd: int = 0,
    block_kv_bwd: int = 0,
    interpret: bool | None = None,
    window: int | None = None,
) -> jax.Array:
    """FlashAttention with GQA + sequence-packing segment masks.

    ``window`` (static, causal only): query i sees key j iff ``0 <= i - j <
    window``. A clause of the block predicate (a block wholly behind the
    window is not visited: the hull of a query block's walk is about ``window
    / block_kv + 1`` blocks whatever the sequence's length) and of the
    in-block mask. Forward only: differentiating it raises.

    Takes/returns the model's (B, S, H, D) layout. Raises ``ValueError`` on
    shapes the kernel cannot tile. ``block_*_bwd`` size the backward kernels'
    tiles independently (0 = same as forward).

    ``segment_ids`` serve queries and keys alike, so every row attends at
    least to itself: that is what lets the kernels skip a block whose queries
    and keys share no id without changing a bit of the result (module
    docstring). ``None`` runs the kernels without the skip operands.
    """
    b, s_q, h, d = q.shape
    _, s_kv, kv_heads, _ = k.shape
    block_q_bwd = block_q_bwd or block_q
    block_kv_bwd = block_kv_bwd or block_kv
    if h % kv_heads:
        raise ValueError(f"q heads {h} not divisible by kv heads {kv_heads}")
    dv = v.shape[-1]
    if not (supports(s_q, s_kv, d, block_q, block_kv, dv)
            and supports(s_q, s_kv, d, block_q_bwd, block_kv_bwd, dv)):
        raise ValueError(
            f"flash_attention cannot tile Sq={s_q} Skv={s_kv} D={d} Dv={dv} "
            f"(block_q={block_q}, block_kv={block_kv}, "
            f"bwd {block_q_bwd}/{block_kv_bwd})"
        )
    blocks = _pick_blocks(s_q, s_kv, block_q, block_kv)
    blocks_bwd = _pick_blocks(s_q, s_kv, block_q_bwd, block_kv_bwd)
    if interpret is None:
        interpret = interpret_default()

    qt = jnp.transpose(q, (0, 2, 1, 3))
    kt = jnp.transpose(k, (0, 2, 1, 3))
    vt = jnp.transpose(v, (0, 2, 1, 3))
    if window is not None:
        if not causal:
            raise ValueError("a window is a clause of the causal mask")
        o = _flash_window(qt, kt, vt, segment_ids, segment_ids, window, d**-0.5, blocks,
                          interpret)
        return jnp.transpose(o, (0, 2, 1, 3))
    o = _flash_bhsd(
        qt, kt, vt, segment_ids, segment_ids,
        causal, d**-0.5, blocks, blocks_bwd, interpret,
    )
    return jnp.transpose(o, (0, 2, 1, 3))
