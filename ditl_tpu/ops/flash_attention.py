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
- **Block skipping**: a grid step whose block is fully masked is predicated
  off with ``pl.when`` (the grid still visits it; compute and both matmuls are
  skipped). Without segment ids that is causality's test alone. With them the
  wrappers reduce the ids, once a call and outside the kernels, to a
  ``(min, max)`` range for every query block and every key block (forward and
  backward tiles apart) and hand the kernels those as scalar-prefetch operands
  (SMEM): a block is needed when it is causally reachable AND the two ranges
  meet (``q_min <= kv_max and kv_min <= q_max``), a few scalar reads a step.
  On packed rows as the loader makes them (ids 1-based and non-decreasing
  along a row) two blocks share a document exactly when their ranges meet, so
  the test is exact; for any other layout it is conservative, and a block it
  lets through is still masked element by element by ``_block_mask``. Rows of
  documents a tenth as long as the row keep 71% of the causal 512 x 512
  blocks at 2,048 tokens and 43% at 4,096.
- **The inner axis walks the hull**: three more prefetch operands a kernel
  are the hull ``[first, last]`` of every outer block's needed inner blocks
  (key blocks for ``flash_fwd`` / ``flash_bwd_dq``; query blocks for
  ``flash_bwd_dkv``) and the widest hull of the call. The inner grid axis
  takes that many steps and no more (a dynamic grid bound: 2-3 on the
  trainer's rows where the axis has 4 or 8 blocks), and the index maps lay
  every walk so that it ENDS on its last needed block; the steps in front of
  a narrower hull name its first block, which is then already in VMEM when
  it is needed, so a skipped step copies nothing: neither K/V nor, in
  ``flash_bwd_dkv``, q, dO, lse and delta. On the v5e a skipped step's cost
  was its copies and the copies it left exposed: the predicate alone takes
  7% off a layer's two forwards and backward at the 2,048-token cell's
  shapes, copying nothing for skipped steps 25%, ending each walk on a
  needed block (the next walk's first copy then runs under compute) 35%,
  and visiting only the widest hull 40-46% (PERF.md section 6, PR 40).
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
               window: int | None = None) -> jax.Array:
    """``(n_q, n_kv)`` bool: the blocks causality leaves (all, if not causal);
    with a ``window``, of those the blocks that are not wholly behind it (the
    block's first query is nearer than ``window`` to the block's last key)."""
    iq = jnp.arange(n_q, dtype=jnp.int32)[:, None]
    ikv = jnp.arange(n_kv, dtype=jnp.int32)[None, :]
    reach = (iq + 1) * blocks.block_q - 1 >= ikv * blocks.block_kv
    if window is not None:
        reach = jnp.logical_and(
            reach, iq * blocks.block_q - ((ikv + 1) * blocks.block_kv - 1) < window)
    return reach if causal else jnp.ones_like(reach)


def _needed_blocks(q_rng, kv_rng, blocks: BlockSizes, causal: bool,
                   window: int | None = None) -> jax.Array:
    """``(B, n_q, n_kv)`` bool, the kernels' block predicate laid out whole:
    causally reachable, and the two blocks' id ranges meet."""
    (q_lo, q_hi), (kv_lo, kv_hi) = q_rng, kv_rng
    meet = jnp.logical_and(q_lo[:, :, None] <= kv_hi[:, None, :],
                           kv_lo[:, None, :] <= q_hi[:, :, None])
    reach = _reachable(q_lo.shape[1], kv_lo.shape[1], blocks, causal, window)
    return jnp.logical_and(meet, reach[None])


def _hull(needed: jax.Array) -> tuple[jax.Array, jax.Array]:
    """First and last needed index along the last axis (int32). A row that
    needs nothing keeps the whole axis: its steps are predicated off anyway."""
    n = needed.shape[-1]
    first = jnp.argmax(needed, axis=-1).astype(jnp.int32)
    last = (n - 1 - jnp.argmax(needed[..., ::-1], axis=-1)).astype(jnp.int32)
    return first, last


def block_counts(
    segment_ids: jax.Array,  # (B, S)
    *,
    causal: bool = True,
    block_q: int = 512,
    block_kv: int = 512,
    window: int | None = None,
) -> tuple[jax.Array, jax.Array]:
    """``(reachable, needed)``: how many blocks of the forward kernel's grid
    causality leaves for this batch, a head, and how many of those the
    predicate keeps (int32 scalars). The arithmetic the kernels' operands are
    made with, for the trainer's counter."""
    s = segment_ids.shape[1]
    blocks = _pick_blocks(s, s, block_q, block_kv)
    needed = _needed_blocks(
        _block_ranges(segment_ids, blocks.block_q),
        _block_ranges(segment_ids, blocks.block_kv), blocks, causal, window)
    reach = _reachable(*needed.shape[1:], blocks, causal)
    return (segment_ids.shape[0] * jnp.sum(reach, dtype=jnp.int32),
            jnp.sum(needed, dtype=jnp.int32))


def _skip_operands(q_seg, kv_seg, blocks: BlockSizes, causal: bool,
                   window: int | None = None):
    """The kernels' scalar-prefetch operands, seven int32 arrays: the id
    ranges of the query blocks ``(B, n_q)`` and of the key blocks
    ``(B, n_kv)``, the hull ``[first, last]`` of each query block's needed
    key blocks, and the widest hull of the call ``(1,)``, which is how many
    steps the inner grid axis takes. Returned twice: for the kernels whose
    inner axis walks key blocks, and with the hulls of each KEY block's
    needed query blocks ``(B, n_kv)`` for the dk/dv kernel, whose inner axis
    walks query blocks."""
    q_rng = _block_ranges(q_seg, blocks.block_q)
    kv_rng = _block_ranges(kv_seg, blocks.block_kv)
    needed = _needed_blocks(q_rng, kv_rng, blocks, causal, window)

    def operands(needed):
        first, last = _hull(needed)
        return (*q_rng, *kv_rng, first, last,
                jnp.max(last - first + 1).reshape(1))

    return operands(needed), operands(jnp.swapaxes(needed, 1, 2))


def _walk(step, ib, outer, n: int, skip):
    """Where a step of the inner grid axis stands: ``(block, inside, last
    step)``. Without ``skip`` operands the axis is the ``n`` blocks. With
    them it is as long as the call's widest hull and ENDS on the outer
    block's last needed block: the steps in front of a narrower hull are
    outside it, and name its first block, so nothing is copied for them.
    Ending a walk on a needed block matters: the pipeline starts the copy of
    the next walk's first blocks one step ahead, and under a skipped step
    that copy has no compute to hide behind."""
    if not skip:
        return step, True, n - 1
    first, last, width = skip[4:]
    at = last[ib, outer] - (width[0] - 1 - step)
    return jnp.maximum(at, first[ib, outer]), at >= first[ib, outer], width[0] - 1


def _unfold(inner, n: int, skip):
    """``(group, step)`` of the dk/dv kernel's inner axis, which folds the
    GQA group loop into the walk over query blocks (``n`` steps a group, or
    the widest hull's with ``skip``)."""
    if not skip:
        return inner // n, inner % n
    width = skip[6][0]
    return jax.lax.div(inner, width), jax.lax.rem(inner, width)


def _block_needed(ib, iq, ikv, inside, *, causal: bool, block_q: int,
                  block_kv: int, skip, window: int | None = None):
    """The grid step's predicate. With causal masking, blocks strictly above
    the diagonal contribute nothing; with ``skip`` (SMEM refs,
    ``_skip_operands``), neither do blocks whose id ranges do not meet, nor
    a step outside its hull (``_walk``). Compute and both matmuls are
    skipped."""
    needed = (iq + 1) * block_q - 1 >= ikv * block_kv if causal else True
    if window is not None and causal:  # a block wholly behind the window
        needed = jnp.logical_and(needed, iq * block_q - ((ikv + 1) * block_kv - 1) < window)
    if skip is None:
        return needed
    q_lo, q_hi, kv_lo, kv_hi = skip[:4]
    meet = jnp.logical_and(q_lo[ib, iq] <= kv_hi[ib, ikv],
                           kv_lo[ib, ikv] <= q_hi[ib, iq])
    meet = jnp.logical_and(inside, meet)
    return meet if needed is True else jnp.logical_and(needed, meet)


def _pallas(kernel, skip, *, grid, in_specs, out_specs, scratch_shapes, **kw):
    """``pl.pallas_call``; with ``skip`` operands, through a scalar-prefetch
    grid spec: they reach the index maps as trailing arguments and the kernel
    as leading refs."""
    if skip is None:
        return pl.pallas_call(
            kernel, grid=grid, in_specs=in_specs, out_specs=out_specs,
            scratch_shapes=scratch_shapes, **kw)

    def with_skip(*refs):
        kernel(*refs[len(skip):], skip=refs[:len(skip)])

    call = pl.pallas_call(
        with_skip,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(skip), grid=grid, in_specs=in_specs,
            out_specs=out_specs, scratch_shapes=scratch_shapes),
        **kw)
    return functools.partial(call, *skip)


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
    skip=None,
    window: int | None = None,
):
    ib = pl.program_id(0)
    iq = pl.program_id(2)
    step = pl.program_id(3)
    ikv, inside, last_step = _walk(step, ib, iq, n_kv, skip)

    @pl.when(step == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    needed = _block_needed(ib, iq, ikv, inside, causal=causal, block_q=block_q,
                           block_kv=block_kv, skip=skip,
                           window=window)

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

    @pl.when(step == last_step)
    def _finalize():
        l = l_scr[...]
        # Fully-masked rows have l == 0; emit 0 there instead of NaN.
        l_safe = jnp.where(l == 0.0, 1.0, l)
        o_ref[0, 0] = (
            acc_scr[...] / _lane_tile(l_safe, acc_scr.shape[-1])
        ).astype(o_ref.dtype)
        lse_ref[0, 0] = m_scr[...] + jnp.log(l_safe)


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
        # the hull of a query block's needed key blocks rides the skip
        # operands: one document a row gives the window's hull alone
        q_seg = jnp.ones((b, s_q), jnp.int32)
        kv_seg = jnp.ones((b, s_kv), jnp.int32)
    skip = (None if q_seg is None
            else _skip_operands(q_seg, kv_seg, blocks, causal, window)[0])
    grid = (b, h, n_q, n_kv if skip is None else skip[6][0])

    def q_map(ib, ih, iq, step, *skip):
        return (ib, ih, iq, 0)

    def kv_map(ib, ih, iq, step, *skip):
        return (ib, ih // groups, _walk(step, ib, iq, n_kv, skip)[0], 0)

    in_specs = [
        pl.BlockSpec((1, 1, bq, d), q_map),
        pl.BlockSpec((1, 1, bkv, d), kv_map),
        pl.BlockSpec((1, 1, bkv, dv), kv_map),
    ]
    args = [q, k, v]
    if q_seg is not None:
        in_specs.append(
            pl.BlockSpec((1, bq, NUM_LANES),
                         lambda ib, ih, iq, ikv, *skip: (ib, iq, 0))
        )
        in_specs.append(
            pl.BlockSpec(
                (1, NUM_SUBLANES, bkv),
                lambda ib, ih, iq, step, *skip: (
                    ib, 0, _walk(step, ib, iq, n_kv, skip)[0]),
            )
        )
        args.append(
            jax.lax.broadcast_in_dim(q_seg, (b, s_q, NUM_LANES), (0, 1))
        )
        args.append(
            jax.lax.broadcast_in_dim(kv_seg, (b, NUM_SUBLANES, s_kv), (0, 2))
        )
    else:
        in_specs += [None, None]
        args += [None, None]

    kernel = functools.partial(
        _fwd_kernel,
        scale=scale,
        causal=causal,
        block_q=bq,
        block_kv=bkv,
        n_kv=n_kv,
        window=window,
    )
    out_shapes = (
        jax.ShapeDtypeStruct((b, h, s_q, dv), q.dtype),
        jax.ShapeDtypeStruct((b, h, s_q, NUM_LANES), jnp.float32),
    )
    out_specs = (
        pl.BlockSpec((1, 1, bq, dv), q_map),
        pl.BlockSpec((1, 1, bq, NUM_LANES), q_map),
    )
    o, lse = _pallas(
        kernel,
        skip,
        grid=grid,
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shapes,
        scratch_shapes=[
            pltpu.VMEM((bq, NUM_LANES), jnp.float32),  # m
            pltpu.VMEM((bq, NUM_LANES), jnp.float32),  # l
            pltpu.VMEM((bq, dv), jnp.float32),  # acc
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel", "arbitrary")
        ),
        interpret=interpret,
        name="flash_fwd",
    )(*args)
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
    skip=None,
):
    ib = pl.program_id(0)
    iq = pl.program_id(2)
    step = pl.program_id(3)
    ikv, inside, last_step = _walk(step, ib, iq, n_kv, skip)

    @pl.when(step == 0)
    def _init():
        dq_scr[...] = jnp.zeros_like(dq_scr)

    needed = _block_needed(ib, iq, ikv, inside, causal=causal, block_q=block_q,
                           block_kv=block_kv, skip=skip)

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

    @pl.when(step == last_step)
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
    skip=None,
):
    """Grid (B, K, n_kv, groups * n_q): the innermost (sequential) dim folds
    the GQA group loop into the q loop so dk/dv accumulation is race-free."""
    ib = pl.program_id(0)
    ikv = pl.program_id(2)
    inner = pl.program_id(3)
    iq, inside, _ = _walk(_unfold(inner, n_q, skip)[1], ib, ikv, n_q, skip)

    @pl.when(inner == 0)
    def _init():
        dk_scr[...] = jnp.zeros_like(dk_scr)
        dv_scr[...] = jnp.zeros_like(dv_scr)

    needed = _block_needed(ib, iq, ikv, inside, causal=causal, block_q=block_q,
                           block_kv=block_kv, skip=skip)

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

    @pl.when(inner == (n_inner if skip is None else pl.num_programs(3)) - 1)
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

    # delta_i = rowsum(do ⊙ o): cheap elementwise+reduce, XLA fuses it.
    delta = jnp.sum(
        do.astype(jnp.float32) * o.astype(jnp.float32), axis=-1
    )  # (B, H, Sq)
    delta = jax.lax.broadcast_in_dim(
        delta, (b, h, s_q, NUM_LANES), (0, 1, 2)
    )

    seg_args = [None, None]
    skip = skip_dkv = None
    if q_seg is not None:
        q_seg_b = jax.lax.broadcast_in_dim(q_seg, (b, s_q, NUM_LANES), (0, 1))
        kv_seg_b = jax.lax.broadcast_in_dim(
            kv_seg, (b, NUM_SUBLANES, s_kv), (0, 2)
        )
        seg_args = [q_seg_b, kv_seg_b]
        skip, skip_dkv = _skip_operands(q_seg, kv_seg, blocks, causal)

    # dk = scale·dsᵀq_unscaled = dsᵀ(scale·q): pre-scaling q once inside the
    # kernels folds the scale into both s and dk, so no post-multiply needed.

    # ---- dq: grid (B, H, n_q, n_kv), accumulate over kv blocks ----
    def q_map(ib, ih, iq, step, *skip):
        return (ib, ih, iq, 0)

    def kv_map(ib, ih, iq, step, *skip):
        return (ib, ih // groups, _walk(step, ib, iq, n_kv, skip)[0], 0)

    dq_in_specs = [
        pl.BlockSpec((1, 1, bq, d), q_map),
        pl.BlockSpec((1, 1, bkv, d), kv_map),
        pl.BlockSpec((1, 1, bkv, dv), kv_map),
        pl.BlockSpec((1, 1, bq, dv), q_map),
        pl.BlockSpec((1, 1, bq, NUM_LANES), q_map),
        pl.BlockSpec((1, 1, bq, NUM_LANES), q_map),
    ]
    if q_seg is not None:
        dq_in_specs.append(
            pl.BlockSpec((1, bq, NUM_LANES),
                         lambda ib, ih, iq, ikv, *skip: (ib, iq, 0))
        )
        dq_in_specs.append(
            pl.BlockSpec(
                (1, NUM_SUBLANES, bkv),
                lambda ib, ih, iq, step, *skip: (
                    ib, 0, _walk(step, ib, iq, n_kv, skip)[0]),
            )
        )
    else:
        dq_in_specs += [None, None]

    dq = _pallas(
        functools.partial(
            _dq_kernel,
            scale=scale,
            causal=causal,
            block_q=bq,
            block_kv=bkv,
            n_kv=n_kv,
        ),
        skip,
        grid=(b, h, n_q, n_kv if skip is None else skip[6][0]),
        in_specs=dq_in_specs,
        out_specs=pl.BlockSpec((1, 1, bq, d), q_map),
        out_shape=jax.ShapeDtypeStruct((b, h, s_q, d), q.dtype),
        scratch_shapes=[pltpu.VMEM((bq, d), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel", "arbitrary")
        ),
        interpret=interpret,
        name="flash_bwd_dq",
    )(q, k, v, do, lse, delta, *seg_args)

    # ---- dk/dv: grid (B, K, n_kv, groups·n_q), accumulate over (g, q) ----
    n_inner = groups * n_q

    def q_map2(ib, ikh, ikv, inner, *skip):
        group, step = _unfold(inner, n_q, skip)
        return (ib, ikh * groups + group,
                _walk(step, ib, ikv, n_q, skip)[0], 0)

    def kv_map2(ib, ikh, ikv, inner, *skip):
        return (ib, ikh, ikv, 0)

    dkv_in_specs = [
        pl.BlockSpec((1, 1, bq, d), q_map2),
        pl.BlockSpec((1, 1, bkv, d), kv_map2),
        pl.BlockSpec((1, 1, bkv, dv), kv_map2),
        pl.BlockSpec((1, 1, bq, dv), q_map2),
        pl.BlockSpec((1, 1, bq, NUM_LANES), q_map2),
        pl.BlockSpec((1, 1, bq, NUM_LANES), q_map2),
    ]
    if q_seg is not None:
        dkv_in_specs.append(
            pl.BlockSpec(
                (1, bq, NUM_LANES),
                lambda ib, ikh, ikv, inner, *skip: (
                    ib, _walk(_unfold(inner, n_q, skip)[1], ib, ikv, n_q,
                              skip)[0], 0),
            )
        )
        dkv_in_specs.append(
            pl.BlockSpec(
                (1, NUM_SUBLANES, bkv),
                lambda ib, ikh, ikv, inner, *skip: (ib, 0, ikv),
            )
        )
    else:
        dkv_in_specs += [None, None]

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
        skip_dkv,
        grid=(b, kv_heads, n_kv,
              n_inner if skip_dkv is None else groups * skip_dkv[6][0]),
        in_specs=dkv_in_specs,
        out_specs=(
            pl.BlockSpec((1, 1, bkv, d), kv_map2),
            pl.BlockSpec((1, 1, bkv, dv), kv_map2),
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
    )(q, k, v, do, lse, delta, *seg_args)
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
