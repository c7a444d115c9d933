"""Dense projection with a hand-scheduled (Pallas) backward.

The second-largest residual in the r4/r5 backward-schedule accounting
(~33 ms of a 562 ms step) is the attention qkv/out-projection weight
gradients — plain ``x^T @ g`` contractions whose in-step rates ran at ~2x
their isolated cost under XLA's backward schedule (the builders' ``iso``
receipts from before this round, not re-measured). This module is the projection-shaped sibling of
ops/mlp_bwd.py: a ``custom_vjp`` whose forward is the exact inline einsum
(bit-identical — same op, same dtypes as ops/quant.weight_einsum on float
weights) and whose backward emits BOTH gradients from one Pallas kernel:

- grid ``(D/bd, N/bn)``, token dim sequential-innermost;
- per (d, n) tile: ``dx = g @ w^T`` (full F contracted in-step) and
  ``d_w = x^T @ g`` accumulated in a (bd, F) f32 VMEM scratch, written out
  on the last token tile — the cotangent tile ``g`` is read once and feeds
  both products.

Selected per config via ``ModelConfig.proj_bwd_impl`` for the attention
projections in models/llama.py; shapes the kernel cannot tile raise on the
TPU and give way to the einsum backward in interpret mode only
(ops/backend.py). Off-TPU the kernel runs in interpret mode so numerics
tests run on CPU. Mesh composition mirrors ops/attention.py's flash dispatch: Pallas
calls carry no GSPMD partitioning rules, so under a mesh the op is
shard_map'ed over the batch axes with replicated weights — shard_map's
transpose inserts the weight-gradient psum.
"""

from __future__ import annotations

from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ditl_tpu.ops.backend import interpret_default, refuse_on_tpu

__all__ = ["projection", "supports", "effective_bwd_impl", "DEFAULT_BLOCKS"]

NUM_LANES = 128
NUM_SUBLANES = 16


class BlockSizes(NamedTuple):
    block_n: int  # token tile
    block_d: int  # input-feature tile


# (bn=256, bd=256) holds ~4.3 MB VMEM at the largest 1b3 projection
# (D=2048, F=4096 fused qkv): w tile 2 MB bf16 + (bd, F) f32 scratch 4 MB is
# the ceiling term; ModelConfig.proj_bwd_block_{n,d} sweep it per chip.
DEFAULT_BLOCKS = BlockSizes(256, 256)


def _pick_blocks(n: int, d: int, blocks) -> BlockSizes:
    bn, bd = blocks or (0, 0)
    bn, bd = bn or DEFAULT_BLOCKS.block_n, bd or DEFAULT_BLOCKS.block_d
    return BlockSizes(min(bn, n), min(bd, d))


def supports(n: int, d: int, f: int, blocks=None) -> bool:
    """True if the backward kernel can tile x (N=B*S, D) @ w (D, F)."""
    bn, bd = _pick_blocks(n, d, blocks)
    return (
        n % bn == 0
        and d % bd == 0
        and bn % NUM_SUBLANES == 0
        and bd % NUM_LANES == 0
        and f % NUM_LANES == 0
    )


def _bwd_kernel(
    x_ref,    # (bn, bd)
    w_ref,    # (bd, F)
    g_ref,    # (bn, F)
    dx_ref,   # (bn, bd) out
    dw_ref,   # (bd, F) out, written on the last token tile
    acc_ref,  # (bd, F) f32 VMEM scratch
    *,
    n_n: int,
):
    i_n = pl.program_id(1)

    @pl.when(i_n == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    g = g_ref[...]
    dx = jax.lax.dot_general(
        g, w_ref[...], (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    )  # (bn, bd)
    dx_ref[...] = dx.astype(dx_ref.dtype)
    acc_ref[...] += jax.lax.dot_general(
        x_ref[...], g, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )  # (bd, F)

    @pl.when(i_n == n_n - 1)
    def _finalize():
        dw_ref[...] = acc_ref[...].astype(dw_ref.dtype)


def _pallas_bwd(x, w, g, *, blocks, interpret):
    b, s, d = x.shape
    f = w.shape[1]
    n = b * s
    bn, bd = _pick_blocks(n, d, blocks)
    if interpret is None:
        interpret = interpret_default()
    x2 = x.reshape(n, d)
    g2 = g.reshape(n, f)
    n_n, n_d = n // bn, d // bd
    dx2, dw = pl.pallas_call(
        partial(_bwd_kernel, n_n=n_n),
        name="proj_bwd",
        grid=(n_d, n_n),
        in_specs=[
            pl.BlockSpec((bn, bd), lambda i_d, i_n: (i_n, i_d)),  # x
            pl.BlockSpec((bd, f), lambda i_d, i_n: (i_d, 0)),     # w
            pl.BlockSpec((bn, f), lambda i_d, i_n: (i_n, 0)),     # g
        ],
        out_specs=(
            pl.BlockSpec((bn, bd), lambda i_d, i_n: (i_n, i_d)),
            pl.BlockSpec((bd, f), lambda i_d, i_n: (i_d, 0)),
        ),
        out_shape=(
            jax.ShapeDtypeStruct((n, d), x.dtype),
            jax.ShapeDtypeStruct((d, f), w.dtype),
        ),
        scratch_shapes=[pltpu.VMEM((bd, f), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")
        ),
        interpret=interpret,
    )(x2, w, g2)
    return dx2.reshape(b, s, d), dw


@partial(jax.custom_vjp, nondiff_argnums=(2, 3, 4))
def _proj(x, w, bwd_impl, blocks, interpret):
    out, _ = _proj_fwd(x, w, bwd_impl, blocks, interpret)
    return out


def _proj_fwd(x, w, bwd_impl, blocks, interpret):
    # Bit-identical to the inline path's weight_einsum on float weights.
    out = jnp.einsum("bsd,df->bsf", x, w, preferred_element_type=x.dtype)
    return out, (x, w)


def _proj_bwd(bwd_impl, blocks, interpret, res, g):
    x, w = res
    if bwd_impl == "pallas":
        n, d, f = x.shape[0] * x.shape[1], x.shape[2], w.shape[1]
        if supports(n, d, f, blocks):
            return _pallas_bwd(x, w, g, blocks=blocks, interpret=interpret)
        refuse_on_tpu(
            "proj_bwd_impl='pallas'",
            f"cannot tile N={n} D={d} F={f} (blocks={blocks})",
        )
    dx = jnp.einsum("bsf,df->bsd", g, w).astype(x.dtype)
    dw = jnp.einsum("bsd,bsf->df", x, g).astype(w.dtype)
    return dx, dw


_proj.defvjp(_proj_fwd, _proj_bwd)


def effective_bwd_impl(bwd_impl: str, b: int, s: int, d: int, f: int,
                       blocks=(), mesh=None, rules=None) -> str:
    """The backward implementation ``projection`` will ACTUALLY run for an
    (B,S,d) @ (d,f) projection — shared gate logic in
    parallel/sharding.pallas_bwd_effective bound to this op's shape
    predicate (mirrors ops/mlp.effective_bwd_impl)."""
    from ditl_tpu.parallel.sharding import pallas_bwd_effective

    return pallas_bwd_effective(bwd_impl, b, s, d, f, blocks, mesh, rules,
                                supports)


def projection(
    x: jax.Array,  # (B, S, D)
    w: jax.Array,  # (D, F) plain float (quantized serving never differentiates)
    *,
    bwd_impl: str = "xla",
    blocks=None,
    mesh=None,
    rules=None,
    interpret: bool | None = None,
) -> jax.Array:
    """``x @ w`` whose backward is dispatched per ``bwd_impl``. Under a mesh
    the Pallas variant is shard_map'ed over the batch axes (weights
    replicated; shard_map's transpose psums ``d_w``); meshes that don't
    divide the batch, or sequence-sharded activations, keep the
    GSPMD-partitionable einsum backward."""
    b, s, d = x.shape
    eff = effective_bwd_impl(bwd_impl, b, s, d, w.shape[1], blocks, mesh,
                             rules)
    if eff != "pallas" or mesh is None:
        return _proj(x, w, eff, tuple(blocks or ()), interpret)
    from ditl_tpu.parallel.sharding import DEFAULT_RULES, logical_to_spec

    rules = rules if rules is not None else DEFAULT_RULES
    x_spec = logical_to_spec(("batch", None, None), rules)
    w_spec = logical_to_spec((None, None), rules)

    def local(x_, w_):
        return _proj(x_, w_, "pallas", tuple(blocks or ()), interpret)

    return jax.shard_map(
        local, mesh=mesh, in_specs=(x_spec, w_spec), out_specs=x_spec,
        check_vma=False,
    )(x, w)
