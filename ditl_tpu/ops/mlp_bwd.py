"""Pallas fused-backward kernels for the fused-gate|up SwiGLU MLP block.

The builders' r5 custom-VJP null (from before this round, not re-measured;
the script is gone, PERF.md section 7) showed the MLP backward residual is XLA's
in-step *schedule*, not the einsum spelling: re-emitting the same contractions by hand changed nothing, because
XLA still owned tiling and interleaving. This module takes the next step the
r5 verdict named — take the backward out of XLA's hands entirely, the same
move ops/flash_attention.py made for attention — by emitting the whole block
backward as a tightly-scheduled PAIR of Pallas (Mosaic) kernels:

- **Pass 1** (grid ``(F/bf, N/bn)``, token dim sequential-innermost): per
  (f, n) tile, compute ``dinner = g @ w_down^T``, recompute the elementwise
  SwiGLU pieces from the stored ``gate``/``up`` residuals (the "dots"-policy
  choice — no extra HBM residuals), emit ``dgate``/``dup`` tiles, and
  accumulate ``d_w_down = inner^T @ g`` in a VMEM f32 scratch written out on
  the last token tile. ``g`` is read once per f-block; the elementwise
  recompute and BOTH consumers of ``dinner`` live in one kernel instance,
  so nothing is ever re-materialized through HBM.
- **Pass 2** (grid ``(D/bd, N/bn)``): per (d, n) tile, ``dh = dgu @ w_gu^T``
  (full 2F contracted in-step) and ``d_w_gu = h^T @ dgu`` accumulated in
  VMEM, sharing the ``dgu`` tile between both products.

Between the passes, ``dgu = concat(dgate, dup)`` is one XLA concat — the
same (N, 2F) intermediate XLA's own backward materializes.

Tiling targets v5e's ~16M scoped VMEM at the pinned 1b3 bench shapes
(D=2048, F=5632, N=8192): pass 1 at (bn=256, bf=512) holds ~10.5 MB; pass 2
at (bn=256, bd=128) holds ~14.6 MB (the (bd, 2F) f32 accumulator dominates —
``ModelConfig.mlp_bwd_block_*`` sweeps the tradeoff per chip). Off-TPU the
kernels run in interpret mode, so the same numerics tests run on CPU
(tests/test_bwd_kernels.py).

Adoption protocol (the VJP-null rigor): the kernel ships behind
``ModelConfig.mlp_bwd_impl`` and becomes a default only on an adjacent
on-chip A/B win in a benchmark cell; a loss is
documented as a kernel-level definitive null, never silently dropped.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ditl_tpu.ops.backend import interpret_default

__all__ = ["fused_mlp_bwd", "supports", "DEFAULT_BLOCKS"]

NUM_LANES = 128
NUM_SUBLANES = 16  # bf16-safe sublane multiple (f32 needs only 8)


class BlockSizes(NamedTuple):
    block_n: int  # token tile (sublane dim of activation tiles)
    block_f: int  # intermediate-dim tile (pass 1)
    block_d: int  # hidden-dim tile (pass 2)


# Defaults sized for the 1b3 bench shapes on v5e (see module docstring);
# ModelConfig.mlp_bwd_block_{n,f,d} override per chip/model.
DEFAULT_BLOCKS = BlockSizes(256, 512, 128)


def _pick_blocks(n: int, d: int, f: int, blocks) -> BlockSizes:
    bn, bf, bd = blocks or (0, 0, 0)
    bn, bf, bd = (bn or DEFAULT_BLOCKS.block_n, bf or DEFAULT_BLOCKS.block_f,
                  bd or DEFAULT_BLOCKS.block_d)
    return BlockSizes(min(bn, n), min(bf, f), min(bd, d))


def supports(n: int, d: int, f: int, blocks=None) -> bool:
    """True if the kernels can tile (N=B*S tokens, D hidden, F intermediate).
    Otherwise callers (ops/mlp.py) raise on the TPU and give way to the
    einsum-spelled backward in interpret mode only."""
    bn, bf, bd = _pick_blocks(n, d, f, blocks)
    return (
        n % bn == 0
        and f % bf == 0
        and d % bd == 0
        and bn % NUM_SUBLANES == 0
        # Full-D rows in pass 1 and full-2F rows in pass 2 sit on lanes.
        and d % NUM_LANES == 0
        and bf % NUM_LANES == 0
        and bd % NUM_LANES == 0
    )


# ---------------------------------------------------------------------------
# Pass 1: dgate/dup tiles + d_w_down
# ---------------------------------------------------------------------------


def _dgu_dwdown_kernel(
    g_ref,      # (bn, D)
    wd_ref,     # (bf, D)
    gate_ref,   # (bn, bf)
    up_ref,     # (bn, bf)
    dgate_ref,  # (bn, bf) out
    dup_ref,    # (bn, bf) out
    dwd_ref,    # (bf, D) out, written on the last token tile
    acc_ref,    # (bf, D) f32 VMEM scratch
    *,
    n_n: int,
):
    i_n = pl.program_id(1)

    @pl.when(i_n == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    g = g_ref[...]
    wd = wd_ref[...]
    # dinner tile: both weight-grad and activation-grad consumers below read
    # this one f32 register-resident product — the shared read the issue's
    # schedule argument is about.
    dinner = jax.lax.dot_general(
        g, wd, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )  # (bn, bf)
    gate = gate_ref[...].astype(jnp.float32)
    up = up_ref[...].astype(jnp.float32)
    sg = jax.nn.sigmoid(gate)
    silu = gate * sg
    # Same d/dgate spelling as ops/mlp.py's einsum backward (bit-for-bit in
    # f32): silu'(gate) = sg * (1 + gate * (1 - sg)).
    dgate = dinner * up * (sg * (1.0 + gate * (1.0 - sg)))
    dup = dinner * silu
    dgate_ref[...] = dgate.astype(dgate_ref.dtype)
    dup_ref[...] = dup.astype(dup_ref.dtype)
    inner = (silu * up).astype(g.dtype)
    acc_ref[...] += jax.lax.dot_general(
        inner, g, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )  # (bf, D)

    @pl.when(i_n == n_n - 1)
    def _finalize():
        dwd_ref[...] = acc_ref[...].astype(dwd_ref.dtype)


# ---------------------------------------------------------------------------
# Pass 2: dh + d_w_gu
# ---------------------------------------------------------------------------


def _dh_dwgu_kernel(
    h_ref,      # (bn, bd)
    dgu_ref,    # (bn, 2F)
    wgu_ref,    # (bd, 2F)
    dh_ref,     # (bn, bd) out
    dwgu_ref,   # (bd, 2F) out, written on the last token tile
    acc_ref,    # (bd, 2F) f32 VMEM scratch
    *,
    n_n: int,
):
    i_n = pl.program_id(1)

    @pl.when(i_n == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    dgu = dgu_ref[...]
    dh = jax.lax.dot_general(
        dgu, wgu_ref[...], (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    )  # (bn, bd): full 2F contracted in-step, no cross-step accumulation
    dh_ref[...] = dh.astype(dh_ref.dtype)
    acc_ref[...] += jax.lax.dot_general(
        h_ref[...], dgu, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )  # (bd, 2F)

    @pl.when(i_n == n_n - 1)
    def _finalize():
        dwgu_ref[...] = acc_ref[...].astype(dwgu_ref.dtype)


# ---------------------------------------------------------------------------
# Wrapper
# ---------------------------------------------------------------------------


def fused_mlp_bwd(
    h: jax.Array,      # (B, S, D)
    w_gu: jax.Array,   # (D, 2F)
    w_down: jax.Array,  # (F, D)
    gate: jax.Array,   # (B, S, F) forward residual
    up: jax.Array,     # (B, S, F) forward residual
    g: jax.Array,      # (B, S, D) output cotangent
    *,
    blocks=None,
    interpret: bool | None = None,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """The fused-gate|up MLP block backward as the two Pallas passes above.
    Returns ``(dh, d_w_gu, d_w_down)`` matching ops/mlp.py's einsum backward
    to f32 tolerance (exactly, in f32). Raises ``ValueError`` on shapes
    ``supports`` rejects."""
    b, s, d = h.shape
    f = w_down.shape[0]
    n = b * s
    if not supports(n, d, f, blocks):
        raise ValueError(
            f"fused_mlp_bwd cannot tile N={n} D={d} F={f} (blocks={blocks})"
        )
    bn, bf, bd = _pick_blocks(n, d, f, blocks)
    if interpret is None:
        interpret = interpret_default()

    h2 = h.reshape(n, d)
    g2 = g.reshape(n, d)
    gate2 = gate.reshape(n, f)
    up2 = up.reshape(n, f)
    n_n, n_f, n_d = n // bn, f // bf, d // bd

    dgate, dup, d_w_down = pl.pallas_call(
        functools.partial(_dgu_dwdown_kernel, n_n=n_n),
        name="mlp_bwd_act",
        grid=(n_f, n_n),
        in_specs=[
            pl.BlockSpec((bn, d), lambda i_f, i_n: (i_n, 0)),    # g
            pl.BlockSpec((bf, d), lambda i_f, i_n: (i_f, 0)),    # w_down
            pl.BlockSpec((bn, bf), lambda i_f, i_n: (i_n, i_f)),  # gate
            pl.BlockSpec((bn, bf), lambda i_f, i_n: (i_n, i_f)),  # up
        ],
        out_specs=(
            pl.BlockSpec((bn, bf), lambda i_f, i_n: (i_n, i_f)),
            pl.BlockSpec((bn, bf), lambda i_f, i_n: (i_n, i_f)),
            pl.BlockSpec((bf, d), lambda i_f, i_n: (i_f, 0)),
        ),
        out_shape=(
            jax.ShapeDtypeStruct((n, f), g.dtype),
            jax.ShapeDtypeStruct((n, f), g.dtype),
            jax.ShapeDtypeStruct((f, d), w_down.dtype),
        ),
        scratch_shapes=[pltpu.VMEM((bf, d), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")
        ),
        interpret=interpret,
    )(g2, w_down, gate2, up2)

    # One concat — the same (N, 2F) intermediate XLA's backward builds; the
    # gate|up column order matches the fused w_gu layout.
    dgu = jnp.concatenate([dgate, dup], axis=-1)

    dh2, d_w_gu = pl.pallas_call(
        functools.partial(_dh_dwgu_kernel, n_n=n_n),
        name="mlp_bwd_wgu",
        grid=(n_d, n_n),
        in_specs=[
            pl.BlockSpec((bn, bd), lambda i_d, i_n: (i_n, i_d)),    # h
            pl.BlockSpec((bn, 2 * f), lambda i_d, i_n: (i_n, 0)),   # dgu
            pl.BlockSpec((bd, 2 * f), lambda i_d, i_n: (i_d, 0)),   # w_gu
        ],
        out_specs=(
            pl.BlockSpec((bn, bd), lambda i_d, i_n: (i_n, i_d)),
            pl.BlockSpec((bd, 2 * f), lambda i_d, i_n: (i_d, 0)),
        ),
        out_shape=(
            jax.ShapeDtypeStruct((n, d), h.dtype),
            jax.ShapeDtypeStruct((d, 2 * f), w_gu.dtype),
        ),
        scratch_shapes=[pltpu.VMEM((bd, 2 * f), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")
        ),
        interpret=interpret,
    )(h2, dgu, w_gu)

    return dh2.reshape(b, s, d), d_w_gu, d_w_down
