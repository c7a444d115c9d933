"""Fused-gate|up MLP block with a hand-written VJP and, per config, a
Pallas fused-backward implementation.

The builders' r5 stop-gradient ablation (figures from before this round,
not re-measured; the script is gone, PERF.md section 7) showed the MLP family's
in-step weight-gradient GEMMs running at ~2x their isolated-peak rates — a property of XLA's backward SCHEDULE, not of
the GEMM shapes. The first instrument against that was this module's
custom VJP: the whole block's backward (activation grads and BOTH weight
grads) emitted as ONE function with explicit einsum contractions. The r5
A/B came back a definitive null — XLA still owned tiling and interleaving
— which is exactly what ``bwd_impl="pallas"`` now changes: the same
backward emitted as hand-tiled Pallas kernels (ops/mlp_bwd.py), so the
schedule is pinned by the grid, not chosen by XLA.

Exactness: forward is bit-identical to the inline path (same ops); the
backward matches autodiff to f32 test tolerance for BOTH implementations
(tests/test_model.py::test_mlp_custom_vjp_matches_autodiff,
tests/test_bwd_kernels.py). Enabled per config via
``ModelConfig.mlp_custom_vjp`` (einsum spelling) /
``ModelConfig.mlp_bwd_impl="pallas"`` (Pallas kernels; requires
``fused_gate_up``; plain float weights only — quantized serving never
differentiates). Shapes ops/mlp_bwd.supports rejects raise on the TPU and
give way to the einsum spelling in interpret mode only (ops/backend.py).
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

__all__ = ["mlp_gu", "mlp_block", "effective_bwd_impl"]


@partial(jax.custom_vjp, nondiff_argnums=(0, 4, 5, 6))
def _mlp_gu(constrain, h: jax.Array, w_gu: jax.Array, w_down: jax.Array,
            bwd_impl, bwd_blocks, interpret) -> jax.Array:
    out, _ = _fwd(constrain, h, w_gu, w_down, bwd_impl, bwd_blocks, interpret)
    return out


def _fwd(constrain, h, w_gu, w_down, bwd_impl, bwd_blocks, interpret):
    gu = jnp.einsum("bsd,df->bsf", h, w_gu)
    gate, up = jnp.split(gu, 2, axis=-1)
    inner = constrain(jax.nn.silu(gate) * up)
    out = jnp.einsum("bsf,fd->bsd", inner, w_down)
    return out, (h, w_gu, w_down, gate, up)


def _bwd(constrain, bwd_impl, bwd_blocks, interpret, res, g):
    h, w_gu, w_down, gate, up = res
    if bwd_impl == "pallas":
        from ditl_tpu.ops import mlp_bwd

        b, s, d = h.shape
        if mlp_bwd.supports(b * s, d, w_down.shape[0], bwd_blocks):
            return mlp_bwd.fused_mlp_bwd(
                h, w_gu, w_down, gate, up, g,
                blocks=bwd_blocks, interpret=interpret,
            )
        # Shapes the kernel can't tile: an error on the TPU; in interpret
        # mode (tiny tests, odd dims) the einsum spelling below.
        from ditl_tpu.ops.backend import refuse_on_tpu

        refuse_on_tpu(
            "mlp_bwd_impl='pallas'",
            f"cannot tile N={b * s} D={d} F={w_down.shape[0]} "
            f"(blocks={bwd_blocks})",
        )
    # Recompute the cheap elementwise pieces (the "dots"-policy choice).
    sg = jax.nn.sigmoid(gate)
    silu_gate = gate * sg
    inner = constrain(silu_gate * up)
    # One explicit contraction per gradient; all four GEMMs share the g /
    # dgu operands, written so XLA sees the reuse directly.
    d_w_down = jnp.einsum("bsf,bsd->fd", inner, g).astype(w_down.dtype)
    dinner = jnp.einsum("bsd,fd->bsf", g, w_down)
    dgate = dinner * up * (sg * (1.0 + gate * (1.0 - sg)))
    dup = dinner * silu_gate
    dgu = jnp.concatenate([dgate, dup], axis=-1)
    d_w_gu = jnp.einsum("bsd,bsf->df", h, dgu).astype(w_gu.dtype)
    dh = jnp.einsum("bsf,df->bsd", dgu, w_gu).astype(h.dtype)
    return dh, d_w_gu, d_w_down


_mlp_gu.defvjp(_fwd, _bwd)


def mlp_gu(constrain, h: jax.Array, w_gu: jax.Array, w_down: jax.Array,
           bwd_impl: str = "xla", bwd_blocks=(), interpret=None) -> jax.Array:
    """SwiGLU MLP over the fused gate|up layout: ``h @ w_gu`` → split →
    ``silu(gate)*up @ w_down``. Shapes: h (B,S,D), w_gu (D,2F),
    w_down (F,D). ``constrain`` (static): sharding-hint callback applied
    to the inner activation — mirrors the inline path's
    ``_constrain(inner, act_mlp)`` so a mesh A/B isolates the backward
    SPELLING, not sharding-propagation differences. Pass identity for
    single-chip. ``bwd_impl`` selects the backward: "xla" (explicit
    einsums, scheduled by XLA) or "pallas" (ops/mlp_bwd.py kernels;
    ``bwd_blocks`` = (block_n, block_f, block_d), 0/empty = defaults)."""
    return _mlp_gu(constrain, h, w_gu, w_down, bwd_impl,
                   tuple(bwd_blocks or ()), interpret)


def _identity(t):
    return t


def effective_bwd_impl(bwd_impl: str, b: int, s: int, d: int, f: int,
                       blocks=(), mesh=None, rules=None) -> str:
    """The backward implementation ``mlp_block`` will ACTUALLY run for these
    shapes — shared gate logic in parallel/sharding.pallas_bwd_effective,
    bound to this op's shape predicate; a record that names the backward
    asks the same call, so an A/B can never attribute a delta to a kernel
    that fell back."""
    from ditl_tpu.ops import mlp_bwd
    from ditl_tpu.parallel.sharding import pallas_bwd_effective

    return pallas_bwd_effective(bwd_impl, b, s, d, f, blocks, mesh, rules,
                                mlp_bwd.supports)


def mlp_block(constrain, h: jax.Array, w_gu: jax.Array, w_down: jax.Array,
              *, bwd_impl: str = "xla", bwd_blocks=(), mesh=None,
              rules=None) -> jax.Array:
    """Mesh-aware dispatch for the custom-VJP MLP block (models/llama.py).

    Pallas calls carry no GSPMD partitioning rules, so under a mesh the
    Pallas-backward variant is shard_map'ed over the batch axes with
    replicated weights — shard_map's transpose inserts the psum that turns
    per-shard weight grads into the global ones (mirrors
    ops/attention.py's flash dispatch). Meshes that don't divide the batch,
    or sequence-sharded activations, keep the GSPMD-partitionable einsum
    backward instead (the constrain hint preserves the activation
    sharding A/Bs rely on)."""
    b, s, d = h.shape
    eff = effective_bwd_impl(bwd_impl, b, s, d, w_down.shape[0], bwd_blocks,
                             mesh, rules)
    if eff != "pallas" or mesh is None:
        return mlp_gu(constrain, h, w_gu, w_down, eff, bwd_blocks)
    from ditl_tpu.parallel.sharding import DEFAULT_RULES, logical_to_spec

    rules = rules if rules is not None else DEFAULT_RULES
    h_spec = logical_to_spec(("batch", None, None), rules)
    w_spec = logical_to_spec((None, None), rules)

    def local(h_, wgu_, wdn_):
        return mlp_gu(_identity, h_, wgu_, wdn_, "pallas", bwd_blocks)

    return jax.shard_map(
        local, mesh=mesh, in_specs=(h_spec, w_spec, w_spec),
        out_specs=h_spec, check_vma=False,
    )(h, w_gu, w_down)
