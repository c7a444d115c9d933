"""Compiled for a described v5e, with no chip attached (tests/tpu_compile.py):
what this repo's first expert layer asks of the TPU's compiler at the
published OLMoE widths. The Mosaic kernels of the grouped matmul (forward, and
the transposed product of the backward pass, whose tiles have to fit 16 MB of
scoped VMEM: a 2,048-wide contraction tile did not).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import pytest

from ditl_tpu.models import moe as moe_mod
from ditl_tpu.models.presets import get_preset
from ditl_tpu.ops import names
from tests.tpu_compile import _instructions

def _moe_shapes(cfg, sharding):
    d, f, e = cfg.hidden_size, cfg.intermediate_size, cfg.num_experts
    s = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=sharding)  # noqa: E731
    return {"router": s((d, e), jnp.float32), "w_gate": s((e, d, f), jnp.bfloat16),
            "w_up": s((e, d, f), jnp.bfloat16), "w_down": s((e, f, d), jnp.bfloat16)}



@pytest.mark.parametrize("rows", [(64, 1), (1, 2048)], ids=["decode-64-slots", "prefill-2048"])
def test_expert_layer_forward_compiles_at_olmoe_widths(one_chip, tpu_branch, rows):
    cfg = get_preset("olmoe-1b-7b")
    h = jax.ShapeDtypeStruct((*rows, cfg.hidden_size), jnp.bfloat16, sharding=one_chip)
    compiled = jax.jit(lambda m, x: moe_mod.moe_block(m, x, cfg)).lower(
        _moe_shapes(cfg, one_chip), h).compile()
    assert names.MOE_KERNELS[0] in _instructions(compiled.as_text())


def test_expert_layer_backward_compiles_at_olmoe_widths(one_chip, tpu_branch):
    cfg = get_preset("olmoe-1b-7b")
    h = jax.ShapeDtypeStruct((1, 2048, cfg.hidden_size), jnp.bfloat16, sharding=one_chip)

    def loss(m, x):
        out, aux, _ = moe_mod.moe_block(m, x, cfg)
        return (out.astype(jnp.float32) ** 2).mean() + aux

    compiled = jax.jit(jax.grad(loss)).lower(_moe_shapes(cfg, one_chip), h).compile()
    assert set(names.MOE_KERNELS) <= _instructions(compiled.as_text())
