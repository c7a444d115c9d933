"""Compiled for a described v5e, with no chip attached (tests/tpu_compile.py):
the first trainer cell that runs experts, ``kanana-2-30b-a3b-cut1.
train-ep8-8k``. Its whole optimizer step at the published widths: the three
flash kernels at 192 / 128 (Mosaic takes a 192-lane operand) on the grid of
their work lists, a bound the call computes, ``gmm`` in the
forward and ``gmm`` and ``tgmm`` (the grouped matmul's transposed product, held
by a test in a training step for the first time) in the backward pass of a held
share, the fused loss, AdamW on float32 masters; and the step's bytes.
"""

from __future__ import annotations

import dataclasses
import json
import os
import re

import jax
import jax.numpy as jnp

from ditl_tpu.config import TrainConfig
from ditl_tpu.models import moe as moe_mod
from ditl_tpu.models.presets import get_preset
from ditl_tpu.ops import names

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_GIB = 2 ** 30
_TENTH_SPARE = 0.9 * 15.75 * _GIB  # of a v5e's 15.75 GiB


def test_the_kanana_cells_step_compiles_with_gmm_tgmm_and_flash_at_two_widths(
        one_chip, tpu_branch, monkeypatch):
    from ditl_tpu.ops import flash_attention, fused_ce
    from ditl_tpu.parallel.sharding import DEFAULT_RULES
    from ditl_tpu.train.state import create_train_state
    from ditl_tpu.train.step import _build_step_fn

    for module in (flash_attention, fused_ce):  # bound the name at their import
        if hasattr(module, "interpret_default"):
            monkeypatch.setattr(module, "interpret_default", lambda: False)
    with open(os.path.join(ROOT, "benchmarks", "configs", "kanana-2-30b-a3b-cut1.json")) as f:
        config = json.load(f)
    with open(os.path.join(ROOT, "benchmarks", "traffic", "train-ep8-8k.json")) as f:
        args = dict(a.split("=", 1) for a in json.load(f)["launch_args"])
    cfg = dataclasses.replace(get_preset(config["preset"]), **config["model_overrides"],
                              attention_impl=args["model.attention_impl"],
                              loss_impl=args["model.loss_impl"])
    rows, seq = int(args["data.batch_size"]), int(args["data.seq_len"])
    assert (rows, seq) == (4, 8192) and "train.grad_accum_steps" not in args
    assert args["train.frozen"] == "router"
    tc = TrainConfig(total_steps=1_000_000, frozen=args["train.frozen"])
    put = lambda t: jax.tree.map(  # noqa: E731
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip), t)
    state = jax.eval_shape(lambda: create_train_state(jax.random.key(0), cfg, tc))
    sd = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt)  # noqa: E731
    batch = {"input_ids": sd((rows, seq), jnp.int32), "loss_mask": sd((rows, seq), jnp.float32),
             "labels": sd((rows,), jnp.int32), "segment_ids": sd((rows, seq), jnp.int32),
             "positions": sd((rows, seq), jnp.int32)}
    compiled = jax.jit(_build_step_fn(cfg, tc, None, DEFAULT_RULES), donate_argnums=(0,)).lower(
        put(state), put(batch)).compile()
    text = compiled.as_text()
    calls = re.findall(r"%([\w\-.]+) = [^\n]*custom-call", text)
    for kernel in ("flash_fwd", "flash_bwd_dq", "flash_bwd_dkv"):
        assert kernel in names.KERNELS and any(kernel in c for c in calls), kernel
    assert "bf16[4,32,8192,192]" in text and "bf16[4,32,8192,128]" in text  # q / k and v
    # a buffer of held pairs: 3 gmm forward, 3 in the layer's rematerialised
    # forward, 3 against the transposed weights and 3 tgmm in the backward pass
    buffers = -(-(rows * seq * cfg.num_experts_per_tok)
                // moe_mod.held_rows(rows * seq * cfg.num_experts_per_tok))
    gmm = len(re.findall(r'op_name="[^"]*jit\(gmm\)/pallas_call', text))
    tgmm = len(re.findall(r'op_name="[^"]*jit\(tgmm\)/pallas_call', text))
    assert names.MOE_KERNELS == ("gmm", "tgmm") and buffers == 8
    assert (gmm, tgmm) == (9 * buffers, 3 * buffers), (gmm, tgmm)
    mem = compiled.memory_analysis()
    state_bytes = sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(state))
    # masters and two moments (none for the 4 x 128 router biases, buffers, nor
    # for the four frozen routers of 2,048 x 128), three counters
    assert state_bytes == 575_955_968 * 12 - 2 * 4 * (128 + 2048 * 128) * 4 + 12
    # The compiler's own peak: 14.10 GiB at this tree (15,144,680,448; the flash
    # kernels' work lists are SMEM operands of 8.5 KiB a call and cost 1,536
    # bytes on the 15,144,678,912 of the tree before them), under
    # the tenth-spare line (0.9 x 15.75 = 14.17 GiB) the issue sets for ONE
    # micro-batch of 32,768 tokens; a buffer of held pairs keeps its inputs
    # alone for the backward pass (models/moe.py), without which the eight
    # buffers' rows stand together at 14.21. temp_size_in_bytes counts 2.15 GiB
    # (one float32 parameter tree) beyond the report's preallocated block:
    # with it the sum would not fit the chip the compiler has just fitted the
    # program to, so the line is held on the peak.
    assert 0.25 * 15.75 * _GIB < mem.peak_memory_in_bytes < _TENTH_SPARE
    assert mem.alias_size_in_bytes >= state_bytes - 1024  # the state is updated in place
