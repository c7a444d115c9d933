"""Compiled for a described v5e, with no chip attached (tests/tpu_compile.py):
a state a slot and no page pool, the decode and prefill programs of
``brumby-14b-cut1.streams-16-ret`` under the tenth-spare line.
"""

from __future__ import annotations

import re

import jax
import jax.numpy as jnp
import pytest

from ditl_tpu.infer.continuous import ContinuousEngine
from ditl_tpu.models.presets import get_preset
from ditl_tpu.ops import names
from tests import family
from tests.tpu_compile import _CHUNK, _TENTH_SPARE, _instructions, _total_bytes

LAYERS, SLOTS = 8, 16
STATE_BYTES = LAYERS * SLOTS * 8 * (128 + 1) * 9216 * 4


# brumby-14b-cut1.streams-16-ret (ISSUE 56): the leading 8 layers at the
# published widths, 16 slots of state, nothing else in the donated tree.
def _brumby_cell(one_chip, chunk):
    """(engine whose programs are the cell's, abstract params, abstract
    cache) with nothing of the model's size allocated: the programs take
    their sizes from their arguments."""
    from ditl_tpu.data.tokenizer import ByteTokenizer
    from ditl_tpu.models import llama, retention

    cfg = get_preset("brumby-14b", param_dtype="bfloat16", num_layers=LAYERS,
                     layer_types="r" * LAYERS)
    s = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)  # noqa: E731
    params = jax.tree.map(
        lambda a: s(a.shape, a.dtype),
        jax.eval_shape(lambda: llama.init_params(jax.random.key(0), cfg)))
    eng = ContinuousEngine({}, cfg, ByteTokenizer(), n_slots=1, cache_mode="paged",
                           page_size=256, max_cache_len=4096, decode_chunk=chunk)
    assert set(eng.cache) == {"ret", "retz"}  # the engine's own tree holds no pool either
    state = jax.eval_shape(lambda: retention.init_state(cfg, SLOTS))
    return eng, params, {k: s(v.shape, v.dtype) for k, v in state.items()}, s


@pytest.mark.parametrize("chunk", [_CHUNK, 16], ids=[f"tick-{_CHUNK}", "tick-16"])
def test_brumby_decode_program_compiles_in_place_under_the_tenth_spare_line(
        one_chip, tpu_branch, chunk):
    """``jit_paged_decode`` of the cell (and of ``paged_check.py``'s 16-step
    ticks): BOTH kernels inside the layer scan (``ret_step_read`` for a step
    that is not its tick's last, ``ret_step`` folding the tick's held tokens
    in at the last), chosen by ONE conditional whose read side hands the
    stack through (ISSUE 57 named the hazard: a copy of the 4.5 GiB stack for
    the side not taken), the state aliased to the output, no instruction that
    produces a second state, temporaries far under one layer's state, no
    attention kernel and no flush, the whole under the tenth-spare line."""
    eng, params, cache, s = _brumby_cell(one_chip, chunk)
    row_i, row_f = s((SLOTS,), jnp.int32), s((SLOTS,), jnp.float32)
    keys = jax.eval_shape(lambda: jax.vmap(jax.random.key)(jnp.arange(SLOTS, dtype=jnp.uint32)))
    keys = jax.ShapeDtypeStruct(keys.shape, keys.dtype, sharding=one_chip)
    compiled = family.build_program(eng, "paged_decode", False, False).lower(
        params, cache, row_i, row_i, s((SLOTS,), jnp.bool_), row_f, row_f, keys,
        s((SLOTS, 16), jnp.int32), row_i, s((SLOTS, 1), jnp.int32), row_i).compile()
    text = compiled.as_text()
    calls = _instructions(text)
    assert set(names.RET_KERNELS) <= calls
    assert not calls & {"paged_attention", names.CACHE_KERNELS[0]}
    state_shape = re.escape(f"f32[{LAYERS},{SLOTS},8,128,9216]")
    producers = set(re.findall(r" = " + state_shape + r"\S* ([\w\-]+)\(", text))
    assert producers <= {"bitcast", "parameter", "get-tuple-element", "custom-call", "while"}
    # the read-only kernel takes the stack and returns none: it writes no state
    read = re.search(r"%ret_step_read[\w.]* = (\S+) custom-call", text).group(1)
    assert read.startswith(f"f32[{SLOTS},8,128,128]"), read  # the read-out alone
    assert len(re.findall(r" conditional\(", text)) == 1
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= STATE_BYTES
    # 0.55 GiB of it: wq, wk, wv turned once a tick; the held tokens are 4 MB (17 at 16 steps)
    assert mem.temp_size_in_bytes < STATE_BYTES / 4
    assert _total_bytes(compiled) < _TENTH_SPARE


@pytest.mark.parametrize("bucket", [256, 512, 1024, 2048])
def test_brumby_prefill_buckets_compile_under_the_tenth_spare_line(one_chip, tpu_branch, bucket):
    """The four prefill programs the cell's prompts reach: the chunked form
    from the slot's state, the state seated in place."""
    eng, params, cache, s = _brumby_cell(one_chip, _CHUNK)
    key = jax.eval_shape(lambda: jax.random.key(0))
    scalar_i, scalar_f = s((), jnp.int32), s((), jnp.float32)
    compiled = family.build_program(eng, "paged_prefill", bucket, 0).lower(
        params, cache, s((1,), jnp.int32), s((1, bucket), jnp.int32), scalar_i, scalar_i,
        scalar_f, scalar_f, jax.ShapeDtypeStruct(key.shape, key.dtype, sharding=one_chip),
        s((bucket // 256,), jnp.int32), s((1,), jnp.int32), scalar_i).compile()
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= STATE_BYTES  # the state in place
    print("prefill", bucket, _total_bytes(compiled) / 2**30, mem.temp_size_in_bytes / 2**30)
    assert _total_bytes(compiled) < _TENTH_SPARE
