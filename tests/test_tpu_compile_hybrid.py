"""Compiled for a described v5e, with no chip attached (tests/tpu_compile.py):
K/V pages with a recurrent state a slot beside them, the decode and prefill
programs of ``granite-4.0-h-micro.chat-wide-ssm`` under the tenth-spare line.
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

# granite-4.0-h-micro.chat-wide-ssm (ISSUE 41): the whole published model, 64
# slots of recurrent state, 512 pages of 256 tokens at 128 stored lanes a head.
def _granite_cell(one_chip, chunk):
    """(engine whose programs are the cell's, abstract params, abstract
    cache) with nothing of the model's size allocated: the programs take
    their sizes from their arguments."""
    from ditl_tpu.data.tokenizer import ByteTokenizer
    from ditl_tpu.models import llama, ssm

    cfg = get_preset("granite-4.0-h-micro", param_dtype="bfloat16")
    s = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)  # noqa: E731
    params = jax.tree.map(
        lambda a: s(a.shape, a.dtype),
        jax.eval_shape(lambda: llama.init_params(jax.random.key(0), cfg)))
    eng = ContinuousEngine({}, cfg, ByteTokenizer(), n_slots=1, cache_mode="paged",
                           page_size=256, max_cache_len=4096, n_pages=2, decode_chunk=chunk)
    slots, pages = 64, 512
    state = jax.eval_shape(lambda: ssm.init_state(cfg, slots))
    cache = {"kp": s((4, pages, 8, 256, 128), jnp.bfloat16),
             "vp": s((4, pages, 8, 256, 128), jnp.bfloat16),
             **{k: s(v.shape, v.dtype) for k, v in state.items()}}
    assert {k: v.shape[1:] for k, v in eng.cache.items() if k in ("kp", "vp")} == {
        "kp": (2, 8, 256, 128), "vp": (2, 8, 256, 128)}  # the engine's own pool is so laid out
    return eng, params, cache, s


@pytest.mark.parametrize("chunk", [_CHUNK, 16], ids=[f"tick-{_CHUNK}", "tick-16"])
def test_granite_decode_program_compiles_in_place_under_the_tenth_spare_line(
        one_chip, tpu_branch, chunk):
    """``jit_paged_decode`` of the cell (and of ``paged_check.py``'s 16-step
    ticks): nine ``ssd_step`` kernels a period scan and the paged attention
    kernel over 128-lane pages, the 4.5 GiB state and the pools aliased to
    the outputs, no instruction that produces a second state, temporaries far
    under one mixer's state, the whole under the tenth-spare line. The kernel
    takes ``x`` and gives ``y`` as the row's flattened ``(H P)`` vector: no
    transpose or layout copy of a (slots, P, H) operand lies under
    ``ssm_scan`` (18 a period while the state's columns lay on the lanes)."""
    eng, params, cache, s = _granite_cell(one_chip, chunk)
    slots = 64
    row_i, row_f = s((slots,), jnp.int32), s((slots,), jnp.float32)
    keys = jax.eval_shape(lambda: jax.vmap(jax.random.key)(jnp.arange(slots, dtype=jnp.uint32)))
    keys = jax.ShapeDtypeStruct(keys.shape, keys.dtype, sharding=one_chip)
    compiled = family.build_program(eng, "paged_decode", False, False).lower(
        params, cache, row_i, row_i, s((slots,), jnp.bool_), row_f, row_f, keys,
        s((slots, 16), jnp.int32), row_i, s((slots, 1), jnp.int32), row_i).compile()
    text = compiled.as_text()
    calls = _instructions(text)
    assert names.SSM_KERNELS[0] in calls and "paged_attention" in calls
    assert names.CACHE_KERNELS[0] in calls
    state_shape = re.escape("f32[36,64,32,128,128]")  # two heads a tile (ops/ssd.py)
    producers = set(re.findall(r" = " + state_shape + r"\S* ([\w\-]+)\(", text))
    assert producers <= {"bitcast", "parameter", "get-tuple-element", "custom-call", "while"}
    scan = [line for line in text.splitlines() if f"/{names.SSM_SCOPES[1]}/" in line]
    assert len([line for line in scan if names.SSM_KERNELS[0] + "/pallas_call" in line]) >= 9
    turned = re.compile(r" = f32\[64,64,64\]\S* (transpose|copy)\(")  # slots, P, H: all 64
    assert not [line for line in scan if turned.search(line)]
    mem = compiled.memory_analysis()
    state_bytes = 36 * 64 * 64 * 64 * 128 * 4
    assert mem.alias_size_in_bytes >= state_bytes + 2 * 4 * 512 * 8 * 256 * 128 * 2
    assert mem.temp_size_in_bytes < state_bytes / 10
    assert _total_bytes(compiled) < _TENTH_SPARE


@pytest.mark.parametrize("bucket", [256, 512, 1024, 2048])
def test_granite_prefill_buckets_compile_under_the_tenth_spare_line(one_chip, tpu_branch, bucket):
    """The four prefill programs the cell's prompts reach (no context pages):
    the mixers' chunked scan, the slot's state seated in place."""
    eng, params, cache, s = _granite_cell(one_chip, _CHUNK)
    key = jax.eval_shape(lambda: jax.random.key(0))
    scalar_i, scalar_f = s((), jnp.int32), s((), jnp.float32)
    compiled = family.build_program(eng, "paged_prefill", bucket, 0).lower(
        params, cache, s((1,), jnp.int32), s((1, bucket), jnp.int32), scalar_i, scalar_i,
        scalar_f, scalar_f, jax.ShapeDtypeStruct(key.shape, key.dtype, sharding=one_chip),
        s((bucket // 256,), jnp.int32), s((1,), jnp.int32), scalar_i).compile()
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= 36 * 64 * 64 * 64 * 128 * 4  # the state in place
    assert _total_bytes(compiled) < _TENTH_SPARE
