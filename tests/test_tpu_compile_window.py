"""Compiled for a described v5e, with no chip attached (tests/tpu_compile.py):
two page pools of keys and values, the full layers' and the window layers',
and the decode and prefill programs of ``trinity-mini-cut1.docs-32k-swa``
under the tenth-spare line.
"""

from __future__ import annotations

import json
import os
import re
import sys

import jax
import jax.numpy as jnp
import pytest

from ditl_tpu.infer.continuous import ContinuousEngine
from ditl_tpu.models.presets import get_preset
from ditl_tpu.ops import names
from tests import family
from tests.tpu_compile import _GIB, _TENTH_SPARE, _instructions, _total_bytes

PAGES, WINDOW_PAGES, SLOTS, MAXP = 2048, 384, 32, 132


# trinity-mini-cut1.docs-32k-swa (ISSUE 48): one chip's share of 8, 32 slots,
# 2,048 pages of 256 tokens in the 4 full layers' pool, 384 in the 12 window
# layers', rows of up to 132 pages.
def _trinity_cell(one_chip):
    """(engine whose programs are the cell's, abstract params, abstract
    cache) with nothing of the model's size allocated."""
    from ditl_tpu.data.tokenizer import ByteTokenizer
    from ditl_tpu.models import llama

    cfg = get_preset("trinity-mini", num_layers=16, layer_types="wwwa" * 4,
                     first_k_dense_replace=1, vocab_size=25024, experts_held_first=0,
                     experts_held_count=16, param_dtype="bfloat16")
    s = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)  # noqa: E731
    params = jax.tree.map(
        lambda a: s(a.shape, a.dtype),
        jax.eval_shape(lambda: llama.init_params(jax.random.key(0), cfg)))
    eng = ContinuousEngine({}, cfg, ByteTokenizer(), n_slots=1, cache_mode="paged",
                           page_size=256, max_cache_len=33792, n_pages=2, window_pages=2)
    cache = {"kp": s((4, PAGES, 4, 256, 128), jnp.bfloat16),
             "vp": s((4, PAGES, 4, 256, 128), jnp.bfloat16),
             "wkp": s((12, WINDOW_PAGES, 4, 256, 128), jnp.bfloat16),
             "wvp": s((12, WINDOW_PAGES, 4, 256, 128), jnp.bfloat16)}
    assert {k: (v.shape[0], *v.shape[2:]) for k, v in eng.cache.items()} == {
        k: (v.shape[0], *v.shape[2:]) for k, v in cache.items()}  # the engine's own layout
    return eng, params, cache, s


def _whole_pool_copies(text: str) -> set[str]:
    """Instructions that produce an array of either whole pool's shape."""
    producers = set()
    for layers, pages in ((4, PAGES), (12, WINDOW_PAGES)):
        shape = re.escape(f"bf16[{layers},{pages},4,256,128]")
        producers |= set(re.findall(r" = " + shape + r"\S* ([\w\-]+)\(", text))
    return producers - {"bitcast", "parameter", "get-tuple-element", "custom-call", "while",
                        "dynamic-update-slice", "conditional"}


def test_trinity_decode_program_compiles_in_place_under_the_tenth_spare_line(
        one_chip, tpu_branch):
    """``jit_paged_decode`` of the cell: the decode kernel over each pool with
    a work list of its own, both built once in front of the scan, the held
    experts' ``gmm`` inside the stack, both pools flushed in place by
    ``kv_flush`` and aliased to the outputs; the whole under the tenth-spare
    line."""
    eng, params, cache, s = _trinity_cell(one_chip)
    row_i, row_f = s((SLOTS,), jnp.int32), s((SLOTS,), jnp.float32)
    keys = jax.eval_shape(lambda: jax.vmap(jax.random.key)(jnp.arange(SLOTS, dtype=jnp.uint32)))
    keys = jax.ShapeDtypeStruct(keys.shape, keys.dtype, sharding=one_chip)
    compiled = family.build_program(eng, "paged_decode", False, False).lower(
        params, cache, row_i, row_i, s((SLOTS,), jnp.bool_), row_f, row_f, keys,
        s((2, SLOTS, MAXP), jnp.int32), row_i, s((SLOTS, 1), jnp.int32), row_i).compile()
    text = compiled.as_text()
    calls = _instructions(text)
    assert names.CACHE_KERNELS[0] in calls and "gmm" in calls and "paged_attention" in calls
    for scope in names.SWA_SCOPES:  # both kinds' kernels, each under its scope
        assert re.search(rf'op_name="[^"]*/{scope}/[^"]*paged_attention', text), scope
    assert not _whole_pool_copies(text)
    mem = compiled.memory_analysis()
    pool_bytes = 2 * (4 * PAGES + 12 * WINDOW_PAGES) * 4 * 256 * 128 * 2
    assert mem.alias_size_in_bytes >= pool_bytes
    assert mem.temp_size_in_bytes < 0.9 * _GIB
    assert _total_bytes(compiled) < _TENTH_SPARE


@pytest.mark.parametrize("bucket, ctx", [(1024, 0), (1024, 64), (1024, 128), (256, 128)],
                         ids=["document-first-chunk", "document-mid", "document-last-chunks",
                              "question-over-a-cached-document"])
def test_trinity_prefill_buckets_compile_under_the_tenth_spare_line(
        one_chip, tpu_branch, bucket, ctx):
    """The prefill programs the cell reaches: a document's 1,024-token chunks
    over 0 to 128 context pages of the full layers' pool and at most 8 of the
    window layers', and a turn's 256-token bucket over a whole cached document.
    A full layer's scores run in blocks of queries: all 32 heads of a 1,024-token
    chunk against 33,792 cached tokens at once would be 4.4 GB in float32."""
    eng, params, cache, s = _trinity_cell(one_chip)
    key = jax.eval_shape(lambda: jax.random.key(0))
    scalar_i, scalar_f = s((), jnp.int32), s((), jnp.float32)
    wctx = min(ctx, 8)
    compiled = family.build_program(eng, "paged_prefill", bucket, ctx).lower(
        params, cache, (s((max(ctx, 1),), jnp.int32), s((max(wctx, 1),), jnp.int32)),
        s((1, bucket), jnp.int32), scalar_i, scalar_i, scalar_f, scalar_f,
        jax.ShapeDtypeStruct(key.shape, key.dtype, sharding=one_chip),
        (s((bucket // 256,), jnp.int32), s((bucket // 256,), jnp.int32)),
        s((1,), jnp.int32)).compile()
    # 10.57 / 11.00 / 11.32 / 11.71 GiB (temporaries 0.25 / 0.68 / 1.00 / 1.39)
    assert compiled.memory_analysis().temp_size_in_bytes < 1.6 * _GIB
    assert _total_bytes(compiled) < _TENTH_SPARE


@pytest.mark.parametrize("config, want", [
    ("qwen2-7b-cut1", 2), ("trinity-mini-cut1", 2), ("olmoe-1b-7b-cut1", 1),
    ("granite-4.0-h-micro", 1), ("longcat-flash-cut1", 1), ("deepseek-v3.2-cut1", 1)])
def test_the_pages_a_step_of_the_serving_configurations(config, want):
    """``pages_a_step`` read off the pools each serving configuration's file
    gives an engine with pages of 256 (no chip and no compile: shapes alone):
    two where a page's keys and values are half a MiB (4 kv heads of 128
    lanes: Qwen2-7B, and both of Trinity-Mini's pools), one at Granite's 1 MiB
    (8 kv heads of 64 stored on 128 lanes) and OLMoE's 2 MiB, whose walk stays
    the one-page walk, and one for the latent kernels, which are not this one."""
    from ditl_tpu.infer.page_format import page_format
    from ditl_tpu.ops.paged_attention import pages_a_step

    bench = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "benchmarks")
    sys.path.insert(0, bench)
    try:
        import reference_check
        from harness import model_override_args
    finally:
        sys.path.remove(bench)
    with open(os.path.join(bench, "configs", f"{config}.json")) as f:
        cell = json.load(f)
    cfg = reference_check.model_config(cell, model_override_args(cell, "serve"))
    fmt = page_format(cfg, n_pages=8, page_size=256, n_slots=2, decode_chunk=4)
    assert fmt.attn_pages_a_step(132) == want
    for shape in (getattr(fmt, name) for name in ("shape", "win_shape") if hasattr(fmt, name)):
        assert pages_a_step(shape, fmt.dtype, 132) == want, shape
    assert fmt.attn_pages_a_step(1) == 1  # never more than the table is wide
