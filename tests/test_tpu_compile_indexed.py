"""Compiled for a described v5e, with no chip attached (tests/tpu_compile.py):
latent pages with an index-key pool beside them, the index scores' kernel and
the decode and prefill programs of ``deepseek-v3.2-cut1.docs-32k-dsa`` under
the tenth-spare line.
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
from tests.tpu_compile import _GIB, _TENTH_SPARE, _instructions, _total_bytes

# deepseek-v3.2-cut1.docs-32k-dsa (ISSUE 44): one chip's share of 16, 32 slots,
# 2,048 pages of 256 tokens in a latent pool and an index-key pool, rows of up
# to 132 pages.
def _deepseek_cell(one_chip):
    """(engine whose programs are the cell's, abstract params, abstract
    cache) with nothing of the model's size allocated."""
    from ditl_tpu.data.tokenizer import ByteTokenizer
    from ditl_tpu.models import llama

    cfg = get_preset("deepseek-v3.2", num_layers=5, first_k_dense_replace=1, vocab_size=16160,
                     experts_held_first=0, experts_held_count=16, param_dtype="bfloat16")
    s = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)  # noqa: E731
    params = jax.tree.map(
        lambda a: s(a.shape, a.dtype),
        jax.eval_shape(lambda: llama.init_params(jax.random.key(0), cfg)))
    eng = ContinuousEngine({}, cfg, ByteTokenizer(), n_slots=1, cache_mode="paged",
                           page_size=256, max_cache_len=33792, n_pages=2)
    pages = 2048
    cache = {"cp": s((5, pages, 256, 640), jnp.bfloat16),
             "ip": s((5, pages, 256, 128), jnp.bfloat16)}
    assert {k: v.shape[2:] for k, v in eng.cache.items()} == {
        "cp": (256, 640), "ip": (256, 128)}  # the engine's own pools are so laid out
    return eng, params, cache, s


def _whole_pool_copies(text: str) -> set[str]:
    """Instructions that produce an array of either whole pool's shape."""
    producers = set()
    for width in (640, 128):
        shape = re.escape(f"bf16[5,2048,256,{width}]")
        producers |= set(re.findall(r" = " + shape + r"\S* ([\w\-]+)\(", text))
    return producers - {"bitcast", "parameter", "get-tuple-element", "custom-call", "while",
                        "dynamic-update-slice"}


def _assert_selection_sorts_nothing(text: str):
    """No compiled instruction traced under the scope ``dsa_select`` is a
    sort or a top-k, by opcode or by custom-call target (ISSUE 45)."""
    ops = set()
    for line in text.splitlines():
        if re.search(r'op_name="[^"]*/dsa_select/', line):
            found = re.findall(r' = \S+ ([\w\-]+)\(|custom_call_target="(\w+)"', line)
            ops.update(name for pair in found for name in pair if name)
    assert ops, "no instruction carries the scope dsa_select"
    assert not {o for o in ops if re.search(r"sort|top_?k", o, re.IGNORECASE)}, ops


def test_index_scores_kernel_compiles_at_the_deepseek_cells_shapes(one_chip):
    """``dsa_index_scores`` as ``deepseek-v3.2-cut1.docs-32k-dsa`` runs it: 32
    slots, 64 index heads against one 128-wide key a token, pages of 256 in a
    pool of 5 layers x 2,048 pages addressed as one, 132 pages a slot walked
    12 a step, the pool left in HBM. The instruction keeps the kernel's name."""
    from ditl_tpu.ops.dsa_index import dsa_index_scores, index_steps, pages_a_step

    b, hi, di, ps, pages, maxp = 32, 64, 128, 256, 5 * 2048, 132
    assert pages_a_step(maxp, ps) == 12
    s = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)  # noqa: E731
    args = (s((b, hi, di), jnp.bfloat16), s((b, hi), jnp.float32),
            s((pages, ps, di), jnp.bfloat16), s((b, maxp), jnp.int32), s((b,), jnp.int32),
            s((b,), jnp.int32), s((b,), jnp.bool_))
    compiled = jax.jit(
        lambda q, w, pool, tab, lens, st, alive: dsa_index_scores(
            q, w, pool, tab, lens, st,
            steps=index_steps(st, alive, page_size=ps, max_pages=maxp))
    ).lower(*args).compile()
    assert names.DSA_KERNELS[0] in _instructions(compiled.as_text())
    # neither a copy of the pool (671 MB) nor the rows' gathered keys (277 MB)
    assert compiled.memory_analysis().temp_size_in_bytes < 8 * 2 ** 20


def test_deepseek_decode_program_compiles_in_place_under_the_tenth_spare_line(
        one_chip, tpu_branch):
    """``jit_paged_decode`` of the cell: index scores over 132 pages a row by
    the kernel that reads them in place, the top-2,048, the gather of the
    selected latent entries, the held experts'
    ``gmm`` inside the stack, both pools flushed in place by ``kv_flush`` and
    aliased to the outputs; the whole under the tenth-spare line."""
    eng, params, cache, s = _deepseek_cell(one_chip)
    slots = 32
    row_i, row_f = s((slots,), jnp.int32), s((slots,), jnp.float32)
    keys = jax.eval_shape(lambda: jax.vmap(jax.random.key)(jnp.arange(slots, dtype=jnp.uint32)))
    keys = jax.ShapeDtypeStruct(keys.shape, keys.dtype, sharding=one_chip)
    compiled = family.build_program(eng, "paged_decode", False, False).lower(
        params, cache, row_i, row_i, s((slots,), jnp.bool_), row_f, row_f, keys,
        s((slots, 132), jnp.int32), row_i, s((slots, 1), jnp.int32), row_i).compile()
    text = compiled.as_text()
    calls = _instructions(text)
    assert names.CACHE_KERNELS[0] in calls and "gmm" in calls
    assert names.DSA_KERNELS[0] in calls  # the index scores read the pages in place
    assert not _whole_pool_copies(text)
    _assert_selection_sorts_nothing(text)
    # no gather of the rows' index keys: nothing of (slots, 132 pages, 256, 128)
    assert not re.search(r"bf16\[32,(132,256|33792),128\]", text)
    mem = compiled.memory_analysis()
    pool_bytes = 5 * 2048 * 256 * (640 + 128) * 2
    assert mem.alias_size_in_bytes >= pool_bytes
    # 0.85 GiB; 0.97 while the index keys were gathered (277 MB a layer)
    assert mem.temp_size_in_bytes < 0.9 * _GIB
    assert _total_bytes(compiled) < _TENTH_SPARE


@pytest.mark.parametrize("bucket, ctx", [(1024, 0), (1024, 64), (1024, 128), (256, 128)],
                         ids=["document-first-chunk", "document-mid", "document-last-chunks",
                              "question-over-a-cached-document"])
def test_deepseek_prefill_buckets_compile_under_the_tenth_spare_line(
        one_chip, tpu_branch, bucket, ctx):
    """The prefill programs the cell reaches: a document's 1,024-token chunks
    over 0 to 128 context pages of BOTH pools and a turn's 256-token bucket
    over a whole cached document. The context is read page by page: one
    gather of all pages made the compiler copy both pools whole in lane
    slices first (2.6 GiB of temporaries, whatever the context)."""
    eng, params, cache, s = _deepseek_cell(one_chip)
    key = jax.eval_shape(lambda: jax.random.key(0))
    scalar_i, scalar_f = s((), jnp.int32), s((), jnp.float32)
    compiled = family.build_program(eng, "paged_prefill", bucket, ctx).lower(
        params, cache, s((max(ctx, 1),), jnp.int32), s((1, bucket), jnp.int32), scalar_i,
        scalar_i, scalar_f, scalar_f,
        jax.ShapeDtypeStruct(key.shape, key.dtype, sharding=one_chip),
        s((bucket // 256,), jnp.int32), s((1,), jnp.int32)).compile()
    if 256 * ctx + bucket > eng.cfg.index_topk:  # else everything is selected: no indexer
        _assert_selection_sorts_nothing(compiled.as_text())
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= 5 * 2048 * 256 * (640 + 128) * 2  # both pools in place
    assert mem.temp_size_in_bytes < 1.5 * _GIB
    assert _total_bytes(compiled) < _TENTH_SPARE
