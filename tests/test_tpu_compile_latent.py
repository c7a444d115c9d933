"""Compiled for a described v5e, with no chip attached (tests/tpu_compile.py):
latent pages at ``longcat-flash-cut1.chat-wide-mla``'s shapes. The latent
decode kernel on its work list, the latent tails' flush in place, and the
prefill that reads its context page by page.
"""

from __future__ import annotations

import math
import re

import jax
import jax.numpy as jnp
import pytest

from ditl_tpu.infer.continuous import ContinuousEngine
from ditl_tpu.models.presets import get_preset
from ditl_tpu.ops import names
from tests import family
from tests.tpu_compile import (
    _GIB,
    _TENTH_SPARE,
    _instructions,
    _steps,
    _total_bytes,
    over_tails,
)

@over_tails
def test_latent_decode_kernel_compiles_at_the_longcat_cells_shapes(one_chip, tail):
    """``mla_paged_attention`` as ``longcat-flash-cut1.chat-wide-mla`` runs it:
    128 slots, 64 heads against ONE 640-wide entry a token (512 of it the
    value), pages of 256 in a pool of 8 sublayers x 1,280 pages addressed as
    one, 16 pages a slot, the tick's tail, and the work list (its count the
    grid's length). The instruction keeps the kernel's name."""
    from ditl_tpu.ops.mla_attention import mla_paged_attention

    b, h, dl, vw, ps, pages, maxp = 128, 64, 640, 512, 256, 8 * 1280, 16
    s = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)  # noqa: E731
    args = (s((b, h, dl), jnp.bfloat16), s((pages, ps, dl), jnp.bfloat16),
            s((b, maxp), jnp.int32), s((b,), jnp.int32), s((b, tail, dl), jnp.bfloat16),
            s((b,), jnp.int32), s((b,), jnp.bool_))
    compiled = jax.jit(
        lambda q, pool, tab, lens, tl, st, alive: mla_paged_attention(
            q, pool, tab, lens, tail=tl, starts=st, value_width=vw, scale=192 ** -0.5,
            steps=_steps(st, alive, ps, maxp), interpret=False)
    ).lower(*args).compile()
    assert names.MLA_KERNELS == ("mla_paged_attention",)
    assert "mla_paged_attention" in _instructions(compiled.as_text())


@over_tails
def test_latent_flush_copies_no_pool(one_chip, tpu_branch, tail):
    """The tick's flush of its latent tails (4 layers x 2 sublayers) into the
    donated latent pool at the longcat cell's shapes: the same ``kv_flush``
    kernel over one pool with one head, a bitcast of the pool on the way in
    and out, and nothing of the pool's size produced but the custom call."""
    from ditl_tpu.infer.page_format import _flush_latent_tail

    b, ps, maxp, dl, pages = 128, 256, 16, 640, 1280
    s = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)  # noqa: E731
    row = s((b,), jnp.int32)
    compiled = jax.jit(_flush_latent_tail, donate_argnums=(0,)).lower(
        {"cp": s((8, pages, ps, dl), jnp.bfloat16)},
        {"tc": s((4, 2, b, tail, dl), jnp.bfloat16)},
        row, row, s((b, maxp), jnp.int32)).compile()
    text = compiled.as_text()
    assert names.CACHE_KERNELS[0] in _instructions(text)
    pool_elements = 8 * pages * ps * dl
    producers = set()
    for dims, op in re.findall(r" = bf16\[([\d,]+)\]\S* ([\w\-]+)\(", text):
        if math.prod(map(int, dims.split(","))) == pool_elements:
            producers.add(op)
    assert producers <= {"bitcast", "parameter", "get-tuple-element", "custom-call"}
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < pool_elements * 2 / 10
    assert mem.alias_size_in_bytes == pool_elements * 2  # the pool in place


@pytest.mark.parametrize("bucket, ctx, temp_gib", [(2048, 0, 0.6), (256, 8, 0.6)],
                         ids=["the-cells-longest-prompt", "over-eight-cached-pages"])
def test_longcat_prefill_reads_its_context_page_by_page(one_chip, tpu_branch, bucket, ctx,
                                                        temp_gib):
    """``jit_paged_prefill`` of ``longcat-flash-cut1.chat-wide-mla`` (1,280
    pages of 256 in a latent pool of 8 sublayers). Its cell's prompts share no
    prefix and are not chunked, so they reach the programs without context
    pages only, whose buffers PR 44's page-by-page ``latent_gather`` left as
    they were (0.489 GiB of temporaries at 2,048 tokens, before and after).
    Over cached pages the ONE gather it replaced made the compiler copy the
    pool whole first: 2.507 GiB of temporaries at 8 context pages, 15.27 GiB
    in all, over the line; the loop needs 0.312 (PERF.md section 6, PR 44)."""
    from ditl_tpu.data.tokenizer import ByteTokenizer
    from ditl_tpu.models import llama

    cfg = get_preset("longcat-flash", num_layers=4, vocab_size=16384, experts_held_first=0,
                     experts_held_count=16, param_dtype="bfloat16")
    s = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)  # noqa: E731
    params = jax.tree.map(
        lambda a: s(a.shape, a.dtype),
        jax.eval_shape(lambda: llama.init_params(jax.random.key(0), cfg)))
    eng = ContinuousEngine({}, cfg, ByteTokenizer(), n_slots=1, cache_mode="paged",
                           page_size=256, max_cache_len=4096, n_pages=2)
    cache = {k: s((v.shape[0], 1280, *v.shape[2:]), v.dtype) for k, v in eng.cache.items()}
    key = jax.eval_shape(lambda: jax.random.key(0))
    scalar_i, scalar_f = s((), jnp.int32), s((), jnp.float32)
    compiled = family.build_program(eng, "paged_prefill", bucket, ctx).lower(
        params, cache, s((max(ctx, 1),), jnp.int32), s((1, bucket), jnp.int32), scalar_i,
        scalar_i, scalar_f, scalar_f,
        jax.ShapeDtypeStruct(key.shape, key.dtype, sharding=one_chip),
        s((bucket // 256,), jnp.int32), s((1,), jnp.int32)).compile()
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= 8 * 1280 * 256 * 640 * 2  # the pool in place
    assert mem.temp_size_in_bytes < temp_gib * _GIB
    assert _total_bytes(compiled) < _TENTH_SPARE
