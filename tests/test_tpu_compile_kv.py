"""Compiled for a described v5e, with no chip attached (tests/tpu_compile.py):
K/V pages and the trainer's flash kernels. The paged decode kernel with ONE
query head to a kv head (7 in both Qwen2 sizes) on its work list, the cached
layer loop that copies no pool, the tick's flush in place, and the three flash
kernels with their scalar-prefetch operands.
"""

from __future__ import annotations

import math
import re

import jax
import jax.numpy as jnp
import pytest

from ditl_tpu.models.presets import get_preset
from ditl_tpu.ops import names
from tests.tpu_compile import _eqns, _instructions, _steps, over_tails

@over_tails
@pytest.mark.parametrize("h, kv, pages, b, maxp, window, group", [
    (16, 16, 10 * 192, 64, 16, None, 1),  # OLMoE: ONE query head a kv head
    (28, 4, 12 * 720, 64, 16, None, 2),  # Qwen2-7B: 7 a kv head
    (32, 8, 4 * 512, 64, 16, None, 1),  # Granite: 64-wide heads, stored on 128 lanes
    (32, 4, 4 * 2048, 32, 132, None, 2),  # Trinity-Mini's full layers: 8 a kv head
    (32, 4, 12 * 384, 32, 132, 2048, 2),  # and its window layers' pool and list
    (28, 4, 12 * 384, 32, 132, 2048, 2),  # a window list at 7 query heads a kv head
], ids=["olmoe-1b-7b-cut1", "qwen2-7b-cut1", "granite-4.0-h-micro", "trinity-mini-cut1-full",
        "trinity-mini-cut1-window", "seven-a-kv-head-window"])
def test_paged_decode_kernel_compiles_on_its_work_list_at_the_cells_shapes(
        one_chip, tpu_branch, tail, h, kv, pages, b, maxp, window, group):
    """``paged_attention`` as the serving cells run it: 64 slots and 16 pages
    a slot (the closed loop over 32k documents: 32 and 132), pages of 256 in
    all layers' pools addressed as one, either tail, the work list's rows /
    steps on the scalar-prefetch channel and its count the length of the
    one-axis grid. The instruction keeps the kernel's name: the readers and
    ``_scopes.py`` find it by that. Inside the kernel every dot takes its
    operands as the pool stores them, bfloat16, and gives float32: no page is
    converted to float32 in front of the score dot (PR 49). A step takes
    ``group`` pages, read off the pool's shape: two where a page's keys and
    values are half a MiB (4 kv heads), each pool then an operand twice, and
    still ONE score dot and ONE value dot a step (PR 53); one where they are
    1 MiB or 2, the call it always was."""
    from ditl_tpu.ops.paged_attention import paged_attention, pages_a_step

    assert pages_a_step((pages, kv, 256, 128), jnp.bfloat16, maxp) == group

    hd, ps = 128, 256
    s = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)  # noqa: E731
    args = (s((b, h, hd), jnp.bfloat16), s((pages, kv, ps, hd), jnp.bfloat16),
            s((pages, kv, ps, hd), jnp.bfloat16), s((b, maxp), jnp.int32), s((b,), jnp.int32),
            s((b, kv, tail, hd), jnp.bfloat16), s((b, kv, tail, hd), jnp.bfloat16),
            s((b,), jnp.int32), s((b,), jnp.bool_))

    def call(q, kp, vp, tab, lens, tk, tv, st, alive):
        return paged_attention(
            q, kp, vp, tab, lens, tail_k=tk, tail_v=tv, starts=st, window=window,
            steps=_steps(st, alive, ps, maxp, window, group), interpret=False)

    compiled = jax.jit(call).lower(*args).compile()
    assert names.KERNELS[3] == "paged_attention"
    assert "paged_attention" in _instructions(compiled.as_text())
    kernel, = [e for e in _eqns(jax.make_jaxpr(call)(*args).jaxpr)
               if e.primitive.name == "pallas_call"]
    dots = [e for e in _eqns(kernel.params["jaxpr"]) if e.primitive.name == "dot_general"]
    assert len(dots) == 4  # scores and values, of a step's pages and of the tail
    # q, the pools once a page of the group, the two tails
    assert len(kernel.params["grid_mapping"].block_mappings) - 1 == 3 + 2 * group
    for dot in dots:
        assert [v.aval.dtype for v in dot.invars] == [jnp.bfloat16] * 2
        assert dot.outvars[0].aval.dtype == jnp.float32


@over_tails
@pytest.mark.parametrize(
    "preset, layers, pages",
    [("qwen2-7b", 12, 720), ("olmoe-1b-7b", 10, 192)],
    ids=["qwen2-7b-cut1", "olmoe-1b-7b-cut1"],
)
def test_paged_decode_layer_loop_copies_no_pool(one_chip, tpu_branch, preset, layers, pages,
                                                tail):
    """The cached layer loop of one paged decode step at the two serving
    cells' shapes (64 slots, pages of 256, either tail). The kernel is a custom
    call, so a pool that the loop slices by layer is COPIED in front of it
    (``dynamic-slice_bitcast_fusion.8/.9``, 360 MiB of temporaries, before
    PR 27). Whole pools addressed through the page table leave no
    instruction of one layer's pool shape, a flattening that is a bitcast,
    and temporaries far under one layer's pool."""
    from ditl_tpu.models import llama
    from ditl_tpu.ops.paged_attention import pages_a_step

    cfg = get_preset(preset, num_layers=layers, param_dtype="bfloat16")
    s = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)  # noqa: E731
    params = jax.tree.map(
        lambda a: s(a.shape, a.dtype),
        jax.eval_shape(lambda: llama.init_params(jax.random.key(0), cfg)))
    b, ps, maxp = 64, 256, 16
    kv, hd = cfg.num_kv_heads, cfg.head_dim
    pool = s((layers, pages, kv, ps, hd), jnp.bfloat16)
    tails = s((layers, b, kv, tail, hd), jnp.bfloat16)
    row = s((b,), jnp.int32)

    def step(params, kp, vp, tk, tv, cur, pos, table, lengths, starts, t):
        return llama.forward(
            params, cur[:, None], cfg, positions=pos[:, None],
            cache={"kp": kp, "vp": vp, "tk": tk, "tv": tv},
            paged={"table": table, "lengths": lengths, "starts": starts, "t": t,
                   "steps": _steps(starts, lengths > 0, ps, maxp,
                                   group=pages_a_step(pool.shape, pool.dtype, maxp))},
            return_hidden=True)

    compiled = jax.jit(step).lower(
        params, pool, pool, tails, tails, row, row, s((b, maxp), jnp.int32),
        row, row, s((), jnp.int32)).compile()
    text = compiled.as_text()
    assert "paged_attention" in _instructions(text)

    def producers(*dims):
        shape = re.escape("bf16[" + ",".join(map(str, dims)) + "]")
        return set(re.findall(r" = " + shape + r"\S* ([\w\-]+)\(", text))

    assert not producers(pages, kv, ps, hd)
    assert producers(layers * pages, kv, ps, hd) <= {"bitcast", "get-tuple-element"}
    layer_pool_bytes = pages * kv * ps * hd * 2
    assert compiled.memory_analysis().temp_size_in_bytes < layer_pool_bytes / 10


@over_tails
@pytest.mark.parametrize(
    "layers, pages, kv",
    [(12, 720, 4), (10, 192, 16)],
    ids=["qwen2-7b-cut1", "olmoe-1b-7b-cut1"],
)
def test_paged_flush_copies_no_pool(one_chip, tpu_branch, layers, pages, kv, tail):
    """The tick's flush of its tail into the donated page pools at the two
    serving cells' shapes (64 slots, pages of 256, either tail, heads of 128). As
    an XLA scatter with a window of ``(L, K, D)`` (``pool.at[:, pid, :,
    off]``, before PR 29) it had the TPU compiler transpose each WHOLE pool
    to another layout in front of the scatter and back behind it: four
    pool-sized ``copy`` instructions, 2.11 GiB of temporaries. The
    ``kv_flush`` kernel takes the pools as they are and gives them back
    aliased: nothing produces an array of a pool's size but the custom call
    itself."""
    from ditl_tpu.infer.page_format import _flush_tail_into_pools

    b, ps, maxp, hd = 64, 256, 16, 128
    s = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)  # noqa: E731
    pool = s((layers, pages, kv, ps, hd), jnp.bfloat16)
    tails = s((layers, b, kv, tail, hd), jnp.bfloat16)
    row = s((b,), jnp.int32)
    compiled = jax.jit(_flush_tail_into_pools, donate_argnums=(0,)).lower(
        {"kp": pool, "vp": pool}, tails, tails, row, row,
        s((b, maxp), jnp.int32)).compile()
    text = compiled.as_text()
    assert names.CACHE_KERNELS[0] in _instructions(text)

    pool_elements = layers * pages * kv * ps * hd
    producers = set()
    for dims, op in re.findall(r" = bf16\[([\d,]+)\]\S* ([\w\-]+)\(", text):
        if math.prod(map(int, dims.split(","))) == pool_elements:
            producers.add(op)
    assert producers <= {"bitcast", "parameter", "get-tuple-element", "custom-call"}
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < pool_elements * 2 / 10
    assert mem.alias_size_in_bytes == 2 * pool_elements * 2  # both pools in place


@pytest.mark.parametrize("kernels", [("flash_fwd",), ("flash_fwd", "flash_bwd_dq", "flash_bwd_dkv")],
                         ids=["forward", "backward"])
@pytest.mark.parametrize("b,h,kv,s,d,dv", [
    (16, 14, 2, 2048, 64, 64), (2, 28, 4, 4096, 128, 128), (4, 32, 32, 8192, 192, 128)],
    ids=["train-2k", "train-fsdp4-4k-a-chip", "train-ep8-8k"])
def test_flash_kernels_compile_with_their_prefetch_operands(one_chip, kernels, b, h, kv, s, d, dv):
    """The three flash kernels as the trainer cells run them, on packed rows:
    each takes the work list of its needed blocks as four scalar-prefetch
    operands (an entry's row, outer block, inner block and flags) and walks a
    grid ``(heads, entries)`` whose second bound is the list's count, a value
    of the call (``ops/flash_attention.py``): what Mosaic makes of that,
    interpret mode cannot say. ``qwen2-0.5b.train-2k``'s batch of 16 rows,
    14/2 heads of 64 (the dk/dv list folds a group of 7: 1,120 entries at
    most); ``qwen2-7b-cut4.train-fsdp4-4k``'s 2 rows a chip, 28/4 of 128;
    ``kanana-2-30b-a3b-cut1.train-ep8-8k``'s 4 rows of 8,192, 32/32 heads at
    192 / 128 (544 entries at most)."""
    from ditl_tpu.ops.flash_attention import flash_attention

    sd = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)  # noqa: E731

    def attend(q, k, v, seg):
        return flash_attention(q, k, v, causal=True, segment_ids=seg, interpret=False)

    def loss(q, k, v, seg):
        return jnp.sum(attend(q, k, v, seg).astype(jnp.float32))

    fn = attend if len(kernels) == 1 else jax.grad(loss, argnums=(0, 1, 2))
    compiled = jax.jit(fn).lower(
        sd((b, s, h, d), jnp.bfloat16), sd((b, s, kv, d), jnp.bfloat16),
        sd((b, s, kv, dv), jnp.bfloat16), sd((b, s), jnp.int32)).compile()
    # outside the trainer's scopes an instruction is named for its transform
    # too (``jvp_flash_fwd_``, ``transpose_jvp_flash_bwd_dq__``)
    calls = _instructions(compiled.as_text())
    assert set(kernels) <= set(names.KERNELS)
    assert all(any(k in call for call in calls) for k in kernels), calls
