"""Compiled for a described v5e, with no chip attached: what this repo's
first expert layer asks of the TPU's compiler at the published OLMoE widths.
The Mosaic kernels of the grouped matmul (forward, and the transposed product
of the backward pass, whose tiles have to fit 16 MB of scoped VMEM: a
2,048-wide contraction tile did not) and the paged decode kernel with ONE
query head to a kv head (7 in both Qwen2 sizes). A compile that passes is not
a chip run: nothing here is a time.

One file, and the topology is described inside a fixture: only one process
may load the TPU's library at a time (``on-chip-measurement`` guide).
"""

from __future__ import annotations

import inspect
import math
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from ditl_tpu.infer.continuous import ContinuousEngine, tail_width
from ditl_tpu.models import moe as moe_mod
from ditl_tpu.models.presets import get_preset
from ditl_tpu.ops import names

# The tail widths the engines build: the serving cells' (the constructor's
# default ``decode_chunk``, a 4-step program in an 8-column tail) and
# ``benchmarks/paged_check.py``'s 16-step ticks.
_CHUNK = inspect.signature(ContinuousEngine).parameters["decode_chunk"].default
over_tails = pytest.mark.parametrize(
    "tail", [tail_width(_CHUNK), tail_width(16)], ids=[f"tick-{_CHUNK}", "tick-16"])


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure to describe it is a skip
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def tpu_branch(monkeypatch):
    """The code asks ``jax.default_backend()``, which is the CPU here: take
    the branch the chip takes."""
    monkeypatch.setattr(moe_mod, "_use_gmm", lambda rows, mesh: rows % 128 == 0)
    from ditl_tpu.ops import backend, kv_flush, paged_attention, ssd

    # every module that bound the name at its import, whichever came first
    for module in (moe_mod, backend, kv_flush, paged_attention, ssd):
        monkeypatch.setattr(module, "interpret_default", lambda: False)


def _moe_shapes(cfg, sharding):
    d, f, e = cfg.hidden_size, cfg.intermediate_size, cfg.num_experts
    s = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=sharding)  # noqa: E731
    return {"router": s((d, e), jnp.float32), "w_gate": s((e, d, f), jnp.bfloat16),
            "w_up": s((e, d, f), jnp.bfloat16), "w_down": s((e, f, d), jnp.bfloat16)}


def _instructions(text: str) -> set[str]:
    return {m.split(".")[0] for m in re.findall(r"%([\w\-.]+) = [^\n]*custom-call", text)}


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


def _steps(starts, alive, ps, maxp):
    """The kernels' work list, built in the compiled program as a decode
    program builds it (``decode_steps``: rows, ks and the traced count that
    is the grid's length)."""
    from ditl_tpu.ops.paged_attention import decode_steps

    return decode_steps(starts, alive, page_size=ps, max_pages=maxp)


@over_tails
@pytest.mark.parametrize("h, kv, pages", [
    (16, 16, 10 * 192),  # OLMoE: ONE query head a kv head
    (28, 4, 12 * 720),  # Qwen2-7B: 7 a kv head
    (32, 8, 4 * 512),  # Granite: 64-wide heads, stored on 128 lanes
], ids=["olmoe-1b-7b-cut1", "qwen2-7b-cut1", "granite-4.0-h-micro"])
def test_paged_decode_kernel_compiles_on_its_work_list_at_the_cells_shapes(
        one_chip, tpu_branch, tail, h, kv, pages):
    """``paged_attention`` as the serving cells run it: 64 slots, pages of
    256 in all layers' pools addressed as one, 16 pages a slot, either tail,
    the work list's rows / steps on the scalar-prefetch channel and its count
    the length of the one-axis grid. The instruction keeps the kernel's name:
    the readers and ``_scopes.py`` find it by that."""
    from ditl_tpu.ops.paged_attention import paged_attention

    b, hd, ps, maxp = 64, 128, 256, 16
    s = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)  # noqa: E731
    args = (s((b, h, hd), jnp.bfloat16), s((pages, kv, ps, hd), jnp.bfloat16),
            s((pages, kv, ps, hd), jnp.bfloat16), s((b, maxp), jnp.int32), s((b,), jnp.int32),
            s((b, kv, tail, hd), jnp.bfloat16), s((b, kv, tail, hd), jnp.bfloat16),
            s((b,), jnp.int32), s((b,), jnp.bool_))
    compiled = jax.jit(
        lambda q, kp, vp, tab, lens, tk, tv, st, alive: paged_attention(
            q, kp, vp, tab, lens, tail_k=tk, tail_v=tv, starts=st,
            steps=_steps(st, alive, ps, maxp), interpret=False)
    ).lower(*args).compile()
    assert names.KERNELS[3] == "paged_attention"
    assert "paged_attention" in _instructions(compiled.as_text())


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
                   "steps": _steps(starts, lengths > 0, ps, maxp)},
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
    from ditl_tpu.infer.continuous import _flush_tail_into_pools

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
    from ditl_tpu.infer.continuous import _flush_latent_tail

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


@pytest.mark.parametrize("kernels", [("flash_fwd",), ("flash_fwd", "flash_bwd_dq", "flash_bwd_dkv")],
                         ids=["forward", "backward"])
@pytest.mark.parametrize("b,h,kv,s,d", [(16, 14, 2, 2048, 64), (2, 28, 4, 4096, 128)],
                         ids=["train-2k", "train-fsdp4-4k-a-chip"])
def test_flash_kernels_compile_with_their_prefetch_operands(one_chip, kernels, b, h, kv, s, d):
    """The three flash kernels as the trainer cells run them, on packed rows:
    each takes the blocks' id ranges and the hull of its needed blocks as
    scalar-prefetch operands and walks an inner grid axis whose bound is the
    widest hull, a value of the call (``ops/flash_attention.py``): what
    Mosaic makes of that, interpret mode cannot say. ``qwen2-0.5b.train-2k``'s batch of 16 rows, 14/2
    heads of 64; ``qwen2-7b-cut4.train-fsdp4-4k``'s 2 rows a chip, 28/4 of 128."""
    from ditl_tpu.ops.flash_attention import flash_attention

    sd = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)  # noqa: E731

    def attend(q, k, v, seg):
        return flash_attention(q, k, v, causal=True, segment_ids=seg, interpret=False)

    def loss(q, k, v, seg):
        return jnp.sum(attend(q, k, v, seg).astype(jnp.float32))

    fn = attend if len(kernels) == 1 else jax.grad(loss, argnums=(0, 1, 2))
    compiled = jax.jit(fn).lower(
        sd((b, s, h, d), jnp.bfloat16), sd((b, s, kv, d), jnp.bfloat16),
        sd((b, s, kv, d), jnp.bfloat16), sd((b, s), jnp.int32)).compile()
    # outside the trainer's scopes an instruction is named for its transform
    # too (``jvp_flash_fwd_``, ``transpose_jvp_flash_bwd_dq__``)
    calls = _instructions(compiled.as_text())
    assert set(kernels) <= set(names.KERNELS)
    assert all(any(k in call for call in calls) for k in kernels), calls


# granite-4.0-h-micro.chat-wide-ssm (ISSUE 41): the whole published model, 64
# slots of recurrent state, 512 pages of 256 tokens at 128 stored lanes a head.
_GIB = 2 ** 30
_TENTH_SPARE = 0.9 * 15.75 * _GIB  # of a v5e's 15.75 GiB


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


def _total_bytes(compiled):
    m = compiled.memory_analysis()
    return (m.argument_size_in_bytes + m.temp_size_in_bytes + m.output_size_in_bytes
            - m.alias_size_in_bytes)


@pytest.mark.parametrize("chunk", [_CHUNK, 16], ids=[f"tick-{_CHUNK}", "tick-16"])
def test_granite_decode_program_compiles_in_place_under_the_tenth_spare_line(
        one_chip, tpu_branch, chunk):
    """``jit_paged_decode`` of the cell (and of ``paged_check.py``'s 16-step
    ticks): nine ``ssd_step`` kernels a period scan and the paged attention
    kernel over 128-lane pages, the 4.5 GiB state and the pools aliased to
    the outputs, no instruction that produces a second state, temporaries far
    under one mixer's state, the whole under the tenth-spare line."""
    eng, params, cache, s = _granite_cell(one_chip, chunk)
    slots = 64
    row_i, row_f = s((slots,), jnp.int32), s((slots,), jnp.float32)
    keys = jax.eval_shape(lambda: jax.vmap(jax.random.key)(jnp.arange(slots, dtype=jnp.uint32)))
    keys = jax.ShapeDtypeStruct(keys.shape, keys.dtype, sharding=one_chip)
    compiled = eng._build_paged_decode(False, False).lower(
        params, cache, row_i, row_i, s((slots,), jnp.bool_), row_f, row_f, keys,
        s((slots, 16), jnp.int32), row_i, s((slots, 1), jnp.int32), row_i).compile()
    text = compiled.as_text()
    calls = _instructions(text)
    assert names.SSM_KERNELS[0] in calls and "paged_attention" in calls
    assert names.CACHE_KERNELS[0] in calls
    state_shape = re.escape("f32[36,64,64,64,128]")
    producers = set(re.findall(r" = " + state_shape + r"\S* ([\w\-]+)\(", text))
    assert producers <= {"bitcast", "parameter", "get-tuple-element", "custom-call", "while"}
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
    compiled = eng._build_paged_prefill(bucket, 0).lower(
        params, cache, s((1,), jnp.int32), s((1, bucket), jnp.int32), scalar_i, scalar_i,
        scalar_f, scalar_f, jax.ShapeDtypeStruct(key.shape, key.dtype, sharding=one_chip),
        s((bucket // 256,), jnp.int32), s((1,), jnp.int32), scalar_i).compile()
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= 36 * 64 * 64 * 64 * 128 * 4  # the state in place
    assert _total_bytes(compiled) < _TENTH_SPARE


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
    compiled = eng._build_paged_prefill(bucket, ctx).lower(
        params, cache, s((max(ctx, 1),), jnp.int32), s((1, bucket), jnp.int32), scalar_i,
        scalar_i, scalar_f, scalar_f,
        jax.ShapeDtypeStruct(key.shape, key.dtype, sharding=one_chip),
        s((bucket // 256,), jnp.int32), s((1,), jnp.int32)).compile()
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= 8 * 1280 * 256 * 640 * 2  # the pool in place
    assert mem.temp_size_in_bytes < temp_gib * _GIB
    assert _total_bytes(compiled) < _TENTH_SPARE


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
    compiled = eng._build_paged_decode(False, False).lower(
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
    compiled = eng._build_paged_prefill(bucket, ctx).lower(
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
