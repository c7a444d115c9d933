"""What a cache entry is (``infer/page_format.py``), without an engine: the
five kinds' pools and page sizes against arithmetic written here, a prefill's
``write`` then ``gather`` against NumPy loops over pages and offsets, the
latent tails' ``flush`` against a NumPy loop over the committed columns, and
the one refusal table, a case a (kind, mode) pair, against the messages the
engines raised before the table was one."""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ditl_tpu.config import ModelConfig
from ditl_tpu.infer.continuous import BadRequestError
from ditl_tpu.infer.page_format import MODES, KVPages, LatentPages, page_format
from ditl_tpu.models.presets import get_preset

P, PS, SLOTS, CHUNK = 7, 16, 3, 4  # pages (page 0 the sentinel), page size, slots, tick steps
KINDS = ["kv", "kv-int8", "kv+state", "latent", "latent+index"]


def config(kind: str) -> ModelConfig:
    if kind.startswith("latent"):
        preset = "deepseek-v3.2" if kind == "latent+index" else "longcat-flash"
        extra = (dict(num_layers=3, first_k_dense_replace=1, index_n_heads=4, index_head_dim=16,
                      index_topk=16, n_group=4, topk_group=2, experts_held_first=0,
                      rope_yarn_original_max_len=64)
                 if kind == "latent+index" else
                 dict(num_layers=2, zero_expert_num=16, experts_held_first=8))
        return dataclasses.replace(
            get_preset(preset), vocab_size=512, hidden_size=64, intermediate_size=128,
            expert_ffn_hidden_size=32, num_heads=4, num_kv_heads=4, head_dim=24, q_lora_rank=32,
            kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
            num_experts=32, num_experts_per_tok=4, experts_held_count=8, max_seq_len=128,
            dtype="float32", **extra)
    if kind == "kv+state":
        return dataclasses.replace(
            get_preset("granite-4.0-h-micro"), vocab_size=512, hidden_size=32,
            intermediate_size=64, num_layers=6, num_heads=4, num_kv_heads=2, head_dim=8,
            layer_types="mmamma", ssm_heads=4, ssm_head_dim=16, ssm_state=8, ssm_chunk=16,
            max_seq_len=128, dtype="float32")
    return ModelConfig(
        vocab_size=128, hidden_size=32, intermediate_size=64, num_layers=2, num_heads=4,
        num_kv_heads=2, head_dim=8, max_seq_len=128, dtype="float32", param_dtype="float32",
        kv_cache_dtype="int8" if kind == "kv-int8" else "")


def fmt_of(kind: str):
    return page_format(config(kind), n_pages=P, page_size=PS, n_slots=SLOTS, decode_chunk=CHUNK)


# What each kind's donated tree holds, and what a page of it costs, by hand.
# K/V: (layers with attention, P, kv heads, page, stored head width); the
# scales of int8 pools one a position; a hybrid stack's 8-wide heads stored on
# 128 lanes and its 4 mixers' state a slot beside them (ssm f32, and the 3
# columns the width-4 convolution looks back over x | B | C = 4 * 16 + 2 * 8).
# Latent: (layers x sublayers, P, page, 128 lanes of [c | rope(kr) | zeros]);
# the index keys (layers, P, page, index_head_dim).
WANT = {
    "kv": ({"kp": (2, P, 2, PS, 8), "vp": (2, P, 2, PS, 8)},
           2 * 2 * 2 * PS * 8 * 4),
    "kv-int8": ({"kp": (2, P, 2, PS, 8), "vp": (2, P, 2, PS, 8),
                 "ks": (2, P, 2, 1, PS), "vs": (2, P, 2, 1, PS)},
                2 * 2 * 2 * PS * 8 * 1 + 2 * 2 * 2 * PS * 4),
    "kv+state": ({"kp": (2, P, 2, PS, 128), "vp": (2, P, 2, PS, 128),
                  "ssm": (4, SLOTS, 4, 8, 16), "conv": (4, 3, SLOTS, 80)},
                 2 * 2 * 2 * PS * 128 * 4),
    "latent": ({"cp": (2 * 2, P, PS, 128)}, 4 * PS * 128 * 4),
    "latent+index": ({"cp": (3, P, PS, 128), "ip": (3, P, PS, 16)},
                     3 * PS * (128 + 16) * 4),
}
COUNTERS = {"kv": set(), "kv-int8": set(), "kv+state": {"ssm_row_steps"},
            "latent": {"decode_ctx_tokens"},
            "latent+index": {"decode_ctx_tokens", "dsa_selected_tokens", "dsa_index_pages"}}
# /v1/stats keys a kind adds, from lifetime totals of 100, 10 and 1 of its
# counters in COUNTERS' order below and 2 slots seated; and what one tick's
# span carries beside its counters, from the same numbers as one tick's
TOTALS = {"ssm_row_steps": 100, "decode_ctx_tokens": 100, "dsa_selected_tokens": 10,
          "dsa_index_pages": 1}
STATS = {"kv+state": {"ssm_state_bytes_per_slot": 4 * (4 * 16 * 8 + 3 * 80) * 4,
                      "ssm_state_bytes_resident": SLOTS * 4 * (4 * 16 * 8 + 3 * 80) * 4,
                      "ssm_slots_seated": 2, "ssm_row_steps_total": 100},
         "latent": {"decode_ctx_tokens": 100},
         "latent+index": {"decode_ctx_tokens": 100, "dsa_ctx_tokens": 100 * 3,
                          "dsa_selected_tokens": 10, "index_pool_bytes": 3 * P * PS * 16 * 4}}
SPAN = {"kv+state": {"ssm_steps": CHUNK}, "latent+index": {"dsa_ctx_tokens": 100 * 3}}


@pytest.mark.parametrize("kind", KINDS)
def test_fresh_pools_page_bytes_tails_and_counters_are_the_kinds_own(kind):
    fmt = fmt_of(kind)
    assert isinstance(fmt, LatentPages if kind.startswith("latent") else KVPages)
    shapes, page_bytes = WANT[kind]
    pools = fmt.fresh()
    assert {k: v.shape for k, v in pools.items()} == shapes
    assert fmt.page_bytes == page_bytes
    # a page's bytes are what the pools hold of it: everything addressed by a
    # page id (the state a slot is not)
    paged = {k: v for k, v in pools.items() if v.shape[1] == P and k not in ("ssm", "conv")}
    assert sum(v.nbytes for v in paged.values()) == P * page_bytes
    int8 = kind == "kv-int8"
    assert {k: v.dtype.name for k, v in paged.items()} == {
        k: "int8" if int8 and k in ("kp", "vp") else "float32" for k in paged}
    assert set(fmt.counters) == COUNTERS[kind]
    assert fmt.masks_tokens == (kind == "kv+state")
    # the content cache is fed by every kind whose pages serve alone
    assert fmt.publishes == (kind != "kv+state")
    assert fmt.slot_operand(2) == (2 if kind == "kv+state" else None)

    # a tick's tails: one column a step and no fewer than 8; what the scan
    # reads and what it carries are the whole tree, each leaf once
    const, carried = fmt.split(pools)
    assert set(const) | set(carried) == set(pools) and not set(const) & set(carried)
    assert set(carried) == ({"ssm", "conv"} if kind == "kv+state" else set())
    tails = fmt.tails0(5)
    if kind.startswith("latent"):
        want = {"tc": (shapes["cp"][0] // fmt.sublayers, fmt.sublayers, 5, 8, 128)}
        if kind == "latent+index":
            want["ti"] = (3, 1, 5, 8, 16)
    else:
        want = dict.fromkeys(("tk", "tv"), (2, 5, 2, 8, shapes["kp"][-1]))
    assert {k: v.shape for k, v in tails.items()} == want
    assert {k: v.shape[3] for k, v in fmt.tails0(5, 27).items()} == dict.fromkeys(want, 27)


@pytest.mark.parametrize("kind", KINDS)
def test_stats_and_span_attributes_derive_from_the_kinds_own_counters(kind):
    fmt = fmt_of(kind)
    mine = {name: TOTALS[name] for name in fmt.counters}
    assert fmt.stats(mine, 2) == STATS.get(kind, {})
    assert fmt.span_attrs(mine, CHUNK) == SPAN.get(kind, {})


def _noise_pools(fmt, rng):
    out = {}
    for name, a in fmt.fresh().items():
        if a.dtype == jnp.int8:
            out[name] = jnp.asarray(rng.integers(-127, 128, a.shape), jnp.int8)
        else:
            out[name] = jnp.asarray(1 + rng.random(a.shape), a.dtype)
    return out


def _rounded(x):
    """int8 pages: what a value reads back as (symmetric, absmax over the
    last axis a position), in NumPy."""
    absmax = np.abs(x).max(-1, keepdims=True)
    scale = np.where(absmax == 0, 1.0, absmax / 127.0).astype(np.float32)
    return np.clip(np.round(x / scale), -127, 127) * scale


@pytest.mark.parametrize("kind", KINDS)
def test_write_then_gather_gives_the_chunk_back_and_touches_no_other_page(kind):
    """A 32-token chunk written at offset 0 into pages 5 and 2 of pools full
    of noise, then gathered as the context of a later chunk: every (page,
    offset) of the row is the chunk's entry, by a NumPy loop; every page no
    ``write_pids`` entry names is bit-identical to before; the slot's state is
    seated at its slot alone, read back by a later chunk and not by a
    sequence's first."""
    fmt = fmt_of(kind)
    rng = np.random.default_rng(3)
    pools = _noise_pools(fmt, rng)
    before = {k: np.array(v) for k, v in pools.items()}
    bucket, pids, slot = 2 * PS, np.array([5, 2], np.int32), 1

    # the transient row of a first chunk: no context pages, room for the chunk
    row0 = jax.jit(lambda p: fmt.gather(
        p, jnp.zeros((1,), jnp.int32), 0, bucket, offset=jnp.int32(0),
        slot=fmt.slot_operand(slot)))(pools)
    assert all(not np.asarray(v).any() for v in row0.values())  # a first chunk reads no state
    row = {k: jnp.asarray(rng.standard_normal(v.shape), v.dtype) for k, v in row0.items()}
    wrote = jax.jit(
        lambda p, r: fmt.write(p, r, jnp.int32(0), jnp.asarray(pids),
                               slot=fmt.slot_operand(slot)),
        donate_argnums=(0,))(pools, row)
    assert set(wrote) == set(before)

    got = {k: np.asarray(v) for k, v in wrote.items()}
    chunk = {k: np.asarray(v) for k, v in row.items()}
    want = {k: v.copy() for k, v in before.items()}
    for j, pid in enumerate(pids):
        for o in range(PS):
            t = j * PS + o
            if kind.startswith("latent"):
                for pool, key in (("cp", "c"), ("ip", "i")):
                    if pool in want:  # (L, sub, 1, T, D) -> (L x sub, P, ps, D)
                        flat = chunk[key].reshape(-1, *chunk[key].shape[2:])
                        want[pool][:, pid, o] = flat[:, 0, t]
            else:
                for pool, key in (("kp", "k"), ("vp", "v")):  # (L, 1, T, K, D) -> (L, P, K, ps, D)
                    x = chunk[key][:, 0, t]
                    if kind == "kv-int8":
                        absmax = np.abs(x).max(-1)
                        scale = np.where(absmax == 0, 1.0, absmax / 127.0).astype(np.float32)
                        want[pool[0] + "s"][:, pid, :, 0, o] = scale
                        x = np.clip(np.round(x / scale[..., None]), -127, 127)
                    want[pool][:, pid, :, o] = x
    if kind == "kv+state":
        want["ssm"][:, slot] = chunk["ssm"][:, 0]
        want["conv"][:, :, slot] = chunk["conv"][:, :, 0]
    for name in want:
        if kind == "kv-int8" and name in ("kp", "vp"):  # a rounding tie may fall either way
            assert np.abs(got[name].astype(np.int32) - want[name].astype(np.int32)).max() <= 1
        else:
            np.testing.assert_allclose(got[name], want[name], rtol=1e-6, err_msg=name)

    # a later chunk's row over those two pages: the chunk, then room
    again = jax.jit(lambda p: fmt.gather(
        p, jnp.asarray(pids), 2, PS, offset=jnp.int32(bucket),
        slot=fmt.slot_operand(slot)))(wrote)
    for key, a in again.items():
        a, c = np.asarray(a), chunk[key]
        if key in ("ssm", "conv"):
            np.testing.assert_array_equal(a, c)  # what the chunk before left
            continue
        axis = 3 if kind.startswith("latent") else 2
        assert a.shape[axis] == bucket + PS
        head, room = np.split(a, [bucket], axis=axis)
        assert not room.any()
        if kind == "kv-int8":
            np.testing.assert_allclose(head, _rounded(c), atol=np.abs(c).max() / 127)
        else:
            np.testing.assert_array_equal(head, c)


@pytest.mark.parametrize("tail_len", [8, 16], ids=["tick-4", "tick-16"])
@pytest.mark.parametrize("kind", ["latent", "latent+index"])
def test_latent_flush_writes_the_committed_columns_and_nothing_else(kind, tail_len):
    """``flush`` of the latent kinds against a NumPy loop over the committed
    columns, on pools filled with noise, as tests/test_paged.py has it for
    K/V: a tail that crosses a page boundary, one that starts on a tile
    boundary, a dead row (``pos == starts``), a row stopped short, one column,
    a free slot. Every row of every page that no committed column names is
    bit-identical to before, the sentinel page 0 too; the index keys go where
    the latent entries go."""
    fmt = page_format(config(kind), n_pages=13, page_size=32, n_slots=6,
                      decode_chunk=tail_len)
    ps = 32
    rng = np.random.default_rng(tail_len)
    table = np.array([[1, 2, 3], [4, 5, 0], [6, 7, 0], [8, 12, 0], [9, 10, 11],
                      [0, 0, 0]], np.int32)
    #                  crosses a page  tile-aligned  dead  short  one column  free slot
    starts = np.array([ps - 3,         16,           5,    40,    2 * ps + 1, 0], np.int32)
    wrote = np.array([tail_len,        tail_len,     0,    5,     1,          0], np.int32)
    pools = _noise_pools(fmt, rng)
    tails = {k: jnp.asarray(rng.standard_normal(v.shape), v.dtype)
             for k, v in fmt.tails0(len(starts)).items()}
    assert {v.shape[3] for v in tails.values()} == {tail_len}

    want = {n: np.array(a) for n, a in pools.items()}
    for pool, tail in (("cp", "tc"), ("ip", "ti")):
        if pool not in want:
            continue
        vals = np.asarray(tails[tail])  # (L, sub, B, T, D) -> pool rows (L x sub)
        vals = vals.reshape(-1, *vals.shape[2:])
        for b in range(len(starts)):
            for j in range(wrote[b]):
                p = starts[b] + j
                want[pool][:, table[b, p // ps], p % ps] = vals[:, b, j]

    const, carried = fmt.split(pools)
    got = jax.jit(fmt.flush, donate_argnums=(0,))(
        const, {**tails, **carried}, jnp.asarray(starts), jnp.asarray(starts + wrote),
        jnp.asarray(table))
    assert set(got) == set(want)
    for n in want:
        np.testing.assert_array_equal(np.asarray(got[n]), want[n], err_msg=n)


# -- the refusal table -------------------------------------------------------------

_OPTION = {
    "contiguous": "the contiguous cache (cache_mode='contiguous')",
    "speculative": "speculative ticks (speculative=True)",
    "int8": "int8 page pools (kv_cache_dtype='int8')",
    "host tier": "the host tier (host_tier_mb)",
}
_LATENT = ("latent attention (kv_lora_rank=32) is served from a latent page pool, which {} "
           "cannot carry yet: serve it with cache_mode='paged', plain ticks, bfloat16 pages, "
           "no host tier and no mesh")
_STATE = ("a state-space layer (layer_types='mmamma') keeps a recurrent state a slot, which "
          "{} cannot carry yet: serve it with cache_mode='paged', plain ticks, bfloat16 "
          "pages, no host tier, no mesh and no adapters")
_HANDOFF = ("the disaggregated KV handoff (export_kv / import_kv) cannot carry latent pages "
            "or a recurrent state yet")
# (kind, mode) -> the message the engine of the parent commit raised for it
REFUSED = {
    **{("latent", m): _LATENT.format(said) for m, said in {
        **_OPTION, "mesh": "a mesh", "adapters": "LoRA adapters"}.items()},
    ("latent", "pod"): "pod serving cannot carry a latent page pool yet (latent attention "
                       "is served by one process on one chip)",
    ("latent", "handoff"): _HANDOFF,
    **{("kv+state", m): _STATE.format(said) for m, said in {
        **_OPTION, "mesh": "a mesh (mesh, and pod serving over it)",
        "adapters": "LoRA adapters (lora_rank)"}.items()},
    ("kv+state", "pod"): "pod serving cannot carry a recurrent state a slot yet (a "
                         "state-space layer is served by one process on one chip)",
    ("kv+state", "handoff"): _HANDOFF,
    ("kv+state", "registered prefix"):
        "register_prefix cannot serve a state-space layer: a prefix's pages are reusable "
        "only with the recurrent state at their boundary, which nothing keeps yet",
}
REFUSED.update({("latent+index", m): said for (k, m), said in list(REFUSED.items())
                if k == "latent"})


@pytest.mark.parametrize("mode", MODES)
@pytest.mark.parametrize("kind", KINDS)
def test_a_format_carries_a_mode_or_refuses_it_in_the_engines_words(kind, mode):
    fmt = fmt_of(kind)
    error = BadRequestError if mode == "handoff" else ValueError
    if (kind, mode) in REFUSED:
        assert mode not in fmt.carries
        with pytest.raises(error) as e:
            fmt.refuse(mode, error=error)
        assert str(e.value) == REFUSED[kind, mode]
        assert type(e.value) is error
    else:
        assert mode in fmt.carries
        fmt.refuse(mode, error=error)  # carried: nothing to say


def test_refuse_names_the_first_refused_mode_asked_and_knows_its_modes():
    fmt = fmt_of("latent")
    fmt.refuse("registered prefix")
    with pytest.raises(ValueError, match="which speculative ticks"):
        fmt.refuse("registered prefix", "speculative", "int8")
    with pytest.raises(KeyError, match="unknown mode"):
        fmt.refuse("a fifth mode")
    assert len(REFUSED) == 8 + 8 + 9 and set(m for _, m in REFUSED) <= set(MODES)
