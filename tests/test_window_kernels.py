"""The window clause in both kernels (ISSUE 48): flash forward with a window
against the XLA mask in interpret mode, blocks visited counted; the paged
decode kernel with a window against the gather oracle, rows on both sides of
the window and a dead row; ``decode_steps`` with a window: which pages, how
many. ``window=None`` leaves every list and every output as it was."""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ditl_tpu.ops import flash_attention as fa
from ditl_tpu.ops.attention import _xla_attention, dot_product_attention
from ditl_tpu.ops.paged_attention import (
    decode_steps,
    paged_attention,
    paged_attention_xla,
    window_first_page,
)
from tests.rect_walk import derive_pages_a_step

PS, MAXP, W = 16, 12, 40  # pages of 16 tokens, 12 a row, a window of 40


def test_the_xla_mask_keeps_the_last_window_keys():
    q = jax.random.normal(jax.random.key(0), (1, 64, 2, 8))
    k = jax.random.normal(jax.random.key(1), (1, 64, 1, 8))
    i, j = np.arange(64)[:, None], np.arange(64)[None, :]
    want = _xla_attention(q, k, k, causal=False, segment_ids=None,
                          mask=jnp.asarray((j <= i) & (i - j < 10))[None])
    got = dot_product_attention(q, k, k, causal=True, window=10)
    assert np.allclose(np.asarray(got), np.asarray(want), atol=1e-6)
    assert np.array_equal(np.asarray(dot_product_attention(q, k, k, causal=True, window=64)),
                          np.asarray(dot_product_attention(q, k, k, causal=True)))
    with pytest.raises(ValueError, match="clause of the causal mask"):
        dot_product_attention(q, k, k, causal=False, window=10)


@pytest.mark.parametrize("packed", [False, True], ids=["one-document", "packed"])
def test_flash_forward_with_a_window_is_the_xla_mask(packed):
    b, s, h, kv, d = 2, 1024, 4, 2, 128
    q = jax.random.normal(jax.random.key(0), (b, s, h, d))
    k = jax.random.normal(jax.random.key(1), (b, s, kv, d))
    v = jax.random.normal(jax.random.key(2), (b, s, kv, d))
    seg = None
    if packed:
        seg = jnp.asarray(np.stack([np.repeat([1, 2, 3], [300, 500, 224]),
                                    np.repeat([1, 2], [1000, 24])]).astype(np.int32))
    want = dot_product_attention(q, k, v, segment_ids=seg, impl="xla", window=200)
    got = dot_product_attention(q, k, v, segment_ids=seg, impl="flash", window=200,
                                block_sizes=(128, 128, 0, 0))
    assert float(jnp.abs(got - want).max()) < 1e-5
    # without a window the kernel is what it was, to the bit
    assert np.array_equal(
        np.asarray(fa.flash_attention(q, k, v, segment_ids=seg, block_q=128, block_kv=128)),
        np.asarray(dot_product_attention(q, k, v, segment_ids=seg, impl="flash",
                                         block_sizes=(128, 128, 0, 0))))


def test_flash_visits_the_blocks_that_meet_the_window_and_no_more():
    """8,192 tokens in blocks of 512 with a window of 2,048: a query block's
    run is at most 2,048 / 512 + 1 = 5 key blocks where causality alone
    leaves up to 16; the work list the forward kernel walks holds those."""
    seg = jnp.ones((1, 8192), jnp.int32)
    reach, needed, walked = fa.block_counts(seg, block_q=512, block_kv=512, window=2048)
    assert int(reach) == 16 * 17 // 2
    assert int(needed) == int(walked) == sum(min(i + 1, 5) for i in range(16)) == 70
    assert int(fa.block_counts(seg, block_q=512, block_kv=512)[1]) == 136
    blocks = fa.BlockSizes(512, 512)
    (_, outer, inner, _), count = fa._work_lists(seg, seg, blocks, True, window=2048)[0]
    assert int(count) == 70 == len(outer)  # the list's static length knows the window too
    outer, inner = np.asarray(outer), np.asarray(inner)
    assert [inner[outer == i].tolist() for i in range(16)] == [
        list(range(max(i - 4, 0), i + 1)) for i in range(16)]
    # a window that is a whole number of blocks less one token: 4 blocks
    assert int(fa._work_lists(seg, seg, blocks, True, window=1537)[0][1]) == sum(
        min(i + 1, 4) for i in range(16))


def _pool(seed, n_pages=40, kv=2, d=128):
    k = jax.random.normal(jax.random.key(seed), (n_pages, kv, PS, d), jnp.float32)
    v = jax.random.normal(jax.random.key(seed + 1), (n_pages, kv, PS, d), jnp.float32)
    return k, v


def _rows():
    """Five rows: inside the window, just across it, far across it (its list
    starts pages in), a page boundary, and a dead one."""
    starts = jnp.asarray([20, 41, 150, 64, 90], jnp.int32)
    alive = jnp.asarray([True, True, True, True, False])
    table = jnp.asarray(np.random.default_rng(0).permutation(np.arange(1, 1 + 5 * MAXP))
                        .reshape(5, MAXP), jnp.int32)
    return starts, alive, table


def test_decode_steps_with_a_window_lists_the_pages_that_meet_it():
    starts, alive, _ = _rows()
    plain = decode_steps(starts, alive, page_size=PS, max_pages=MAXP)
    steps = decode_steps(starts, alive, page_size=PS, max_pages=MAXP, window=W)
    first = np.asarray(window_first_page(starts, W, PS))
    assert first.tolist() == [0, 0, 6, 1, 3]  # the page of position starts - 39
    pages = [-(-int(s) // PS) - int(f) for s, f in zip(starts, first)]
    assert pages == [2, 3, 4, 3, 3]
    want_rows = sum(([r] * (pages[r] + 1) for r in range(4)), [])  # + the tail step
    n = int(steps["count"])
    assert n == len(want_rows) == 16 and int(plain["count"]) == 2 + 3 + 10 + 4 + 4
    assert np.asarray(steps["rows"])[:n].tolist() == want_rows
    assert np.asarray(steps["ks"])[:n].tolist() == sum(
        (list(range(pages[r] + 1)) for r in range(4)), [])
    # a window wider than every context changes nothing
    wide = decode_steps(starts, alive, page_size=PS, max_pages=MAXP, window=4096)
    assert all(np.array_equal(np.asarray(wide[n]), np.asarray(plain[n])) for n in plain)


@pytest.mark.parametrize("group", [2, 3, 4], ids="{}-pages-a-step".format)
def test_decode_steps_with_a_window_groups_from_the_windows_first_page(group):
    """A step a ``group`` of pages: a row's groups start at its first page
    inside the window (pages 0, 0, 6, 1 here: an even, an odd one), the last
    group ragged, and the list is as long as the widest table needs."""
    starts, alive, _ = _rows()
    steps = decode_steps(starts, alive, page_size=PS, max_pages=MAXP, window=W, group=group)
    groups = [-(-pages // group) for pages in (2, 3, 4, 3)]
    n = int(steps["count"])
    assert n == sum(groups) + 4
    assert steps["rows"].shape == (5 * (-(-MAXP // group) + 1),)
    assert np.asarray(steps["rows"])[:n].tolist() == sum(
        ([r] * (groups[r] + 1) for r in range(4)), [])
    assert np.asarray(steps["ks"])[:n].tolist() == sum(
        (list(range(groups[r] + 1)) for r in range(4)), [])


@pytest.mark.parametrize("pages", [1, 2, 4], ids="{}-pages-a-step".format)
@pytest.mark.parametrize("t", [0, 3, 7], ids=lambda t: f"step-{t}")
def test_the_paged_kernel_with_a_window_is_the_gather_oracle(t, pages, monkeypatch):
    """Step ``t`` of a program that began at ``starts``: positions [starts,
    starts + t] sit in the tail. The list is built once, for step 0; at later
    steps the first listed page may have fallen wholly behind the window. A
    step takes ``pages`` pages from the row's first page inside the window
    (rows whose list starts at page 0, at 6 and at the odd page 1): the
    window's first page masked in part, the last group ragged."""
    starts, alive, table = _rows()
    k_pages, v_pages = _pool(3, n_pages=1 + 5 * MAXP)
    derive_pages_a_step(monkeypatch, pages, k_pages)
    h, kv, d, tail = 4, 2, 128, 8
    q = jax.random.normal(jax.random.key(7), (5, h, d), jnp.float32)
    tk = jax.random.normal(jax.random.key(8), (5, kv, tail, d), jnp.float32)
    tv = jax.random.normal(jax.random.key(9), (5, kv, tail, d), jnp.float32)
    lengths = jnp.where(alive, starts + t + 1, 0)
    steps = decode_steps(starts, alive, page_size=PS, max_pages=MAXP, window=W, group=pages)
    want = paged_attention_xla(q, k_pages, v_pages, table, lengths, tail_k=tk, tail_v=tv,
                               starts=starts, window=W)
    got = paged_attention(q, k_pages, v_pages, table, lengths, tail_k=tk, tail_v=tv,
                          starts=starts, steps=steps, window=W, interpret=True)
    assert np.allclose(np.asarray(got), np.asarray(want), atol=2e-5)
    assert not np.asarray(got[4]).any()  # the dead row: exactly zero
    # the window matters for the rows across it, and only for them
    plain = paged_attention_xla(q, k_pages, v_pages, table, lengths, tail_k=tk, tail_v=tv,
                                starts=starts)
    far = np.abs(np.asarray(want) - np.asarray(plain)).max(axis=(1, 2))
    assert far[0] < 1e-6 and far[2] > 1e-3
    # the list left out: built inside from lengths > 0, the same numbers
    again = paged_attention(q, k_pages, v_pages, table, lengths, tail_k=tk, tail_v=tv,
                            starts=starts, window=W, interpret=True)
    assert np.array_equal(np.asarray(again), np.asarray(got))


def test_the_oracle_itself_masks_what_the_mask_says():
    """``paged_attention_xla`` with a window against attention over the row's
    own last ``window`` tokens, gathered by hand."""
    starts, alive, table = _rows()
    k_pages, v_pages = _pool(5, n_pages=1 + 5 * MAXP)
    q = jax.random.normal(jax.random.key(7), (5, 4, 128), jnp.float32)
    tk = jnp.zeros((5, 2, 8, 128)); tv = jnp.zeros((5, 2, 8, 128))  # noqa: E702
    lengths = jnp.where(alive, starts, 0)  # nothing in the tail yet
    got = np.asarray(paged_attention_xla(q, k_pages, v_pages, table, lengths, tail_k=tk,
                                         tail_v=tv, starts=starts, window=W))
    r, n = 2, 150
    at = np.arange(n - W, n)
    keys = np.asarray(k_pages)[np.asarray(table)[r, at // PS], :, at % PS]  # (W, kv, d)
    vals = np.asarray(v_pages)[np.asarray(table)[r, at // PS], :, at % PS]
    qr = np.asarray(q)[r].reshape(2, 2, 128)
    s = np.einsum("kgd,wkd->kgw", qr, keys) / np.sqrt(128)
    p = np.exp(s - s.max(-1, keepdims=True)); p /= p.sum(-1, keepdims=True)  # noqa: E702
    assert np.allclose(got[r].reshape(2, 2, 128), np.einsum("kgw,wkd->kgd", p, vals), atol=1e-5)


def test_a_window_is_refused_where_the_kernel_cannot_carry_it():
    starts, alive, table = _rows()
    k_pages, v_pages = _pool(3, n_pages=1 + 5 * MAXP)
    q4 = jnp.zeros((5, 2, 4, 128))
    tk = jnp.zeros((5, 2, 8, 128))
    with pytest.raises(ValueError, match="no speculative verify, no mesh"):
        paged_attention(q4, k_pages, v_pages, table, starts, tail_k=tk, tail_v=tk,
                        starts=starts, window=W)
    with pytest.raises(ValueError, match="no speculative verify, no mesh"):
        paged_attention(q4[:, 0], k_pages, v_pages, table, starts, window=W)


# The four K/V serving cells' heads: kv heads, query heads a kv head, the lanes
# a head is stored on and those it fills (Granite's 64-wide heads lie on 128).
CELL_HEADS = {
    "trinity-mini-cut1": (4, 8, 128, 128),
    "qwen2-7b-cut1": (4, 7, 128, 128),
    "olmoe-1b-7b-cut1": (16, 1, 128, 128),
    "granite-4.0-h-micro": (8, 4, 128, 64),
}
# the pages a step of their walk takes (tests/test_tpu_compile_window.py reads
# them off the configurations' files)
CELL_PAGES_A_STEP = {"trinity-mini-cut1": 2, "qwen2-7b-cut1": 2, "olmoe-1b-7b-cut1": 1,
                     "granite-4.0-h-micro": 1}


@pytest.mark.parametrize("window", [None, 2048], ids=["full", "window-2048"])
@pytest.mark.parametrize("cell", list(CELL_HEADS))
def test_the_kernel_on_bfloat16_pools_at_the_cells_heads_is_the_gather_oracle(cell, window,
                                                                              monkeypatch):
    """What the serving cells hand the kernel: bfloat16 q, pools and tail, so
    both dots take bfloat16 operands and accumulate in float32, against the
    gather on the SAME bfloat16 operands. Rows: far across the window, inside
    it, on a page edge, one that ended inside the program (listed, ``lengths``
    0) and a dead one the list never names (both exactly zero).

    Tolerance, from bfloat16's rounding of the OUTPUT and not from the
    arithmetic in front of it: the products are exact in float32 on both
    sides; the kernel rounds its probabilities to bfloat16 before the
    division by their sum and the gather after it, so the two float32
    results differ by a fraction of an output ulp and may round to
    neighbouring bfloat16 values: an ulp is at most ``2 ** -7`` of the value.
    The float32 cases above keep ``atol=2e-5``."""
    kv, groups, d, real = CELL_HEADS[cell]
    ps, maxp, tail, b = 128, 20, 8, 5
    starts = jnp.asarray([2300, 300, 17 * ps, 1500, 900], jnp.int32)
    listed = jnp.asarray([True, True, True, True, False])
    lengths = jnp.asarray([2303, 301, 17 * ps + 5, 0, 0], jnp.int32)
    table = jnp.asarray(np.random.default_rng(1).permutation(np.arange(1, 1 + b * maxp))
                        .reshape(b, maxp), jnp.int32)
    keys = jax.random.split(jax.random.key(49), 5)
    lanes = (jnp.arange(d) < real).astype(jnp.bfloat16)  # zeros on the unfilled lanes
    draw = lambda key, *shape: jax.random.normal(key, shape, jnp.bfloat16) * lanes  # noqa: E731
    q = draw(keys[0], b, kv * groups, d)
    k_pages, v_pages = draw(keys[1], 1 + b * maxp, kv, ps, d), draw(keys[2], 1 + b * maxp, kv, ps, d)
    kw = dict(tail_k=draw(keys[3], b, kv, tail, d), tail_v=draw(keys[4], b, kv, tail, d),
              starts=starts, window=window)
    group = CELL_PAGES_A_STEP[cell]  # as the cell's pages of 256 give it
    derive_pages_a_step(monkeypatch, group, k_pages)
    steps = decode_steps(starts, listed, page_size=ps, max_pages=maxp, window=window,
                         group=group)
    # 18 + 3 + 17 + 12 pages and four tails; a window of 2,048 drops the far rows'
    # first page; two pages a step: 9 + 2 + 9 + 6 steps, and 9 + 2 + 8 + 6
    want_steps = {1: (54, 52), 2: (30, 29)}[group]
    assert int(steps["count"]) == want_steps[window is not None]
    want = np.asarray(paged_attention_xla(q, k_pages, v_pages, table, lengths, **kw)
                      .astype(jnp.float32))
    got = paged_attention(q, k_pages, v_pages, table, lengths, steps=steps, interpret=True, **kw)
    assert got.dtype == jnp.bfloat16
    got = np.asarray(got.astype(jnp.float32))
    np.testing.assert_allclose(got, want, atol=2.0 ** -7 * np.abs(want).max(), rtol=0)
    assert np.abs(want[:3]).max() > 0.1  # the live rows say something
    assert not got[3:].any()  # ended inside the program; never listed
    assert not got[..., real:].any()  # lanes no head fills stay zero
