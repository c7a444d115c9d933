"""FlashAttention Pallas kernel vs the XLA reference implementation.

Runs in Pallas interpreter mode on the simulated-CPU backend (conftest.py), so
the same numerics are exercised without TPU hardware. The reference has no
attention code (SURVEY.md §5 'long-context'); the testing idea mirrored here is
its capability-gated device test (ref ``tests/test_distributed_finetuning.py:38-44``)
done properly: one numerical reference, one fast path, asserted equal.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ditl_tpu.ops.attention import _xla_attention
from ditl_tpu.ops.flash_attention import flash_attention, supports

pytestmark = pytest.mark.pallas


def _make_qkv(key, b, s, h, kv, d, dtype=jnp.float32):
    kq, kk, kv_ = jax.random.split(key, 3)
    q = jax.random.normal(kq, (b, s, h, d), dtype)
    k = jax.random.normal(kk, (b, s, kv, d), dtype)
    v = jax.random.normal(kv_, (b, s, kv, d), dtype)
    return q, k, v


@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("blocks", [(128, 128), (256, 128)])
def test_forward_matches_xla(causal, blocks):
    q, k, v = _make_qkv(jax.random.key(0), 2, 256, 4, 2, 64)
    ref = _xla_attention(q, k, v, causal=causal, segment_ids=None)
    out = flash_attention(
        q, k, v, causal=causal, block_q=blocks[0], block_kv=blocks[1]
    )
    np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)


def test_forward_segment_ids():
    q, k, v = _make_qkv(jax.random.key(1), 2, 256, 4, 2, 64)
    # Two packed segments plus trailing padding (segment 0 matches itself,
    # which is exactly what the XLA path does too).
    seg = np.ones((2, 256), np.int32)
    seg[:, 128:] = 2
    seg[:, 240:] = 0
    seg = jnp.asarray(seg)
    ref = _xla_attention(q, k, v, causal=True, segment_ids=seg)
    out = flash_attention(q, k, v, causal=True, segment_ids=seg, block_q=128,
                          block_kv=128)
    np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("with_segments", [False, True])
def test_grads_match_xla(with_segments):
    q, k, v = _make_qkv(jax.random.key(2), 1, 256, 4, 2, 64)
    seg = None
    if with_segments:
        seg = jnp.asarray(
            np.repeat([[1, 2]], 128, axis=1).reshape(1, 256).astype(np.int32)
        )

    def loss_flash(q, k, v):
        o = flash_attention(q, k, v, causal=True, segment_ids=seg,
                            block_q=128, block_kv=128)
        return jnp.sum(o * o)

    def loss_ref(q, k, v):
        o = _xla_attention(q, k, v, causal=True, segment_ids=seg)
        return jnp.sum(o * o)

    g_flash = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for gf, gr, name in zip(g_flash, g_ref, "qkv"):
        np.testing.assert_allclose(
            gf, gr, atol=5e-4, rtol=5e-4, err_msg=f"d{name} mismatch"
        )


def test_gqa_groups():
    # 8 query heads sharing 2 KV heads: exercises the head-index division in
    # the KV block index map and the group fold in the dkv grid.
    q, k, v = _make_qkv(jax.random.key(3), 2, 128, 8, 2, 64)
    ref = _xla_attention(q, k, v, causal=True, segment_ids=None)
    out = flash_attention(q, k, v, causal=True, block_q=128, block_kv=128)
    np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)

    def loss(fn):
        return lambda k_: jnp.sum(fn(q, k_, v) ** 2)

    gk_flash = jax.grad(
        loss(lambda q_, k_, v_: flash_attention(
            q_, k_, v_, causal=True, block_q=128, block_kv=128))
    )(k)
    gk_ref = jax.grad(
        loss(lambda q_, k_, v_: _xla_attention(
            q_, k_, v_, causal=True, segment_ids=None))
    )(k)
    np.testing.assert_allclose(gk_flash, gk_ref, atol=5e-4, rtol=5e-4)


def test_supports_gate():
    assert supports(1024, 1024, 128)
    assert supports(256, 256, 64)
    assert not supports(100, 100, 64)  # not tileable
    assert not supports(256, 256, 100)  # bad head dim


def test_untileable_flash_gives_way_in_interpret_mode_and_raises_on_tpu(monkeypatch):
    """A requested kernel that cannot run: in interpret mode (this CPU tier)
    the dispatch gives way to XLA; on the TPU backend it is an error naming
    the shape, never a silent substitution (ops/backend.py)."""
    from ditl_tpu.ops.attention import dot_product_attention

    q, k, v = _make_qkv(jax.random.key(7), 1, 100, 4, 2, 64)
    out = dot_product_attention(q, k, v, impl="flash")
    ref = _xla_attention(q, k, v, causal=True, segment_ids=None)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref))
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    with pytest.raises(ValueError, match=r"attention_impl='flash'.*Sq=100 Skv=100 D=64"):
        dot_product_attention(q, k, v, impl="flash")


def test_bf16_forward_close():
    q, k, v = _make_qkv(jax.random.key(4), 1, 256, 4, 2, 64, dtype=jnp.bfloat16)
    ref = _xla_attention(q, k, v, causal=True, segment_ids=None)
    out = flash_attention(q, k, v, causal=True, block_q=128, block_kv=128)
    assert out.dtype == jnp.bfloat16
    np.testing.assert_allclose(
        out.astype(np.float32), ref.astype(np.float32), atol=3e-2, rtol=3e-2
    )


# ---------------------------------------------------------------------------
# Packed rows: blocks that lie between two documents are skipped
# ---------------------------------------------------------------------------

S = 512


def _ids(*runs):
    """((id, length), ...) -> (2, S) int32, the second row the first shifted
    by a document so the rows of a batch differ."""
    row = np.concatenate([np.full(n, i, np.int32) for i, n in runs])
    assert row.shape == (S,)
    return np.stack([row, np.where(row > 0, row + 1, 0)])


LAYOUTS = {
    "one-document": _ids((1, S)),
    "three-on-block-edges": _ids((1, 128), (2, 256), (3, 128)),
    "three-inside-blocks": _ids((1, 100), (2, 230), (3, 182)),
    "many": _ids(*[(i + 1, 32) for i in range(15)], (16, 32)),
    # what the serving prefill sends: 1 over the prompt, 0 over the bucket's padding
    "trailing-zero-padding": _ids((1, 300), (0, 212)),
    # ids that come back: the range test may only be conservative here
    "non-monotone": _ids((2, 100), (1, 156), (3, 128), (1, 128)),
    # rows that need different counts: one document (10 of 10 causal blocks
    # at 128 x 128) beside sixteen (4)
    "rows-differ": np.stack([np.ones(S, np.int32), np.repeat(np.arange(1, 17, dtype=np.int32), 32)]),
}
# (block_q, block_kv, block_q_bwd, block_kv_bwd): the second's backward tiles
# differ from its forward's
BLOCKS = [(128, 128, 0, 0), (256, 128, 128, 256)]


def _out_and_grads(attn, q, k, v):
    out, vjp = jax.vjp(attn, q, k, v)
    return (out, *vjp(0.5 + out))


def _cover_everything(monkeypatch):
    """Ranges that meet every other: the predicate is causality's alone, as
    before the kernels knew of segments."""
    from ditl_tpu.ops import flash_attention as fa

    def whole(seg, block):
        lo = jnp.zeros((seg.shape[0], seg.shape[1] // block), jnp.int32)
        return lo, lo + np.iinfo(np.int32).max

    monkeypatch.setattr(fa, "_block_ranges", whole)


@pytest.mark.parametrize("blocks", BLOCKS, ids=lambda b: "x".join(map(str, b)))
@pytest.mark.parametrize("causal", [True, False], ids=["causal", "full"])
@pytest.mark.parametrize("layout", LAYOUTS)
def test_packed_rows_match_xla_and_the_kernels_that_skip_nothing(
        monkeypatch, layout, causal, blocks):
    seg = jnp.asarray(LAYOUTS[layout])
    q, k, v = _make_qkv(jax.random.key(11), 2, S, 4, 2, 64)
    bq, bkv, bqb, bkvb = blocks

    def flash(q, k, v):
        return flash_attention(q, k, v, causal=causal, segment_ids=seg, block_q=bq,
                               block_kv=bkv, block_q_bwd=bqb, block_kv_bwd=bkvb)

    got = _out_and_grads(flash, q, k, v)
    want = _out_and_grads(
        lambda q, k, v: _xla_attention(q, k, v, causal=causal, segment_ids=seg), q, k, v)
    for g, w, name in zip(got, want, ("out", "dq", "dk", "dv")):
        np.testing.assert_allclose(g, w, atol=5e-4, rtol=5e-4, err_msg=name)
    # the same numbers, not close ones: a skipped block contributed nothing
    _cover_everything(monkeypatch)
    for g, w, name in zip(got, _out_and_grads(flash, q, k, v), ("out", "dq", "dk", "dv")):
        np.testing.assert_array_equal(g, w, err_msg=name)


@pytest.mark.parametrize("heads", [(2, 2), (4, 2), (8, 2)], ids=lambda h: f"{h[0]}q-{h[1]}kv")
@pytest.mark.parametrize("layout", ["three-inside-blocks", "rows-differ"])
def test_packed_rows_with_gqa_groups_match_the_kernels_that_skip_nothing(
        monkeypatch, layout, heads):
    seg = jnp.asarray(LAYOUTS[layout])
    q, k, v = _make_qkv(jax.random.key(12), 2, S, *heads, 64, dtype=jnp.bfloat16)

    def flash(q, k, v):
        return flash_attention(q, k, v, causal=True, segment_ids=seg, block_q=128,
                               block_kv=128)

    got = _out_and_grads(flash, q, k, v)
    ref = _xla_attention(q, k, v, causal=True, segment_ids=seg)
    np.testing.assert_allclose(got[0].astype(np.float32), ref.astype(np.float32),
                               atol=3e-2, rtol=3e-2)
    _cover_everything(monkeypatch)
    for g, w in zip(got, _out_and_grads(flash, q, k, v)):
        np.testing.assert_array_equal(g, w)


def _pallas_calls(jaxpr):
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _pallas_calls(sub)


@pytest.mark.parametrize("with_segments,operands", [(False, 0), (True, 4)])
def test_only_a_call_with_segment_ids_carries_prefetch_operands(with_segments, operands):
    q, k, v = _make_qkv(jax.random.key(13), 1, 256, 2, 1, 64)
    seg = jnp.ones((1, 256), jnp.int32) if with_segments else None

    def loss(q, k, v):
        return jnp.sum(flash_attention(q, k, v, segment_ids=seg, block_q=128, block_kv=128))

    calls = list(_pallas_calls(jax.make_jaxpr(jax.grad(loss, (0, 1, 2)))(q, k, v).jaxpr))
    assert len(calls) == 3  # flash_fwd, flash_bwd_dq, flash_bwd_dkv
    assert [c.params["grid_mapping"].num_index_operands for c in calls] == [operands] * 3


def _equations_outside_kernels(jaxpr):
    n = 0
    for eqn in jaxpr.eqns:
        if eqn.primitive.name != "pallas_call":
            n += 1 + sum(_equations_outside_kernels(sub)
                         for sub in jax.core.jaxprs_in_params(eqn.params))
    return n


def test_building_the_work_lists_traces_to_a_few_hundred_equations():
    """The lists are dense array arithmetic, traced once a call whatever the
    batch and the row's length: no loop over rows, blocks or entries is
    written out (forward and backward together: three lists)."""
    def count(b, s):
        q, k, v = (jax.ShapeDtypeStruct((b, s, h, 64), jnp.bfloat16) for h in (4, 2, 2))
        seg = jax.ShapeDtypeStruct((b, s), jnp.int32)

        def loss(q, k, v, seg):
            return jnp.sum(flash_attention(q, k, v, segment_ids=seg, block_q=128,
                                           block_kv=128).astype(jnp.float32))

        return _equations_outside_kernels(
            jax.make_jaxpr(jax.grad(loss, (0, 1, 2)))(q, k, v, seg).jaxpr)

    assert count(1, 256) == count(16, 2048) < 400


def _brute_counts(seg, bq, bkv, causal):
    """Blocks with a row at or past a column (causal), and of those the
    blocks in which some query and some key it may see share an id."""
    pos = np.arange(seg.shape[1])
    reachable = needed = 0
    for row in seg:
        for q0 in range(0, len(row), bq):
            for k0 in range(0, len(row), bkv):
                see = (pos[q0:q0 + bq, None] >= pos[None, k0:k0 + bkv]) | (not causal)
                same = row[q0:q0 + bq, None] == row[None, k0:k0 + bkv]
                reachable += bool(see.any())
                needed += bool((see & same).any())
    return reachable, needed


@pytest.mark.parametrize("blocks", [(128, 128), (256, 128), (128, 256)],
                         ids=lambda b: "x".join(map(str, b)))
@pytest.mark.parametrize("causal", [True, False], ids=["causal", "full"])
@pytest.mark.parametrize("layout", LAYOUTS)
def test_block_counts_against_a_brute_force_count(layout, causal, blocks):
    from ditl_tpu.ops.flash_attention import block_counts

    seg = LAYOUTS[layout]
    reachable, needed, walked = map(int, block_counts(
        jnp.asarray(seg), causal=causal, block_q=blocks[0], block_kv=blocks[1]))
    want_reachable, want_needed = _brute_counts(seg, *blocks, causal)
    assert reachable == want_reachable
    assert walked == needed  # every grid step computes: one id array, so no block needs nothing
    if layout == "non-monotone":  # conservative: never fewer than there are
        assert want_needed <= needed <= reachable
    else:  # exact for the loader's rows: ids that never come back
        assert needed == want_needed


def test_block_counts_fixed_points():
    from ditl_tpu.ops.flash_attention import block_counts

    one = jnp.ones((3, S), jnp.int32)
    own = jnp.asarray(np.repeat(np.arange(1, 5, dtype=np.int32), 128)[None])
    # one document a row: every causal block is needed, 100%
    assert tuple(map(int, block_counts(one, block_q=128, block_kv=128))) == (30, 30, 30)
    # every block its own document: the diagonal only
    assert tuple(map(int, block_counts(own, block_q=128, block_kv=128))) == (10, 4, 4)
    assert tuple(map(int, block_counts(own, causal=False, block_q=128, block_kv=128))) == (16, 4, 4)
    # the defaults are the kernels': 512 x 512, so S is one block
    assert tuple(map(int, block_counts(own))) == (1, 1, 1)


# The claimed cell's row (benchmarks/traffic/train-ep8-8k.json): eight
# documents in 8,192 tokens, 51 of the 136 causal 512 x 512 blocks.
KANANA_ROW = np.repeat(np.arange(1, 9, dtype=np.int32), [4096, 2048, 1024, 512, 256, 128, 64, 64])
# name -> (ids, (block_q, block_kv), GQA group, entries a row)
WALKS = {
    **{name: (LAYOUTS[name], (128, 128), group, a_row) for name, group, a_row in [
        ("one-document", 1, (10, 10)),  # the causal triangle
        ("three-on-block-edges", 2, (5, 5)),  # 1 + (1 + 2) + 1: the 256-token document is two blocks
        ("three-inside-blocks", 7, (8, 8)),  # 1 + 2 + 3 + 2: block 2 holds the ends of documents 2 and 3
        ("many", 1, (4, 4)),  # every block its own documents: the diagonal
        ("trailing-zero-padding", 2, (8, 8)),  # the prompt's three blocks (6); the padding's two
        ("rows-differ", 2, (10, 4)),
    ]},
    "kanana-row": (np.stack([KANANA_ROW] * 4), (512, 512), 1, (51,) * 4),
    "kanana-row-tiles-differ": (np.stack([KANANA_ROW] * 2), (256, 512), 4, None),
}


@pytest.mark.parametrize("name", WALKS)
def test_the_work_lists_hold_every_needed_block_once_and_nothing_else(name):
    """The kernels' prefetch operands and grid bounds (``_work_lists``): the
    list the forward and dq kernels walk, query block by query block over
    key blocks, and the dk/dv kernel's, key block by key block over (query
    head of the group, query block)."""
    from ditl_tpu.ops import flash_attention as fa

    ids, tiles, group, a_row = WALKS[name]
    seg, blocks = jnp.asarray(ids), fa.BlockSizes(*tiles)
    needed = np.asarray(fa._needed_blocks(
        fa._block_ranges(seg, blocks.block_q), fa._block_ranges(seg, blocks.block_kv),
        blocks, True))
    b, n_q, n_kv = needed.shape
    _, want, walked = map(int, fa.block_counts(seg, block_q=tiles[0], block_kv=tiles[1]))
    assert want == walked == needed.sum()
    if a_row is not None:
        assert tuple(needed.sum(axis=(1, 2))) == a_row

    over_kv, over_q = fa._work_lists(seg, seg, blocks, True, groups=group)
    for (work, count), folds, wanted in (
            (over_kv, 1, needed), (over_q, group, np.swapaxes(needed, 1, 2))):
        rows, outer, inner, flags = (np.asarray(x) for x in work)
        count = int(count)
        assert count == folds * want <= len(rows) == len(outer) == len(inner) == len(flags)
        rows, outer, inner, flags = rows[:count], outer[:count], inner[:count], flags[:count]
        fold, block = np.divmod(inner, wanted.shape[2])  # the group loop folded in
        entries = list(zip(rows, outer, fold, block))
        # every entry is a needed block, and every needed block appears once a fold
        assert wanted[rows, outer, block].all() and (flags & fa.NEEDED).all()
        assert len(set(entries)) == count and (fold < folds).all()
        # row-major: rows in turn, a row's outer blocks in turn and each in ONE
        # run, a run's entries ascending (the order the kernels accumulate in)
        assert entries == sorted(entries)
        # every outer block has a run, flagged where it opens and where it closes
        runs = list(zip(rows, outer))
        assert set(runs) == {(r, o) for r in range(b) for o in range(wanted.shape[1])}
        opens = [i == 0 or runs[i] != runs[i - 1] for i in range(count)]
        closes = [i == count - 1 or runs[i] != runs[i + 1] for i in range(count)]
        assert ((flags & fa.FIRST) != 0).tolist() == opens
        assert ((flags & fa.LAST) != 0).tolist() == closes


def test_an_outer_block_that_needs_nothing_keeps_one_step_that_computes_nothing():
    """Separate ids for queries and keys, which the public call cannot send:
    a query block that shares no document with any key block still writes its
    output (zeros, as under the hull: the module docstring's one exception)."""
    from ditl_tpu.ops import flash_attention as fa

    blocks = fa.BlockSizes(128, 128)
    q_seg = jnp.asarray(np.repeat([[1, 1, 9, 2]], 128, axis=1).astype(np.int32))
    kv_seg = jnp.asarray(np.repeat([[1, 1, 2, 2]], 128, axis=1).astype(np.int32))
    (work, count), (_, count_t) = fa._work_lists(q_seg, kv_seg, blocks, True, groups=1)
    rows, outer, inner, flags = (np.asarray(x)[:int(count)] for x in work)
    # query block 2 needs nothing: one entry, first and last of its run, not needed
    assert outer.tolist() == [0, 1, 1, 2, 3, 3] and inner.tolist() == [0, 0, 1, 0, 2, 3]
    assert flags.tolist() == [7, 5, 6, 3, 5, 6]
    assert int(count_t) == 5  # every key block has a query block: the five needed
    q, k, v = _make_qkv(jax.random.key(14), 1, S, 2, 2, 64)
    o, lse = fa._fwd(*(jnp.swapaxes(x, 1, 2) for x in (q, k, v)), q_seg, kv_seg,
                     causal=True, scale=0.125, blocks=blocks, interpret=True)
    assert not np.asarray(o[:, :, 256:384]).any() and np.asarray(o[:, :, 384:]).any()
    assert (np.asarray(lse[:, :, 256:384]) < -1e30).all()
