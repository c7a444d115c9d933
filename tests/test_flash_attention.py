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
