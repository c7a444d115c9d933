"""Sequence-sharded (long-context) decode: the KV cache's context dim
splits over the ``sequence`` mesh axis and decode attention merges
per-shard partial softmax over the mesh — flash-decoding over ICI
(ops/attention._seq_sharded_decode)."""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ditl_tpu.config import MeshConfig, ModelConfig
from ditl_tpu.data.tokenizer import ByteTokenizer
from ditl_tpu.infer.continuous import ContinuousEngine
from ditl_tpu.infer.engine import GenerateConfig
from ditl_tpu.models import llama
from ditl_tpu.ops.attention import _seq_sharded_decode, _xla_attention
from ditl_tpu.runtime.mesh import build_mesh


@pytest.fixture(scope="module")
def seq_mesh():
    return build_mesh(MeshConfig(sequence=4))


@pytest.fixture(scope="module")
def setup():
    cfg = ModelConfig(
        vocab_size=512,
        hidden_size=64,
        intermediate_size=128,
        num_layers=2,
        num_heads=4,
        num_kv_heads=2,
        head_dim=16,
        max_seq_len=128,
        dtype="float32",
        param_dtype="float32",
    )
    params = llama.init_params(jax.random.key(0), cfg)
    return params, cfg, ByteTokenizer()


def test_op_matches_unsharded_softmax(seq_mesh):
    """The log-sum-exp merge equals one global softmax (f32, random mask)."""
    from ditl_tpu.parallel.sharding import DEFAULT_RULES

    rng = np.random.default_rng(0)
    b, sq, h, kh, d, skv = 2, 1, 4, 2, 16, 64
    q = jnp.asarray(rng.normal(size=(b, sq, h, d)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(b, skv, kh, d)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(b, skv, kh, d)), jnp.float32)
    # per-row prefix-valid mask (the decode shape), some rows short
    lengths = np.array([37, 64])
    mask = jnp.asarray(
        np.arange(skv)[None, None, :] < lengths[:, None, None]
    )
    ref = _xla_attention(q, k, v, causal=False, segment_ids=None, mask=mask)
    got = _seq_sharded_decode(
        q, k, v, mask, mesh=seq_mesh, rules=DEFAULT_RULES
    )
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.slow
def test_op_int8_scales_compose(seq_mesh):
    from ditl_tpu.parallel.sharding import DEFAULT_RULES

    rng = np.random.default_rng(1)
    b, sq, h, kh, d, skv = 2, 2, 4, 2, 16, 64
    q = jnp.asarray(rng.normal(size=(b, sq, h, d)), jnp.float32)
    kf = rng.normal(size=(b, skv, kh, d)).astype(np.float32)
    vf = rng.normal(size=(b, skv, kh, d)).astype(np.float32)
    ks = np.abs(kf).max(-1) / 127.0 + 1e-8
    vs = np.abs(vf).max(-1) / 127.0 + 1e-8
    k8 = np.clip(np.round(kf / ks[..., None]), -127, 127).astype(np.int8)
    v8 = np.clip(np.round(vf / vs[..., None]), -127, 127).astype(np.int8)
    mask = jnp.ones((b, sq, skv), bool)
    ref = _xla_attention(
        q, jnp.asarray(k8), jnp.asarray(v8), causal=False, segment_ids=None,
        mask=mask, k_scale=jnp.asarray(ks), v_scale=jnp.asarray(vs),
    )
    got = _seq_sharded_decode(
        q, jnp.asarray(k8), jnp.asarray(v8), mask,
        mesh=seq_mesh, rules=DEFAULT_RULES,
        k_scale=jnp.asarray(ks), v_scale=jnp.asarray(vs),
    )
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=1e-4, atol=1e-4)


@pytest.mark.slow
def test_engine_seq_sharded_matches_unsharded(setup, seq_mesh):
    """A continuous engine on a sequence=4 mesh (context-sharded cache)
    generates the same greedy tokens as the mesh-less engine (f32)."""
    params, cfg, tok = setup
    prompts = ["the quick brown fox jumps", "hello"]
    gen = GenerateConfig(max_new_tokens=10)
    ref = ContinuousEngine(
        params, cfg, tok, n_slots=2, decode_chunk=4, gen=gen,
    ).generate(prompts)
    eng = ContinuousEngine(
        params, cfg, tok, n_slots=2, decode_chunk=4, gen=gen, mesh=seq_mesh,
    )
    got = eng.generate(prompts)
    assert got == ref
    # the cache really is context-sharded
    spec = eng.cache["k"].sharding.spec
    assert spec[2] is not None


@pytest.mark.slow
def test_engine_seq_sharded_smax_divisibility(setup, seq_mesh):
    params, cfg, tok = setup
    with pytest.raises(ValueError, match="divisible"):
        ContinuousEngine(
            params, cfg, tok, n_slots=2, mesh=seq_mesh, max_cache_len=126,
        )


@pytest.mark.slow
def test_engine_seq_sharded_int8_kv(setup, seq_mesh):
    """int8 KV composes with the context-sharded cache: quantization is
    per-position (elementwise over the sharded axis), so the sharded
    engine matches the single-device int8 engine exactly."""
    import dataclasses

    params, cfg, tok = setup
    cfg8 = dataclasses.replace(cfg, kv_cache_dtype="int8")
    gen = GenerateConfig(max_new_tokens=10)
    prompts = ["the quick brown fox jumps", "hello"]
    ref = ContinuousEngine(
        params, cfg8, tok, n_slots=2, decode_chunk=4, gen=gen,
    ).generate(prompts)
    eng = ContinuousEngine(
        params, cfg8, tok, n_slots=2, decode_chunk=4, gen=gen,
        mesh=seq_mesh,
    )
    assert eng.generate(prompts) == ref
    assert eng.cache["k"].sharding.spec[2] is not None  # context-sharded


@pytest.mark.slow
def test_paged_pools_replicate_over_sequence_axis(setup, seq_mesh, caplog):
    """The written decision: paged pools do NOT shard on
    the sequence axis — they replicate (correct output, warned loudly),
    because the axis's regime (contexts beyond one chip's HBM, concurrency
    of a few) is exactly where paged capacity-sharing buys nothing. The
    contiguous cache is the long-context configuration."""
    import logging

    params, cfg, tok = setup
    gen = GenerateConfig(max_new_tokens=8)
    ref = ContinuousEngine(
        params, cfg, tok, n_slots=2, decode_chunk=4, gen=gen,
    ).generate(["hello world"])
    with caplog.at_level(logging.WARNING):
        eng = ContinuousEngine(
            params, cfg, tok, n_slots=2, decode_chunk=4, gen=gen,
            mesh=seq_mesh, cache_mode="paged", page_size=16,
        )
    assert any("sequence" in r.message for r in caplog.records)
    # Construction intent: pools are NOT context-sharded (page-slot axis
    # carries capacity, and no spec entry maps it to 'sequence'). After a
    # step GSPMD may re-lay the donated pool however it likes.
    spec = eng.cache["kp"].sharding.spec
    assert len(spec) < 2 or spec[1] is None  # page-slot axis unsharded
    assert eng.generate(["hello world"]) == ref  # correct, just unscaled


@pytest.mark.slow
def test_engine_seq_sharded_speculative(setup, seq_mesh):
    """Spec ticks' (B, K+1)-query verify also rides the sharded-context
    merge path."""
    params, cfg, tok = setup
    gen = GenerateConfig(max_new_tokens=8)
    ref = ContinuousEngine(
        params, cfg, tok, n_slots=2, decode_chunk=4, gen=gen,
    ).generate(["a b a b a b a b"])
    eng = ContinuousEngine(
        params, cfg, tok, n_slots=2, decode_chunk=4, gen=gen, mesh=seq_mesh,
        speculative=True, spec_k=3, spec_threshold=0.0,
    )
    got = eng.generate(["a b a b a b a b"])
    assert got == ref
    assert eng.spec_ticks > 0
