"""Pipeline parallelism (parallel/pipeline.py) on the 8-device CPU mesh.

The pipelined forward must equal the plain scanned forward — stage-sharded
layers + microbatch rotation is an execution-schedule change, not a math
change — and a full train step over a (data x stage) mesh must run and
produce finite, matching metrics.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ditl_tpu.config import MeshConfig, ModelConfig, TrainConfig
from ditl_tpu.data.loader import make_global_batch
from ditl_tpu.models import llama
from ditl_tpu.runtime.mesh import build_mesh
from ditl_tpu.train.state import create_train_state
from ditl_tpu.train.step import loss_fn, make_train_step


def _cfg(**kw):
    base = ModelConfig(
        vocab_size=512,
        hidden_size=64,
        intermediate_size=128,
        num_layers=4,  # divisible by 2 and 4 stages
        num_heads=4,
        num_kv_heads=2,
        head_dim=16,
        max_seq_len=64,
        dtype="float32",  # exact comparison across schedules
        param_dtype="float32",
    )
    return dataclasses.replace(base, **kw)


def _host_batch(b=8, s=32, vocab=512, seed=0):
    rng = np.random.default_rng(seed)
    return {
        "input_ids": rng.integers(3, vocab, size=(b, s)).astype(np.int32),
        "loss_mask": np.ones((b, s), np.float32),
        "labels": np.zeros((b,), np.int32),
        "segment_ids": np.ones((b, s), np.int32),
        "positions": np.tile(np.arange(s, dtype=np.int32), (b, 1)),
    }


@pytest.mark.parametrize("n_stages", [2, 4])
def test_pipeline_forward_matches_scan(devices8, n_stages):
    cfg = _cfg()
    params = llama.init_params(jax.random.key(0), cfg)
    host = _host_batch()
    ids = jnp.asarray(host["input_ids"])

    ref_logits = llama.forward(params, ids, cfg)  # plain scanned forward

    mesh = build_mesh(MeshConfig(data=-1, stage=n_stages))
    from ditl_tpu.parallel.pipeline import PIPELINE_RULES

    pipe_logits = jax.jit(
        lambda p, i: llama.forward(p, i, cfg, mesh=mesh, rules=PIPELINE_RULES)
    )(params, ids)
    np.testing.assert_allclose(
        np.asarray(pipe_logits), np.asarray(ref_logits), rtol=2e-4, atol=2e-4
    )


def test_pipeline_microbatch_count(devices8):
    """More microbatches than stages (the realistic schedule) stays exact."""
    cfg = _cfg(pipeline_microbatches=8)
    params = llama.init_params(jax.random.key(1), cfg)
    ids = jnp.asarray(_host_batch(b=32, seed=1)["input_ids"])
    ref = llama.forward(params, ids, cfg)
    mesh = build_mesh(MeshConfig(data=-1, stage=2))
    from ditl_tpu.parallel.pipeline import PIPELINE_RULES

    got = jax.jit(
        lambda p, i: llama.forward(p, i, cfg, mesh=mesh, rules=PIPELINE_RULES)
    )(params, ids)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref), rtol=2e-4, atol=2e-4)


def test_pipeline_train_step_matches_single_device(devices8):
    """One train step on a (data=2, stage=4) mesh == one step on 1 device."""
    cfg = _cfg()
    tcfg = TrainConfig(total_steps=4, warmup_steps=1)
    host = _host_batch()

    # Reference: single-device mesh.
    mesh1 = build_mesh(MeshConfig(data=1), devices=jax.devices()[:1])
    gb1 = make_global_batch(mesh1, host)
    state1 = create_train_state(jax.random.key(0), cfg, tcfg)
    step1 = make_train_step(cfg, tcfg, mesh1, gb1)
    state1, m1 = step1(state1, gb1)

    # Pipelined: 2-way data x 4-stage pipeline.
    mesh = build_mesh(MeshConfig(data=2, stage=4))
    gb = make_global_batch(mesh, host)
    state = create_train_state(jax.random.key(0), cfg, tcfg)
    step = make_train_step(cfg, tcfg, mesh, gb)
    state, m = step(state, gb)

    assert np.isfinite(float(m["loss"]))
    np.testing.assert_allclose(float(m["loss"]), float(m1["loss"]), rtol=1e-4)
    np.testing.assert_allclose(
        float(m["grad_norm"]), float(m1["grad_norm"]), rtol=1e-3
    )


def test_pipeline_rejects_tensor_axis(devices8):
    cfg = _cfg()
    params = llama.init_params(jax.random.key(0), cfg)
    ids = jnp.asarray(_host_batch()["input_ids"])
    mesh = build_mesh(MeshConfig(data=-1, stage=2, tensor=2))
    from ditl_tpu.parallel.pipeline import PIPELINE_RULES

    with pytest.raises(ValueError, match="does not compose"):
        llama.forward(params, ids, cfg, mesh=mesh, rules=PIPELINE_RULES)


def test_pipeline_moe_aux_matches(devices8):
    """MoE router aux survives the pipeline schedule (masked bubble ticks)."""
    cfg = _cfg(num_experts=4, num_experts_per_tok=2)
    params = llama.init_params(jax.random.key(2), cfg)
    host = _host_batch(seed=2)
    batch = {k: jnp.asarray(v) for k, v in host.items()}

    ref_loss, ref_aux = loss_fn(params, batch, cfg)
    mesh = build_mesh(MeshConfig(data=-1, stage=2))
    from ditl_tpu.parallel.pipeline import PIPELINE_RULES

    pipe_loss, pipe_aux = jax.jit(
        lambda p, b: loss_fn(p, b, cfg, mesh=mesh, rules=PIPELINE_RULES)
    )(params, batch)
    # The loss is declared replicated — every device's copy must be identical
    # (the router aux must be pmean'ed over the data axes, not just the
    # stage axis, or each data shard trains on a different loss).
    shard_vals = [float(np.asarray(s.data)) for s in pipe_loss.addressable_shards]
    assert len(set(shard_vals)) == 1, f"loss diverges across devices: {shard_vals}"
    # The expert layer is token-exact (no capacity, no drop), so the
    # cross-entropy does not depend on how the batch is cut into microbatches:
    # float32 summation order only. The router's load-balancing term is a
    # product of two means, taken per microbatch and averaged by the schedule
    # (standard for pipelined MoE), so it is not the whole batch's (1.30
    # against 1.06 here); at its weight of 0.01 that is 4e-4 of the loss.
    np.testing.assert_allclose(
        float(pipe_aux["loss"]), float(ref_aux["loss"]), rtol=1e-5
    )
    np.testing.assert_allclose(float(pipe_loss), float(ref_loss), rtol=1e-3)
    assert float(pipe_aux["router_aux_loss"]) >= 1.0 - 1e-6
