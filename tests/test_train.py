"""Train-step tests: loss decreases, grad accumulation, LoRA freezing,
sharded-state layouts on the 8-device mesh."""

import dataclasses

import jax
import jax.flatten_util
import numpy as np
import pytest

from ditl_tpu.config import MeshConfig, TrainConfig
from ditl_tpu.data.loader import make_global_batch
from ditl_tpu.runtime.mesh import build_mesh
from ditl_tpu.train.state import create_train_state, state_logical_axes
from ditl_tpu.train.step import make_train_step


def _setup(tiny_model_cfg, example_batch, mesh_cfg=MeshConfig(), train_cfg=None):
    mesh = build_mesh(mesh_cfg)
    tcfg = train_cfg or TrainConfig(total_steps=20, warmup_steps=2, learning_rate=1e-3)
    state = create_train_state(jax.random.key(0), tiny_model_cfg, tcfg)
    gb = make_global_batch(mesh, example_batch)
    step = make_train_step(tiny_model_cfg, tcfg, mesh, gb)
    return mesh, state, gb, step


def test_loss_decreases_dp(tiny_model_cfg, example_batch):
    _, state, gb, step = _setup(tiny_model_cfg, example_batch)
    state, m0 = step(state, gb)
    first = float(m0["loss"])
    for _ in range(10):
        state, m = step(state, gb)
    assert float(m["loss"]) < first - 0.3
    assert np.isfinite(float(m["grad_norm"]))
    assert float(m["n_tokens"]) == example_batch["loss_mask"][:, 1:].sum()


def test_loss_decreases_fsdp_tp(tiny_model_cfg, example_batch):
    mesh, state, gb, step = _setup(
        tiny_model_cfg, example_batch, MeshConfig(data=2, fsdp=2, tensor=2)
    )
    # params actually sharded: wq's embed dim over fsdp, head dim over tensor
    state, _ = step(state, gb)
    wq = state.params["layers"]["attn"]["wq"]
    shard_shape = wq.addressable_shards[0].data.shape
    assert shard_shape[1] == wq.shape[1] // 2  # fsdp over embed
    assert shard_shape[2] == wq.shape[2] // 2  # tensor over heads
    prev = None
    for _ in range(8):
        state, m = step(state, gb)
        cur = float(m["loss"])
        if prev is not None:
            assert cur < prev + 0.1
        prev = cur


def test_dp_and_fsdp_agree(tiny_model_cfg, example_batch):
    """Same seed + data => same loss trajectory regardless of mesh layout
    (SPMD invariance: parallelism must not change the math)."""
    cfg = dataclasses.replace(tiny_model_cfg, dtype="float32", param_dtype="float32")
    losses = {}
    for name, mesh_cfg in [
        ("dp", MeshConfig()),
        ("fsdp", MeshConfig(data=1, fsdp=8)),
        ("tp", MeshConfig(data=2, fsdp=2, tensor=2)),
    ]:
        _, state, gb, step = _setup(cfg, example_batch, mesh_cfg)
        traj = []
        for _ in range(3):
            state, m = step(state, gb)
            traj.append(float(m["loss"]))
        losses[name] = traj
    np.testing.assert_allclose(losses["dp"], losses["fsdp"], rtol=1e-4)
    np.testing.assert_allclose(losses["dp"], losses["tp"], rtol=1e-4)


def test_grad_accum_matches_full_batch(tiny_model_cfg, example_batch):
    """accum=2 over half-batches == accum=1 over the full batch (same update
    in exact arithmetic; f32 here so tolerance is tight)."""
    cfg = dataclasses.replace(tiny_model_cfg, dtype="float32", param_dtype="float32")
    tcfg1 = TrainConfig(total_steps=5, warmup_steps=1, grad_accum_steps=1)
    tcfg2 = TrainConfig(total_steps=5, warmup_steps=1, grad_accum_steps=2)
    mesh = build_mesh(MeshConfig())
    gb = make_global_batch(mesh, example_batch)
    s1 = create_train_state(jax.random.key(0), cfg, tcfg1)
    s2 = create_train_state(jax.random.key(0), cfg, tcfg2)
    step1 = make_train_step(cfg, tcfg1, mesh, gb)
    step2 = make_train_step(cfg, tcfg2, mesh, gb)
    s1, m1 = step1(s1, gb)
    s2, m2 = step2(s2, gb)
    # loss reported by accum path averages the two microbatch losses
    np.testing.assert_allclose(float(m1["loss"]), float(m2["loss"]), rtol=1e-5)
    w1 = np.asarray(s1.params["layers"]["attn"]["wq"])
    w2 = np.asarray(s2.params["layers"]["attn"]["wq"])
    np.testing.assert_allclose(w1, w2, rtol=1e-4, atol=1e-6)


def test_lora_freezes_base(tiny_model_cfg, example_batch):
    cfg = dataclasses.replace(tiny_model_cfg, lora_rank=4)
    tcfg = TrainConfig(total_steps=5, warmup_steps=1, learning_rate=1e-2)
    mesh = build_mesh(MeshConfig())
    gb = make_global_batch(mesh, example_batch)
    state = create_train_state(jax.random.key(0), cfg, tcfg)
    step = make_train_step(cfg, tcfg, mesh, gb)
    wq_before = np.asarray(state.params["layers"]["attn"]["wq"]).copy()
    lora_b_before = np.asarray(state.params["layers"]["lora"]["wq"]["b"]).copy()
    for _ in range(3):
        state, m = step(state, gb)
    wq_after = np.asarray(state.params["layers"]["attn"]["wq"])
    lora_b_after = np.asarray(state.params["layers"]["lora"]["wq"]["b"])
    np.testing.assert_array_equal(wq_before, wq_after)  # base frozen
    assert not np.allclose(lora_b_before, lora_b_after)  # adapters train


def test_state_logical_axes_cover_state(tiny_model_cfg):
    tcfg = TrainConfig()
    axes = state_logical_axes(tiny_model_cfg, tcfg)
    state = create_train_state(jax.random.key(1), tiny_model_cfg, tcfg)
    from ditl_tpu.parallel.sharding import is_axes_leaf

    flat_state = jax.tree_util.tree_flatten(state)[0]
    flat_axes = jax.tree_util.tree_flatten(axes, is_leaf=is_axes_leaf)[0]
    assert len(flat_state) == len(flat_axes)
    for arr, ax in zip(flat_state, flat_axes):
        assert arr.ndim == len(ax), f"{arr.shape} vs {ax}"


def test_train_step_attention_impls(tiny_model_cfg):
    """The same train step runs with every attention implementation; flash
    (Pallas, shard_mapped) and ring (sequence-parallel) agree with the XLA
    path on the loss to float tolerance."""
    # seq 128 so the flash kernel's tiling gate passes (kv blocks are
    # 128-lane); the default 32-token example batch would silently fall back.
    rng = np.random.default_rng(0)
    b, s = 8, 128
    example_batch = {
        "input_ids": rng.integers(3, 500, size=(b, s)).astype(np.int32),
        "loss_mask": np.ones((b, s), np.float32),
        "labels": np.zeros((b,), np.int32),
        "segment_ids": np.ones((b, s), np.int32),
        "positions": np.tile(np.arange(s, dtype=np.int32), (b, 1)),
    }
    losses = {}
    for impl, mesh_cfg in [
        ("xla", MeshConfig(data=4, tensor=2)),
        ("flash", MeshConfig(data=4, tensor=2)),
        ("ring", MeshConfig(data=2, sequence=4)),
    ]:
        cfg = dataclasses.replace(
            tiny_model_cfg,
            attention_impl=impl,
            dtype="float32",
            param_dtype="float32",
            # flash kernel tiling needs seq % 8 == 0 and head_dim 64/128;
            # the tiny cfg uses head_dim 16 -> widen for this test
            head_dim=64,
            num_heads=4,
            num_kv_heads=2,
        )
        _, state, gb, step = _setup(cfg, example_batch, mesh_cfg)
        state, m = step(state, gb)
        losses[impl] = float(m["loss"])
        assert np.isfinite(losses[impl]), impl
    np.testing.assert_allclose(losses["flash"], losses["xla"], rtol=1e-4)
    np.testing.assert_allclose(losses["ring"], losses["xla"], rtol=1e-4)


def test_multi_step_matches_single_steps(tiny_model_cfg, example_batch):
    """K steps inside one compiled scan == K sequential single-step calls."""
    import jax.numpy as jnp

    from ditl_tpu.train.step import make_multi_step

    cfg = dataclasses.replace(tiny_model_cfg, dtype="float32", param_dtype="float32")
    mesh, state, gb, step = _setup(cfg, example_batch)
    k = 3
    # K distinct batches: rotate the example batch so steps differ.
    hosts = []
    for i in range(k):
        hb = {kk: np.roll(v, i, axis=0) for kk, v in example_batch.items()}
        hosts.append(make_global_batch(mesh, hb))
    stacked = jax.tree.map(lambda *xs: jnp.stack(xs, axis=0), *hosts)

    s_ref = state
    for i in range(k):
        s_ref, m_ref = step(s_ref, hosts[i])

    tcfg = TrainConfig(total_steps=20, warmup_steps=2, learning_rate=1e-3)
    s2 = create_train_state(jax.random.key(0), cfg, tcfg)
    multi = make_multi_step(cfg, tcfg, mesh, hosts[0], k)
    s2, ms = multi(s2, stacked)

    assert int(s2.step) == int(s_ref.step) == k
    assert ms["loss"].shape == (k,)
    np.testing.assert_allclose(float(ms["loss"][-1]), float(m_ref["loss"]), rtol=1e-5)
    ref_flat, _ = jax.flatten_util.ravel_pytree(s_ref.params)
    got_flat, _ = jax.flatten_util.ravel_pytree(s2.params)
    np.testing.assert_allclose(np.asarray(got_flat), np.asarray(ref_flat), rtol=1e-4, atol=1e-6)


def test_local_validation_eval(tmp_path):
    """data.eval_fraction + train.val_every: held-out NLL is computed and
    logged without touching any network."""
    from ditl_tpu.config import Config, DataConfig, ModelConfig
    from ditl_tpu.train.trainer import train

    out = train(
        Config(
            model=ModelConfig(
                vocab_size=512, hidden_size=64, intermediate_size=128,
                num_layers=2, num_heads=4, num_kv_heads=2, head_dim=16,
                max_seq_len=64,
            ),
            data=DataConfig(
                synthetic=True, synthetic_examples=256, batch_size=8,
                seq_len=32, num_epochs=2, eval_fraction=0.25,
            ),
            train=TrainConfig(
                total_steps=6, warmup_steps=1, log_every=100,
                val_every=3, val_batches=2,
            ),
        )
    )
    assert out["steps"] == 6
    assert "val_loss" in out and np.isfinite(out["val_loss"])


def test_bf16_adam_mu(tiny_model_cfg, example_batch):
    """adam_mu_dtype=bfloat16 stores a bf16 first moment and still trains."""
    import jax.numpy as jnp

    tcfg = TrainConfig(total_steps=10, warmup_steps=1, adam_mu_dtype="bfloat16")
    mesh, state, gb, step = _setup(
        tiny_model_cfg, example_batch, train_cfg=tcfg
    )
    mus = [
        leaf
        for path, leaf in jax.tree_util.tree_leaves_with_path(state.opt_state)
        if any(getattr(k, "name", "") == "mu" for k in path)
    ]
    assert mus and all(m.dtype == jnp.bfloat16 for m in mus)
    losses = []
    for _ in range(5):
        state, m = step(state, gb)
        losses.append(float(m["loss"]))
    assert losses[-1] < losses[0]


@pytest.mark.parametrize("opt", ["adafactor", "lion", "sgd"])
def test_alternate_optimizers_train(tiny_model_cfg, example_batch, opt):
    # Each optimizer family builds, shards (factored adafactor stats restore
    # replicated by the ndim guard in state_logical_axes), and reduces loss.
    lr = 3e-4 if opt == "lion" else 1e-3  # lion's sign updates want a lower lr
    _, state, gb, step = _setup(
        tiny_model_cfg, example_batch,
        train_cfg=TrainConfig(
            total_steps=20, warmup_steps=2, learning_rate=lr, optimizer=opt
        ),
    )
    state, m0 = step(state, gb)
    first = float(m0["loss"])
    for _ in range(10):
        state, m = step(state, gb)
    assert np.isfinite(float(m["loss"]))
    assert float(m["loss"]) < first


def test_unknown_optimizer_raises(tiny_model_cfg):
    with pytest.raises(ValueError, match="unknown optimizer"):
        create_train_state(
            jax.random.key(0), tiny_model_cfg, TrainConfig(optimizer="frobnicate")
        )


def test_train_step_attention_bias(tiny_model_cfg, example_batch):
    """Qwen2-family q/k/v bias: params exist, gradients flow, loss falls."""
    import dataclasses

    cfg = dataclasses.replace(tiny_model_cfg, attention_bias=True)
    _, state, gb, step = _setup(cfg, example_batch)
    assert "bq" in state.params["layers"]["attn"]
    b0 = np.asarray(state.params["layers"]["attn"]["bq"])
    state, m0 = step(state, gb)
    for _ in range(6):
        state, m = step(state, gb)
    assert float(m["loss"]) < float(m0["loss"])
    b1 = np.asarray(state.params["layers"]["attn"]["bq"])
    assert np.abs(b1 - b0).max() > 0  # the bias actually trains


# ---------------------------------------------------------------------------
# The flash kernels' block counts in the step's metrics (ISSUE 40)
# ---------------------------------------------------------------------------


def _packed_batch(b=8, s=256):
    """Rows of three documents: in the even rows the third starts on token
    128, a block's edge, in the odd rows 8 tokens before it."""
    rng = np.random.default_rng(1)
    seg = np.stack([np.repeat([1, 2, 3], [64, 64 - 8 * (r % 2), s - 128 + 8 * (r % 2)])
                    for r in range(b)])
    return {
        "input_ids": rng.integers(3, 500, size=(b, s)).astype(np.int32),
        "loss_mask": np.ones((b, s), np.float32),
        "labels": np.zeros((b,), np.int32),
        "segment_ids": seg.astype(np.int32),
        "positions": np.tile(np.arange(s, dtype=np.int32), (b, 1)),
    }


def _flash_cfg(tiny_model_cfg, **kw):
    return dataclasses.replace(tiny_model_cfg, **{
        "attention_impl": "flash", "head_dim": 64, "num_heads": 4, "num_kv_heads": 2,
        "flash_block_q": 128, "flash_block_kv": 128, "max_seq_len": 256, **kw})


def test_a_packed_flash_step_counts_its_blocks_into_the_metrics_rows(tiny_model_cfg, tmp_path):
    import json

    from ditl_tpu.train.metrics import MetricsLogger

    batch = _packed_batch()
    _, state, gb, step = _setup(_flash_cfg(tiny_model_cfg), batch,
                                MeshConfig(data=4, tensor=2))
    state, metrics = step(state, gb)
    # 8 rows of 2 x 2 blocks of 128, 3 of them causally reachable; an even
    # row's second query block is one document that its first key block
    # holds nothing of
    assert int(metrics["flash_blocks_reachable"]) == 24
    assert int(metrics["flash_blocks_needed"]) == 20
    assert int(metrics["flash_steps_walked"]) == 20  # every grid step computes
    path = tmp_path / "rows.jsonl"
    logger = MetricsLogger(log_every=1, n_chips=1, metrics_file=str(path))
    logger.start_step()
    logger.end_step(0, metrics)
    logger.close()
    (row,) = [json.loads(ln) for ln in path.read_text().splitlines()]
    assert (row["flash_blocks_reachable"], row["flash_blocks_needed"],
            row["flash_steps_walked"]) == (24.0, 20.0, 20.0)


@pytest.mark.parametrize("change,names", [
    ({}, ("flash_blocks_reachable", "flash_blocks_needed", "flash_steps_walked")),
    ({"attention_impl": "xla"}, ()),
    ({"head_dim": 16}, ()),  # a head the kernel cannot tile: XLA runs
    ({"no_segment_ids": True}, ()),
    ({"sequence": 2}, ()),  # a sharded sequence: ring attention's own loop
], ids=["flash-packed", "xla", "untileable", "no-segment-ids", "sequence-sharded"])
def test_the_block_counts_are_reported_only_where_the_flash_kernel_runs(
        tiny_model_cfg, change, names):
    from ditl_tpu.parallel.sharding import DEFAULT_RULES
    from ditl_tpu.train.step import flash_metric_names

    change = dict(change)
    batch = _packed_batch()
    if change.pop("no_segment_ids", False):
        del batch["segment_ids"]
    mesh = build_mesh(MeshConfig(data=8 // change.get("sequence", 1),
                                 sequence=change.pop("sequence", 1)))
    cfg = _flash_cfg(tiny_model_cfg, **change)
    assert flash_metric_names(cfg, mesh, DEFAULT_RULES, batch) == names
