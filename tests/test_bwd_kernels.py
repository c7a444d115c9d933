"""Pallas fused-backward kernels (ops/mlp_bwd.py, ops/projection.py).

Gradient agreement at three levels, interpret-mode on CPU so the same
assertions run in tier-1 (and as real Mosaic kernels on TPU):

1. kernel vs the einsum-spelled VJP (ops/mlp.py's "xla" backward) — the
   two implementations behind the same custom-VJP seam must agree;
2. kernel vs plain autodiff through the op;
3. full-model ``loss_fn`` grads with the Pallas flags vs the pinned
   defaults, single-device AND on the 8-virtual-device DP/FSDP/TP mesh —
   the composition the kernels must survive in training (the shard_map
   wrapper's psum of replicated-weight grads, the activation constraints,
   remat).
"""

from __future__ import annotations

import dataclasses

import jax
import jax.flatten_util
import jax.numpy as jnp
import numpy as np
import pytest

from ditl_tpu.config import MeshConfig, ModelConfig
from ditl_tpu.models import llama
from ditl_tpu.ops import mlp_bwd
from ditl_tpu.ops import projection as projmod
from ditl_tpu.ops.mlp import effective_bwd_impl, mlp_block, mlp_gu
from ditl_tpu.runtime.mesh import build_mesh
from ditl_tpu.train.step import loss_fn

pytestmark = pytest.mark.pallas

B, S, D, F = 2, 32, 256, 128
MLP_BLOCKS = (64, 128, 128)
PROJ_BLOCKS = (64, 128)


def _identity(t):
    return t


@pytest.fixture(scope="module")
def tensors():
    key = jax.random.key(0)
    h = jax.random.normal(jax.random.fold_in(key, 1), (B, S, D), jnp.float32)
    w_gu = jax.random.normal(jax.random.fold_in(key, 2), (D, 2 * F)) * 0.05
    w_down = jax.random.normal(jax.random.fold_in(key, 3), (F, D)) * 0.05
    g = jax.random.normal(jax.random.fold_in(key, 4), (B, S, D), jnp.float32)
    return h, w_gu, w_down, g


def test_supports_rejects_unaligned_shapes():
    assert mlp_bwd.supports(B * S, D, F, MLP_BLOCKS)
    assert not mlp_bwd.supports(B * S, D, 96, MLP_BLOCKS)   # F not lane-tiled
    assert not mlp_bwd.supports(B * S - 1, D, F, MLP_BLOCKS)
    assert projmod.supports(B * S, D, 2 * F, PROJ_BLOCKS)
    assert not projmod.supports(B * S, 200, 2 * F, PROJ_BLOCKS)


def test_fused_mlp_bwd_matches_einsum_vjp(tensors):
    """Level 1: the Pallas kernels vs the einsum-spelled backward — the
    exact pair an on-chip A/B compares."""
    h, w_gu, w_down, g = tensors
    gu = jnp.einsum("bsd,df->bsf", h, w_gu)
    gate, up = jnp.split(gu, 2, axis=-1)
    dh_p, dwgu_p, dwdn_p = mlp_bwd.fused_mlp_bwd(
        h, w_gu, w_down, gate, up, g, blocks=MLP_BLOCKS
    )
    # The einsum spelling, inlined (ops/mlp.py _bwd with constrain=identity).
    sg = jax.nn.sigmoid(gate)
    silu_gate = gate * sg
    inner = silu_gate * up
    dwdn = jnp.einsum("bsf,bsd->fd", inner, g)
    dinner = jnp.einsum("bsd,fd->bsf", g, w_down)
    dgu = jnp.concatenate(
        [dinner * up * (sg * (1.0 + gate * (1.0 - sg))), dinner * silu_gate],
        axis=-1,
    )
    dwgu = jnp.einsum("bsd,bsf->df", h, dgu)
    dh = jnp.einsum("bsf,df->bsd", dgu, w_gu)
    np.testing.assert_allclose(np.asarray(dh_p), np.asarray(dh),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(dwgu_p), np.asarray(dwgu),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(dwdn_p), np.asarray(dwdn),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("blocks", [MLP_BLOCKS, (16, 128, 256)])
def test_mlp_gu_pallas_matches_autodiff(tensors, blocks):
    """Level 2: grads through the op vs autodiff of the plain forward."""
    h, w_gu, w_down, _ = tensors

    def ref(h, a, b):
        gu = jnp.einsum("bsd,df->bsf", h, a)
        gate, up = jnp.split(gu, 2, axis=-1)
        return jnp.sum(jnp.einsum("bsf,fd->bsd", jax.nn.silu(gate) * up, b) ** 2)

    def pallas(h, a, b):
        return jnp.sum(mlp_gu(_identity, h, a, b, "pallas", blocks) ** 2)

    g_ref = jax.grad(ref, argnums=(0, 1, 2))(h, w_gu, w_down)
    g_pal = jax.grad(jax.jit(pallas), argnums=(0, 1, 2))(h, w_gu, w_down)
    for r, p in zip(g_ref, g_pal):
        np.testing.assert_allclose(np.asarray(p), np.asarray(r),
                                   rtol=1e-4, atol=1e-5)


def test_mlp_gu_pallas_falls_back_on_untileable_shapes(tensors):
    """Shapes supports() rejects keep working through the einsum backward
    (the dispatch is a fallback, not a crash; bench records which ran)."""
    h, w_gu, w_down, _ = tensors
    w_gu_odd = w_gu[:, : 2 * 96]  # F=96: not lane-tileable
    w_down_odd = w_down[:96]

    def f(impl):
        return jax.grad(
            lambda h: jnp.sum(
                mlp_gu(_identity, h, w_gu_odd, w_down_odd, impl, ()) ** 2
            )
        )(h)

    np.testing.assert_allclose(np.asarray(f("pallas")), np.asarray(f("xla")),
                               rtol=1e-5, atol=1e-6)


def test_untileable_bwd_kernels_raise_on_tpu_backend(tensors, monkeypatch):
    """The give-way above is for interpret mode only: on the TPU backend a
    requested backward kernel that cannot run raises, naming the shape —
    at the dispatch (effective_bwd_impl) and inside both VJPs."""
    h, w_gu, w_down, _ = tensors
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    with pytest.raises(ValueError, match=r"cannot tile.*f=96"):
        effective_bwd_impl("pallas", B, S, D, 96, ())
    with pytest.raises(ValueError, match=r"cannot tile.*d=200"):
        projmod.effective_bwd_impl("pallas", B, S, 200, 2 * F, PROJ_BLOCKS)
    with pytest.raises(ValueError, match=r"mlp_bwd_impl='pallas'.*F=96"):
        jax.grad(lambda h: jnp.sum(mlp_gu(
            _identity, h, w_gu[:, : 2 * 96], w_down[:96], "pallas", ()) ** 2))(h)
    w_odd = jax.random.normal(jax.random.key(3), (D, 96)) * 0.05
    with pytest.raises(ValueError, match=r"proj_bwd_impl='pallas'.*F=96"):
        jax.grad(lambda x: jnp.sum(
            projmod._proj(x, w_odd, "pallas", (), None) ** 2))(h)


def test_projection_pallas_matches_autodiff(tensors):
    h, *_ = tensors
    w = jax.random.normal(jax.random.key(9), (D, 2 * F)) * 0.05

    def ref(x, w):
        return jnp.sum(jnp.einsum("bsd,df->bsf", x, w) ** 2)

    def pallas(x, w):
        return jnp.sum(
            projmod.projection(x, w, bwd_impl="pallas", blocks=PROJ_BLOCKS) ** 2
        )

    g_ref = jax.grad(ref, argnums=(0, 1))(h, w)
    g_pal = jax.grad(jax.jit(pallas), argnums=(0, 1))(h, w)
    for r, p in zip(g_ref, g_pal):
        np.testing.assert_allclose(np.asarray(p), np.asarray(r),
                                   rtol=1e-4, atol=1e-5)


def _pallas_cfg(cfg):
    return dataclasses.replace(
        cfg, mlp_bwd_impl="pallas", proj_bwd_impl="pallas",
        mlp_bwd_block_n=32, mlp_bwd_block_f=128, mlp_bwd_block_d=128,
        proj_bwd_block_n=32, proj_bwd_block_d=128,
    )


@pytest.fixture(scope="module")
def model_cfg():
    # Tile-able dims (D, F, head projections all 128-multiples), f32 so the
    # comparison is exact-to-accumulation-order.
    return ModelConfig(
        vocab_size=512, hidden_size=256, intermediate_size=128, num_layers=2,
        num_heads=4, num_kv_heads=2, head_dim=64, max_seq_len=64,
        dtype="float32", param_dtype="float32", fused_gate_up=True,
    )


def test_full_model_grads_match_xla(model_cfg):
    """Level 3 (single device): loss_fn grads, Pallas backward vs default."""
    params = llama.init_params(jax.random.key(0), model_cfg)
    rng = np.random.default_rng(0)
    batch = {
        "input_ids": jnp.asarray(rng.integers(3, 500, size=(2, 16)), jnp.int32),
        "loss_mask": jnp.ones((2, 16), jnp.float32),
    }
    pcfg = _pallas_cfg(model_cfg)
    l_ref, g_ref = jax.value_and_grad(
        lambda p: loss_fn(p, batch, model_cfg)[0]
    )(params)
    l, g = jax.value_and_grad(lambda p: loss_fn(p, batch, pcfg)[0])(params)
    np.testing.assert_allclose(float(l), float(l_ref), rtol=1e-6)
    flat, _ = jax.flatten_util.ravel_pytree(g)
    flat_ref, _ = jax.flatten_util.ravel_pytree(g_ref)
    np.testing.assert_allclose(np.asarray(flat), np.asarray(flat_ref),
                               rtol=1e-4, atol=1e-6)


@pytest.mark.parametrize("mesh_cfg,expect_mlp", [
    # DP/FSDP: the Pallas path is ACTIVE (weights replicated inside the
    # island = FSDP's own per-use all-gather cost model).
    (MeshConfig(data=2, fsdp=4), "pallas"),
    # TP shards the weights the wrapper would replicate: the gate keeps the
    # GSPMD backward (running the kernel would silently de-shard TP's
    # compute while bench records "pallas").
    (MeshConfig(data=2, fsdp=2, tensor=2), "xla"),
])
def test_full_model_grads_on_dp_fsdp_tp_mesh(model_cfg, devices8, mesh_cfg,
                                             expect_mlp):
    """Level 3 (sharded): the kernels compose with DP/FSDP/TP — the
    shard_map wrapper's weight-grad psum, GSPMD constraints around it, and
    remat all active where the gate admits the kernel, and the documented
    fallback where it does not. Compares against the single-device XLA
    backward either way."""
    from ditl_tpu.ops.mlp import effective_bwd_impl

    mesh = build_mesh(mesh_cfg)
    pcfg = _pallas_cfg(model_cfg)
    assert effective_bwd_impl(
        "pallas", 8, 16, model_cfg.hidden_size, model_cfg.intermediate_size,
        (32, 128, 128), mesh,
    ) == expect_mlp
    params = llama.init_params(jax.random.key(0), model_cfg)
    rng = np.random.default_rng(1)
    batch = {
        "input_ids": jnp.asarray(rng.integers(3, 500, size=(8, 16)), jnp.int32),
        "loss_mask": jnp.ones((8, 16), jnp.float32),
    }
    l_ref, g_ref = jax.value_and_grad(
        lambda p: loss_fn(p, batch, model_cfg)[0]
    )(params)
    with mesh:
        l, g = jax.jit(jax.value_and_grad(
            lambda p: loss_fn(p, batch, pcfg, mesh=mesh)[0]
        ))(params)
    np.testing.assert_allclose(float(l), float(l_ref), rtol=1e-5)
    # Per-leaf comparison (ravel_pytree over mesh-sharded leaves misorders
    # data on this jax version — the leaves themselves are correct).
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-5
        ),
        g, g_ref,
    )


def test_sharded_kernel_ops_match_plain(devices8):
    """The op-level shard_map dispatch itself (no model around it):
    batch-sharded activations, replicated weights, psummed wgrads (DP/FSDP
    mesh — the gate admits the kernel here, see the TP case above)."""
    mesh = build_mesh(MeshConfig(data=4, fsdp=2))
    key = jax.random.key(0)
    h = jax.random.normal(jax.random.fold_in(key, 1), (4, 16, D), jnp.float32)
    w_gu = jax.random.normal(jax.random.fold_in(key, 2), (D, 2 * F)) * 0.05
    w_down = jax.random.normal(jax.random.fold_in(key, 3), (F, D)) * 0.05

    def mesh_loss(h, a, b):
        return jnp.sum(mlp_block(
            _identity, h, a, b, bwd_impl="pallas",
            bwd_blocks=(16, 128, 128), mesh=mesh,
        ) ** 2)

    def plain_loss(h, a, b):
        return jnp.sum(mlp_block(_identity, h, a, b, bwd_impl="xla") ** 2)

    with mesh:
        lm, gm = jax.jit(
            jax.value_and_grad(mesh_loss, argnums=(0, 1, 2))
        )(h, w_gu, w_down)
    lp, gp = jax.jit(
        jax.value_and_grad(plain_loss, argnums=(0, 1, 2))
    )(h, w_gu, w_down)
    np.testing.assert_allclose(float(lm), float(lp), rtol=1e-5)
    for a, b in zip(gm, gp):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-4)


def test_config_validation():
    with pytest.raises(ValueError, match="fused_gate_up"):
        ModelConfig(mlp_bwd_impl="pallas")
    with pytest.raises(ValueError, match="xla|pallas"):
        ModelConfig(mlp_bwd_impl="cuda")
    with pytest.raises(ValueError, match="MoE|dense"):
        ModelConfig(num_experts=4, fused_gate_up=True, mlp_bwd_impl="pallas")
    with pytest.raises(ValueError, match="mlp_bwd_block_n"):
        ModelConfig(fused_gate_up=True, mlp_bwd_impl="pallas",
                    mlp_bwd_block_n=-256)


def test_effective_impl_tracks_dispatch_gates(devices8):
    """The predicate a record reports must agree with what the dispatch
    actually runs — including the mesh batch-divisibility gate."""
    from ditl_tpu.ops.mlp import effective_bwd_impl

    mesh = build_mesh(MeshConfig(data=8))
    assert effective_bwd_impl("pallas", 8, S, D, F, MLP_BLOCKS, mesh) == "pallas"
    # batch 6 % dp 8 != 0: the dispatch keeps the einsum backward.
    assert effective_bwd_impl("pallas", 6, S, D, F, MLP_BLOCKS, mesh) == "xla"
    # Tensor parallelism: the kernel would de-shard TP's weights — gated.
    tp_mesh = build_mesh(MeshConfig(data=2, tensor=4))
    assert effective_bwd_impl("pallas", 8, S, D, F, MLP_BLOCKS, tp_mesh) == "xla"
    # Untileable F without a mesh: same verdict as mlp_gu's fallback.
    assert effective_bwd_impl("pallas", 2, S, D, 96, MLP_BLOCKS) == "xla"
    assert effective_bwd_impl("xla", 8, S, D, F, MLP_BLOCKS, mesh) == "xla"


def _effective_proj_bwd_impl(cfg, batch: int, seq: int) -> str:
    """Which projection backward will actually run for this config — the
    SAME predicate the dispatch uses (ops/projection.py), over the model's
    ACTUAL projection layout (fused vs per-projection qkv). A projection
    set that only partially tiles reports "mixed"."""
    from ditl_tpu.ops import projection

    d, hd = cfg.hidden_size, cfg.head_dim
    if cfg.fused_qkv:
        proj_shapes = [(d, (cfg.num_heads + 2 * cfg.num_kv_heads) * hd)]
    else:
        proj_shapes = [(d, cfg.num_heads * hd), (d, cfg.num_kv_heads * hd)]
    proj_shapes.append((cfg.num_heads * hd, d))  # wo
    blocks = (cfg.proj_bwd_block_n, cfg.proj_bwd_block_d)
    effs = {
        projection.effective_bwd_impl(
            cfg.proj_bwd_impl, batch, seq, d_in, f, blocks, None
        )
        for d_in, f in proj_shapes
    }
    return effs.pop() if len(effs) == 1 else "mixed"


def test_bench_records_per_projection_layout():
    # Unfused qkv with nkv*hd = 96: wk/wv cannot tile even though the
    # fused-sum shape could — the record must not claim a clean "pallas".
    cfg = ModelConfig(
        vocab_size=512, hidden_size=256, intermediate_size=128, num_layers=2,
        num_heads=4, num_kv_heads=3, head_dim=32, max_seq_len=64,
        dtype="float32", param_dtype="float32", fused_gate_up=True,
        proj_bwd_impl="pallas",
    )
    # wq/wo tile (128), wk/wv (96) do not
    assert _effective_proj_bwd_impl(cfg, 2, 32) == "mixed"


def test_proj_pallas_rejects_quantized_weights(model_cfg):
    from ditl_tpu.ops.quant import quantize_weights

    cfg = dataclasses.replace(model_cfg, proj_bwd_impl="pallas")
    params = quantize_weights(llama.init_params(jax.random.key(0), cfg))
    with pytest.raises(ValueError, match="float weights"):
        llama.forward(params, jnp.ones((1, 8), jnp.int32), cfg)
