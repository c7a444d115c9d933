"""Fused blockwise cross-entropy == naive full-logits cross-entropy.

The fused path (ops/fused_ce.py) must match the naive loss (train/step.py)
in value and in gradients — it is a memory-layout change, not a math change.
"""

import dataclasses
import functools

import jax
import jax.flatten_util
import jax.numpy as jnp
import numpy as np
import pytest

from ditl_tpu.config import ModelConfig
from ditl_tpu.models import llama
from ditl_tpu.ops.fused_ce import fused_cross_entropy
from ditl_tpu.train.step import loss_fn


def _cfg(**kw):
    base = ModelConfig(
        vocab_size=512,
        hidden_size=64,
        intermediate_size=128,
        num_layers=2,
        num_heads=4,
        num_kv_heads=2,
        head_dim=16,
        max_seq_len=64,
        dtype="float32",  # keep the comparison exact-ish on CPU
        param_dtype="float32",
    )
    return dataclasses.replace(base, **kw)


def _batch(rng, b=4, s=32, vocab=512):
    ids = rng.integers(3, vocab, size=(b, s)).astype(np.int32)
    mask = np.ones((b, s), np.float32)
    mask[0, s // 2 :] = 0.0  # exercise masking
    return {
        "input_ids": jnp.asarray(ids),
        "loss_mask": jnp.asarray(mask),
        "positions": jnp.tile(jnp.arange(s, dtype=jnp.int32), (b, 1)),
        "segment_ids": jnp.ones((b, s), jnp.int32),
    }


def test_fused_op_matches_dense_formula():
    rng = np.random.default_rng(0)
    n, d, v = 48, 32, 256  # n not divisible by block: exercises padding
    x = jnp.asarray(rng.normal(size=(n, d)), jnp.float32)
    head = jnp.asarray(rng.normal(size=(d, v)) * 0.1, jnp.float32)
    targets = jnp.asarray(rng.integers(0, v, size=(n,)), jnp.int32)
    mask = jnp.asarray((rng.random(n) > 0.25).astype(np.float32))

    logits = x @ head
    lse = jax.nn.logsumexp(logits, axis=-1)
    tl = jnp.take_along_axis(logits, targets[:, None], axis=1)[:, 0]
    expected = jnp.sum((lse - tl) * mask)

    got = fused_cross_entropy(
        x, head, targets, mask, block_tokens=32, compute_dtype=jnp.float32
    )
    np.testing.assert_allclose(np.asarray(got), np.asarray(expected), rtol=1e-5)


@pytest.mark.parametrize("tie", [False, True])
def test_fused_loss_matches_naive_loss_and_grads(tie):
    cfg_naive = _cfg(tie_embeddings=tie, loss_impl="naive")
    cfg_fused = _cfg(tie_embeddings=tie, loss_impl="fused", loss_block_tokens=32)
    params = llama.init_params(jax.random.key(0), cfg_naive)
    batch = _batch(np.random.default_rng(1))

    def naive(p):
        return loss_fn(p, batch, cfg_naive)[0]

    def fused(p):
        return loss_fn(p, batch, cfg_fused)[0]

    l_naive, g_naive = jax.value_and_grad(naive)(params)
    l_fused, g_fused = jax.value_and_grad(fused)(params)
    np.testing.assert_allclose(np.asarray(l_fused), np.asarray(l_naive), rtol=1e-5)
    flat_n, _ = jax.flatten_util.ravel_pytree(g_naive)
    flat_f, _ = jax.flatten_util.ravel_pytree(g_fused)
    np.testing.assert_allclose(
        np.asarray(flat_f), np.asarray(flat_n), rtol=2e-4, atol=2e-5
    )


def test_fused_loss_trains_end_to_end():
    """One compiled train step with the fused loss produces finite metrics."""
    from ditl_tpu.config import MeshConfig, TrainConfig
    from ditl_tpu.data.loader import make_global_batch
    from ditl_tpu.runtime.mesh import build_mesh
    from ditl_tpu.train.state import create_train_state
    from ditl_tpu.train.step import make_train_step

    cfg = _cfg(loss_impl="fused", loss_block_tokens=32, dtype="bfloat16")
    tcfg = TrainConfig(total_steps=2, warmup_steps=1)
    mesh = build_mesh(MeshConfig())
    rng = np.random.default_rng(2)
    host = {
        "input_ids": rng.integers(3, 500, size=(8, 32)).astype(np.int32),
        "loss_mask": np.ones((8, 32), np.float32),
        "labels": np.zeros((8,), np.int32),
        "segment_ids": np.ones((8, 32), np.int32),
        "positions": np.tile(np.arange(32, dtype=np.int32), (8, 1)),
    }
    gb = make_global_batch(mesh, host)
    state = create_train_state(jax.random.key(0), cfg, tcfg)
    step = make_train_step(cfg, tcfg, mesh, gb)
    state, metrics = step(state, gb)
    assert np.isfinite(float(metrics["loss"]))


# -- the fused loss under a mesh that shards the head (vocabulary-parallel) --


def _mesh(**axes):
    from ditl_tpu.config import MeshConfig
    from ditl_tpu.runtime.mesh import build_mesh

    n = int(np.prod(list(axes.values())))
    return build_mesh(MeshConfig(data=axes.pop("data", 1), **axes),
                      devices=jax.devices()[:n])


@pytest.mark.parametrize("tie", [False, True], ids=["untied", "tied"])
@pytest.mark.parametrize(
    "axes,vocab,label",
    [
        (dict(fsdp=4), 512, "vocab:fsdpx4"),
        (dict(data=2, fsdp=2), 512, "vocab:fsdpx2"),
        (dict(fsdp=2, tensor=2), 512, "vocab:tensor+fsdpx4"),
        (dict(data=2, fsdp=2, tensor=2), 512, "vocab:tensor+fsdpx4"),
        (dict(fsdp=4), 510, "vocab:fsdpx4"),  # V % k != 0: padded columns
    ],
    ids=["fsdp4", "data2-fsdp2", "fsdp2-tensor2", "data2-fsdp2-tensor2",
         "fsdp4-v510"],
)
def test_sharded_fused_loss_matches_one_device_and_naive(devices8, axes, vocab,
                                                         label, tie):
    """Value, d_x and d_head of the vocabulary-parallel loss, with the arrays
    laid out as the train step lays them out, against the one-device fused
    loss and the dense formula: the same mathematics to float32 rounding."""
    from jax.sharding import NamedSharding

    from ditl_tpu.ops.fused_ce import loss_partition
    from ditl_tpu.parallel.sharding import logical_to_spec

    mesh = _mesh(**axes)
    part = loss_partition(mesh, None)
    assert str(part) == label
    rng = np.random.default_rng(3)
    n, d = 8 * 13, 32  # 104 tokens: not a multiple of the 32-token block
    x = jnp.asarray(rng.normal(size=(n, d)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(vocab, d) if tie else (d, vocab)) * 0.1,
                    jnp.float32)
    targets = jnp.asarray(rng.integers(0, vocab, size=(n,)), jnp.int32)
    mask = jnp.asarray((rng.random(n) > 0.25).astype(np.float32))

    def put(a, logical):
        return jax.device_put(a, NamedSharding(mesh, logical_to_spec(logical)))

    def head_of(w):
        return w.T if tie else w

    def fused(x, w, **kw):
        return fused_cross_entropy(x, head_of(w), targets, mask, block_tokens=32,
                                   compute_dtype=jnp.float32, **kw)

    def naive(x, w):
        logits = x @ head_of(w)
        lse = jax.nn.logsumexp(logits, axis=-1)
        tl = jnp.take_along_axis(logits, targets[:, None], axis=1)[:, 0]
        return jnp.sum((lse - tl) * mask)

    sharded = jax.jit(jax.value_and_grad(
        lambda x, w: fused(x, w, partition=part), argnums=(0, 1)))
    got = sharded(
        put(x, ("batch", None)),
        put(w, ("vocab", "embed") if tie else ("embed", "vocab")),
    )
    for ref_fn in (fused, naive):
        ref = jax.value_and_grad(ref_fn, argnums=(0, 1))(x, w)
        for g, r in zip(jax.tree.leaves(got), jax.tree.leaves(ref)):
            r = np.asarray(r)
            np.testing.assert_allclose(
                np.asarray(g), r, rtol=1e-5, atol=1e-5 * np.abs(r).max())


@pytest.mark.parametrize(
    "axes,rules,want",
    [
        (dict(data=4), None, "None"),  # pure data parallelism: head whole
        (dict(data=1), None, "None"),  # one device
        (dict(fsdp=2, sequence=2), None, "None"),  # sequence-sharded tokens
        (dict(fsdp=2, stage=2), "pipeline", "None"),  # pipeline rules un-shard
        (dict(tensor=2), None, "vocab:tensorx2"),
        (dict(data=2, fsdp=4), None, "vocab:fsdpx4"),
    ],
    ids=["data4", "one-device", "sequence", "pipeline", "tensor2", "data2-fsdp4"],
)
def test_loss_partition_follows_mesh_and_rules(devices8, axes, rules, want):
    from ditl_tpu.ops.fused_ce import loss_partition
    from ditl_tpu.parallel.pipeline import PIPELINE_RULES

    mesh = _mesh(**axes)
    part = loss_partition(mesh, PIPELINE_RULES if rules == "pipeline" else None)
    assert str(part) == want
    assert loss_partition(None, None) is None


@functools.partial(jax.jit, static_argnames=("block_tokens", "compute_dtype"))
@jax.named_scope("loss")
def fused_cross_entropy_before(x, head, targets, mask, *, block_tokens=1024,
                               compute_dtype=jnp.bfloat16):
    """The one-device op as it was before it had a VJP of its own, frozen here
    as the yardstick: every block checkpointed, the gradient left to autodiff
    (a forward loop and a backward loop, four head matmuls a block)."""
    n, d = x.shape
    block = min(block_tokens, n) if n > 0 else block_tokens
    pad = (-n) % block
    if pad:
        x = jnp.pad(x, ((0, pad), (0, 0)))
        targets = jnp.pad(targets, (0, pad))
        mask = jnp.pad(mask, (0, pad))
    nb = (n + pad) // block
    xb = x.reshape(nb, block, d)
    tb = targets.reshape(nb, block).astype(jnp.int32)
    mb = mask.reshape(nb, block).astype(jnp.float32)

    def block_nll(head, x_blk, t_blk, m_blk):
        logits = jnp.einsum(
            "td,dv->tv", x_blk.astype(compute_dtype), head.astype(compute_dtype),
            preferred_element_type=jnp.float32,
        )
        lse = jax.nn.logsumexp(logits, axis=-1)
        target_logit = jnp.take_along_axis(logits, t_blk[:, None], axis=1)[:, 0]
        return jnp.sum((lse - target_logit) * m_blk)

    block_nll = jax.checkpoint(block_nll)

    def scan_step(nll_sum, xs):
        x_blk, t_blk, m_blk = xs
        return nll_sum + block_nll(head, x_blk, t_blk, m_blk), None

    nll_sum, _ = jax.lax.scan(
        scan_step, jnp.zeros((), jnp.float32), (xb, tb, mb))
    return nll_sum


def test_k1_lowers_to_the_parents_jaxpr(devices8):
    """Where no mesh axis shards the head (no mesh, one device: train-2k) the
    op knows nothing of meshes, its gradient is one loop of three head
    matmuls a block where ``fused_cross_entropy_before`` takes two loops and
    four, and called for its value it lowers to that function's program,
    string for string (a jaxpr shows the VJP's wrapper, so the lowered module
    is what is compared)."""
    from ditl_tpu.ops.fused_ce import loss_partition

    args = (jnp.zeros((48, 32), jnp.bfloat16), jnp.zeros((32, 256), jnp.float32),
            jnp.zeros((48,), jnp.int32), jnp.ones((48,), jnp.float32))

    def jaxpr_of(fn, **kw):
        grad = jax.grad(lambda x, h: fn(x, h, *args[2:], block_tokens=32, **kw),
                        argnums=(0, 1))
        return str(jax.make_jaxpr(grad)(*args[:2]))

    def counts(jaxpr):
        return jaxpr.count("dot_general["), jaxpr.count("scan[")

    ours = jaxpr_of(fused_cross_entropy)
    assert jaxpr_of(fused_cross_entropy,
                    partition=loss_partition(_mesh(data=1), None)) == ours
    assert "shard_map" not in ours
    assert counts(ours) == (3, 1)
    assert counts(jaxpr_of(fused_cross_entropy_before)) == (4, 2)

    def lowered(fn):
        return fn.lower(*args, block_tokens=32).as_text()

    assert lowered(fused_cross_entropy) == lowered(
        fused_cross_entropy_before).replace(
            "fused_cross_entropy_before", "fused_cross_entropy")


def _dense(x, head, targets, mask):
    logits = x @ head
    lse = jax.nn.logsumexp(logits, axis=-1)
    tl = jnp.take_along_axis(logits, targets[:, None], axis=1)[:, 0]
    return jnp.sum((lse - tl) * mask)


_COTANGENTS = {
    "times-0.37": lambda nll, mask: nll * 0.37,
    "over-n-tokens": lambda nll, mask: nll / jnp.maximum(mask.sum(), 1.0),
}


def _loss_inputs(seed, n, d, v, dead=None):
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.normal(size=(n, d)), jnp.float32)
    head = jnp.asarray(rng.normal(size=(d, v)) * 0.1, jnp.float32)
    targets = jnp.asarray(rng.integers(0, v, size=(n,)), jnp.int32)
    mask = (rng.random(n) > 0.25).astype(np.float32)
    if dead is not None:
        mask[dead] = 0.0
    return x, head, targets, jnp.asarray(mask)


@pytest.mark.parametrize("cotangent", list(_COTANGENTS))
@pytest.mark.parametrize(
    "n,dead", [(48, None), (96, slice(32, 64))], ids=["pads", "dead-block"])
def test_gradients_under_a_cotangent_that_is_not_one(n, dead, cotangent):
    """The backward rule only scales what the forward pass stored: value,
    ``d_x`` and ``d_head`` against the dense formula and against the frozen
    parent where the loss is scaled or divided by its token count, with an
    ``N`` the 32-token block does not divide and with a block whose mask is
    all zero. The value is the parent's to the bit, differentiated or not."""
    x, head, targets, mask = _loss_inputs(5, n, 32, 256, dead)
    scale = _COTANGENTS[cotangent]

    def of(fn, **kw):
        return jax.value_and_grad(
            lambda x, h: scale(fn(x, h, targets, mask, **kw), mask),
            argnums=(0, 1))(x, head)

    kw = dict(block_tokens=32, compute_dtype=jnp.float32)
    got = of(fused_cross_entropy, **kw)
    before = of(fused_cross_entropy_before, **kw)
    assert float(got[0]) == float(before[0])
    assert float(got[0]) == float(
        scale(fused_cross_entropy(x, head, targets, mask, **kw), mask))
    if dead is not None:
        assert not np.asarray(got[1][0])[dead].any()
    for ref in (of(_dense), before):
        for g, r in zip(jax.tree.leaves(got), jax.tree.leaves(ref)):
            r = np.asarray(r)
            np.testing.assert_allclose(
                np.asarray(g), r, rtol=1e-5, atol=1e-5 * np.abs(r).max())


def test_frozen_head_costs_no_more_dots_than_the_parent():
    """A fine-tune that freezes the head differentiates ``x`` alone: the
    gradient is the parent's, and the compiler drops the unused ``d_head``
    from the loop, so the compiled program holds the parent's two dots a
    block (the logits, ``d_x``) and no third."""
    import re

    x, head, targets, mask = _loss_inputs(6, 80, 32, 256)

    def grad_of(fn):
        return jax.jit(jax.grad(lambda x: fn(
            x, head, targets, mask, block_tokens=32,
            compute_dtype=jnp.float32) / 7.0))

    def dots(fn):
        return len(re.findall(r"= \S+ dot\(", fn.lower(x).compile().as_text()))

    ours, before = grad_of(fused_cross_entropy), grad_of(fused_cross_entropy_before)
    want = np.asarray(before(x))
    np.testing.assert_allclose(np.asarray(ours(x)), want, rtol=1e-5,
                               atol=1e-5 * np.abs(want).max())
    assert dots(ours) == 2
    assert dots(ours) <= dots(before)


def test_micro_batches_through_multi_step_equal_one_step_over_their_union():
    """The loss is a pure sum whose gradient the forward pass stores, under a
    scan too: two micro-batches (``grad_accum_steps=2``) inside
    ``make_multi_step``'s scan over steps give the loss, the gradient norm
    and the parameters of plain steps over the whole batches."""
    from ditl_tpu.config import MeshConfig, TrainConfig
    from ditl_tpu.data.loader import make_global_batch
    from ditl_tpu.runtime.mesh import build_mesh
    from ditl_tpu.train.state import create_train_state
    from ditl_tpu.train.step import make_multi_step, make_train_step

    cfg = _cfg(loss_impl="fused", loss_block_tokens=32, num_layers=1)
    mesh = build_mesh(MeshConfig(), devices=jax.devices()[:1])
    rng = np.random.default_rng(7)
    b, s, steps = 4, 33, 2

    def host():
        return {
            "input_ids": rng.integers(3, 512, size=(b, s)).astype(np.int32),
            # every micro-batch counts the same tokens, so the mean of the
            # micro-batches' losses is the loss over their union
            "loss_mask": np.ones((b, s), np.float32),
            "segment_ids": np.ones((b, s), np.int32),
            "positions": np.tile(np.arange(s, dtype=np.int32), (b, 1)),
        }

    batches = [make_global_batch(mesh, host()) for _ in range(steps)]
    plain_cfg = TrainConfig(total_steps=4, warmup_steps=1)
    state = create_train_state(jax.random.key(0), cfg, plain_cfg)
    plain = make_train_step(cfg, plain_cfg, mesh, batches[0])
    want = []
    for gb in batches:
        state, metrics = plain(state, gb)
        want.append(metrics)

    accum_cfg = TrainConfig(total_steps=4, warmup_steps=1, grad_accum_steps=2)
    multi = make_multi_step(cfg, accum_cfg, mesh, batches[0], steps)
    window = jax.tree.map(lambda *xs: jnp.stack(xs), *batches)
    got_state, got = multi(
        create_train_state(jax.random.key(0), cfg, accum_cfg), window)
    for k in ("loss", "grad_norm"):
        np.testing.assert_allclose(
            np.asarray(got[k]), np.asarray([float(m[k]) for m in want]), rtol=2e-5)
    for g, w in zip(jax.tree.leaves(got_state.params), jax.tree.leaves(state.params)):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), rtol=1e-4, atol=1e-6)


@pytest.mark.parametrize("cotangent", list(_COTANGENTS))
@pytest.mark.parametrize(
    "axes", [dict(fsdp=4), dict(data=2, fsdp=2, tensor=2)],
    ids=["fsdp4", "data2-fsdp2-tensor2"])
def test_sharded_gradients_under_a_cotangent_that_is_not_one(devices8, axes,
                                                             cotangent):
    """The vocabulary-parallel forward pass stores ``d_x`` and ``d_head``
    summed over the chips and laid out as ``x`` and the head arrived, so what
    the cotangent is worth on a chip is no convention of a transpose: scaled
    or divided by the token count, the sharded gradients are the dense
    formula's (``fsdp=4``: tokens gathered, ``d_x`` reduce-scattered; with
    ``data`` and ``tensor``: ``d_head`` summed over ``data``, ``d_x`` over
    ``tensor``)."""
    from jax.sharding import NamedSharding

    from ditl_tpu.ops.fused_ce import loss_partition
    from ditl_tpu.parallel.sharding import logical_to_spec

    mesh = _mesh(**axes)
    part = loss_partition(mesh, None)
    x, head, targets, mask = _loss_inputs(8, 8 * 13, 32, 510)
    scale = _COTANGENTS[cotangent]

    def put(a, logical):
        return jax.device_put(a, NamedSharding(mesh, logical_to_spec(logical)))

    got = jax.jit(jax.value_and_grad(
        lambda x, h: scale(fused_cross_entropy(
            x, h, targets, mask, block_tokens=32, compute_dtype=jnp.float32,
            partition=part), mask), argnums=(0, 1)))(
        put(x, ("batch", None)), put(head, ("embed", "vocab")))
    ref = jax.value_and_grad(
        lambda x, h: scale(_dense(x, h, targets, mask), mask), argnums=(0, 1))(x, head)
    for g, r in zip(jax.tree.leaves(got), jax.tree.leaves(ref)):
        r = np.asarray(r)
        np.testing.assert_allclose(
            np.asarray(g), r, rtol=1e-5, atol=1e-5 * np.abs(r).max())


def _collectives(hlo_text):
    """(kind, largest result array's element count, scope path) of every
    collective instruction in a compiled module's text."""
    import re

    kinds = ("all-reduce", "all-gather", "all-to-all", "reduce-scatter",
             "collective-permute")
    line = re.compile(
        r"= (?P<type>.*?) (?P<kind>" + "|".join(kinds) + r")(-start)?\(")
    shape = re.compile(r"\w+\[([\d,]*)\]")
    out = []
    for ln in hlo_text.splitlines():
        m = line.search(ln)
        if not m:
            continue
        sizes = [int(np.prod([int(s) for s in dims.split(",") if s] or [1]))
                 for dims in shape.findall(m["type"])]
        op = re.search(r'op_name="([^"]*)"', ln)
        out.append((m["kind"], max(sizes), op.group(1) if op else ""))
    return out


def test_fsdp4_train_step_moves_no_logits_block(devices8):
    """The compiled fsdp=4 step holds no collective the size of a logits
    block, whole (block x V: what GSPMD all-reduced when the head's split
    along D was left to it) or sharded (block x V/k); what the loss does move
    is bounded by the head's shard and the hidden states. And one step of it
    gives the one-device step's loss."""
    from ditl_tpu.config import TrainConfig
    from ditl_tpu.data.loader import make_global_batch
    from ditl_tpu.train.state import create_train_state
    from ditl_tpu.train.step import make_train_step

    block, vocab, d, b, s, k = 96, 1024, 64, 8, 33, 4
    cfg = _cfg(vocab_size=vocab, loss_impl="fused", loss_block_tokens=block)
    tcfg = TrainConfig(total_steps=2, warmup_steps=1)
    rng = np.random.default_rng(4)
    host = {
        "input_ids": rng.integers(3, vocab, size=(b, s)).astype(np.int32),
        "loss_mask": (rng.random((b, s)) > 0.2).astype(np.float32),
        "segment_ids": np.ones((b, s), np.int32),
        "positions": np.tile(np.arange(s, dtype=np.int32), (b, 1)),
    }
    losses = {}
    for name, mesh in (("fsdp4", _mesh(fsdp=4)), ("one", _mesh(data=1))):
        gb = make_global_batch(mesh, host)
        state = create_train_state(jax.random.key(0), cfg, tcfg)
        step = make_train_step(cfg, tcfg, mesh, gb)
        if name == "fsdp4":
            found = _collectives(step.lower(state, gb).compile().as_text())
        losses[name] = float(step(state, gb)[1]["loss"])
    np.testing.assert_allclose(losses["fsdp4"], losses["one"], rtol=1e-5)

    n = b * (s - 1)
    assert found, "a four-way sharded step with no collective at all"
    assert not [c for c in found if c[1] in (block * vocab, block * vocab // k)], found
    of_loss = [c for c in found if "fused_cross_entropy" in c[2]]
    assert of_loss, "no collective carries the loss's scope: is it still named?"
    bound = max(d * vocab // k, n * d)  # the head's shard, the hidden states
    assert block * vocab // k > bound  # or the bound would say nothing here
    assert max(c[1] for c in of_loss) <= bound, of_loss


@pytest.mark.parametrize(
    "mesh_kw,want", [(dict(data=2, fsdp=4), "vocab:fsdpx4"), (dict(data=8), "local")],
    ids=["data2-fsdp4", "data8"],
)
def test_a_runs_record_says_which_loss_path_it_took(tmp_path, devices8, mesh_kw, want):
    """``loss_partition`` in the trainer's summary and on the step's
    ``jit.compile`` journal event."""
    import json

    from ditl_tpu.config import Config, DataConfig, MeshConfig, TrainConfig
    from ditl_tpu.train.trainer import train

    summary = train(Config(
        model=_cfg(loss_impl="fused", loss_block_tokens=96, dtype="bfloat16"),
        mesh=MeshConfig(**mesh_kw),  # the trainer's mesh spans all 8 devices
        data=DataConfig(synthetic=True, synthetic_examples=64, batch_size=8,
                        num_epochs=1, seq_len=32),
        train=TrainConfig(total_steps=2, warmup_steps=1, log_every=1,
                          telemetry_dir=str(tmp_path)),
    ))
    assert summary["loss_partition"] == want
    assert np.isfinite(summary["final_loss"])
    events = [json.loads(ln) for f in tmp_path.glob("events-worker-*.jsonl")
              for ln in f.read_text().splitlines()]
    steps = [e for e in events if e.get("event") == "jit.compile"
             and e["program"] == "jit(train_step)"]
    assert steps and all(e["loss_partition"] == want for e in steps)
