"""Granite-4.0-H's stack (ISSUE 41): the period scan of Mamba-2 mixers and
attention layers against the plain reference, the chunked recurrence against
its token-by-token form, the trainer's loss and gradients, the checkpoint
names. Tiny sizes on the CPU, seeded random weights; the serving engine has
tests/test_ssm_serving.py."""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ditl_tpu.models import llama, ssm
from ditl_tpu.models.presets import get_preset
from ditl_tpu.ops import ssd
from tests import family
from tests.family import rel

ref = family.reference("granite_hybrid")
PRESET = "granite-4.0-h-micro"

# Both sides compute in float32 on the same weights and differ in the order
# of their sums (chunks of 16 with masked matmuls against a scan over tokens;
# a scan over periods against a loop over layers): 1e-6 relative is what
# float32 leaves of that; 1e-4 gives it a hundred times of room and is twenty
# times under what a bfloat16 state (2^-9 a rounding, every token) leaves.
TOL = 1e-4

TINY = dict(vocab_size=512, hidden_size=32, intermediate_size=64, num_heads=4, num_kv_heads=2,
            head_dim=8, ssm_heads=4, ssm_head_dim=16, ssm_state=8, ssm_chunk=16,
            attention_multiplier=1 / 16, max_seq_len=1024, dtype="float32", remat="none")


def stack(period="mma", periods=1, **kw):
    """``periods`` periods of ``period``'s layers."""
    kw = {"num_layers": len(period) * periods, "layer_types": period * periods, **kw}
    return family.tiny(PRESET, TINY, **kw)


def packed(rows, s, cuts):
    seg = np.ones((rows, s), np.int32)
    for r, row_cuts in enumerate(cuts):
        seg[r] = np.searchsorted(np.asarray(row_cuts), np.arange(s), side="right") + 1
    return jnp.asarray(seg)


@pytest.mark.parametrize("period, periods, segments", [
    ("mma", 1, False), ("mma", 2, False), ("mmmmmammmm", 1, False),
    ("mma", 2, True), ("amm", 1, True),
], ids=["one-period", "two-periods", "published-period", "two-periods-packed",
        "attention-first-packed"])
def test_forward_matches_the_reference(period, periods, segments):
    """37 tokens a row: two chunks of 16 and a tail of 5. Packed rows: three
    documents, one cut inside a chunk and one on a chunk's edge."""
    cfg = stack(period, periods)
    params = family.seeded(ref, cfg)
    ids = jax.random.randint(jax.random.key(1), (2, 37), 3, cfg.vocab_size)
    kw = {"segment_ids": packed(2, 37, [(10, 32), (16, 30)])} if segments else {}
    got = llama.forward(params, ids, cfg, **kw)
    want = ref.forward(params, ids, ref.sizes(cfg, {}), **kw)
    assert rel(got, want) < TOL


def test_loss_and_every_gradient_match_the_reference_through_the_trainers_loss_fn():
    from ditl_tpu.train.step import loss_fn

    cfg = stack("mma", 2)
    params = family.seeded(ref, cfg)
    ids = jax.random.randint(jax.random.key(2), (2, 40), 3, cfg.vocab_size)
    seg = packed(2, 40, [(13,), (16, 29)])
    pos = jnp.broadcast_to(jnp.arange(40, dtype=jnp.int32), ids.shape)
    mask = jnp.ones(ids.shape, jnp.float32)
    batch = {"input_ids": ids, "positions": pos, "segment_ids": seg, "loss_mask": mask}
    sizes = ref.sizes(cfg, {})

    def want_fn(p):
        return ref.loss(ref.forward(p, ids, sizes, segment_ids=seg), ids, mask, sizes)

    got, g_got = jax.value_and_grad(lambda p: loss_fn(p, batch, cfg)[0])(params)
    want, g_want = jax.value_and_grad(want_fn)(params)
    assert abs(float(got) - float(want)) / float(want) < TOL
    flat_got, flat_want = jax.tree.leaves_with_path(g_got), jax.tree.leaves(g_want)
    assert len(flat_got) == len(flat_want)
    for (path, a), b in zip(flat_got, flat_want):
        assert float(jnp.abs(b).max()) > 0, path  # every leaf is reached
        assert rel(a, b) < 10 * TOL, jax.tree_util.keystr(path)


def _token_by_token(x, dt, a, bmat, cmat, state):
    ys = []
    for t in range(x.shape[1]):
        y, state = ssd.ssd_step(state, x[:, t], dt[:, t], a, bmat[:, t], cmat[:, t])
        ys.append(y)
    return jnp.stack(ys, axis=1), state


@pytest.mark.parametrize("s, chunk", [(37, 16), (16, 16), (5, 16), (50, 8)],
                         ids=["two-chunks-and-a-tail", "one-chunk", "under-a-chunk", "six-chunks"])
def test_the_chunked_form_equals_the_token_by_token_form(s, chunk):
    """With an initial state in and the final state out, and a padded tail
    (``dt = 0``) that leaves the state exactly as the last real token left it."""
    k = jax.random.split(jax.random.key(s), 6)
    b, h, p, n = 2, 4, 16, 8
    x = jax.random.normal(k[0], (b, s, h, p))
    dt = jax.nn.softplus(jax.random.normal(k[1], (b, s, h)) - 2.0)
    a = -jnp.exp(jax.random.uniform(k[2], (h,), minval=0.0, maxval=2.5))
    bmat, cmat = jax.random.normal(k[3], (b, s, n)), jax.random.normal(k[4], (b, s, n))
    s0 = jax.random.normal(k[5], (b, h, p, n))
    y, last = ssd.ssd_scan(x, dt, a, bmat, cmat, chunk=chunk, state=s0)
    y_want, last_want = _token_by_token(x, dt, a, bmat, cmat, s0)
    assert rel(y, y_want) < TOL and rel(last, last_want) < TOL
    real = s - 3  # the last three positions are padding
    dt_pad = dt.at[:, real:].set(0.0)
    _, last_pad = ssd.ssd_scan(x, dt_pad, a, bmat, cmat, chunk=chunk, state=s0)
    _, last_real = ssd.ssd_scan(x[:, :real], dt[:, :real], a, bmat[:, :real], cmat[:, :real],
                                chunk=chunk, state=s0)
    assert rel(last_pad, last_real) < 1e-6


def _step_operands(key, b, h, p, n, alive):
    k = jax.random.split(key, 5)
    x = jax.random.normal(k[0], (b, h, p))
    a = -jnp.exp(jax.random.normal(k[1], (h,)))
    bvec, cvec = jax.random.normal(k[2], (b, n)), jax.random.normal(k[3], (b, n))
    dt = jax.nn.softplus(jax.random.normal(k[4], (b, h))) * jnp.asarray(alive, jnp.float32)[:, None]
    return x, dt, a, bvec, cvec


@pytest.mark.pallas
@pytest.mark.parametrize("alive", [(1, 1, 1, 1, 1, 1), (0, 1, 0, 0, 1, 0), (0, 0, 0, 0, 0, 0),
                                   (0, 0, 0, 0, 0, 1), (1, 0, 0, 0, 0, 0), (0, 1, 1, 1, 1, 1)],
                         ids=["all", "dead-first-between-last", "none", "last", "first",
                              "all-but-row-0"])
def test_the_interpreted_step_kernel_equals_the_plain_step(alive):
    """``ssd_step`` over the stacked state in its STORED layout (two 64-wide
    heads a tile): the walk visits the live rows alone and updates them in
    place, a dead row's state is as it was, every other mixer's entry
    untouched; against ``ssd_step`` on the ``(b, H, P, N)`` form."""
    k = jax.random.split(jax.random.key(7), 2)
    n_mix, b, h, p, n = 3, 6, 4, 64, 128
    plain = jax.random.normal(k[0], (n_mix, b, h, p, n))
    stack = ssd.to_stored(plain)
    assert stack.shape == (n_mix, b, 2, n, 128)
    x, dt, a, bvec, cvec = _step_operands(k[1], b, h, p, n, alive)
    live = jnp.asarray(alive, bool)
    y_want, s_want = ssd.ssd_step(plain[1], x, dt, a, bvec, cvec)
    y, s = jax.jit(lambda st, at: ssd.ssd_step_rows(
        st, at, x.reshape(b, h * p), dt, a, bvec, cvec, live, interpret=True))(
            stack, jnp.int32(1))
    assert y.shape == (b, h * p)
    assert rel(y[live], y_want.reshape(b, h * p)[live]) < 1e-5 if any(alive) else True
    assert not bool(jnp.any(y[~live]))
    assert float(jnp.abs(ssd.from_stored(s[1], h) - s_want).max()) < 1e-5
    assert bool(jnp.all(s[:, ~live] == stack[:, ~live]))
    assert bool(jnp.all(s[0] == stack[0])) and bool(jnp.all(s[2] == stack[2]))
    # off the chip the plain form runs on the same stored stack
    y_cpu, s_cpu = ssd.ssd_step_rows(stack, jnp.int32(1), x.reshape(b, h * p), dt, a, bvec,
                                     cvec, live)
    assert float(jnp.abs(y_cpu - y_want.reshape(b, h * p)).max()) < 1e-5
    assert float(jnp.abs(s_cpu - s).max()) < 1e-5


@pytest.mark.pallas
@pytest.mark.parametrize("h, p, n, hp", [(4, 64, 128, 2), (8, 16, 8, 8), (4, 16, 8, 1),
                                         (3, 64, 16, 1), (4, 48, 8, 1), (2, 128, 8, 1)],
                         ids=["two-heads-a-tile", "eight-heads-a-tile", "too-few-heads",
                              "odd-heads", "width-48", "width-128"])
def test_the_stored_layout_round_trips_through_the_prefill_boundary(h, p, n, hp):
    """A prefill's final state (``ssd_scan``, ``(b, H, P, N)``) seated in the
    stored layout, then one kernel step on it, against ``ssd_step`` on the
    plain form; ``hp`` heads a tile read off the shapes (one where ``P`` does
    not divide 128 or ``H`` is no multiple of ``128 / P``)."""
    assert ssd.heads_a_tile(h, p) == hp
    assert ssd.stored_shape(h, p, n) == (h // hp, n, hp * p)
    k = jax.random.split(jax.random.key(p + h), 6)
    b, s = 3, 21
    xs = jax.random.normal(k[0], (b, s, h, p))
    dts = jax.nn.softplus(jax.random.normal(k[1], (b, s, h)) - 2.0)
    bmat, cmat = jax.random.normal(k[2], (b, s, n)), jax.random.normal(k[3], (b, s, n))
    alive = (1, 0, 1)
    x, dt, a, bvec, cvec = _step_operands(k[4], b, h, p, n, alive)
    _, last = ssd.ssd_scan(xs, dts, a, bmat, cmat, chunk=8)
    seated = ssd.to_stored(last)
    assert seated.shape == (b, h // hp, n, hp * p)
    assert bool(jnp.all(ssd.from_stored(seated, h) == last))  # a permutation, exactly
    y, stack = jax.jit(lambda st: ssd.ssd_step_rows(
        st, jnp.int32(0), x.reshape(b, h * p), dt, a, bvec, cvec, jnp.asarray(alive, bool),
        interpret=True))(seated[None])
    y_want, s_want = ssd.ssd_step(last, x, dt, a, bvec, cvec)
    live = jnp.asarray(alive, bool)
    assert rel(y[live], y_want.reshape(b, h * p)[live]) < 1e-6
    assert rel(ssd.from_stored(stack[0], h), s_want) < 1e-6
    assert bool(jnp.all(stack[0, 1] == seated[1]))  # the dead row, bit for bit


def test_the_preset_is_the_published_model():
    cfg = get_preset("granite-4.0-h-micro")
    assert cfg.layer_period == "mmmmmammmm" and ssm.period_counts(cfg) == (4, 9, 1)
    assert [i for i, t in enumerate(cfg.layer_types) if t == "a"] == [5, 15, 25, 35]
    # shapes alone: this size is never drawn
    shapes = jax.eval_shape(lambda: llama.init_params(jax.random.key(0), cfg))
    # ISSUE 41's count: 36 mixers + 4 attention layers + the tied embedding
    assert llama.num_params(shapes) == 3_191_396_096
    assert ssm.state_bytes_per_slot(cfg) == 36 * (64 * 64 * 128 * 4 + 3 * 4352 * 2)
    # two 64-wide heads a tile, the state columns on the sublanes (ops/ssd.py)
    assert jax.eval_shape(lambda: ssm.init_state(cfg, 64))["ssm"].shape == (36, 64, 32, 128, 128)
    axes = llama.param_logical_axes(cfg)
    assert jax.tree.structure(shapes) == jax.tree.structure(
        axes, is_leaf=lambda x: isinstance(x, tuple))


@pytest.mark.parametrize("kw, said", [
    (dict(layer_types="mmx"), "each 'm', 'a', 'w' or 'r'"),
    (dict(layer_types="mm"), "num_layers"),
    (dict(ssm_state=0), "ssm_heads"),
    (dict(fused_gate_up=False), "fused_gate_up"),
    (dict(position_embedding="alibi"), "position_embedding"),
])
def test_a_setting_the_stack_cannot_run_is_refused(kw, said):
    with pytest.raises(ValueError, match=said):
        stack("mma", 1, **kw)


def test_the_converter_round_trips_the_hybrid_tree():
    from ditl_tpu.models.convert import params_from_state_dict, state_dict_from_params

    cfg = stack("mma", 2)
    params = family.seeded(ref, cfg)
    hf = state_dict_from_params(params, cfg)
    assert "model.layers.0.mamba.in_proj.weight" in hf
    assert hf["model.layers.0.mamba.in_proj.weight"].shape == (
        2 * 64 + 2 * 8 + 4, cfg.hidden_size)
    assert "model.layers.2.self_attn.q_proj.weight" in hf
    assert "model.layers.5.shared_mlp.input_linear.weight" in hf
    back = params_from_state_dict(hf, cfg)
    for (path, a), b in zip(jax.tree.leaves_with_path(params), jax.tree.leaves(back)):
        assert a.shape == b.shape and bool(jnp.all(a == b)), jax.tree_util.keystr(path)
