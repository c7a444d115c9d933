"""Brumby's power-retention layer (ISSUE 56): the feature map, the three
forms of one equation held to each other and to the plain reference (the
attention form alone), the step kernel in interpret mode, the preset, the
refusals and the converter (tests/test_retention_serving.py has the engine)."""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ditl_tpu.models import llama, retention
from ditl_tpu.models.presets import get_preset
from ditl_tpu.ops import retention as ret
from tests import family

ref = family.reference("brumby")
PRESET = "brumby-14b"

TINY = dict(vocab_size=512, hidden_size=64, intermediate_size=128, num_layers=3,
            layer_types="rrr", num_heads=4, num_kv_heads=2, head_dim=16, ret_chunk=16,
            max_seq_len=1024, dtype="float32")
CFG = family.tiny(PRESET, TINY)
EPS = 1e-5


def rel(a, b):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.sqrt(np.mean((a - b) ** 2)) / (np.sqrt(np.mean(b ** 2)) + 1e-30))


def draws(seed, b=2, s=50, n_kv=2, n_g=2, d=16):
    rng = np.random.default_rng(seed)
    f = lambda *shape: jnp.asarray(rng.normal(size=shape), jnp.float32)  # noqa: E731
    q, k = f(b, s, n_kv, n_g, d) * d ** -0.25, f(b, s, n_kv, d) * d ** -0.25
    log_g = jnp.log(jnp.asarray(rng.uniform(0.8, 0.999, (b, s, n_kv)), jnp.float32))
    return q, k, f(b, s, n_kv, d), log_g


def attention_form(q, k, v, log_g):
    """y (b, s, K, G, P) by the definition: no feature map, no state."""
    s = q.shape[1]
    cs = jnp.moveaxis(jnp.cumsum(log_g, axis=1), 1, 2)  # (b, K, s)
    dots = jnp.einsum("bikgd,bjkd->bkgij", q, k, precision="highest")
    a = jnp.where(jnp.tril(jnp.ones((s, s), bool)),
                  dots ** 2 * jnp.exp(cs[..., :, None] - cs[..., None, :])[:, :, None], 0.0)
    return jnp.einsum("bkgij,bjkp->bikgp", a, v, precision="highest") / (
        jnp.moveaxis(a.sum(-1), 3, 1) + EPS)[..., None]


@pytest.mark.parametrize("d, features", [(128, 9216), (16, 144), (12, 78), (64, 2304)])
def test_the_feature_map_reproduces_the_squared_product(d, features):
    """``phi(u) . phi(w) == (u . w)^2``; 9,216 features a 128-wide head (72
    lanes of 128, never the 16,384 of the full square), the distinct products
    exactly where the head is no multiple of 8."""
    rng = np.random.default_rng(d)
    u, w = (jnp.asarray(rng.normal(size=(5, d)), jnp.float32) for _ in range(2))
    assert ret.features(d) == features and ret.phi(u).shape == (5, features)
    assert features <= 9216 * (d / 128) ** 2 + 1 or d % 8
    got = (np.asarray(ret.phi(u), np.float64) * np.asarray(ret.phi(w), np.float64)).sum(-1)
    want = (np.asarray(u, np.float64) * np.asarray(w, np.float64)).sum(-1) ** 2
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-4)  # a dropped sqrt(2) is order 1


@pytest.mark.parametrize("chunk", [7, 16, 32, 64])
def test_the_chunked_form_equals_the_attention_form(chunk):
    """Query blocks that divide the 50 tokens and blocks that do not, one
    block for all of them (the state's part is the next test's)."""
    q, k, v, log_g = draws(1)
    y, _ = ret.ret_scan(q, k, v, log_g, chunk=chunk, eps=EPS)
    assert rel(y, attention_form(q, k, v, log_g)) < 2e-6


def test_a_sequence_in_pieces_equals_the_whole_and_padding_leaves_the_state():
    """Two calls that hand the state on equal one; a masked tail (k = 0,
    log g = 0) moves neither the state nor any real position."""
    q, k, v, log_g = draws(2)
    whole, state = ret.ret_scan(q, k, v, log_g, chunk=16, eps=EPS)
    first, mid = ret.ret_scan(q[:, :23], k[:, :23], v[:, :23], log_g[:, :23], chunk=16, eps=EPS)
    second, end = ret.ret_scan(q[:, 23:], k[:, 23:], v[:, 23:], log_g[:, 23:], chunk=16,
                               eps=EPS, state=mid)
    assert rel(jnp.concatenate([first, second], axis=1), whole) < 2e-6
    assert all(rel(a, b) < 2e-6 for a, b in zip(end, state))
    pad = lambda t, fill=0.0: jnp.pad(t, [(0, 0), (0, 14)] + [(0, 0)] * (t.ndim - 2),  # noqa: E731
                                      constant_values=fill)
    padded, kept = ret.ret_scan(pad(q, 1.0), pad(k), pad(v, 1.0), pad(log_g), chunk=16, eps=EPS)
    assert rel(padded[:, :50], whole) < 2e-6 and all(rel(a, b) < 2e-6 for a, b in zip(kept, state))


def test_the_recurrent_form_equals_the_attention_form_token_by_token():
    q, k, v, log_g = draws(3)
    big = jnp.zeros((2, 2, 16, ret.features(16)))
    z = jnp.zeros((2, 2, ret.features(16)))
    ys = []
    for t in range(q.shape[1]):
        y, big, z = ret.ret_step(big, z, ret.phi(q[:, t]), ret.phi(k[:, t]), v[:, t],
                                 jnp.exp(log_g[:, t]), eps=EPS)
        ys.append(y)
    _, (big_scan, z_scan) = ret.ret_scan(q, k, v, log_g, chunk=16, eps=EPS)
    assert rel(jnp.stack(ys, axis=1), attention_form(q, k, v, log_g)) < 2e-6
    assert rel(big, big_scan) < 2e-6 and rel(z, z_scan) < 2e-6


def _held(layers, tick, rows, n_kv=2, d=16):
    lead = (layers, tick, rows, n_kv)
    return {"hk": jnp.zeros((*lead, d)), "hv": jnp.zeros((*lead, d)), "hl": jnp.zeros(lead)}


# (steps a tick, this step's index in it): a tick of one step (no held
# tokens: the kernel of every step before ISSUE 57), a step that only reads,
# and a tick's last step folding 2, 3 and 4 held tokens
@pytest.mark.parametrize("tick, t", [(1, 0), (4, 1), (2, 1), (3, 2), (4, 3)],
                         ids=["tick-1", "read-only", "fold-2", "fold-3", "fold-4"])
@pytest.mark.parametrize("alive", [(1, 0, 1, 1), (0, 0, 0, 0), (1, 1, 1, 1), (0, 0, 1, 0)])
def test_the_interpreted_step_kernel_equals_the_plain_step(alive, tick, t):
    """Both kernels on one layer's entry of the stacked state, ``t`` tokens
    of the tick already held beside it: live rows as the plain recurrence
    token by token, a dead row's state bit for bit and its output zero, the
    other layers untouched; the read-only kernel returns the WHOLE stack bit
    for bit, the folding one writes the live rows' state after all ``t + 1``
    tokens."""
    rng = np.random.default_rng(4)
    f = lambda *shape: jnp.asarray(rng.normal(size=shape), jnp.float32)  # noqa: E731
    n_f = ret.features(16)
    stack, z0 = f(3, 4, 2, 16, n_f), f(4, 2, n_f)
    q = f(4, 2, 2, 16)
    k, v = f(t + 1, 4, 2, 16), f(t + 1, 4, 2, 16)
    g = jnp.asarray(rng.uniform(0.5, 1.0, (t + 1, 4, 2)), jnp.float32)
    live = jnp.asarray(alive, bool)
    # the plain recurrence over the tick's tokens, from the tick's start
    s_want, z_want = stack[1], z0
    for j in range(t + 1):
        z_in = z_want
        y_want, s_want, z_want = ret.ret_step(s_want, z_want, ret.phi(q), ret.phi(k[j]), v[j],
                                              g[j], eps=EPS)
    zstack = f(3, 4, 2, n_f).at[1].set(z_in)  # the sum of keys is rewritten every step
    held = None
    if tick > 1:
        held = _held(3, tick, 4)
        held = {"hk": held["hk"].at[1, :t].set(k[:t]), "hv": held["hv"].at[1, :t].set(v[:t]),
                "hl": held["hl"].at[1, :t].set(jnp.log(g[:t]))}
    y, new, znew, kept = jax.jit(lambda st, zs, at: ret.ret_step_rows(
        st, zs, jnp.int32(1), q, k[t], v[t], jnp.log(g[t]), live, eps=EPS, held=held, t=at,
        interpret=True))(stack, zstack, jnp.int32(t))
    if t < tick - 1:
        assert bool(jnp.all(new == stack))
        s_want = stack[1]
    if live.any():
        # (random states of either sign: a denominator near 0 magnifies a reordered sum)
        assert rel(y[live], y_want[live]) < 1e-4 and rel(new[1][live], s_want[live]) < 1e-6
        assert rel(znew[1][live], z_want[live]) < 1e-6
    assert bool(jnp.all(y[~live] == 0.0))
    assert bool(jnp.all(new[1][~live] == stack[1][~live]))
    assert bool(jnp.all(znew[1][~live] == zstack[1][~live]))
    assert bool(jnp.all(new[0] == stack[0])) and bool(jnp.all(new[2] == stack[2]))
    if tick > 1:  # this step's token held where the next step finds it
        assert bool(jnp.all(kept["hv"][1, t] == v[t])) and bool(jnp.all(kept["hv"][0] == 0))
        assert bool(jnp.all(kept["hk"][1, t][live] == k[t][live]))
        assert bool(jnp.all(kept["hk"][1, t][~live] == 0))


def test_the_step_kernels_trace_small(monkeypatch):
    """What a process pays to trace the decode program it pays at EVERY
    start, whatever the compile cache holds (ISSUE 57's first chip runs:
    `setup_s` +14 s with the cache warm). Two costs are held down here. An
    integer index into a kernel's ref is made an ARRAY where it is written
    (``jax._src.state.indexing``: ``jnp.asarray`` on the default device, a
    host-to-device transfer on the chip): inside loops written out over 72
    lane tiles that was ~2,700 transfers a trace. And the loops themselves:
    3,300 equations of kernel where a ``fori_loop`` of 8 tiles a turn has a
    ninth of them."""
    from jax._src.state import primitives as state_primitives

    made = []
    broadcast_to = state_primitives.broadcast_to
    monkeypatch.setattr(state_primitives, "broadcast_to",
                        lambda a, shape: (made.append(a), broadcast_to(a, shape))[1])
    rng = np.random.default_rng(9)
    f = lambda *shape: jnp.asarray(rng.normal(size=shape), jnp.float32)  # noqa: E731
    n_f, tick = ret.features(128), 4
    stack, zstack = f(1, 2, 1, 128, n_f), f(1, 2, 1, n_f)
    q, k, v, log_g = f(2, 1, 5, 128), f(2, 1, 128), f(2, 1, 128), -jnp.abs(f(2, 1))
    # both branches of the conditional are traced: the read-only kernel and the folding one
    jaxpr = jax.make_jaxpr(lambda st, zs: ret.ret_step_rows(
        st, zs, jnp.int32(0), q, k, v, log_g, jnp.ones((2,), bool), eps=EPS,
        held=_held(1, tick, 2, 1, 128), t=jnp.int32(1), interpret=False))(stack, zstack)
    # the integers left are the folding kernel's ``vb_ref[j]`` and the scalars' ``count[0]``
    assert len([a for a in made if isinstance(a, int)]) < 20
    from tests.tpu_compile import _eqns

    assert len(list(_eqns(jaxpr.jaxpr))) < 1200  # 3,900 with the loops written out


# which of three rows are live at each of 64 steps
ALIVE = {"all-live": lambda step: (True, True, True),
         "dead-from-the-start": lambda step: (True, False, True),
         "ends-at-step-1": lambda step: (True, step < 1, True)}


@pytest.mark.parametrize("pattern", list(ALIVE))
@pytest.mark.parametrize("tick", [1, 2, 4])
def test_the_held_token_form_equals_the_recurrent_form_step_by_step(tick, pattern):
    """64 steps in ticks of ``tick`` from a non-zero state: every step's ``y``
    and the state after every tick against ``ret_step`` token by token. A row
    that ended inside a tick is not folded (its state is never read again)
    and stays, bit for bit, where its last whole tick left it."""
    rng = np.random.default_rng(7)
    f = lambda *shape: jnp.asarray(rng.normal(size=shape), jnp.float32)  # noqa: E731
    n_f, n_b, steps = ret.features(16), 3, 64
    # a state of outer products with positive weights, as a sequence leaves one
    k0 = f(5, n_b, 2, 16) * 16 ** -0.25
    stack = jnp.zeros((2, n_b, 2, 16, n_f)).at[1].set(
        jnp.einsum("tbkp,tbkd->bkpd", f(5, n_b, 2, 16), ret.phi(k0)))
    zstack = jnp.zeros((2, n_b, 2, n_f)).at[1].set(ret.phi(k0).sum(0))
    q, k = f(steps, n_b, 2, 2, 16) * 16 ** -0.25, f(steps, n_b, 2, 16) * 16 ** -0.25
    v = f(steps, n_b, 2, 16)
    log_g = jnp.log(jnp.asarray(rng.uniform(0.8, 0.999, (steps, n_b, 2)), jnp.float32))

    @jax.jit
    def step(stack, zstack, held, t, q, k, v, log_g, live):
        return ret.ret_step_rows(stack, zstack, jnp.int32(1), q, k, v, log_g, live, eps=EPS,
                                 held=held, t=t)

    @jax.jit
    def plain(big, z, q, k, v, log_g, live):
        on = live[:, None]
        return ret.ret_step(big, z, ret.phi(q), jnp.where(on[..., None], ret.phi(k), 0.0), v,
                            jnp.where(on, jnp.exp(log_g), 1.0), eps=EPS)

    big, z = stack[1], zstack[1]
    for first in range(0, steps, tick):
        held, before = _held(2, tick, n_b) if tick > 1 else None, stack
        for t in range(tick):
            at = first + t
            live = jnp.asarray(ALIVE[pattern](at))
            y, stack, zstack, held = step(stack, zstack, held, jnp.int32(t), q[at], k[at], v[at],
                                          log_g[at], live)
            y_want, big, z = plain(big, z, q[at], k[at], v[at], log_g[at], live)
            assert rel(y[live], y_want[live]) < 1e-5, (at, pattern)
            assert bool(jnp.all(y[~live] == 0.0))
        assert rel(stack[1][live], big[live]) < 1e-5 and rel(zstack[1][live], z[live]) < 1e-5
        assert bool(jnp.all(stack[1][~live] == before[1][~live]))
        assert bool(jnp.all(stack[0] == 0.0))


@pytest.mark.parametrize("tokens, chunk", [(70, 16), (40, 64), (33, 8)])
def test_forward_matches_the_reference(tokens, chunk):
    """The system's uncached pass (``ret_scan`` inside the layer scan)
    against the reference's attention form, float32 on both sides."""
    cfg = family.tiny(PRESET, TINY, ret_chunk=chunk)
    params = family.seeded(ref, cfg)
    ids = jnp.asarray(np.random.default_rng(0).integers(3, 512, (2, tokens)), jnp.int32)
    with jax.default_matmul_precision("highest"):
        got = jax.jit(lambda p: llama.forward(p, ids, cfg))(params)
    assert rel(got, ref.forward(params, ids, ref.sizes(cfg, {}))) < 1e-5


def test_a_power_taken_in_bfloat16_is_refused_by_the_reference(monkeypatch):
    """The feature map rounded to bfloat16 (a product of two rounded products
    is no feature map): what a second call reads out of the state the first
    one left no longer equals the attention form over both."""
    q, k, v, log_g = draws(5)
    want = attention_form(q, k, v, log_g)[:, 23:]

    def in_two_calls():
        _, mid = ret.ret_scan(q[:, :23], k[:, :23], v[:, :23], log_g[:, :23], chunk=16, eps=EPS)
        return ret.ret_scan(q[:, 23:], k[:, 23:], v[:, 23:], log_g[:, 23:], chunk=16, eps=EPS,
                            state=mid)[0]

    exact = in_two_calls()
    phi = ret.phi
    monkeypatch.setattr(ret, "phi", lambda u: phi(u).astype(jnp.bfloat16).astype(jnp.float32))
    assert rel(exact, want) < 2e-6 and rel(in_two_calls(), want) > 100 * 2e-6


def test_the_preset_is_the_published_model():
    cfg = get_preset("brumby-14b")
    assert cfg.layer_period == "r" and cfg.retention_layer and not cfg.window_layer
    # shapes alone: this size is never drawn
    shapes = jax.eval_shape(lambda: llama.init_params(jax.random.key(0), cfg))
    layer = 26_214_400 * 2 + 5_242_880 * 2 + 267_386_880 + 40_968 + 10_496
    assert layer == 330_352_904
    assert llama.num_params(shapes) == 40 * layer + 2 * 777_912_320 + 5_120
    cut = dataclasses.replace(cfg, num_layers=8, layer_types="r" * 8)
    assert llama.num_params(jax.eval_shape(
        lambda: llama.init_params(jax.random.key(0), cut))) == 4_198_652_992
    # a state a slot: 8 kv heads x 9,216 features x (128 + 1) float32 a layer
    assert retention.state_bytes_per_slot(cut) == 8 * 8 * 9216 * 129 * 4
    state = jax.eval_shape(lambda: retention.init_state(cut, 16))
    assert state["ret"].shape == (8, 16, 8, 128, 9216) and state["ret"].dtype == jnp.float32
    axes = llama.param_logical_axes(cfg)
    assert jax.tree.structure(shapes) == jax.tree.structure(
        axes, is_leaf=lambda x: isinstance(x, tuple))


def test_seeded_gates_remember_for_tens_to_thousands_of_tokens():
    cfg = CFG
    m = family.seeded(None, cfg, 3)["layers"]["sub0"]["ret"]
    centre = jax.nn.sigmoid(m["bg"].astype(jnp.float32))
    lo, hi = retention.GATE_RANGE
    assert bool(jnp.all((centre >= lo - 1e-6) & (centre <= hi + 1e-6)))
    assert float(jnp.std(m["wg"].astype(jnp.float32))) < 0.3 / cfg.hidden_size ** 0.5


@pytest.mark.parametrize("kw, said", [
    (dict(layer_types="rra"), "mixes retention"),
    (dict(layer_types="rrm", ssm_heads=4, ssm_head_dim=16, ssm_state=8), "mixes retention"),
    (dict(layer_types="rr"), "num_layers"),
    (dict(ret_degree=4), "ret_degree 2"),
    (dict(ret_chunk=0), "ret_chunk"),
    (dict(num_experts=8, fused_gate_up=False), "experts"),
    (dict(kv_lora_rank=32), "latent attention"),
    (dict(lora_rank=4), "LoRA"),
    (dict(sliding_window=64), "sliding_window"),
    (dict(attention_bias=True), "attention_bias"),
    (dict(position_embedding="nope"), "position_embedding"),
    (dict(fused_gate_up=False), "fused_gate_up"),
])
def test_a_setting_the_stack_cannot_run_is_refused(kw, said):
    with pytest.raises(ValueError, match=said):
        family.tiny(PRESET, TINY, **kw)


def test_the_trainer_refuses_a_retention_stack_by_name():
    from ditl_tpu.train.step import loss_fn

    cfg = CFG
    ids = jnp.zeros((1, 8), jnp.int32)
    batch = {"input_ids": ids, "loss_mask": jnp.ones_like(ids), "segment_ids": jnp.ones_like(ids)}
    with pytest.raises(ValueError, match="served, not trained"):
        loss_fn({}, batch, cfg)
    with pytest.raises(ValueError, match="packed documents"):
        llama.forward(family.seeded(ref, cfg), ids, cfg, segment_ids=jnp.ones_like(ids))


def test_the_converter_round_trips_the_retention_tree():
    from ditl_tpu.models.convert import (config_from_hf, params_from_state_dict,
                                         state_dict_from_params)

    cfg = CFG
    params = family.seeded(ref, cfg)
    hf = state_dict_from_params(params, cfg)
    assert hf["model.layers.2.self_attn.gate_proj.weight"].shape == (2, cfg.hidden_size)
    assert hf["model.layers.0.self_attn.q_norm.weight"].shape == (16,)
    assert hf["model.layers.1.mlp.up_proj.weight"].shape == (128, cfg.hidden_size)
    assert "lm_head.weight" in hf
    back = params_from_state_dict(hf, cfg)
    for (path, a), b in zip(jax.tree.leaves_with_path(params), jax.tree.leaves(back)):
        assert a.shape == b.shape and bool(jnp.all(a == b)), jax.tree_util.keystr(path)
    import types

    hf_cfg = types.SimpleNamespace(
        model_type="brumby", num_attention_heads=40, head_dim=128, vocab_size=151936,
        hidden_size=5120, intermediate_size=17408, num_hidden_layers=40,
        num_key_value_heads=8, max_position_embeddings=32768, rope_theta=1000000,
        rms_norm_eps=1e-6, tie_word_embeddings=False, attention_bias=False, rope_scaling=None)
    assert dataclasses.replace(config_from_hf(hf_cfg), name="brumby-14b") == get_preset(
        "brumby-14b")
