"""Brumby's layer: the Qwen3 dense block with POWER RETENTION (degree 2,
gated; arXiv:2507.04239, ops/retention.py) where softmax attention stood. A
stack of them is a hybrid stack whose every position is an ``r``
(``cfg.layer_types``): ``ssm.hybrid_period`` carries it, one scan step a
layer, and this module is that position's mixer, parameters and state.

With ``n`` an RMSNorm with its own scale, ``d`` the head width, ``H`` query
heads in ``K`` groups, one key/value head a group::

    x = x + Ret(n_in(x)) W_o;   x = x + W_down(silu(W_gate n_post(x)) * W_up n_post(x))

    q = rope(n_q(h W_q)) a head;  k = rope(n_k(h W_k)) a head;  v = h W_v
    g_t = sigmoid(h_t W_g + b_g)      (one a kv head, float32);  G_t = sum_{s<=t} log g_s

    attention form (benchmarks/reference/brumby.py computes this and nothing else):
        a_ts = exp(G_t - G_s) * ((q_t . k_s) / sqrt(d))^2        for s <= t, else 0
        y_t  = sum_s a_ts v_s / (sum_s a_ts + eps)

    recurrent form (what a slot keeps; phi(u) . phi(w) = (u . w)^2):
        S_t = g_t S_(t-1) + v_t (outer) phi(k_t)        (d x D, float32, a kv head)
        z_t = g_t z_(t-1) + phi(k_t)                    (D, float32)
        y_t = S_t phi(q_t) / (phi(q_t) . z_t + eps)     (each of the group's query heads)

    a decode step (``ops/retention.py`` ``ret_step_rows``): the recurrent form
        through the state as the TICK's start left it, the attention form over
        the tick's own tokens, held beside the state; the tick's last step
        folds them into ``S`` (read once a step, written once a tick; ``z``
        is rewritten every step)

    a prefill call (``ops/retention.py`` ``ret_scan``): the attention form
        against the call's own keys, ``ret_chunk`` queries at a time; what the
        sequence carried in, through phi(q_t) and the state decayed from the
        call's start; the call's keys into the state through phi(k)

A sequence's cache entry is STATE and nothing else: ``rec = {"ret": (layers,
B, K, d, D), "retz": (layers, B, K, D)}``, float32, in the layout every form
of ``ops/retention.py`` computes in (the head's values on the sublanes, the
features on the lanes), riding the layer scan's carry and addressed in place
by the layer's index. No keys, no values, no page. Inside a decode tick, and
never in the donated tree, ``rec`` also carries the tick's held tokens
(``held_tokens``: ``hk``, ``hv`` (layers, steps, B, K, d) and ``hl`` (layers,
steps, B, K), float32), zeros at the tick's start and dropped at its end.

``q`` and ``k`` leave their bfloat16 matmuls as float32 sums and stay float32
through norm, rotation and the power: squared in bfloat16 the weights ``a_ts``
carry 2^-7 each and no two of the three forms agree.

Scopes (``ops/names.py`` ``RET_SCOPES``), each INSIDE the scope of ``SCOPES``
it refines: ``ret_in`` (projections, norms, rotation, gate) inside
``attn_qkv``; ``ret_state`` (a prefill call's scan, or a decode step's ``phi``,
``z``, the held tokens' sums, the kernel over the live rows' states,
``ret_step_read`` or at a tick's last step ``ret_step``, and the division)
inside ``attn_core``; ``ret_out`` (``W_o``) inside ``attn_out``.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from ditl_tpu.config import ModelConfig
from ditl_tpu.ops import retention as ret

__all__ = ["init_retention", "retention_axes", "retention_mixer", "init_state", "held_tokens",
           "state_bytes_per_slot", "SLOT_AXIS", "GATE_RANGE"]

F32 = jnp.float32

# Which axis of each leaf of ``init_state`` counts the sequences (slots).
SLOT_AXIS = {"ret": 1, "retz": 1}

# Where seeded weights put a gate's centre (``b_g``): half-lives of ~7 to
# ~1,400 tokens. With ``b_g = 0`` a gate is ~0.5, a state is dead three
# tokens on, and no check could see a wrong term between chunks.
GATE_RANGE = (0.9, 0.9995)


def init_state(cfg: ModelConfig, rows: int) -> dict[str, jax.Array]:
    """The state of ``rows`` sequences, every layer's: ``ret`` (layers, rows,
    K, d, D) and ``retz`` (layers, rows, K, D), float32."""
    n_f = ret.features(cfg.head_dim)
    lead = (cfg.num_layers, rows, cfg.num_kv_heads)
    return {"ret": jnp.zeros((*lead, cfg.head_dim, n_f), F32),
            "retz": jnp.zeros((*lead, n_f), F32)}


def state_bytes_per_slot(cfg: ModelConfig) -> int:
    one = jax.eval_shape(lambda: init_state(cfg, 1))
    return sum(math.prod(a.shape) * a.dtype.itemsize for a in jax.tree.leaves(one))


def init_retention(dense, uniform, cfg: ModelConfig, lead: int) -> dict:
    """A retention position's mixer: ``dense(shape, fan_in)`` and
    ``uniform(shape, lo, hi)`` draw leaves with the stack's leading axis.
    ``W_g`` at a quarter of ``1 / sqrt(fan_in)`` and ``b_g`` the logit of a
    centre whose distance from 1 is log-uniform over ``GATE_RANGE``."""
    pd = jnp.dtype(cfg.param_dtype)
    d, hd, nh, nkv = cfg.hidden_size, cfg.head_dim, cfg.num_heads, cfg.num_kv_heads
    lo, hi = (math.log(1.0 - g) for g in GATE_RANGE[::-1])
    off = jnp.exp(uniform((nkv,), lo, hi))  # 1 - g
    return {
        "wq": dense((d, nh * hd), d), "wk": dense((d, nkv * hd), d),
        "wv": dense((d, nkv * hd), d), "wo": dense((nh * hd, d), nh * hd),
        "q_norm": jnp.ones((lead, hd), pd), "k_norm": jnp.ones((lead, hd), pd),
        "wg": (dense((d, nkv), d).astype(F32) * 0.25).astype(pd),
        "bg": (jnp.log1p(-off) - jnp.log(off)).astype(pd),
    }


def retention_axes() -> dict:
    return {
        "wq": ("layers", "embed", "heads"), "wk": ("layers", "embed", "kv_heads"),
        "wv": ("layers", "embed", "kv_heads"), "wo": ("layers", "heads", "embed"),
        "q_norm": ("layers", "norm"), "k_norm": ("layers", "norm"),
        "wg": ("layers", "embed", None), "bg": ("layers", None),
    }


def held_tokens(cfg: ModelConfig, rows: int, steps: int) -> dict[str, jax.Array]:
    """Room for a decode tick's tokens beside the state of ``rows`` sequences,
    every layer's (``ops/retention.py`` ``HELD``): ``hk`` and ``hv`` (layers,
    steps, rows, K, d) and ``hl`` (layers, steps, rows, K), float32 zeros (a
    step still to come weighs nothing)."""
    lead = (cfg.num_layers, steps, rows, cfg.num_kv_heads)
    return {"hk": jnp.zeros((*lead, cfg.head_dim), F32),
            "hv": jnp.zeros((*lead, cfg.head_dim), F32), "hl": jnp.zeros(lead, F32)}


def retention_mixer(m, h, *, cfg: ModelConfig, positions, rec, at, valid, t=None):
    """The mixer on the normed input ``h`` (B, S, D): ``(out (B, S, D) before
    the residual, rec)``. ``rec``: every layer's state (module docstring), of
    which entry ``at`` is this layer's: what the sequences carried in,
    updated in place; or None (a sequence's start, nothing kept). ``valid``
    (B, S) bool or None: positions that are real tokens; the others leave the
    state as the last real token left it. S == 1 with ``rec`` is one cached
    step; where ``rec`` also carries a tick's held tokens (``held_tokens``),
    step ``t`` of that tick."""
    from ditl_tpu.models.llama import apply_rope, rms_norm
    from ditl_tpu.ops.quant import weight_einsum

    b, s, _ = h.shape
    cd = jnp.dtype(cfg.dtype)
    nh, nkv, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    step = s == 1 and rec is not None
    with jax.named_scope("attn_qkv"), jax.named_scope("ret_in"):
        def heads(name, n, norm):  # float32 from the matmul's sums on
            t = weight_einsum("bsd,df->bsf", h, m[name], compute_dtype=cd, preferred=F32)
            t = rms_norm(t.reshape(b, s, n, hd), m[norm], cfg.rms_norm_eps)
            return apply_rope(t, positions, cfg=cfg) * hd ** -0.25

        q = heads("wq", nh, "q_norm").reshape(b, s, nkv, nh // nkv, hd)
        k = heads("wk", nkv, "k_norm")
        v = weight_einsum("bsd,df->bsf", h, m["wv"], compute_dtype=cd).reshape(b, s, nkv, hd)
        log_g = jax.nn.log_sigmoid(
            weight_einsum("bsd,dk->bsk", h, m["wg"], compute_dtype=cd, preferred=F32)
            + m["bg"].astype(F32))
        if valid is not None:  # padding and dead rows: no decay, no weight
            k = jnp.where(valid[..., None, None], k, 0.0)
            log_g = jnp.where(valid[..., None], log_g, 0.0)
    held = {}  # a decode tick's held tokens, where ``rec`` carries them
    with jax.named_scope("attn_core"), jax.named_scope("ret_state"):
        if step:  # the stack in place, live rows only (ops/retention.py)
            alive = jnp.ones((b,), bool) if valid is None else valid[:, 0]
            held = {name: rec[name] for name in ret.HELD if name in rec}
            y, big, z, held = ret.ret_step_rows(
                rec["ret"], rec["retz"], at, q[:, 0], k[:, 0], v[:, 0], log_g[:, 0], alive,
                eps=cfg.ret_eps, held=held, t=t)
            y = y[:, None]
        else:
            state = None if rec is None else tuple(
                jax.lax.dynamic_index_in_dim(rec[name], at, keepdims=False)
                for name in ("ret", "retz"))
            y, state = ret.ret_scan(q, k, v, log_g, chunk=cfg.ret_chunk, eps=cfg.ret_eps,
                                    state=state)
            if rec is not None:
                big, z = (jax.lax.dynamic_update_index_in_dim(rec[name], new, at, 0)
                          for name, new in zip(("ret", "retz"), state))
        if rec is not None:
            rec = {"ret": big, "retz": z, **held}
    with jax.named_scope("attn_out"), jax.named_scope("ret_out"):
        out = weight_einsum("bsf,fd->bsd", y.reshape(b, s, nh * hd).astype(cd), m["wo"],
                            compute_dtype=cd)
    return out, rec
