"""Granite-4.0-H's stack: PERIODS of unlike layers, Mamba-2 state-space
mixers beside grouped-query attention, every layer followed by the dense
FFN. ``llama.forward``'s layer scans carry ``hybrid_period`` in place of
``_decoder_layer`` when ``cfg.layer_types`` is set, one scan step a period
(``cfg.layer_period``: ``mmmmmammmm`` in the published model, four of them).

With ``n`` an RMSNorm with its own scale, ``r`` the residual multiplier::

    x = x + r Mixer_i(n_in(x));   x = x + r MLP(n_post(x))

    attention mixer: ``_decoder_layer``'s, with no rotation and the scores
        times ``attention_multiplier`` (models/llama.py)
    Mamba-2 mixer (H heads of P, one group, N state columns, K taps):
        [z (H P) | xBC (H P + 2 N) | dt (H)] = h W_in
            (stored as two matrices, ``w_in`` for z and xBC and ``w_dt``: one
            of 8,512 columns is no whole number of 128 lanes, and the
            compiler then transposes all of it in front of every decode
            tick, 133 MB a mixer)
        xBC = silu(conv1d_causal_depthwise_K(xBC) + b_conv) -> x, B, C
        delta = softplus(dt + dt_bias);  A = -exp(A_log)        (a head)
        S_t = exp(delta_t A) S_(t-1) + delta_t x_t (outer) B_t  (H, P, N)
        y_t = S_t C_t + D x_t
        Mixer(h) = RMSNorm_w(y silu(z)) W_out      (gate first, then the norm)

A sequence's cache entry is not pages a token but STATE a sequence, of fixed
size: ``S`` (float32) and the last K - 1 pre-activation ``xBC`` columns
(the convolution's window), one of each a mixer.

The tree under ``layers`` is one subtree a POSITION of the period (``sub0``
.. ``sub9``), every leaf ``(n_periods, ...)``: a leaf that held a period's
nine mixers together would be sliced ``(1, 9, d_in, d_out)`` by the period
scan and materialised before the matmuls that read it (models/mla.py has the
same lesson for its two halves). An attention position's subtree is
``_decoder_layer``'s own (``attn_norm``, ``attn``, ``mlp_norm``, ``mlp``); a
mixer's holds ``ssm`` in place of ``attn``.

Cached forms (``hybrid_period``'s ``layer_cache``):

- keys and values belong to the attention layers alone: a prefill's row
  ``{"k", "v"}`` and a paged decode's tails ``{"tk", "tv"}`` arrive with a
  leading axis of attention layers a period; the pools (whole, outside the
  scan) hold ``n_periods x`` that many layers;
- ``rec`` (``{"ssm": (n_mixers, B, H / hp, N, hp P) float32, "conv":
  (n_mixers, K - 1, B, H P + 2 N)}``, ALL mixers of the stack; the state in
  ``ops/ssd.py``'s STORED layout, the state columns on the sublanes and ``hp``
  heads' ``P`` side by side on the lanes (two at P = 64), which the decode
  step's kernel reads and writes as it lies; the window's taps before the
  rows, so that the last two dimensions tile whole) rides the scan's carry and
  each mixer reads and writes its own entry by index, in place: as a scanned
  input and output the whole state would be copied every step. A prefill
  works on its one slot's rows and converts them at its boundary
  (``ssd.from_stored`` in front of ``ssd_scan``, ``ssd.to_stored`` behind it:
  2 MiB a mixer each way, once a chunk); whoever moves state between slots
  (``infer/page_format.py``) does so by ``SLOT_AXIS`` alone.

Scopes (``ops/names.py`` ``SSM_SCOPES``), each INSIDE the scope of
``SCOPES`` it refines: ``ssm_in`` (``W_in``, convolution, activation) inside
``attn_qkv``; ``ssm_scan`` (the chunked scan or the step's state update and
``S C``) inside ``attn_core``; ``ssm_out`` (gate, norm, ``W_out``) inside
``attn_out``.
"""

from __future__ import annotations

import math
from typing import Any

import jax
import jax.numpy as jnp

from ditl_tpu.config import ModelConfig
from ditl_tpu.ops import ssd

__all__ = ["init_hybrid_params", "hybrid_logical_axes", "hybrid_period", "period_counts",
           "conv_width", "init_state", "state_bytes_per_slot", "SLOT_AXIS", "state_axes",
           "tick_leaves"]

F32 = jnp.float32


def period_counts(cfg: ModelConfig) -> tuple[int, int, int]:
    """(periods in the stack, mixers a period, attention layers a period)."""
    period = cfg.layer_period
    return cfg.num_layers // len(period), period.count("m"), period.count("a")


def conv_width(cfg: ModelConfig) -> int:
    """Columns the convolution runs over: ``x`` and the group's ``B``, ``C``."""
    return cfg.ssm_heads * cfg.ssm_head_dim + 2 * cfg.ssm_state


def init_state(cfg: ModelConfig, rows: int) -> dict[str, jax.Array]:
    """The recurrent state of ``rows`` sequences, all mixers of the stack:
    ``ssm`` (n_mixers, rows, H / hp, N, hp P) float32, a row's state as the
    decode step's kernel keeps it (``ssd.stored_shape``: the same bytes as
    ``(H, P, N)``); ``conv`` (n_mixers, K - 1, rows, H P + 2 N)."""
    n_per, m, _ = period_counts(cfg)
    return {
        "ssm": jnp.zeros((n_per * m, rows, *ssd.stored_shape(
            cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_state)), F32),
        "conv": jnp.zeros((n_per * m, cfg.ssm_conv - 1, rows, conv_width(cfg)),
                          jnp.dtype(cfg.dtype)),
    }


# Which axis of each leaf of ``init_state`` counts the sequences (slots).
SLOT_AXIS = {"ssm": 1, "conv": 2}


def state_axes(cfg: ModelConfig) -> dict[str, int]:
    """The leaves of the stack's state a slot (``init_state``'s, or
    ``retention.init_state``'s) and the axis of each that counts the slots:
    what a cached forward pass carries beside the stream."""
    if cfg.retention_layer:
        from ditl_tpu.models.retention import SLOT_AXIS as axes

        return axes
    return SLOT_AXIS


def tick_leaves(cfg: ModelConfig) -> tuple[str, ...]:
    """The leaves a decode tick may carry beside ``state_axes``' and drops at
    its end: a retention layer's held tokens (``retention.held_tokens``)."""
    if cfg.retention_layer:
        from ditl_tpu.ops.retention import HELD

        return HELD
    return ()


def state_bytes_per_slot(cfg: ModelConfig) -> int:
    one = jax.eval_shape(lambda: init_state(cfg, 1))
    return sum(math.prod(a.shape) * a.dtype.itemsize for a in jax.tree.leaves(one))


def init_hybrid_params(rng: jax.Array, cfg: ModelConfig) -> dict[str, Any]:
    """The ``layers`` subtree (module docstring). Matrices at ``1 /
    sqrt(fan_in)``, drawn in ``param_dtype`` (``moe.lean_dense``). The
    mixer's own scalars as the Mamba-2 reference starts them, so that seeded
    random weights have a trained model's range of memory: ``A = -a``, ``a``
    uniform in [1, 16]; ``dt_bias`` the inverse softplus of a log-uniform
    step in [1e-3, 1e-1]; ``D = 1``; the convolution's taps and bias uniform
    within ``1 / sqrt(K)``. With every scalar at 0 a state either dies in a
    token or never forgets."""
    from ditl_tpu.models.moe import lean_dense

    pd = jnp.dtype(cfg.param_dtype)
    d, f, hd = cfg.hidden_size, cfg.intermediate_size, cfg.head_dim
    nh, nkv = cfg.num_heads, cfg.num_kv_heads
    h, p, k = cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_conv
    inner, cw = h * p, conv_width(cfg)
    n_per = period_counts(cfg)[0]
    keys = iter(jax.random.split(rng, 16 * len(cfg.layer_period)))

    def dense(shape, fan_in):
        return lean_dense(next(keys), (n_per,) + shape, fan_in, pd)

    def uniform(shape, lo, hi):
        return jax.random.uniform(next(keys), (n_per,) + shape, F32, lo, hi)

    def mixer():
        step = jnp.exp(uniform((h,), math.log(1e-3), math.log(1e-1)))
        return {
            "w_in": dense((d, inner + cw), d),
            "w_dt": dense((d, h), d),
            "conv_w": uniform((k, cw), -k ** -0.5, k ** -0.5).astype(pd),
            "conv_b": uniform((cw,), -k ** -0.5, k ** -0.5).astype(pd),
            "dt_bias": (step + jnp.log(-jnp.expm1(-step))).astype(pd),
            "A_log": jnp.log(uniform((h,), 1.0, 16.0)).astype(pd),
            "D": jnp.ones((n_per, h), pd),
            "norm": jnp.ones((n_per, inner), pd),
            "w_out": dense((inner, d), inner),
        }

    def attention():
        return {
            "wq": dense((d, nh * hd), d), "wk": dense((d, nkv * hd), d),
            "wv": dense((d, nkv * hd), d), "wo": dense((nh * hd, d), nh * hd),
        }

    def mixer_of(kind):
        if kind == "r":
            from ditl_tpu.models.retention import init_retention

            return {"ret": init_retention(dense, uniform, cfg, n_per)}
        return {"ssm": mixer()} if kind == "m" else {"attn": attention()}

    return {
        f"sub{j}": {
            "attn_norm": {"scale": jnp.ones((n_per, d), pd)},
            **mixer_of(kind),
            "mlp_norm": {"scale": jnp.ones((n_per, d), pd)},
            "mlp": {"w_gu": dense((d, 2 * f), d), "w_down": dense((f, d), f)},
        }
        for j, kind in enumerate(cfg.layer_period)
    }


def hybrid_logical_axes(cfg: ModelConfig) -> dict[str, Any]:
    mixer = {
        "w_in": ("layers", "embed", "mlp"), "w_dt": ("layers", "embed", None),
        "conv_w": ("layers", None, "mlp"),
        "conv_b": ("layers", "mlp"), "dt_bias": ("layers", None),
        "A_log": ("layers", None), "D": ("layers", None), "norm": ("layers", "norm"),
        "w_out": ("layers", "mlp", "embed"),
    }
    attention = {
        "wq": ("layers", "embed", "heads"), "wk": ("layers", "embed", "kv_heads"),
        "wv": ("layers", "embed", "kv_heads"), "wo": ("layers", "heads", "embed"),
    }
    from ditl_tpu.models.retention import retention_axes

    kinds = {"m": {"ssm": mixer}, "a": {"attn": attention}, "r": {"ret": retention_axes()}}
    return {
        f"sub{j}": {
            "attn_norm": {"scale": ("layers", "norm")},
            **{name: dict(axes) for name, axes in kinds[kind].items()},
            "mlp_norm": {"scale": ("layers", "norm")},
            "mlp": {"w_gu": ("layers", "embed", "mlp"), "w_down": ("layers", "mlp", "embed")},
        }
        for j, kind in enumerate(cfg.layer_period)
    }


def _mamba_mixer(m, h, *, cfg: ModelConfig, rec, at, valid, doc):
    """The mixer on the normed input ``h`` (B, S, D): ``(out (B, S, D) before
    the residual, rec)``. ``rec``: every mixer's state and window (module
    docstring), of which entry ``at`` is this mixer's: what the sequences
    carried in, updated in place; or None (a sequence's start, nothing kept).
    ``valid`` (B, S) bool or None: positions that are real tokens; the others
    leave state and window as the last real token left them. ``doc`` (B, S):
    the packed documents' count, without a cache. S == 1 with ``rec`` is one
    cached step."""
    from ditl_tpu.models.llama import rms_norm
    from ditl_tpu.ops.quant import weight_einsum

    b, s, _ = h.shape
    cd = jnp.dtype(cfg.dtype)
    nh, p, n = cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_state
    inner = nh * p
    step = s == 1 and rec is not None
    state = conv = None
    if rec is not None:
        conv = jax.lax.dynamic_index_in_dim(rec["conv"], at, keepdims=False)
    with jax.named_scope("attn_qkv"), jax.named_scope("ssm_in"):
        z, u = jnp.split(
            weight_einsum("bsd,df->bsf", h, m["w_in"], compute_dtype=cd, preferred=F32),
            (inner,), axis=-1)
        dt = weight_einsum("bsd,dh->bsh", h, m["w_dt"], compute_dtype=cd, preferred=F32)
        if step:
            xbc, new_conv = ssd.conv_step(conv, u[:, 0], m["conv_w"], m["conv_b"])
            xbc = xbc[:, None]
            if valid is not None:  # a dead row's window stays
                new_conv = jnp.where(valid[None, :, :], new_conv, conv)
        else:
            lengths = None if valid is None else valid.sum(axis=1).astype(jnp.int32)
            xbc, new_conv = ssd.causal_conv(
                u, m["conv_w"], m["conv_b"], doc=doc, lengths=lengths,
                conv=None if conv is None else jnp.swapaxes(conv, 0, 1))
            new_conv = jnp.swapaxes(new_conv, 0, 1)  # (K - 1, B, C), as stored
        xbc = jax.nn.silu(xbc)
        x, bmat, cmat = jnp.split(xbc, (inner, inner + n), axis=-1)
        delta = jax.nn.softplus(dt.astype(F32) + m["dt_bias"].astype(F32))
        if valid is not None:
            delta = delta * valid[..., None]
        a = -jnp.exp(m["A_log"].astype(F32))
    with jax.named_scope("attn_core"), jax.named_scope("ssm_scan"):
        if step:  # the stack in place, live rows only (ops/ssd.py)
            alive = jnp.ones((b,), bool) if valid is None else valid[:, 0]
            y, stack = ssd.ssd_step_rows(rec["ssm"], at, x[:, 0], delta[:, 0], a,
                                         bmat[:, 0], cmat[:, 0], alive)
            y = y[:, None]
        else:
            if rec is not None:  # this chunk's rows alone, out of the stored layout
                state = ssd.from_stored(
                    jax.lax.dynamic_index_in_dim(rec["ssm"], at, keepdims=False), nh)
            y, state = ssd.ssd_scan(x.reshape(b, s, nh, p), delta, a, bmat, cmat,
                                    chunk=cfg.ssm_chunk, state=state, doc=doc)
            y = y.reshape(b, s, inner)
            if rec is not None:  # and back into it
                stack = jax.lax.dynamic_update_index_in_dim(
                    rec["ssm"], ssd.to_stored(state), at, 0)
        if rec is not None:
            rec = {"ssm": stack, "conv": jax.lax.dynamic_update_index_in_dim(
                rec["conv"], new_conv.astype(rec["conv"].dtype), at, 0)}
        y = y + jnp.repeat(m["D"].astype(F32), p) * x.astype(F32)
    with jax.named_scope("attn_out"), jax.named_scope("ssm_out"):
        gated = y * jax.nn.silu(z.astype(F32))
        out = weight_einsum(
            "bsf,fd->bsd", rms_norm(gated, m["norm"], cfg.rms_norm_eps).astype(cd),
            m["w_out"], compute_dtype=cd)
    return out, rec


def hybrid_period(
    layer_params: dict[str, Any],
    x: jax.Array,
    *,
    cfg: ModelConfig,
    positions: jax.Array,
    segment_ids: jax.Array | None,
    mesh,
    rules,
    layer_cache: dict | None = None,
    cache_index: jax.Array | None = None,
    attn_mask: jax.Array | None = None,
    paged: dict | None = None,
    prefill_causal: bool = False,
    token_mask: jax.Array | None = None,
    with_moe_counts: bool = False,
    moe_stack: dict | None = None,
    layer_index: jax.Array | None = None,
    pools: dict | None = None,
    adapter_ids: jax.Array | None = None,
    rec: dict | None = None,
) -> tuple:
    """One period, with ``_decoder_layer``'s protocol: ``(x, aux)``, then
    with a cache the attention layers' ``new_kv`` and the carried ``rec``.
    ``layer_index``: which period this is. ``paged["table"]`` names this
    PERIOD's first attention layer's pages and ``paged["n_pages"]`` is the
    stride to the next's. Without a cache ``segment_ids`` are the packed
    documents (state and window start anew at each); with one they only mark
    the real tokens for attention, and ``token_mask`` does for the mixers."""
    from ditl_tpu.models.llama import _constrain, _decoder_layer, dense_mlp, rms_norm

    if adapter_ids is not None or with_moe_counts or moe_stack is not None:
        raise ValueError("a hybrid stack has no LoRA adapters and no experts")
    n_m = len(cfg.layer_period) - cfg.layer_period.count("a")  # mixers a period
    cd = jnp.dtype(cfg.dtype)
    cached = layer_cache is not None
    doc = None
    if not cached and segment_ids is not None:
        # the count of document starts so far: 0 for a row's first document
        starts = segment_ids[:, 1:] != segment_ids[:, :-1]
        doc = jnp.pad(jnp.cumsum(starts, axis=1, dtype=jnp.int32), [(0, 0), (1, 0)])
    res = cfg.residual_multiplier
    new_kv: list = []
    i_m = i_a = 0
    for j, kind in enumerate(cfg.layer_period):
        sub = layer_params[f"sub{j}"]
        if kind == "a":
            sub_cache, sub_paged = None, paged
            if cached:
                sub_cache = {k: v[i_a] for k, v in layer_cache.items()}
            if pools is not None:
                sub_paged = {**paged, "table": paged["table"] + i_a * paged["n_pages"]}
            x, _, *kv = _decoder_layer(
                sub, x, cfg=cfg, positions=positions, segment_ids=segment_ids,
                mesh=mesh, rules=rules, layer_cache=sub_cache, cache_index=cache_index,
                attn_mask=attn_mask, paged=sub_paged, prefill_causal=prefill_causal,
                pools=pools)
            new_kv += kv
            i_a += 1
            continue
        with jax.named_scope("attn_qkv"):
            h = rms_norm(x, sub["attn_norm"]["scale"], cfg.rms_norm_eps).astype(cd)
        at = None if rec is None else layer_index * n_m + i_m
        if kind == "r":
            from ditl_tpu.models.retention import retention_mixer

            if doc is not None:
                raise ValueError("a retention layer does not carry packed documents")
            out, rec = retention_mixer(sub["ret"], h, cfg=cfg, positions=positions, rec=rec,
                                       at=at, valid=token_mask if cached else None,
                                       t=(paged or {}).get("t"))
        else:
            out, rec = _mamba_mixer(sub["ssm"], h, cfg=cfg, rec=rec, at=at,
                                    valid=token_mask if cached else None, doc=doc)
        with jax.named_scope("attn_out"):
            x = _constrain(x + res * out, ("batch", "seq", "act_embed"), mesh, rules)
        with jax.named_scope("mlp"):
            h = rms_norm(x, sub["mlp_norm"]["scale"], cfg.rms_norm_eps).astype(cd)
            x = x + res * dense_mlp(sub["mlp"], h, cfg=cfg, mesh=mesh, rules=rules)
            x = _constrain(x, ("batch", "seq", "act_embed"), mesh, rules)
        i_m += 1
    out = (x, jnp.zeros((), F32))
    if cached:  # a period without an attention layer returns no keys and values
        out += ({k: jnp.stack([kv[k] for kv in new_kv]) for k in (new_kv or [{}])[0]}, rec)
    return out
