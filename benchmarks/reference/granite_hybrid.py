"""Plain reference of the Granite-4.0-H decoder (ibm-granite/granite-4.0-h-micro,
``model_type`` ``granitemoehybrid``; the Mamba-2 mixer of arXiv:2405.21060 and
``modeling_granitemoehybrid.py`` in Hugging Face transformers, as recalled:
this sandbox has no network), independent of the code under test.

Straightforward ``jax.numpy`` in float32 at ``highest`` matmul precision: a
Python loop over the layers in the published order, the state recurrence as a
``lax.scan`` over TOKENS. No chunks, no cache, no kernel, no scan over layers;
it shares no code with ``ditl_tpu/models/ssm.py`` or ``ditl_tpu/ops/ssd.py``.

What it computes (``n`` an RMSNorm with its own scale, eps from the config)::

    x = embedding_multiplier * E[ids]
    layer i:  x = x + residual_multiplier * Mixer_i(n_in(x))
              x = x + residual_multiplier * MLP(n_post(x))
    logits = (n_f(x) @ E^T) / logits_scaling              (tied head)

    MLP(h) = (silu(g) * u) @ W_out,   [g | u] = h @ W_in          (no bias)

    attention mixer (``layer_types[i] == "attention"``): q, k, v without
        bias, grouped queries (4 query heads a kv head in the published
        model), NO rotation and no other positional term
        (``position_embedding_type: nope``), scores (q . k) *
        attention_multiplier (1 / 64 at 64-wide heads, not 1 / sqrt(64)),
        causal softmax inside the token's own packed document, o projection

    Mamba-2 mixer (``"mamba"``): H heads of P, ONE group, N state columns,
        a causal depthwise convolution of K taps with bias
        [z (H P) | xBC (H P + 2 N) | dt (H)] = h @ W_in
        xBC = silu(conv(xBC) + b_conv);  xBC -> x (H, P), B (N), C (N)
        delta = softplus(dt + dt_bias);  A = -exp(A_log)           (a head)
        S_t = exp(delta_t A) S_(t-1) + delta_t x_t (outer) B_t;  S_0 = 0 at
            the first token of a sequence and of every packed document,
            where the convolution's window is empty too
        y_t = S_t @ C_t + D x_t
        Mixer(h) = RMSNorm_w(y * silu(z)) @ W_out   (over all H P, one group)

Departures from the published model, and what the catalog has no key for
(the configuration file's ``assumed`` says the same):
- the order "gate, then norm" and the ``[z | xBC | dt]`` split order are
  recalled from the public Mamba-2 / granitemoehybrid modelling code;
- no clamp on ``delta`` (``time_step_limit`` is (0, inf) by default there);
- ``num_local_experts`` is 0 in this size: the "MoE" of the model type is
  absent, the shared MLP of ``shared_intermediate_size`` is the only FFN;
- the program stores ``W_in`` as two matrices, ``w_in`` (the z and xBC
  columns) and ``w_dt`` (the dt columns); they are joined here;
- weights are seeded random values (the caller's): the mixer's scalars as
  the Mamba-2 reference initialises them (``A_log = log a``, a uniform in
  [1, 16]; ``dt_bias`` the inverse softplus of a log-uniform step in [1e-3,
  1e-1]; ``D = 1``; taps uniform within 1 / sqrt(K)): the program's
  initialiser draws them, ``perturb`` moves ``D`` and the gate norm's scale
  off 1 so that a dropped one cannot pass.

Parameters come as the pytree the program uses: ``layers`` holds one subtree
a position of the period (``sub0`` .. ``sub9``), each leaf ``(periods,
...)``; layer ``i`` is position ``i % period`` of period ``i // period``,
sliced out and upcast on its own, so the published widths in bfloat16 fit one
chip beside a float32 working copy of one layer (0.3 GB). Rows are computed
one at a time.

Hooks (``reference_check.compare`` and ``flops.py`` ask for them by name;
``reference/qwen2.py``'s docstring lists what each is for): ``forward``,
``loss``, ``check_sizes``, ``sizes``, ``perturb``,
``forward_flops_per_token``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

F32 = jnp.float32

# ModelConfig field -> key of the published config.json it must equal.
PUBLISHED = {
    "hidden_size": "hidden_size",
    "intermediate_size": "shared_intermediate_size",
    "num_layers": "num_hidden_layers",
    "num_heads": "num_attention_heads",
    "num_kv_heads": "num_key_value_heads",
    "vocab_size": "vocab_size",
    "rms_norm_eps": "rms_norm_eps",
    "tie_embeddings": "tie_word_embeddings",
    "max_seq_len": "max_position_embeddings",
    "attention_bias": "attention_bias",
    "num_experts": "num_local_experts",
    "ssm_heads": "mamba_n_heads",
    "ssm_head_dim": "mamba_d_head",
    "ssm_state": "mamba_d_state",
    "ssm_conv": "mamba_d_conv",
    "ssm_chunk": "mamba_chunk_size",
    "embedding_multiplier": "embedding_multiplier",
    "residual_multiplier": "residual_multiplier",
    "attention_multiplier": "attention_multiplier",
    "logits_scaling": "logits_scaling",
}


def check_sizes(cfg, config: dict) -> list[str]:
    """The program's ModelConfig against the published sizes in the
    configuration file: a width that differs is an error, not a note."""
    want = {field: config[key] for field, key in PUBLISHED.items()}
    want["head_dim"] = config["hidden_size"] // config["num_attention_heads"]
    want["layer_types"] = "".join(t[0] for t in config["layer_types"])
    want["position_embedding"] = config["position_embedding_type"]
    problems = [f"{k}: program {getattr(cfg, k)!r}, configuration file {v!r}"
                for k, v in want.items() if getattr(cfg, k) != v]
    if config["mamba_n_groups"] != 1 or config["mamba_expand"] * config["hidden_size"] != (
            config["mamba_n_heads"] * config["mamba_d_head"]):
        problems.append("the mixer has one group and an inner width of heads x head")
    return problems


def perturb(params, cfg, seed: int):
    """``D`` and the gate norm's scale moved away from 1: the initialiser
    sets them to 1, and a value of 1 would let a dropped one pass."""
    layers = dict(params["layers"])
    for j, (name, sub) in enumerate(sorted(layers.items())):
        if "ssm" not in sub:
            continue
        m = dict(sub["ssm"])
        for i, leaf in enumerate(("D", "norm")):
            k = jax.random.fold_in(jax.random.key(seed), 3000 + 10 * j + i)
            m[leaf] = (1.0 + 0.3 * jax.random.normal(k, m[leaf].shape, F32)
                       ).astype(m[leaf].dtype)
        layers[name] = {**sub, "ssm": m}
    return {**params, "layers": layers}


def sizes(cfg, config: dict) -> dict:
    """What ``forward`` and ``loss`` need besides the weights, as the program
    holds it (``check_sizes`` has held the program to the file)."""
    return {"layer_types": ["mamba" if t == "m" else "attention" for t in cfg.layer_types],
            "period": len(cfg.layer_period),
            "num_attention_heads": cfg.num_heads,
            "num_key_value_heads": cfg.num_kv_heads, "head_dim": cfg.head_dim,
            "rms_norm_eps": cfg.rms_norm_eps,
            "mamba_n_heads": cfg.ssm_heads, "mamba_d_head": cfg.ssm_head_dim,
            "mamba_d_state": cfg.ssm_state, "mamba_d_conv": cfg.ssm_conv,
            "embedding_multiplier": cfg.embedding_multiplier,
            "residual_multiplier": cfg.residual_multiplier,
            "attention_multiplier": cfg.attention_multiplier,
            "logits_scaling": cfg.logits_scaling}


def forward_flops_per_token(config: dict, context_mean: float) -> float:
    """Forward operations a token needs: a mixer's two projections and its
    state (decay, update and read-out of H P N values: two operations each),
    an attention layer's projections and its scores against ``context_mean``
    keys, every layer's FFN (three matmuls) and the head."""
    d, f = config["hidden_size"], config["shared_intermediate_size"]
    heads, kv = config["num_attention_heads"], config["num_key_value_heads"]
    hd = d // heads
    h, p, n = config["mamba_n_heads"], config["mamba_d_head"], config["mamba_d_state"]
    inner = h * p
    mamba = 2 * d * (2 * inner + 2 * n + h) + 2 * inner * d + 6 * inner * n
    attention = (2 * d * (heads + 2 * kv) * hd + 2 * heads * hd * d
                 + 4 * heads * hd * context_mean)
    n_attn = sum(t == "attention" for t in config["layer_types"])
    n_layers = config["num_hidden_layers"]
    return ((n_layers - n_attn) * mamba + n_attn * attention + n_layers * 6 * d * f
            + 2 * d * config["vocab_size"])


def _rms_norm(x, scale, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * scale


def _attention(a, h, *, allowed, sizes):
    s = h.shape[0]
    n_heads, n_kv, head_dim = (sizes["num_attention_heads"], sizes["num_key_value_heads"],
                               sizes["head_dim"])
    q = (h @ a["wq"]).reshape(s, n_heads, head_dim)
    k = jnp.repeat((h @ a["wk"]).reshape(s, n_kv, head_dim), n_heads // n_kv, axis=1)
    v = jnp.repeat((h @ a["wv"]).reshape(s, n_kv, head_dim), n_heads // n_kv, axis=1)
    scores = jnp.einsum("qhd,khd->hqk", q, k) * sizes["attention_multiplier"]
    probs = jax.nn.softmax(jnp.where(allowed[None], scores, -jnp.inf), axis=-1)
    return jnp.einsum("hqk,khd->qhd", probs, v).reshape(s, n_heads * head_dim) @ a["wo"]


def _mamba(m, h, *, first, sizes):
    """One row. h: (S, D); first: (S,) bool, a document's first token."""
    s = h.shape[0]
    nh, p, n, taps = (sizes["mamba_n_heads"], sizes["mamba_d_head"], sizes["mamba_d_state"],
                      sizes["mamba_d_conv"])
    inner = nh * p
    zxd = h @ jnp.concatenate([m["w_in"], m["w_dt"]], axis=1)  # W_in, as published
    z, u, dt = zxd[:, :inner], zxd[:, inner:inner + inner + 2 * n], zxd[:, -nh:]
    # tokens since the document's first: tap j reaches K - 1 - j tokens back
    idx = jnp.arange(s)
    since = idx - jax.lax.cummax(jnp.where(first, idx, 0))
    conv = jnp.zeros_like(u) + m["conv_b"]
    for j in range(taps):
        back = taps - 1 - j
        shifted = jnp.pad(u, [(back, 0), (0, 0)])[:s]
        conv = conv + jnp.where((since >= back)[:, None], shifted, 0.0) * m["conv_w"][j]
    xbc = jax.nn.silu(conv)
    x, bmat, cmat = xbc[:, :inner].reshape(s, nh, p), xbc[:, inner:inner + n], xbc[:, -n:]
    delta = jax.nn.softplus(dt + m["dt_bias"])  # (S, H)
    a = -jnp.exp(m["A_log"])

    def token(state, t):
        x_t, b_t, c_t, d_t, first_t = t
        state = jnp.where(first_t, 0.0, state)
        state = (jnp.exp(d_t * a)[:, None, None] * state
                 + (d_t[:, None] * x_t)[:, :, None] * b_t[None, None, :])
        return state, state @ c_t

    _, y = jax.lax.scan(token, jnp.zeros((nh, p, n), F32), (x, bmat, cmat, delta, first))
    y = y + m["D"][:, None] * x
    gated = y.reshape(s, inner) * jax.nn.silu(z)
    return _rms_norm(gated, m["norm"], sizes["rms_norm_eps"]) @ m["w_out"]


def forward(params, input_ids, sizes: dict, *, positions=None, segment_ids=None):
    """Token ids (B, S) -> float32 logits (B, S, V). ``positions`` are taken
    and not used: the model has no positional term."""
    del positions
    up = lambda t: jax.tree.map(lambda w: w.astype(F32), t)  # noqa: E731
    b, s = input_ids.shape
    eps, res = sizes["rms_norm_eps"], sizes["residual_multiplier"]
    idx = jnp.arange(s)
    causal = idx[:, None] >= idx[None, :]
    rows = []
    with jax.default_matmul_precision("highest"):
        table = params["embed"]["embedding"]
        for r in range(b):
            allowed, first = causal, idx == 0
            if segment_ids is not None:
                seg = segment_ids[r]
                allowed = allowed & (seg[:, None] == seg[None, :])
                first = first | (seg != jnp.roll(seg, 1))
            x = sizes["embedding_multiplier"] * table[input_ids[r]].astype(F32)
            for i, kind in enumerate(sizes["layer_types"]):
                sub = params["layers"][f"sub{i % sizes['period']}"]
                layer = up(jax.tree.map(lambda w: w[i // sizes["period"]], sub))  # noqa: B023
                h = _rms_norm(x, layer["attn_norm"]["scale"], eps)
                if kind == "mamba":
                    mixed = _mamba(layer["ssm"], h, first=first, sizes=sizes)
                else:
                    mixed = _attention(layer["attn"], h, allowed=allowed, sizes=sizes)
                x = x + res * mixed
                h = _rms_norm(x, layer["mlp_norm"]["scale"], eps)
                g, u = jnp.split(h @ layer["mlp"]["w_gu"], 2, axis=-1)
                x = x + res * ((jax.nn.silu(g) * u) @ layer["mlp"]["w_down"])
            x = _rms_norm(x, params["final_norm"]["scale"].astype(F32), eps)
            rows.append((x @ table.astype(F32).T) / sizes["logits_scaling"])
    return jnp.stack(rows)


def loss(outputs, input_ids, loss_mask, sizes: dict):
    """Mean next-token cross-entropy over the masked positions."""
    del sizes
    lg = (outputs["logits"] if isinstance(outputs, dict) else outputs)[:, :-1]
    mask = loss_mask[:, 1:].astype(F32)
    logz = jax.nn.logsumexp(lg, axis=-1)
    tgt = jnp.take_along_axis(lg, input_ids[:, 1:, None], axis=-1)[..., 0]
    return ((logz - tgt) * mask).sum() / jnp.maximum(mask.sum(), 1.0)
