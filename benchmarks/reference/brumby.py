"""Plain reference of the Brumby decoder (manifestai/Brumby-14B-Base,
``model_type`` ``brumby``: the Qwen3-14B block with every attention layer a
gated POWER RETENTION layer of degree 2; "Scaling Context Requires Rethinking
Attention", arXiv:2507.04239, and Manifest AI's ``retention`` package, as
recalled: this sandbox has no network), independent of the code under test.

Straightforward ``jax.numpy`` in float32 at ``highest`` matmul precision: a
Python loop over the layers, the retention in its ATTENTION FORM and nothing
else, a block of queries at a time against every key in front of it. No
feature map, no state, no chunks, no cache, no kernel, no scan over layers;
it shares no code with ``ditl_tpu/models/retention.py`` or
``ditl_tpu/ops/retention.py``.

What it computes (``n`` an RMSNorm with its own scale, eps from the config;
``d`` the head width; H query heads in K groups, a key/value head a group)::

    x = E[ids]
    layer i:  x = x + Ret(n_in(x)) @ W_o
              x = x + (silu(g) * u) @ W_down,   [g | u] = n_post(x) @ W_gu
    logits = n_f(x) @ W_head                                  (untied head)

    Ret(h):  q = rope(n_q(h W_q)) a head;  k = rope(n_k(h W_k)) a head;  v = h W_v
             g_t = sigmoid(h_t W_g + b_g) a kv head;   G_t = sum_{s<=t} log g_s
             a_ts = exp(G_t - G_s) * ((q_t . k_s) / sqrt(d))^2    for s <= t, else 0
             y_t  = sum_s a_ts v_s / (sum_s a_ts + eps)

Departures from the published model, and what the catalog has no key for
(the configuration file's ``assumed`` says the same): the degree (2), the
gate's projection and bias, a gate a kv head, the output divided by the sum
of its weights plus ``eps``, the scale 1 / sqrt(d) inside the power, rotation
and q/k norm a head in front of the power as in the Qwen3 block, no gate on
the output. Weights are seeded random values (the caller's): ``perturb``
moves the two head norms' scales off 1 so that a dropped one cannot pass; the
program's initialiser has already drawn the gates' centres in [0.9, 0.9995].

Parameters come as the pytree the program uses: ``layers["sub0"]`` with every
leaf ``(layers, ...)``; a layer is sliced out and upcast on its own, so the
published widths in bfloat16 fit one chip beside a float32 working copy of
one layer (1.3 GB). Rows are computed one at a time.

Hooks (``reference_check.compare`` and ``flops.py`` ask for them by name;
``reference/qwen2.py``'s docstring lists what each is for): ``forward``,
``loss``, ``check_sizes``, ``sizes``, ``perturb``,
``forward_flops_per_token``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

F32 = jnp.float32
QUERY_BLOCK = 256

# ModelConfig field -> key of the published config.json it must equal.
PUBLISHED = {
    "hidden_size": "hidden_size",
    "intermediate_size": "intermediate_size",
    "num_layers": "num_hidden_layers",
    "num_heads": "num_attention_heads",
    "num_kv_heads": "num_key_value_heads",
    "head_dim": "head_dim",
    "vocab_size": "vocab_size",
    "rms_norm_eps": "rms_norm_eps",
    "rope_theta": "rope_theta",
    "tie_embeddings": "tie_word_embeddings",
    "max_seq_len": "max_position_embeddings",
    "attention_bias": "attention_bias",
}


def check_sizes(cfg, config: dict) -> list[str]:
    """The program's ModelConfig against the published sizes in the
    configuration file: a width that differs is an error, not a note."""
    want = {field: config[key] for field, key in PUBLISHED.items()}
    want["layer_types"] = "r" * config["num_hidden_layers"]
    want["ret_degree"] = config["assumed_values"]["degree"]
    want["ret_eps"] = config["assumed_values"]["eps"]
    want["qk_norm"] = True
    return [f"{k}: program {getattr(cfg, k)!r}, configuration file {v!r}"
            for k, v in want.items() if getattr(cfg, k) != v]


def perturb(params, cfg, seed: int):
    """The head norms' scales moved away from 1: the initialiser sets them
    to 1, and a value of 1 would let a dropped norm pass."""
    sub = params["layers"]["sub0"]
    m = dict(sub["ret"])
    for i, leaf in enumerate(("q_norm", "k_norm")):
        k = jax.random.fold_in(jax.random.key(seed), 4000 + i)
        m[leaf] = (1.0 + 0.3 * jax.random.normal(k, m[leaf].shape, F32)).astype(m[leaf].dtype)
    return {**params, "layers": {"sub0": {**sub, "ret": m}}}


def sizes(cfg, config: dict) -> dict:
    """What ``forward`` and ``loss`` need besides the weights, as the program
    holds it (``check_sizes`` has held the program to the file)."""
    return {"num_hidden_layers": cfg.num_layers, "num_attention_heads": cfg.num_heads,
            "num_key_value_heads": cfg.num_kv_heads, "head_dim": cfg.head_dim,
            "rms_norm_eps": cfg.rms_norm_eps, "rope_theta": cfg.rope_theta,
            "eps": cfg.ret_eps}


def forward_flops_per_token(config: dict, context_mean: float) -> float:
    """Forward operations a token needs, in the recurrent form a server runs:
    a layer's four projections and its gate, the state (decay, update and one
    read-out a query head of D x d values a kv head: two operations each,
    with D the blocked second power of a d-wide head, 36 (d / 8)^2), the FFN
    (three matmuls), and the head. ``context_mean`` is taken and not used: a
    state's cost does not grow with the context."""
    del context_mean
    d, f = config["hidden_size"], config["intermediate_size"]
    heads, kv, hd = (config["num_attention_heads"], config["num_key_value_heads"],
                     config["head_dim"])
    feats = 36 * (hd // 8) ** 2
    mixer = (2 * d * (heads + 2 * kv) * hd + 2 * d * kv + 2 * heads * hd * d
             + kv * feats * hd * 4 + heads * feats * hd * 2)
    return config["num_hidden_layers"] * (mixer + 6 * d * f) + 2 * d * config["vocab_size"]


def _rms_norm(x, scale, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * scale


def _rope(x, positions, theta):
    """x: (S, H, d); the two halves of a head are the rotation's pairs."""
    half = x.shape[-1] // 2
    freqs = 1.0 / theta ** (jnp.arange(half, dtype=F32) / half)
    angles = positions.astype(F32)[:, None] * freqs
    cos, sin = jnp.cos(angles)[:, None, :], jnp.sin(angles)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def _retention(m, h, *, positions, sizes):
    """One row. h: (S, D) -> (S, H d), before ``W_o``."""
    s = h.shape[0]
    n_heads, n_kv, hd = (sizes["num_attention_heads"], sizes["num_key_value_heads"],
                         sizes["head_dim"])
    eps, theta = sizes["rms_norm_eps"], sizes["rope_theta"]
    q = _rope(_rms_norm((h @ m["wq"]).reshape(s, n_heads, hd), m["q_norm"], eps),
              positions, theta)
    k = _rope(_rms_norm((h @ m["wk"]).reshape(s, n_kv, hd), m["k_norm"], eps),
              positions, theta)
    v = (h @ m["wv"]).reshape(s, n_kv, hd)
    big_g = jnp.cumsum(jax.nn.log_sigmoid(h @ m["wg"] + m["bg"]), axis=0)  # (S, K)
    rep = n_heads // n_kv
    k, v, big_g = (jnp.repeat(t, rep, axis=1) for t in (k, v, big_g))
    idx = jnp.arange(s)
    out = []
    for lo in range(0, s, QUERY_BLOCK):  # a block of queries against keys 0 .. hi
        hi = min(lo + QUERY_BLOCK, s)
        dots = jnp.einsum("qhd,khd->hqk", q[lo:hi], k[:hi]) / hd ** 0.5
        seen = idx[lo:hi, None] >= idx[None, :hi]
        decay = jnp.exp(jnp.where(seen[None], big_g[lo:hi].T[:, :, None]
                                  - big_g[:hi].T[:, None, :], -jnp.inf))
        a = dots * dots * decay
        y = jnp.einsum("hqk,khd->qhd", a, v[:hi]) / (a.sum(axis=-1).T[..., None]
                                                   + sizes["eps"])
        out.append(y.reshape(hi - lo, n_heads * hd))
    return jnp.concatenate(out)


def forward(params, input_ids, sizes: dict, *, positions=None, segment_ids=None):
    """Token ids (B, S) -> float32 logits (B, S, V). One document a row."""
    if segment_ids is not None:
        raise ValueError("the Brumby reference takes one document a row")
    up = lambda t: jax.tree.map(lambda w: w.astype(F32), t)  # noqa: E731
    b, s = input_ids.shape
    eps = sizes["rms_norm_eps"]
    rows = []
    with jax.default_matmul_precision("highest"):
        for r in range(b):
            pos = jnp.arange(s) if positions is None else positions[r]
            x = params["embed"]["embedding"][input_ids[r]].astype(F32)
            for i in range(sizes["num_hidden_layers"]):
                layer = up(jax.tree.map(lambda w: w[i], params["layers"]["sub0"]))  # noqa: B023
                h = _rms_norm(x, layer["attn_norm"]["scale"], eps)
                x = x + _retention(layer["ret"], h, positions=pos, sizes=sizes) @ layer["ret"]["wo"]
                h = _rms_norm(x, layer["mlp_norm"]["scale"], eps)
                g, u = jnp.split(h @ layer["mlp"]["w_gu"], 2, axis=-1)
                x = x + (jax.nn.silu(g) * u) @ layer["mlp"]["w_down"]
            x = _rms_norm(x, params["final_norm"]["scale"].astype(F32), eps)
            rows.append(x @ params["lm_head"]["kernel"].astype(F32))
    return jnp.stack(rows)


def loss(outputs, input_ids, loss_mask, sizes: dict):
    """Mean next-token cross-entropy over the masked positions."""
    del sizes
    lg = (outputs["logits"] if isinstance(outputs, dict) else outputs)[:, :-1]
    mask = loss_mask[:, 1:].astype(F32)
    logz = jax.nn.logsumexp(lg, axis=-1)
    tgt = jnp.take_along_axis(lg, input_ids[:, 1:, None], axis=-1)[..., 0]
    return ((logz - tgt) * mask).sum() / jnp.maximum(mask.sum(), 1.0)
