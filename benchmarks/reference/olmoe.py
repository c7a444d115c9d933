"""Plain reference of the OLMoE decoder (allenai/OLMoE-1B-7B-0125-Instruct:
arXiv:2409.02060, and ``modeling_olmoe.py`` in Hugging Face transformers, as
recalled: this sandbox has no network), independent of the code under test.

Straightforward ``jax.numpy`` in float32 at ``highest`` matmul precision: a
Python loop over the layers and, inside each, over the experts. No sort, no
grouped matmul, no kernel, no cache, no scan.

What it computes, per layer (pre-norm residual block; ``x`` the stream):

    h = rmsnorm(x)
    q = rmsnorm_q(h Wq)      k = rmsnorm_k(h Wk)      v = h Wv
        each of rmsnorm_q / rmsnorm_k an RMSNorm with its own learned scale
        over the WHOLE projected vector (all heads together, 2,048 wide in
        the published model), before the split into heads; no bias anywhere;
        ``clip_qkv`` is null in the published config and is not implemented
    rotate-half RoPE on q and k (theta from the config: the two halves of
        each head, not interleaved pairs)
    causal softmax attention inside the token's own packed document, every
        query head on its own kv head (16 of each; grouped queries work too)
    x = x + attn Wo
    h = rmsnorm(x)
    p = softmax_float32(h Wr)             over ALL experts (64)
    the k (8) largest p and their experts; the weights are those p AS THEY
        ARE when ``norm_topk_prob`` is false (OLMoE: they sum to less than
        1), divided by their sum when it is true (Mixtral)
    x = x + sum_i p_i * W_down,i (silu(h W_gate,i) * (h W_up,i))
        every one of the T * k pairs is computed: no capacity, no drop

then a final RMSNorm and the untied head (the transposed embedding only when
``tie_word_embeddings``). Here every expert runs on every token and the
outputs of the experts a token did not choose are weighted by zero.

Training loss: the mean next-token cross-entropy over the masked positions,
plus ``router_aux_loss_coef`` (0.01) times the load-balancing term
``E * sum_e f_e P_e`` of each layer, averaged over the layers: ``f_e`` the
share of the ``T * k`` assignments that went to expert ``e`` and ``P_e`` the
mean of ``p_e``, both over the tokens the loss mask keeps (position by
position: the mask that is shifted for the targets is used unshifted here).

Departures from the published model:
- Hugging Face's ``load_balancing_loss_func`` concatenates the router
  outputs of all layers before it takes ``f`` and ``P`` (one product over
  the pooled tokens of every layer, times E); this takes the product layer
  by layer and averages, which is what balances each layer's own experts and
  what the program does. The two agree when every layer routes alike. It
  also masks by the attention (padding) mask where this uses the loss mask.
- Weights are seeded random values (the caller's).

Parameters come as the pytree the program uses (layers stacked on axis 0,
experts on axis 1 of ``moe``); each layer is sliced out and upcast on its
own, so the published widths in bfloat16 fit one chip beside a float32
working copy of one layer (1.68 GB).

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
    "intermediate_size": "intermediate_size",
    "num_layers": "num_hidden_layers",
    "num_heads": "num_attention_heads",
    "num_kv_heads": "num_key_value_heads",
    "vocab_size": "vocab_size",
    "rope_theta": "rope_theta",
    "rms_norm_eps": "rms_norm_eps",
    "tie_embeddings": "tie_word_embeddings",
    "max_seq_len": "max_position_embeddings",
    "num_experts": "num_experts",
    "num_experts_per_tok": "num_experts_per_tok",
    "norm_topk_prob": "norm_topk_prob",
    "attention_bias": "attention_bias",
}


def check_sizes(cfg, config: dict) -> list[str]:
    """The program's ModelConfig against the published sizes in the
    configuration file: a width that differs is an error, not a note. What
    config.json has no key for is fixed by the architecture: q/k
    normalisation on, a head as wide as hidden / heads."""
    want = {field: config[key] for field, key in PUBLISHED.items()}
    want["head_dim"] = config["hidden_size"] // config["num_attention_heads"]
    want["qk_norm"] = True
    return [f"{k}: program {getattr(cfg, k)!r}, configuration file {v!r}"
            for k, v in want.items() if getattr(cfg, k) != v]


def perturb(params, cfg, seed: int):
    """The q/k norm scales moved away from 1: the program's initialiser sets
    them to 1, and a scale of 1 would let a dropped scale pass.

    The router is left as the initialiser draws it. With random routers the
    8th and 9th largest of 64 gates lie ~0.08 apart in logit, so bfloat16
    activations flip one expert for 6-8% of the (token, layer) pairs (measured
    on the chip at the published widths: PERF.md section 6, PR 26), and the
    logits still agree to 1.2%: a flipped expert carries a thirtieth of the
    top-8's weight. Spreading the router's logits (x 2, x 4) was tried as a
    remedy and made it worse (1.5%, 3.4%): the flip rate does not fall, since
    the noise in a logit scales with the logit, and every gate's VALUE becomes
    as many times more sensitive to that noise."""
    attn = dict(params["layers"]["attn"])
    for i, name in enumerate(("q_norm", "k_norm")):
        k = jax.random.fold_in(jax.random.key(seed), 2000 + i)
        attn[name] = (1.0 + 0.3 * jax.random.normal(k, attn[name].shape, F32)
                      ).astype(attn[name].dtype)
    return {**params, "layers": {**params["layers"], "attn": attn}}


def sizes(cfg, config: dict) -> dict:
    """What ``forward`` and ``loss`` need besides the weights, as the program
    holds it (``check_sizes`` has held the program to the file)."""
    return {"num_attention_heads": cfg.num_heads,
            "num_key_value_heads": cfg.num_kv_heads, "head_dim": cfg.head_dim,
            "rope_theta": cfg.rope_theta, "rms_norm_eps": cfg.rms_norm_eps,
            "tie_word_embeddings": cfg.tie_embeddings,
            "num_experts_per_tok": cfg.num_experts_per_tok,
            "norm_topk_prob": cfg.norm_topk_prob,
            "router_aux_loss_coef": cfg.router_aux_coef}


def forward_flops_per_token(config: dict, context_mean: float) -> float:
    """Forward matmul operations a token needs: the attention projections,
    scores against ``context_mean`` keys, the router, the k ACTIVE experts of
    64 (three matmuls each) and the head. A later trainer cell's ``mfu``
    multiplies by three for the backward pass."""
    d, f = config["hidden_size"], config["intermediate_size"]
    heads, kv = config["num_attention_heads"], config["num_key_value_heads"]
    hd = d // heads
    per_layer = (2 * d * (heads + 2 * kv) * hd + 2 * heads * hd * d  # q k v, o
                 + 4 * heads * hd * context_mean  # scores and values
                 + 2 * d * config["num_experts"]  # router
                 + config["num_experts_per_tok"] * 6 * d * f)
    return config["num_hidden_layers"] * per_layer + 2 * d * config["vocab_size"]


def _rms_norm(x, scale, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * scale


def _rope(x, positions, theta):
    """x: (B, S, H, D); positions: (B, S). Rotate-half convention."""
    d = x.shape[-1]
    inv_freq = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=F32) / d))
    ang = positions[..., None].astype(F32) * inv_freq  # (B, S, D/2)
    cos, sin = jnp.cos(ang)[:, :, None, :], jnp.sin(ang)[:, :, None, :]
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def _experts(m, h, k: int, renormalise: bool):
    """(B, S, D) -> the expert layer's output, the router's probabilities
    (B, S, E) and the chosen experts as a 0/1 mask (B, S, E)."""
    n_experts = m["router"].shape[-1]
    p = jax.nn.softmax(h @ m["router"], axis=-1)
    top_p, top_i = jax.lax.top_k(p, k)
    if renormalise:
        top_p = top_p / top_p.sum(axis=-1, keepdims=True)
    chosen = jax.nn.one_hot(top_i, n_experts, dtype=F32)  # (B, S, k, E)
    weight = (chosen * top_p[..., None]).sum(axis=-2)  # (B, S, E), 0 if not chosen
    out = jnp.zeros_like(h)
    for e in range(n_experts):
        y = (jax.nn.silu(h @ m["w_gate"][e]) * (h @ m["w_up"][e])) @ m["w_down"][e]
        out = out + weight[..., e:e + 1] * y
    return out, p, chosen.sum(axis=-2)


def _layer(p, x, *, positions, allowed, sizes):
    b, s, _ = x.shape
    n_heads, n_kv = sizes["num_attention_heads"], sizes["num_key_value_heads"]
    head_dim, eps = sizes["head_dim"], sizes["rms_norm_eps"]
    a = p["attn"]
    h = _rms_norm(x, p["attn_norm"]["scale"], eps)
    q = _rms_norm(h @ a["wq"], a["q_norm"], eps).reshape(b, s, n_heads, head_dim)
    k = _rms_norm(h @ a["wk"], a["k_norm"], eps).reshape(b, s, n_kv, head_dim)
    v = (h @ a["wv"]).reshape(b, s, n_kv, head_dim)
    q, k = _rope(q, positions, sizes["rope_theta"]), _rope(k, positions, sizes["rope_theta"])
    group = n_heads // n_kv
    k = jnp.repeat(k, group, axis=2)
    v = jnp.repeat(v, group, axis=2)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) / jnp.sqrt(F32(head_dim))
    scores = jnp.where(allowed[:, None, :, :], scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    attn = jnp.einsum("bhqk,bkhd->bqhd", probs, v).reshape(b, s, n_heads * head_dim)
    x = x + attn @ a["wo"]
    h = _rms_norm(x, p["mlp_norm"]["scale"], eps)
    out, router_p, chosen = _experts(p["moe"], h, sizes["num_experts_per_tok"],
                                     sizes["norm_topk_prob"])
    return x + out, router_p, chosen


def forward(params, input_ids, sizes: dict, *, positions=None, segment_ids=None):
    """Token ids (B, S) -> ``{"logits": float32 (B, S, V), "router_probs":
    (L, B, S, E), "chosen": (L, B, S, E) 0/1}``: the logits and what the
    loss's load-balancing term needs."""
    up = lambda t: jax.tree.map(lambda w: w.astype(F32), t)  # noqa: E731
    b, s = input_ids.shape
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (b, s))
    idx = jnp.arange(s)
    allowed = (idx[None, :, None] >= idx[None, None, :])  # causal (1, S, S)
    allowed = jnp.broadcast_to(allowed, (b, s, s))
    if segment_ids is not None:
        allowed = allowed & (segment_ids[:, :, None] == segment_ids[:, None, :])
    router_probs, chosen = [], []
    with jax.default_matmul_precision("highest"):
        x = params["embed"]["embedding"][input_ids].astype(F32)
        n_layers = params["layers"]["attn_norm"]["scale"].shape[0]
        for i in range(n_layers):
            layer = up(jax.tree.map(lambda w: w[i], params["layers"]))
            x, p, c = _layer(layer, x, positions=positions, allowed=allowed, sizes=sizes)
            router_probs.append(p)
            chosen.append(c)
        x = _rms_norm(x, params["final_norm"]["scale"].astype(F32),
                      sizes["rms_norm_eps"])
        if sizes["tie_word_embeddings"]:
            head = params["embed"]["embedding"].astype(F32).T
        else:
            head = params["lm_head"]["kernel"].astype(F32)
        logits = x @ head
    return {"logits": logits, "router_probs": jnp.stack(router_probs),
            "chosen": jnp.stack(chosen)}


def router_aux(outputs, loss_mask):
    """The load-balancing term ``E * sum_e f_e P_e`` of each layer over the
    tokens ``loss_mask`` (B, S) keeps, averaged over the layers."""
    mask = loss_mask.astype(F32)[None, :, :, None]  # (1, B, S, 1)
    n_experts = outputs["chosen"].shape[-1]
    assigned = (outputs["chosen"] * mask).sum(axis=(1, 2))  # (L, E)
    f = assigned / jnp.maximum(assigned.sum(axis=-1, keepdims=True), 1.0)
    p = (outputs["router_probs"] * mask).sum(axis=(1, 2)) / jnp.maximum(mask.sum(), 1.0)
    return jnp.mean(n_experts * (f * p).sum(axis=-1))


def loss(outputs, input_ids, loss_mask, sizes: dict):
    """Mean next-token cross-entropy over the masked positions plus
    ``router_aux_loss_coef`` times ``router_aux``."""
    targets = input_ids[:, 1:]
    mask = loss_mask[:, 1:].astype(F32)
    lg = outputs["logits"][:, :-1]
    logz = jax.nn.logsumexp(lg, axis=-1)
    tgt = jnp.take_along_axis(lg, targets[..., None], axis=-1)[..., 0]
    ce = ((logz - tgt) * mask).sum() / jnp.maximum(mask.sum(), 1.0)
    return ce + sizes["router_aux_loss_coef"] * router_aux(outputs, loss_mask)
