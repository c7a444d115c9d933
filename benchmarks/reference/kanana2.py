"""Plain reference of the Kanana-2-30B-A3B decoder (kakaocorp/
kanana-2-30b-a3b-instruct-2601, ``model_type: deepseek_v3``: its
``config.json`` for the sizes, the DeepSeek-V3 modelling code as recalled for
the rest: this sandbox has no network), independent of the code under test:
it imports nothing from ``ditl_tpu``.

Straightforward ``jax.numpy`` in float32 at ``highest`` matmul precision: a
Python loop over the layers and over the held experts. Keys and values are
decompressed from the latent for every position, attention runs a head under
a mask over ALL positions. No kernel, no sort of pairs, no grouped matmul, no
scan, no cache. Gradients are ``jax.grad`` of ``loss``.

With ``n`` an RMSNorm (its own scale, eps from the config), ``x`` the stream
and ``u = n(h)``::

    h = x + Attn(n(x));   y = h + FFN(n(h));   final norm; untied head

    Attn(z): q = z Wq -> heads of [q_nope | q_rope]      (NO query latent)
             [ckv | kr] = z Wkva;  c = n_kv(ckv);  [k_nope | v] = c Wkvb a head
             rotary on q_rope and on the ONE kr a token all heads share:
             neighbouring pairs (2i, 2i+1), theta from the config, no scaling
             scores = (q_nope . k_nope + q_rope . kr) (nope + rope)^-0.5,
             causal inside the document; out = (softmax v) Wo
    FFN, the first first_k_dense_replace layers: SwiGLU of intermediate_size
    FFN, the rest: p = sigmoid_float32(u Wr) over n_routed_experts
             choice: the num_experts_per_tok largest of p + b (n_group 1,
             topk_group 1: no group limiting)
             w_i = p_i / sum_chosen p * routed_scaling_factor (no b in it)
             y = Shared(u) + sum_i w_i E_i(u); E_i SwiGLU of
             moe_intermediate_size; Shared ONE SwiGLU of n_shared_experts x it
    loss:    mean next-token cross-entropy over the masked positions; NO
             auxiliary term (topk_method noaux_tc); b only chooses, so no
             gradient reaches it

**The share.** ``sizes["experts_held"] = (first, count)``: only those routed
experts have weights; a chosen routed expert outside the range adds NOTHING
here, exactly as in the program: it is another chip's part of the sum. The
shared expert is on every chip. Given ``(0, n_routed_experts)`` this is the
uncut layer, and the 8 shares' routed parts plus the shared expert counted
once add up to it, forward and gradient (``tests/test_kanana.py``).

Departures from the published code, choices of this reference and the program
alike (the configuration file lists them under ``assumed``):
- weights are seeded random values (the caller's), with ``perturb``'s folded
  start;
- the router computes in float32; its bias joins the choice only and is
  frozen (the DeepSeek-V3 report's update of the bias by the sign of an
  expert's load, once a step, is not in ``config.json`` and is not built);
- ``n_shared_experts`` 2 is one FFN of twice the width (the same function as
  two side by side, summed);
- the head is untied.

Parameters come as the pytree the program uses (``layers`` -> ``dense`` /
``sparse``, each stacked on axis 0); each matrix is sliced out of its stack
where it is used and upcast on its own.

Hooks (``reference_check.compare``, ``train_grad_check`` and ``flops.py`` ask
for them by name): ``check_sizes``, ``sizes``, ``forward``, ``loss``,
``perturb``, ``forward_flops_per_token``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

F32 = jnp.float32

# ModelConfig field -> key of the published config.json it must equal.
PUBLISHED = {
    "hidden_size": "hidden_size",
    "intermediate_size": "intermediate_size",
    "expert_ffn_hidden_size": "moe_intermediate_size",
    "num_heads": "num_attention_heads",
    "kv_lora_rank": "kv_lora_rank",
    "qk_nope_head_dim": "qk_nope_head_dim",
    "qk_rope_head_dim": "qk_rope_head_dim",
    "v_head_dim": "v_head_dim",
    "num_experts": "n_routed_experts",
    "num_experts_per_tok": "num_experts_per_tok",
    "n_group": "n_group",
    "topk_group": "topk_group",
    "n_shared_experts": "n_shared_experts",
    "scoring_func": "scoring_func",
    "norm_topk_prob": "norm_topk_prob",
    "routed_scaling_factor": "routed_scaling_factor",
    "first_k_dense_replace": "first_k_dense_replace",
    "rms_norm_eps": "rms_norm_eps",
    "rope_theta": "rope_theta",
    "attention_bias": "attention_bias",
    "tie_embeddings": "tie_word_embeddings",
    "max_seq_len": "max_position_embeddings",
}


def check_sizes(cfg, config: dict) -> list[str]:
    """The program's ModelConfig against the configuration file: every
    published width as published, every cut as the file's ``cut`` states it
    (the published count stays beside it in the file)."""
    want = {field: config[key] for field, key in PUBLISHED.items()}
    cut = config["cut"]
    want.update(num_layers=cut["num_hidden_layers"], vocab_size=cut["vocab_size"],
                experts_held_first=cut["experts_held"][0],
                experts_held_count=cut["experts_held"][1],
                q_lora_rank=0, index_topk=0, rope_yarn_factor=0.0,
                router_bias=True, router_aux_coef=0.0, zero_expert_num=0)
    bad = [f"{k}: program {getattr(cfg, k)!r}, configuration file {v!r}"
           for k, v in want.items() if getattr(cfg, k) != v]
    if (config["q_lora_rank"] is not None or config["rope_scaling"] is not None
            or not config["rope_interleave"] or config["topk_method"] != "noaux_tc"
            or config["hidden_act"] != "silu" or config["moe_layer_freq"] != 1
            or config["qk_head_dim"] != config["qk_nope_head_dim"] + config["qk_rope_head_dim"]):
        bad.append("the file states a query latent, a rope scaling, another rotary "
                   "pairing, choice method, activation or expert-layer frequency than "
                   "this reference computes")
    return bad


def sizes(cfg, config: dict) -> dict:
    """What ``forward`` needs besides the weights, as the program holds it
    (``check_sizes`` has held the program to the file)."""
    first = cfg.experts_held_first if cfg.experts_held_count else 0
    count = cfg.experts_held_count or cfg.num_experts
    return {"num_attention_heads": cfg.num_heads, "kv_lora_rank": cfg.kv_lora_rank,
            "qk_nope_head_dim": cfg.qk_nope_head_dim,
            "qk_rope_head_dim": cfg.qk_rope_head_dim, "v_head_dim": cfg.v_head_dim,
            "rope_theta": cfg.rope_theta, "rms_norm_eps": cfg.rms_norm_eps,
            "n_routed_experts": cfg.num_experts,
            "num_experts_per_tok": cfg.num_experts_per_tok,
            "norm_topk_prob": cfg.norm_topk_prob,
            "routed_scaling_factor": cfg.routed_scaling_factor,
            "experts_held": (first, count)}


def perturb(params, cfg, seed: int):
    """Every norm scale and the router's selection bias moved away from what
    the initialiser gives them (1 and 0), so that a path that drops one fails.
    The bias is drawn on the scale of the gaps between neighbouring sigmoid
    scores (128 scores spread over ~0.5: 0.02), so it changes some of the
    choices and enters no weight.

    And the published scale a checkpoint's weights have absorbed in training
    is folded into these seeded ones, as the sibling families' references do:
    the routed experts' ``w_down`` / ``routed_scaling_factor`` (renormalised
    weights x 2.448 give each of a token's 6 chosen experts 0.41 of its FFN
    output while seeded sigmoid scores tie far more often than a trained
    router's, so a bfloat16 rounding that flips ONE choice moves a token's
    stream by a fifth). Program and reference compute the published
    arithmetic on the SAME weights."""
    key = jax.random.key(seed)
    n = iter(range(5800, 5900))

    def normal(shape, std, mean=0.0):
        k = jax.random.fold_in(key, next(n))
        return mean + std * jax.random.normal(k, shape, F32)

    def scale_like(w):
        return normal(w.shape, 0.3, 1.0).astype(w.dtype)

    routed = cfg.routed_scaling_factor if cfg.norm_topk_prob else 1.0

    def one(tree):
        tree = dict(tree)
        tree["attn_norm"] = {"scale": scale_like(tree["attn_norm"]["scale"])}
        tree["mlp_norm"] = {"scale": scale_like(tree["mlp_norm"]["scale"])}
        tree["attn"] = {**tree["attn"], "kv_norm": scale_like(tree["attn"]["kv_norm"])}
        if "moe" in tree:
            m = tree["moe"]
            tree["moe"] = {**m,
                           "router_bias": normal(m["router_bias"].shape, 0.02).astype(
                               m["router_bias"].dtype),
                           "w_down": jax.jit(lambda w: (w.astype(F32) / routed).astype(
                               w.dtype))(m["w_down"])}
        return tree

    return {**params, "layers": {k: one(v) for k, v in params["layers"].items()},
            "final_norm": {"scale": scale_like(params["final_norm"]["scale"])}}


def forward_flops_per_token(config: dict, context_mean: float) -> float:
    """Forward matmul operations (2 a multiply-add) a token needs in the SHARE
    the configuration file cuts (its ``cut``): per layer the four attention
    projections and attention itself in the DECOMPRESSED form at
    ``context_mean`` keys a query (a head and key ``nope + rope`` for the
    score and ``v_head_dim`` for the value: 192 + 128), then the dense FFN, or
    the router, the shared expert and the routed experts held here at the
    BALANCED share of a token's choices (``num_experts_per_tok x held /
    n_routed_experts`` = 6 x 16 / 128 = 0.75 experts a token a layer: what an
    even router sends this rank, not what a run's router sent); then the head's
    slice."""
    d, f, fe = config["hidden_size"], config["intermediate_size"], config["moe_intermediate_size"]
    h, kr = config["num_attention_heads"], config["kv_lora_rank"]
    nope, rope, vd = (config["qk_nope_head_dim"], config["qk_rope_head_dim"],
                      config["v_head_dim"])
    cut = config["cut"]
    routed = config["n_routed_experts"]
    attn = (2 * d * h * (nope + rope) + 2 * d * (kr + rope) + 2 * kr * h * (nope + vd)
            + 2 * h * vd * d + context_mean * h * 2 * ((nope + rope) + vd))
    held = config["num_experts_per_tok"] * cut["experts_held"][1] / routed
    sparse = 2 * d * routed + (config["n_shared_experts"] + held) * 6 * d * fe
    n_dense = config["first_k_dense_replace"]
    n_sparse = cut["num_hidden_layers"] - n_dense
    return (cut["num_hidden_layers"] * attn + n_dense * 6 * d * f + n_sparse * sparse
            + 2 * d * cut["vocab_size"])


def loss(outputs, input_ids, loss_mask, sizes: dict):
    """Mean next-token cross-entropy over the masked positions; no auxiliary
    term."""
    logits = outputs["logits"] if isinstance(outputs, dict) else outputs
    logp = jax.nn.log_softmax(logits[:, :-1], axis=-1)
    nll = -jnp.take_along_axis(logp, input_ids[:, 1:, None], axis=-1)[..., 0]
    m = loss_mask[:, 1:]
    return (nll * m).sum() / jnp.maximum(m.sum(), 1.0)


def _up(w):
    return w.astype(F32)


def _rms_norm(x, scale, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * _up(scale)


def _rope_pairs(x, positions, theta: float):
    """x: (B, S, H, D); positions: (B, S). Neighbouring pairs (2i, 2i+1)
    rotate by ``position * theta ** (-2i / D)``."""
    d = x.shape[-1]
    inv_freq = theta ** (-2.0 * jnp.arange(d // 2, dtype=F32) / d)
    ang = positions[..., None].astype(F32) * inv_freq  # (B, S, D/2)
    cos, sin = jnp.cos(ang)[:, :, None, :], jnp.sin(ang)[:, :, None, :]
    even, odd = x[..., 0::2], x[..., 1::2]
    out = jnp.stack([even * cos - odd * sin, odd * cos + even * sin], axis=-1)
    return out.reshape(x.shape)


def _attention(a, z, *, positions, allowed, sizes):
    """The attention sublayer, weights ``a``, on the normed input ``z`` (B, S,
    D), a head at a time."""
    b, s, _ = z.shape
    nh = sizes["num_attention_heads"]
    nope, rope, vd = sizes["qk_nope_head_dim"], sizes["qk_rope_head_dim"], sizes["v_head_dim"]
    r, theta = sizes["kv_lora_rank"], sizes["rope_theta"]
    q = (z @ _up(a["wq"])).reshape(b, s, nh, nope + rope)
    q_rope = _rope_pairs(q[..., nope:], positions, theta)
    ckr = z @ _up(a["w_kva"])
    c = _rms_norm(ckr[..., :r], a["kv_norm"], sizes["rms_norm_eps"])
    k_rope = _rope_pairs(ckr[:, :, None, r:], positions, theta)[:, :, 0]  # one a token
    w_kvb = _up(a["w_kvb"]).reshape(r, nh, nope + vd)
    heads = []
    for j in range(nh):
        k_nope, v = c @ w_kvb[:, j, :nope], c @ w_kvb[:, j, nope:]  # decompressed
        scores = (jnp.einsum("bqd,bkd->bqk", q[:, :, j, :nope], k_nope)
                  + jnp.einsum("bqd,bkd->bqk", q_rope[:, :, j], k_rope)) * (nope + rope) ** -0.5
        probs = jax.nn.softmax(jnp.where(allowed, scores, -jnp.inf), axis=-1)
        heads.append(jnp.einsum("bqk,bkd->bqd", probs, v))
    return jnp.concatenate(heads, axis=-1) @ _up(a["wo"])


def _ffn(m, u):
    return (jax.nn.silu(u @ _up(m["w_gate"])) * (u @ _up(m["w_up"]))) @ _up(m["w_down"])


def _experts(m, i, u, sizes):
    """(B, S, D) -> layer ``i``'s expert block's output here, and the chosen
    experts as a 0/1 mask (B, S, routed). ``m``: the stacked leaves."""
    first, count = sizes["experts_held"]
    e = sizes["n_routed_experts"]
    p = jax.nn.sigmoid(u @ _up(m["router"][i]))
    _, top_i = jax.lax.top_k(p + _up(m["router_bias"][i]), sizes["num_experts_per_tok"])
    chosen = jax.nn.one_hot(top_i, e, dtype=F32).sum(axis=-2)
    weight = chosen * p
    if sizes["norm_topk_prob"]:
        weight = weight / weight.sum(axis=-1, keepdims=True)
    weight = weight * sizes["routed_scaling_factor"]
    out = _ffn(jax.tree.map(lambda w: w[i], m["shared"]), u)
    for j in range(count):  # the held ones; a routed expert held elsewhere adds nothing
        y = _ffn({k: m[k][i, j] for k in ("w_gate", "w_up", "w_down")}, u)
        out = out + weight[..., first + j:first + j + 1] * y
    return out, chosen


def forward(params, input_ids, sizes: dict, *, positions=None, segment_ids=None):
    """Token ids (B, S) -> ``{"logits": float32 (B, S, V), "chosen": (expert
    layers, B, S, routed) 0/1}``."""
    b, s = input_ids.shape
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (b, s))
    idx = jnp.arange(s)
    allowed = jnp.broadcast_to(idx[None, :, None] >= idx[None, None, :], (b, s, s))
    if segment_ids is not None:
        allowed = allowed & (segment_ids[:, :, None] == segment_ids[:, None, :])
    chosen = []
    eps = sizes["rms_norm_eps"]
    with jax.default_matmul_precision("highest"):
        x = _up(params["embed"]["embedding"])[input_ids]
        for kind in ("dense", "sparse"):
            stack = params["layers"][kind]
            for i in range(stack["attn_norm"]["scale"].shape[0]):
                at = lambda tree: jax.tree.map(lambda w: w[i], tree)  # noqa: E731,B023
                h = x + _attention(
                    at(stack["attn"]), _rms_norm(x, stack["attn_norm"]["scale"][i], eps),
                    positions=positions, allowed=allowed, sizes=sizes)
                u = _rms_norm(h, stack["mlp_norm"]["scale"][i], eps)
                if kind == "dense":
                    x = h + _ffn(at(stack["mlp"]), u)
                else:
                    y, c = _experts(stack["moe"], i, u, sizes)
                    chosen.append(c)
                    x = h + y
        x = _rms_norm(x, params["final_norm"]["scale"], eps)
        logits = x @ _up(params["lm_head"]["kernel"])
    return {"logits": logits, "chosen": jnp.stack(chosen)}
