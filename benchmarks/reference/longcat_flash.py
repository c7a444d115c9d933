"""Plain reference of the LongCat-Flash decoder (meituan-longcat/
LongCat-Flash-Chat: its ``config.json`` for the sizes, ``modeling_longcat_
flash.py`` as recalled for the rest: this sandbox has no network),
independent of the code under test.

Straightforward ``jax.numpy`` in float32 at ``highest`` matmul precision: a
Python loop over the layers, over the two halves of each and over the held
experts. No sort, no grouped matmul, no kernel, no cache, no scan, and no
absorption: keys and values are decompressed from the latent for every
position and attention runs per head.

Each layer is a DOUBLE layer. With ``n`` an RMSNorm (its own scale, eps from
the config) and ``x`` the stream:

    h1 = x  + MLA_0(n_in0(x));      u  = n_post0(h1)
    s  = MoE(u)                     # the shortcut: from u, added at the end
    h2 = h1 + FFN_0(u)              # SwiGLU, hidden -> ffn_hidden -> hidden
    h3 = h2 + MLA_1(n_in1(h2))
    y  = h3 + FFN_1(n_post1(h3)) + s

    MLA(z):  cq = n_q(z Wqa);  q = (cq Wqb) * sqrt(hidden / q_lora_rank)
                 heads of qk_nope_head_dim + qk_rope_head_dim
             [c | kr] = z Wkva;  c = n_kv(c) * sqrt(hidden / kv_lora_rank)
             [k_nope | v] = c Wkvb        (per head: qk_nope_head_dim + v_head_dim)
             scores = (q_nope . k_nope + rope(q_rope) . rope(kr))
                      / sqrt(qk_nope_head_dim + qk_rope_head_dim)
             causal softmax inside the token's own document; out = (P v) Wo
             rope: neighbouring pairs (2i, 2i+1), theta from the config,
             unscaled; kr is one vector a token, shared by all heads
    MoE(u):  p = softmax_float32(u Wr) over n_routed_experts + zero_expert_num
             idx = the moe_topk largest of (p + b); b biases the CHOICE only
             w_j = routed_scaling_factor * p[idx_j], not renormalised
             s = sum_j w_j E_idx_j(u);  E_e = SwiGLU of expert_ffn_hidden_size
             for e < n_routed_experts, E_e(u) = u for the zero-compute ones

then a final RMSNorm and the untied head.

**The share.** ``sizes["experts_held"] = (first, count)``: only those routed
experts have weights (``params["layers"]["moe"]["w_*"]`` holds ``count`` of
them); a chosen routed expert outside the range adds NOTHING here, exactly as
in the program: it is another chip's part of the sum. The zero-compute
experts are everywhere. The vocabulary slice needs no code: embedding and
head simply have fewer rows. Given ``(0, n_routed_experts)`` this is the
uncut model, and the four quarter shares of a 32-expert model plus the
zero-compute part counted once add up to it (``tests/test_longcat.py``).

Departures from the published model, all of them choices of this reference
and the program alike (the configuration file lists them under ``assumed``):
- weights are seeded random values (the caller's);
- the order inside the double layer, the scales on q and c, the bias used
  for the choice only, no renormalisation, interleaved rope, silu, the
  untied head are recalled, not read;
- the published router may compute in the stream's dtype; this one and the
  program use float32.

Parameters come as the pytree the program uses (layers stacked on axis 0;
the two halves' norm scales on axis 1, their matrices under ``sub0`` /
``sub1``); each matrix is sliced out of its stack where it is used and upcast
on its own, so the published widths in bfloat16 (9.63 GiB) fit one chip
beside a float32 copy of ONE matrix (the head: 0.4 GB). (Slicing a whole
layer out first, 2.5 GB in bfloat16, twice over while the loop's variable
changes hands, put the first chip run's peak at 16.17 GB.)

Hooks (``reference_check.compare``, ``paged_check.check`` and ``flops.py``
ask for them by name): ``forward``, ``check_sizes``, ``sizes``, ``perturb``,
``forward_flops_per_token``. No ``loss``: serving only.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

F32 = jnp.float32

# ModelConfig field -> key of the published config.json it must equal.
PUBLISHED = {
    "hidden_size": "hidden_size",
    "intermediate_size": "ffn_hidden_size",
    "expert_ffn_hidden_size": "expert_ffn_hidden_size",
    "num_heads": "num_attention_heads",
    "q_lora_rank": "q_lora_rank",
    "kv_lora_rank": "kv_lora_rank",
    "qk_nope_head_dim": "qk_nope_head_dim",
    "qk_rope_head_dim": "qk_rope_head_dim",
    "v_head_dim": "v_head_dim",
    "mla_scale_q_lora": "mla_scale_q_lora",
    "mla_scale_kv_lora": "mla_scale_kv_lora",
    "routed_scaling_factor": "routed_scaling_factor",
    "num_experts": "n_routed_experts",
    "zero_expert_num": "zero_expert_num",
    "num_experts_per_tok": "moe_topk",
    "max_seq_len": "max_position_embeddings",
    "rms_norm_eps": "rms_norm_eps",
    "rope_theta": "rope_theta",
    "attention_bias": "attention_bias",
}


def check_sizes(cfg, config: dict) -> list[str]:
    """The program's ModelConfig against the configuration file: every
    published width as published, every cut as the file's ``cut`` states it
    (the published count stays beside it in the file)."""
    want = {field: config[key] for field, key in PUBLISHED.items()}
    cut = config["cut"]
    want.update(num_layers=cut["num_layers"], vocab_size=cut["vocab_size"],
                experts_held_first=cut["experts_held"][0],
                experts_held_count=cut["experts_held"][1],
                router_bias=True,
                norm_topk_prob=False, tie_embeddings=False)
    bad = [f"{k}: program {getattr(cfg, k)!r}, configuration file {v!r}"
           for k, v in want.items() if getattr(cfg, k) != v]
    if config["attention_method"] != "MLA" or config["zero_expert_type"] != "identity":
        bad.append("the file states another attention or zero-expert kind than "
                   "this reference computes")
    return bad


def sizes(cfg, config: dict) -> dict:
    """What ``forward`` needs besides the weights, as the program holds it
    (``check_sizes`` has held the program to the file)."""
    first = cfg.experts_held_first if cfg.experts_held_count else 0
    count = cfg.experts_held_count or cfg.num_experts
    return {"hidden_size": cfg.hidden_size, "num_attention_heads": cfg.num_heads,
            "q_lora_rank": cfg.q_lora_rank, "kv_lora_rank": cfg.kv_lora_rank,
            "qk_nope_head_dim": cfg.qk_nope_head_dim,
            "qk_rope_head_dim": cfg.qk_rope_head_dim, "v_head_dim": cfg.v_head_dim,
            "mla_scale_q_lora": cfg.mla_scale_q_lora,
            "mla_scale_kv_lora": cfg.mla_scale_kv_lora,
            "rope_theta": cfg.rope_theta, "rms_norm_eps": cfg.rms_norm_eps,
            "n_routed_experts": cfg.num_experts, "zero_expert_num": cfg.zero_expert_num,
            "moe_topk": cfg.num_experts_per_tok,
            "routed_scaling_factor": cfg.routed_scaling_factor,
            "experts_held": (first, count)}


def perturb(params, cfg, seed: int):
    """Every norm scale and the router's selection bias moved away from what
    the initialiser gives them (1 and 0), so that a path that drops a scale,
    or chooses without the bias, or weighs with it, fails. The bias is drawn
    on the scale of the probabilities it joins (a top-12 probability of 768
    is ~0.004), so it changes about a third of the choices."""
    key = jax.random.key(seed)
    n = iter(range(3000, 3100))

    def scale_like(w):
        k = jax.random.fold_in(key, next(n))
        return (1.0 + 0.3 * jax.random.normal(k, w.shape, F32)).astype(w.dtype)

    layers = dict(params["layers"])
    layers["attn_norm"] = {"scale": scale_like(layers["attn_norm"]["scale"])}
    layers["mlp_norm"] = {"scale": scale_like(layers["mlp_norm"]["scale"])}
    layers["attn"] = {h: {**a, "q_norm": scale_like(a["q_norm"]),
                          "kv_norm": scale_like(a["kv_norm"])}
                      for h, a in layers["attn"].items()}
    bias = layers["moe"]["router_bias"]
    layers["moe"] = {**layers["moe"], "router_bias": (
        2e-3 * jax.random.normal(jax.random.fold_in(key, next(n)), bias.shape, F32)
    ).astype(bias.dtype)}
    return {**params, "layers": layers,
            "final_norm": {"scale": scale_like(params["final_norm"]["scale"])}}


def forward_flops_per_token(config: dict, context_mean: float) -> float:
    """Forward matmul operations a token needs in the SHARE the
    configuration file cuts (its ``cut``): per layer two attention sublayers
    (projections, scores and values against ``context_mean`` keys in the
    absorbed form: per head 2 x (kv_lora_rank + qk_rope_head_dim) +
    2 x kv_lora_rank a key), two dense FFNs, the router over all its outputs,
    and the expected held experts of the moe_topk choices; then the head's
    slice."""
    d, f, fe = config["hidden_size"], config["ffn_hidden_size"], config["expert_ffn_hidden_size"]
    h, qr, kr = config["num_attention_heads"], config["q_lora_rank"], config["kv_lora_rank"]
    nope, rope, vd = (config["qk_nope_head_dim"], config["qk_rope_head_dim"],
                      config["v_head_dim"])
    cut = config["cut"]
    routed, zero = config["n_routed_experts"], config["zero_expert_num"]
    attn = (2 * d * qr + 2 * qr * h * (nope + rope) + 2 * d * (kr + rope)
            + 2 * kr * h * (nope + vd) + 2 * h * vd * d
            + context_mean * h * 2 * ((kr + rope) + kr))
    held = config["moe_topk"] * cut["experts_held"][1] / (routed + zero)
    per_layer = 2 * attn + 2 * 6 * d * f + 2 * d * (routed + zero) + held * 6 * d * fe
    return cut["num_layers"] * per_layer + 2 * d * cut["vocab_size"]


def _up(w):
    return w.astype(F32)


def _rms_norm(x, scale, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * _up(scale)


def _rope_pairs(x, positions, theta):
    """x: (B, S, H, D); positions: (B, S). Neighbouring pairs (2i, 2i+1)
    rotate by ``position * theta ** (-2i / D)``."""
    d = x.shape[-1]
    inv_freq = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=F32) / d))
    ang = positions[..., None].astype(F32) * inv_freq  # (B, S, D/2)
    cos, sin = jnp.cos(ang)[:, :, None, :], jnp.sin(ang)[:, :, None, :]
    even, odd = x[..., 0::2], x[..., 1::2]
    out = jnp.stack([even * cos - odd * sin, odd * cos + even * sin], axis=-1)
    return out.reshape(x.shape)


def _mla(a, z, *, positions, allowed, sizes):
    """One attention sublayer, weights ``a``, on the normed input ``z`` (B,
    S, D)."""
    b, s, _ = z.shape
    nh, eps = sizes["num_attention_heads"], sizes["rms_norm_eps"]
    nope, rope, vd = sizes["qk_nope_head_dim"], sizes["qk_rope_head_dim"], sizes["v_head_dim"]
    r, d = sizes["kv_lora_rank"], sizes["hidden_size"]
    cq = _rms_norm(z @ _up(a["w_qa"]), a["q_norm"], eps)
    q = cq @ _up(a["w_qb"])
    if sizes["mla_scale_q_lora"]:
        q = q * math.sqrt(d / sizes["q_lora_rank"])
    q = q.reshape(b, s, nh, nope + rope)
    ckr = z @ _up(a["w_kva"])
    c = _rms_norm(ckr[..., :r], a["kv_norm"], eps)
    if sizes["mla_scale_kv_lora"]:
        c = c * math.sqrt(d / r)
    kv = (c @ _up(a["w_kvb"])).reshape(b, s, nh, nope + vd)
    k_nope, v = kv[..., :nope], kv[..., nope:]
    q_rope = _rope_pairs(q[..., nope:], positions, sizes["rope_theta"])
    k_rope = _rope_pairs(ckr[:, :, None, r:], positions, sizes["rope_theta"])  # one head
    scores = (jnp.einsum("bqhd,bkhd->bhqk", q[..., :nope], k_nope)
              + jnp.einsum("bqhd,bkd->bhqk", q_rope, k_rope[:, :, 0]))
    scores = scores / jnp.sqrt(F32(nope + rope))
    scores = jnp.where(allowed[:, None, :, :], scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bhqk,bkhd->bqhd", probs, v).reshape(b, s, nh * vd)
    return out @ _up(a["wo"])


def _ffn(m, u):
    return (jax.nn.silu(u @ _up(m["w_gate"])) * (u @ _up(m["w_up"]))) @ _up(m["w_down"])


def _experts(m, i, u, sizes):
    """(B, S, D) -> layer ``i``'s expert block's output here, and the chosen
    experts as a 0/1 mask (B, S, routed + zero). ``m``: the stacked leaves."""
    routed, zero = sizes["n_routed_experts"], sizes["zero_expert_num"]
    first, count = sizes["experts_held"]
    p = jax.nn.softmax(u @ _up(m["router"][i]), axis=-1)
    _, top_i = jax.lax.top_k(p + _up(m["router_bias"][i]), sizes["moe_topk"])
    chosen = jax.nn.one_hot(top_i, routed + zero, dtype=F32).sum(axis=-2)  # (B, S, E)
    weight = chosen * p * sizes["routed_scaling_factor"]  # 0 where not chosen
    # the zero-compute experts: each is the identity
    out = u * weight[..., routed:].sum(axis=-1, keepdims=True)
    for e in range(count):  # the held ones; a routed expert held elsewhere adds nothing
        y = ((jax.nn.silu(u @ _up(m["w_gate"][i, e])) * (u @ _up(m["w_up"][i, e])))
             @ _up(m["w_down"][i, e]))
        out = out + weight[..., first + e:first + e + 1] * y
    return out, chosen


def _double_layer(layers, i, x, *, positions, allowed, sizes):
    """Layer ``i`` of the stacked ``layers``; a matrix leaves its stack only
    where it is used."""
    eps = sizes["rms_norm_eps"]
    at = lambda tree: jax.tree.map(lambda w: w[i], tree)  # noqa: E731
    p = {"attn_norm": at(layers["attn_norm"]), "mlp_norm": at(layers["mlp_norm"])}
    h1 = x + _mla(at(layers["attn"]["sub0"]), _rms_norm(x, p["attn_norm"]["scale"][0], eps),
                  positions=positions, allowed=allowed, sizes=sizes)
    u = _rms_norm(h1, p["mlp_norm"]["scale"][0], eps)
    shortcut, chosen = _experts(layers["moe"], i, u, sizes)
    h2 = h1 + _ffn(at(layers["mlp"]["sub0"]), u)
    h3 = h2 + _mla(at(layers["attn"]["sub1"]), _rms_norm(h2, p["attn_norm"]["scale"][1], eps),
                   positions=positions, allowed=allowed, sizes=sizes)
    y = (h3 + _ffn(at(layers["mlp"]["sub1"]), _rms_norm(h3, p["mlp_norm"]["scale"][1], eps))
         + shortcut)
    return y, chosen


def forward(params, input_ids, sizes: dict, *, positions=None, segment_ids=None):
    """Token ids (B, S) -> ``{"logits": float32 (B, S, V), "chosen": (L, B,
    S, routed + zero) 0/1}``."""
    b, s = input_ids.shape
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (b, s))
    idx = jnp.arange(s)
    allowed = jnp.broadcast_to(idx[None, :, None] >= idx[None, None, :], (b, s, s))
    if segment_ids is not None:
        allowed = allowed & (segment_ids[:, :, None] == segment_ids[:, None, :])
    chosen = []
    with jax.default_matmul_precision("highest"):
        x = _up(params["embed"]["embedding"][input_ids])
        n_layers = params["layers"]["attn_norm"]["scale"].shape[0]
        for i in range(n_layers):
            x, c = _double_layer(params["layers"], i, x, positions=positions,
                                 allowed=allowed, sizes=sizes)
            chosen.append(c)
        x = _rms_norm(x, params["final_norm"]["scale"], sizes["rms_norm_eps"])
        logits = x @ _up(params["lm_head"]["kernel"])
    return {"logits": logits, "chosen": jnp.stack(chosen)}
