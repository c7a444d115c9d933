"""Plain reference of the Trinity-Mini decoder (arcee-ai/Trinity-Mini,
``model_type: afmoe``: its ``config.json`` for the sizes, the family's public
``afmoe`` modelling code in ``transformers`` as recalled for the rest: this
sandbox has no network), independent of the code under test.

Straightforward ``jax.numpy`` in float32 at ``highest`` matmul precision: a
Python loop over the layers and over the held experts. No kernel, no cache,
no page, no scan, no sort of pairs, no grouped matmul, no batching of
requests: attention runs over ALL positions under a mask, and the window is
part of that mask.

With ``N(.)`` an RMSNorm with its own learned weight (eps from the config, in
float32), ``x`` the stream, ``i`` a query position and ``j`` a key position::

    x0 = E[ids] * sqrt(hidden_size)                             (mup_enabled)
    h  = N_in(x);  q = h Wq (heads of head_dim), k = h Wk, v = h Wv (kv heads),
                   g = h Wg (heads x head_dim), no bias anywhere
    q  = N_q(q), k = N_k(k)  over each head's head_dim values, one weight each
    in a ``sliding_attention`` layer q and k are rotated (rotate-half: value d
    pairs with value d + head_dim / 2; theta from the config, no scaling); in a
    ``full_attention`` layer nothing positional is applied
    scores = q k^T / sqrt(head_dim); i sees j iff j <= i, both in the same
    document and, in a sliding layer, i - j < sliding_window
    a  = softmax(scores) v;  a = a * sigmoid(g);  x = x + N_post_attn(a Wo)
    h2 = N_pre_mlp(x);  x = x + N_post_mlp(F(h2))
    F: SwiGLU of intermediate_size in the first num_dense_layers layers; behind
       them s = sigmoid_float32(h2 Wr) over num_experts; the choice is the
       num_experts_per_tok largest of s + b (b the expert bias, for the choice
       only); w = s[choice], w = w / sum(w) (route_norm), w = route_scale w;
       F = SwiGLU_shared(h2) + sum_e w_e SwiGLU_e(h2), moe_intermediate_size wide
    logits = N_final(x) W_head (untied)

**The share.** ``sizes["experts_held"] = (first, count)``: only those routed
experts have weights; a chosen routed expert outside the range adds NOTHING
here, exactly as in the program: it is another chip's part of the sum. The
shared expert is on every chip. Given ``(0, num_experts)`` this is the uncut
layer, and the eight shares' routed parts plus the shared expert counted once
add up to it (``tests/test_trinity.py``; the sum is taken in front of
``N_post_mlp``, which is where the chips of a deployment would reduce).

``window=False`` leaves the window clause out of every layer's mask: the
control of ``benchmarks/window_check.py``, which has to FAIL the check.

Departures from the published description, all of them choices of this
reference and of the program alike (the configuration file lists them under
``assumed``): weights are seeded random values (the caller's); the attention
output gate, where it multiplies, the q/k norm over the head, the absence of
a positional term in full layers, the four norms and their order, the
embedding's factor, the bias for the choice only and the rotate-half pairing
are recalled from the family's modelling code, which ``config.json`` has no
key for; the router computes in float32; ``load_balance_coeff`` belongs to
training and is not computed.

Parameters come as the pytree the program uses (``layers`` -> ``dense`` /
``sparse``, each stacked on axis 0); each matrix is sliced out of its stack
where it is used and upcast on its own.

Hooks (``reference_check.compare``, ``window_check`` and ``flops.py`` ask for
them by name): ``forward``, ``check_sizes``, ``sizes``, ``perturb``,
``forward_flops_per_token``; ``loss`` for the tests.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

F32 = jnp.float32
Q_BLOCK = 512  # queries a block, so that a long sample's scores fit

# ModelConfig field -> config.json key, where the program has to be AS PUBLISHED
PUBLISHED = {
    "hidden_size": "hidden_size",
    "intermediate_size": "intermediate_size",
    "expert_ffn_hidden_size": "moe_intermediate_size",
    "num_heads": "num_attention_heads",
    "num_kv_heads": "num_key_value_heads",
    "head_dim": "head_dim",
    "num_experts": "num_experts",
    "num_experts_per_tok": "num_experts_per_tok",
    "n_shared_experts": "num_shared_experts",
    "scoring_func": "score_func",
    "norm_topk_prob": "route_norm",
    "routed_scaling_factor": "route_scale",
    "sliding_window": "sliding_window",
    "rms_norm_eps": "rms_norm_eps",
    "rope_theta": "rope_theta",
    "tie_embeddings": "tie_word_embeddings",
}
LETTER = {"sliding_attention": "w", "full_attention": "a"}


def check_sizes(cfg, config: dict) -> list[str]:
    """The program's ModelConfig against the configuration file: every
    published width as published, every cut as the file's ``cut`` states it
    (the published count stays beside it in the file)."""
    cut = config["cut"]
    want = {field: config[key] for field, key in PUBLISHED.items()}
    n = cut["num_hidden_layers"]
    want.update(
        num_layers=n, vocab_size=cut["vocab_size"],
        first_k_dense_replace=cut["num_dense_layers"],
        experts_held_first=cut["experts_held"][0], experts_held_count=cut["experts_held"][1],
        max_seq_len=config["max_position_embeddings"],
        layer_types="".join(LETTER[t] for t in config["layer_types"][:n]),
        embedding_multiplier=math.sqrt(config["hidden_size"]) if config["mup_enabled"] else 1.0,
        position_embedding="rope_window", attn_gate=True, sandwich_norm=True, qk_norm=True,
        router_bias=True, zero_expert_num=0, n_group=0, topk_group=0, attention_bias=False)
    bad = [f"{k}: program {getattr(cfg, k)!r}, configuration file {v!r}"
           for k, v in want.items() if getattr(cfg, k) != v]
    every = config["global_attn_every_n_layers"]
    if (config["hidden_act"] != "silu" or config["rope_scaling"] is not None
            or config["n_group"] != 1 or config["topk_group"] != 1
            or n % every or config["layer_types"] != (
                ["sliding_attention"] * (every - 1) + ["full_attention"]
            ) * (config["num_hidden_layers"] // every)):
        bad.append("the file states another activation, rope scaling, group limit or "
                   "layer pattern than this reference computes (whole periods only)")
    return bad


def sizes(cfg, config: dict) -> dict:
    """What ``forward`` needs besides the weights, as the program holds it
    (``check_sizes`` has held the program to the file)."""
    first = cfg.experts_held_first if cfg.experts_held_count else 0
    count = cfg.experts_held_count or cfg.num_experts
    return {"num_attention_heads": cfg.num_heads, "num_key_value_heads": cfg.num_kv_heads,
            "head_dim": cfg.head_dim, "rope_theta": cfg.rope_theta,
            "rms_norm_eps": cfg.rms_norm_eps, "sliding_window": cfg.sliding_window,
            "sliding": [c == "w" for c in cfg.layer_types],
            "embedding_multiplier": cfg.embedding_multiplier,
            "num_experts_per_tok": cfg.num_experts_per_tok,
            "route_norm": cfg.norm_topk_prob, "route_scale": cfg.routed_scaling_factor,
            "experts_held": (first, count)}


def perturb(params, cfg, seed: int):
    """Every norm weight and the router's bias moved away from what the
    initialiser gives them (1 and 0), so that a path that drops one fails.

    And what seeded weights need so that the comparison measures the
    precision of the arithmetic and not the start: 128 sigmoid scores of a
    seeded router lie ~0.005 apart at the boundary of the top 8, closer than
    a bfloat16 stream's rounding moves them, so a bfloat16 pass flips a
    choice in a large share of tokens and layers where a trained router's
    margins are wide; and ``init_params`` draws every matrix at 1 /
    sqrt(fan_in), so renormalised weights x ``route_scale`` give each flipped
    expert 0.35 of an FFN's output where training has absorbed the scale into
    the experts. On the chip at the published widths the reference check
    reads 7.06 / 6.81% as drawn (seeds 0 / 1), 1.56 / 1.57% with no routed
    expert at all, and against the 3% of ``reference_check.py`` (not this
    PR's) 2.71 / 2.41% with the fold below, 2.35 / 2.29% with the fold and
    this bias; weight-only int8 reads 5.35% there and fails, as it has to
    (PERF.md section 6, PR 48). So the routed experts' ``w_down`` is divided
    by ``route_scale`` (as ``deepseek_v32.perturb`` folds its scale), and the
    bias is drawn at 0.3, the spread of the sigmoid scores themselves: it
    then decides most choices (a bias dropped, or added to the weights,
    fails at once) and widens the margins. Program and reference compute the
    published arithmetic on the SAME weights; ``init_params`` is untouched."""
    key = jax.random.key(seed)
    n = iter(range(4800, 4900))

    def normal(shape, std, mean=0.0):
        return mean + std * jax.random.normal(jax.random.fold_in(key, next(n)), shape, F32)

    def scale_like(w):
        return normal(w.shape, 0.3, 1.0).astype(w.dtype)

    def scaled(w, by):  # one fused pass: the expert stack is 3 GB at full width
        return jax.jit(lambda w: (w.astype(F32) * by).astype(w.dtype))(w)

    routed = cfg.routed_scaling_factor if cfg.norm_topk_prob else 1.0

    def one(tree):
        tree = dict(tree)
        for name in ("attn_norm", "mlp_norm", "attn_post_norm", "mlp_post_norm"):
            tree[name] = {"scale": scale_like(tree[name]["scale"])}
        a = tree["attn"]
        tree["attn"] = {**a, "q_norm": scale_like(a["q_norm"]), "k_norm": scale_like(a["k_norm"])}
        if "moe" in tree:
            bias = tree["moe"]["router_bias"]
            tree["moe"] = {**tree["moe"],
                           "router_bias": normal(bias.shape, 0.3).astype(bias.dtype),
                           "w_down": scaled(tree["moe"]["w_down"], 1.0 / routed)}
        return tree

    return {**params, "layers": {k: one(v) for k, v in params["layers"].items()},
            "final_norm": {"scale": scale_like(params["final_norm"]["scale"])}}


def forward_flops_per_token(config: dict, context_mean: float) -> float:
    """Forward matmul operations a token needs in the SHARE the configuration
    file cuts (its ``cut``): per layer the four projections and the gate's,
    attention over ``context_mean`` keys in a full layer and over at most
    ``sliding_window`` in a sliding one, then the dense FFN, or the router, the
    shared expert and the expected held experts of the choices; then the
    head's slice."""
    d, f, fe = config["hidden_size"], config["intermediate_size"], config["moe_intermediate_size"]
    h, kv, hd = (config["num_attention_heads"], config["num_key_value_heads"],
                 config["head_dim"])
    cut = config["cut"]
    n = cut["num_hidden_layers"]
    kinds = config["layer_types"][:n]
    proj = 2 * d * hd * (3 * h + 2 * kv)  # q, the gate, o; k and v
    seen = lambda kind: (min(config["sliding_window"], context_mean)  # noqa: E731
                         if kind == "sliding_attention" else context_mean)
    attn = sum(proj + 4 * seen(kind) * h * hd for kind in kinds)
    held = config["num_experts_per_tok"] * cut["experts_held"][1] / config["num_experts"]
    sparse = 2 * d * config["num_experts"] + (config["num_shared_experts"] + held) * 6 * d * fe
    n_dense = cut["num_dense_layers"]
    return attn + n_dense * 6 * d * f + (n - n_dense) * sparse + 2 * d * cut["vocab_size"]


def loss(outputs, input_ids, loss_mask, sizes: dict):
    """Mean next-token cross-entropy over the masked positions (the tests'
    hook: this family has no trainer cell)."""
    logits = outputs["logits"] if isinstance(outputs, dict) else outputs
    logp = jax.nn.log_softmax(logits[:, :-1], axis=-1)
    nll = -jnp.take_along_axis(logp, input_ids[:, 1:, None], axis=-1)[..., 0]
    m = loss_mask[:, 1:]
    return (nll * m).sum() / jnp.maximum(m.sum(), 1.0)


def _up(w):
    return w.astype(F32)


def _rms_norm(x, weight, eps):
    x = _up(x)
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * _up(weight)


def _rotate_half(x, positions, theta: float):
    """(B, S, H, D) rotated at ``positions`` (B, S): value d pairs with value
    d + D / 2, frequency ``theta ** (-2 d / D)``."""
    half = x.shape[-1] // 2
    inv = 1.0 / (theta ** (jnp.arange(half, dtype=F32) / half))
    ang = positions.astype(F32)[..., None] * inv  # (B, S, D / 2)
    cos, sin = jnp.cos(ang)[:, :, None, :], jnp.sin(ang)[:, :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def _attention(a, z, *, positions, allowed, sliding: bool, sizes):
    """The attention sublayer's output in front of ``N_post_attn``: ``z`` the
    normed input (B, S, D), ``allowed`` (B, S, S) this layer's mask."""
    b, s, _ = z.shape
    h, kv, hd = sizes["num_attention_heads"], sizes["num_key_value_heads"], sizes["head_dim"]
    eps = sizes["rms_norm_eps"]
    q = _rms_norm((z @ _up(a["wq"])).reshape(b, s, h, hd), a["q_norm"], eps)
    k = _rms_norm((z @ _up(a["wk"])).reshape(b, s, kv, hd), a["k_norm"], eps)
    v = (z @ _up(a["wv"])).reshape(b, s, kv, hd)
    gate = jax.nn.sigmoid(z @ _up(a["wg"]))
    if sliding:
        q = _rotate_half(q, positions, sizes["rope_theta"])
        k = _rotate_half(k, positions, sizes["rope_theta"])
    k = jnp.repeat(k, h // kv, axis=2)  # query head j reads kv head j // (h / kv)
    v = jnp.repeat(v, h // kv, axis=2)
    outs = []
    for q0 in range(0, s, Q_BLOCK):
        q1 = min(q0 + Q_BLOCK, s)
        scores = jnp.einsum("bqhd,bkhd->bhqk", q[:, q0:q1], k) / math.sqrt(hd)
        scores = jnp.where(allowed[:, None, q0:q1], scores, -jnp.inf)
        outs.append(jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(scores, axis=-1), v))
    out = jnp.concatenate(outs, axis=1).reshape(b, s, h * hd)
    return (out * gate) @ _up(a["wo"])


def _ffn(m, u):
    return (jax.nn.silu(u @ _up(m["w_gate"])) * (u @ _up(m["w_up"]))) @ _up(m["w_down"])


def experts(m, i, u, sizes, *, shared: bool = True):
    """(B, S, D) -> layer ``i``'s expert block's output here, in front of
    ``N_post_mlp``, and the chosen experts as a 0/1 mask (B, S, routed).
    ``m``: the stacked leaves. ``shared=False`` leaves the shared expert out
    (the tests add the shares up and count it once)."""
    first, count = sizes["experts_held"]
    s = jax.nn.sigmoid(u @ _up(m["router"][i]))
    _, top_i = jax.lax.top_k(s + _up(m["router_bias"][i]), sizes["num_experts_per_tok"])
    chosen = jax.nn.one_hot(top_i, s.shape[-1], dtype=F32).sum(axis=-2)
    weight = chosen * s
    if sizes["route_norm"]:
        weight = weight / weight.sum(axis=-1, keepdims=True)
    weight = weight * sizes["route_scale"]
    out = _ffn(jax.tree.map(lambda w: w[i], m["shared"]), u) if shared else jnp.zeros_like(u)
    for e in range(count):  # the held ones; a routed expert held elsewhere adds nothing
        y = ((jax.nn.silu(u @ _up(m["w_gate"][i, e])) * (u @ _up(m["w_up"][i, e])))
             @ _up(m["w_down"][i, e]))
        out = out + weight[..., first + e:first + e + 1] * y
    return out, chosen


def forward(params, input_ids, sizes: dict, *, positions=None, segment_ids=None,
            window: bool = True):
    """Token ids (B, S) -> ``{"logits": float32 (B, S, V), "chosen": (expert
    layers, B, S, routed) 0/1}``."""
    b, s = input_ids.shape
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (b, s))
    idx = jnp.arange(s)
    causal = jnp.broadcast_to(idx[None, :, None] >= idx[None, None, :], (b, s, s))
    if segment_ids is not None:
        causal = causal & (segment_ids[:, :, None] == segment_ids[:, None, :])
    near = (idx[:, None] - idx[None, :] < sizes["sliding_window"])[None]
    chosen = []
    eps = sizes["rms_norm_eps"]
    with jax.default_matmul_precision("highest"):
        x = _up(params["embed"]["embedding"][input_ids]) * sizes["embedding_multiplier"]
        layer = 0
        for kind in ("dense", "sparse"):
            stack = params["layers"].get(kind)
            for i in range(stack["attn_norm"]["scale"].shape[0] if stack else 0):
                at = lambda tree: jax.tree.map(lambda w: w[i], tree)  # noqa: E731,B023
                sliding = sizes["sliding"][layer]
                out = _attention(
                    at(stack["attn"]), _rms_norm(x, stack["attn_norm"]["scale"][i], eps),
                    positions=positions, sizes=sizes, sliding=sliding,
                    allowed=causal & near if sliding and window else causal)
                x = x + _rms_norm(out, stack["attn_post_norm"]["scale"][i], eps)
                u = _rms_norm(x, stack["mlp_norm"]["scale"][i], eps)
                if kind == "dense":
                    y = _ffn(at(stack["mlp"]), u)
                else:
                    y, c = experts(stack["moe"], i, u, sizes)
                    chosen.append(c)
                x = x + _rms_norm(y, stack["mlp_post_norm"]["scale"][i], eps)
                layer += 1
        x = _rms_norm(x, params["final_norm"]["scale"], eps)
        logits = x @ _up(params["lm_head"]["kernel"])
    return {"logits": logits, "chosen": jnp.stack(chosen) if chosen else None}
