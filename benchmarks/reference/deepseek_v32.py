"""Plain reference of the DeepSeek-V3.2 decoder (deepseek-ai/DeepSeek-V3.2:
its ``config.json`` for the sizes, ``inference/model.py`` as recalled for the
rest: this sandbox has no network), independent of the code under test.

Straightforward ``jax.numpy`` in float32 at ``highest`` matmul precision: a
Python loop over the layers and over the held experts. No sort of pairs, no
grouped matmul, no kernel, no cache, no scan, no absorption and no gather:
keys and values are decompressed from the latent for every position,
attention runs per head over ALL positions under a mask, and the selection is
that mask (a dense pass under a mask is this file's way; the program never
attends to more than ``index_topk`` entries).

With ``n`` an RMSNorm (its own scale, eps from the config), ``x`` the stream,
``t`` a query position and ``s <= t`` a key position::

    h = x + Attn(n(x));   y = h + FFN(n(h));   final norm; untied head

    Attn(z): cq = n(z Wqa);  q = cq Wqb -> heads of [q_nope | q_rope]
             [ckv | kr] = z Wkva;  c = n(ckv);  [k_nope | v] = c Wkvb per head
             rotary on q_rope and on the ONE kr a token: neighbouring pairs
             (2i, 2i+1), theta from the config, YaRN: frequency i is
             f_i (1 - ramp_i) + f_i / factor ramp_i, ramp linear between the
             correction dimensions of beta_fast and beta_slow rotations over
             original_max_position_embeddings
             scores = (q_nope . k_nope + q_rope . kr) (nope + rope)^-0.5 m^2,
             m = 0.1 mscale_all_dim ln(factor) + 1
             softmax over the selected s only; out through Wo
    Indexer: qI = cq Wq_b -> index_n_heads heads of index_head_dim, rotary on
             the first qk_rope_head_dim of each
             kI = LayerNorm(z Wk) (scale and bias), one a token, rotary likewise
             w  = z Wproj index_n_heads^-0.5 index_head_dim^-0.5
             I[t, s] = sum_j w[t, j] relu(qI[t, j] . kI[s])
             S_t = the min(index_topk, t + 1) positions s <= t (same document)
             of largest I; ties to the lower position
    Router:  p = sigmoid_float32(u Wr) over n_routed_experts
             choice on p + b: a group's score is the sum of its two largest,
             the topk_group best of n_group groups stay, the num_experts_per_tok
             largest p + b inside them are chosen
             w_i = p_i / sum_chosen p * routed_scaling_factor (no bias in it)
             y = shared(u) + sum_i w_i E_i(u), SwiGLU throughout
    The first first_k_dense_replace layers have a dense SwiGLU FFN instead.

**The share.** ``sizes["experts_held"] = (first, count)``: only those routed
experts have weights; a chosen routed expert outside the range adds NOTHING
here, exactly as in the program: it is another chip's part of the sum. The
shared expert is on every chip. Given ``(0, n_routed_experts)`` this is the
uncut layer, and the 16 shares' routed parts plus the shared expert counted
once add up to it (``tests/test_deepseek.py``).

``selected=``: the program's own index sets ((L, B, S, S) bool) in place of
this file's, to tell attention's arithmetic from a flipped choice at the
selection's boundary (``benchmarks/dsa_check.py``). ``forward`` returns its
own sets under ``"selected"`` either way. Long samples are computed in blocks
of ``Q_BLOCK`` queries.

Departures from the published code, all of them choices of this reference
and the program alike (the configuration file lists them under ``assumed``):
- weights are seeded random values (the caller's);
- the indexer's q and k stay in the stream's precision where the published
  inference code quantises them to FP8 after a Hadamard rotation (the
  rotation is orthogonal and cancels in q . k);
- the rotary pairing inside the indexer is interleaved like the main
  attention's, on the first qk_rope_head_dim values of a head; recalled;
- kI's norm is a LayerNorm with scale and bias; recalled;
- the router computes in float32;
- the multi-token-prediction module is left out (it does not enter the
  logits).

Parameters come as the pytree the program uses (``layers`` -> ``dense`` /
``sparse``, each stacked on axis 0); each matrix is sliced out of its stack
where it is used and upcast on its own.

Hooks (``reference_check.compare``, ``dsa_check`` and ``flops.py`` ask for
them by name): ``forward``, ``check_sizes``, ``sizes``, ``perturb``,
``forward_flops_per_token``; ``loss`` for the tests.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
Q_BLOCK = 64

# ModelConfig field -> key of the published config.json it must equal.
PUBLISHED = {
    "hidden_size": "hidden_size",
    "intermediate_size": "intermediate_size",
    "expert_ffn_hidden_size": "moe_intermediate_size",
    "num_heads": "num_attention_heads",
    "q_lora_rank": "q_lora_rank",
    "kv_lora_rank": "kv_lora_rank",
    "qk_nope_head_dim": "qk_nope_head_dim",
    "qk_rope_head_dim": "qk_rope_head_dim",
    "v_head_dim": "v_head_dim",
    "index_n_heads": "index_n_heads",
    "index_head_dim": "index_head_dim",
    "index_topk": "index_topk",
    "num_experts": "n_routed_experts",
    "num_experts_per_tok": "num_experts_per_tok",
    "n_group": "n_group",
    "topk_group": "topk_group",
    "n_shared_experts": "n_shared_experts",
    "scoring_func": "scoring_func",
    "norm_topk_prob": "norm_topk_prob",
    "routed_scaling_factor": "routed_scaling_factor",
    "rms_norm_eps": "rms_norm_eps",
    "rope_theta": "rope_theta",
    "attention_bias": "attention_bias",
    "tie_embeddings": "tie_word_embeddings",
}
YARN = {
    "rope_yarn_factor": "factor",
    "rope_yarn_original_max_len": "original_max_position_embeddings",
    "rope_yarn_beta_fast": "beta_fast",
    "rope_yarn_beta_slow": "beta_slow",
    "rope_yarn_mscale_all_dim": "mscale_all_dim",
}


def check_sizes(cfg, config: dict) -> list[str]:
    """The program's ModelConfig against the configuration file: every
    published width as published, every cut as the file's ``cut`` states it
    (the published count stays beside it in the file)."""
    want = {field: config[key] for field, key in PUBLISHED.items()}
    want.update({field: config["rope_scaling"][key] for field, key in YARN.items()})
    cut = config["cut"]
    want.update(num_layers=cut["num_hidden_layers"], vocab_size=cut["vocab_size"],
                first_k_dense_replace=cut["first_k_dense_replace"],
                experts_held_first=cut["experts_held"][0],
                experts_held_count=cut["experts_held"][1],
                max_seq_len=cut.get("max_position_embeddings",
                                    config["max_position_embeddings"]),
                router_bias=True, zero_expert_num=0)
    bad = [f"{k}: program {getattr(cfg, k)!r}, configuration file {v!r}"
           for k, v in want.items() if getattr(cfg, k) != v]
    rs = config["rope_scaling"]
    # mscale / mscale_all_dim would scale cos and sin: 1 in the source, and
    # neither side computes another ratio
    if (rs["type"] != "yarn" or rs["mscale"] != rs["mscale_all_dim"]
            or config["topk_method"] != "noaux_tc"
            or config["hidden_act"] != "silu" or config["moe_layer_freq"] != 1
            or cut["num_nextn_predict_layers"] != 0):
        bad.append("the file states another rope scaling (or mscale other than "
                   "mscale_all_dim), choice method, activation, expert-layer frequency "
                   "or prediction depth than this reference computes")
    return bad


def sizes(cfg, config: dict) -> dict:
    """What ``forward`` needs besides the weights, as the program holds it
    (``check_sizes`` has held the program to the file)."""
    first = cfg.experts_held_first if cfg.experts_held_count else 0
    count = cfg.experts_held_count or cfg.num_experts
    return {"num_attention_heads": cfg.num_heads, "kv_lora_rank": cfg.kv_lora_rank,
            "qk_nope_head_dim": cfg.qk_nope_head_dim,
            "qk_rope_head_dim": cfg.qk_rope_head_dim, "v_head_dim": cfg.v_head_dim,
            "index_n_heads": cfg.index_n_heads, "index_head_dim": cfg.index_head_dim,
            "index_topk": cfg.index_topk,
            "rope_theta": cfg.rope_theta, "rms_norm_eps": cfg.rms_norm_eps,
            "rope_scaling": {key: getattr(cfg, field) for field, key in YARN.items()},
            "n_routed_experts": cfg.num_experts,
            "num_experts_per_tok": cfg.num_experts_per_tok,
            "n_group": cfg.n_group, "topk_group": cfg.topk_group,
            "norm_topk_prob": cfg.norm_topk_prob,
            "routed_scaling_factor": cfg.routed_scaling_factor,
            "experts_held": (first, count)}


def perturb(params, cfg, seed: int):
    """Every norm scale, the index key's bias and the router's selection bias
    moved away from what the initialiser gives them (1, 0 and 0), so that a
    path that drops one fails. The router's bias is drawn on the scale of the
    gaps between neighbouring sigmoid scores (256 scores spread over ~0.5:
    0.02), so it changes some of the choices and enters no weight.

    And the two published scales a checkpoint's weights have absorbed in
    training are folded into these seeded ones, which ``init_params`` draws at
    1 / sqrt(fan_in) like every family's: ``w_qb`` / ``m ** 2`` (YaRN's factor
    on the softmax scale gives seeded attention scores a standard deviation
    of 1.87 where every other family's start at 1) and the routed experts'
    ``w_down`` / ``routed_scaling_factor`` (renormalised weights x 2.5 give
    each chosen expert 0.31 of a token's FFN output, and seeded sigmoid
    scores tie far more often than a trained router's: one flipped choice
    moves a token's stream by a sixth). Program and reference compute the
    published arithmetic on the SAME weights; the configuration file's
    ``random_weights_start`` has the readings with and without."""
    key = jax.random.key(seed)
    n = iter(range(4400, 4500))

    def normal(shape, std, mean=0.0):
        k = jax.random.fold_in(key, next(n))
        return mean + std * jax.random.normal(k, shape, F32)

    def scale_like(w):
        return normal(w.shape, 0.3, 1.0).astype(w.dtype)

    def scaled(w, by):  # one fused pass: the expert stack is 1.9 GB at full width
        return jax.jit(lambda w: (w.astype(F32) * by).astype(w.dtype))(w)

    m2 = 1.0
    if cfg.rope_yarn_factor > 0 and cfg.rope_yarn_mscale_all_dim:
        m2 = (0.1 * cfg.rope_yarn_mscale_all_dim * math.log(cfg.rope_yarn_factor) + 1.0) ** 2
    routed = cfg.routed_scaling_factor if cfg.norm_topk_prob else 1.0

    def one(tree):
        tree = dict(tree)
        tree["attn_norm"] = {"scale": scale_like(tree["attn_norm"]["scale"])}
        tree["mlp_norm"] = {"scale": scale_like(tree["mlp_norm"]["scale"])}
        a, ix = tree["attn"], tree["index"]
        tree["attn"] = {**a, "q_norm": scale_like(a["q_norm"]),
                        "kv_norm": scale_like(a["kv_norm"]),
                        "w_qb": scaled(a["w_qb"], 1.0 / m2)}
        kn = ix["k_norm"]
        tree["index"] = {**ix, "k_norm": {
            "scale": scale_like(kn["scale"]),
            "bias": normal(kn["bias"].shape, 0.3).astype(kn["bias"].dtype)}}
        if "moe" in tree:
            bias = tree["moe"]["router_bias"]
            tree["moe"] = {**tree["moe"],
                           "router_bias": normal(bias.shape, 0.02).astype(bias.dtype),
                           "w_down": scaled(tree["moe"]["w_down"], 1.0 / routed)}
        return tree

    return {**params, "layers": {k: one(v) for k, v in params["layers"].items()},
            "final_norm": {"scale": scale_like(params["final_norm"]["scale"])}}


def forward_flops_per_token(config: dict, context_mean: float) -> float:
    """Forward matmul operations a token needs in the SHARE the configuration
    file cuts (its ``cut``): per layer the attention's and the indexer's
    projections, the index scores against ``context_mean`` keys, attention in
    the absorbed form over the min(index_topk, context_mean) selected entries
    (per head 2 x (kv_lora_rank + qk_rope_head_dim) + 2 x kv_lora_rank an
    entry), then the dense FFN, or the router, the shared expert and the
    expected held experts of the choices; then the head's slice."""
    d, f, fe = config["hidden_size"], config["intermediate_size"], config["moe_intermediate_size"]
    h, qr, kr = config["num_attention_heads"], config["q_lora_rank"], config["kv_lora_rank"]
    nope, rope, vd = (config["qk_nope_head_dim"], config["qk_rope_head_dim"],
                      config["v_head_dim"])
    hi, di = config["index_n_heads"], config["index_head_dim"]
    cut = config["cut"]
    routed = config["n_routed_experts"]
    attended = min(config["index_topk"], context_mean)
    attn = (2 * d * qr + 2 * qr * h * (nope + rope) + 2 * d * (kr + rope)
            + 2 * kr * h * (nope + vd) + 2 * h * vd * d
            + 2 * qr * hi * di + 2 * d * di + 2 * d * hi
            + context_mean * 2 * hi * di
            + attended * h * 2 * ((kr + rope) + kr))
    held = config["num_experts_per_tok"] * cut["experts_held"][1] / routed
    sparse = 2 * d * routed + (config["n_shared_experts"] + held) * 6 * d * fe
    n_dense = cut["first_k_dense_replace"]
    n_sparse = cut["num_hidden_layers"] - n_dense
    return (cut["num_hidden_layers"] * attn + n_dense * 6 * d * f + n_sparse * sparse
            + 2 * d * cut["vocab_size"])


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


def _rms_norm(x, scale, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * _up(scale)


def _layer_norm(x, scale, bias, eps):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean((x - mean) ** 2, axis=-1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + eps) * _up(scale) + _up(bias)


def yarn_inv_freq(dim: int, theta: float, rs: dict):
    """The closed form of the docstring: (dim / 2,) float32."""
    i = jnp.arange(dim // 2, dtype=F32)
    f = theta ** (-2.0 * i / dim)
    if not rs["factor"]:
        return f

    def corr(rot):
        return (dim * math.log(rs["original_max_position_embeddings"] / (rot * 2 * math.pi))
                / (2 * math.log(theta)))

    low = max(math.floor(corr(rs["beta_fast"])), 0)
    high = min(math.ceil(corr(rs["beta_slow"])), dim - 1)
    ramp = jnp.clip((i - low) / ((high - low) or 0.001), 0.0, 1.0)
    return f * (1.0 - ramp) + f / rs["factor"] * ramp


def _rope_pairs(x, positions, inv_freq):
    """x: (B, S, H, D); positions: (B, S). Neighbouring pairs (2i, 2i+1)
    rotate by ``position * inv_freq[i]``."""
    ang = positions[..., None].astype(F32) * inv_freq  # (B, S, D/2)
    cos, sin = jnp.cos(ang)[:, :, None, :], jnp.sin(ang)[:, :, None, :]
    even, odd = x[..., 0::2], x[..., 1::2]
    out = jnp.stack([even * cos - odd * sin, odd * cos + even * sin], axis=-1)
    return out.reshape(x.shape)


def select(scores, allowed, k: int):
    """(B, Q, S) index scores, ``allowed`` (B, Q, S) -> the mask of each
    query's ``min(k, allowed)`` largest allowed scores."""
    masked = jnp.where(allowed, scores, -jnp.inf)
    if scores.shape[-1] <= k:
        return allowed
    kth = jax.lax.top_k(masked, k)[0][..., -1:]  # the k-th largest (or -inf)
    above = masked > kth
    tie = (masked == kth) & allowed
    room = k - above.sum(axis=-1, keepdims=True)
    return above | (tie & (jnp.cumsum(tie, axis=-1) <= room))


def _query_latent(a, z, sizes):
    return _rms_norm(z @ _up(a["w_qa"]), a["q_norm"], sizes["rms_norm_eps"])


def _indexer(ix, cq, z, positions, sizes):
    """-> a function of a slice of queries: their index scores (B, Q, S)
    against every position."""
    b, s, _ = z.shape
    rope, hi, di = sizes["qk_rope_head_dim"], sizes["index_n_heads"], sizes["index_head_dim"]
    inv_freq = yarn_inv_freq(rope, sizes["rope_theta"], sizes["rope_scaling"])
    qi = (cq @ _up(ix["wq_b"])).reshape(b, s, hi, di)
    qi = jnp.concatenate([_rope_pairs(qi[..., :rope], positions, inv_freq),
                          qi[..., rope:]], axis=-1)
    ki = _layer_norm(z @ _up(ix["wk"]), ix["k_norm"]["scale"], ix["k_norm"]["bias"],
                     sizes["rms_norm_eps"])
    ki = jnp.concatenate([_rope_pairs(ki[:, :, None, :rope], positions, inv_freq)[:, :, 0],
                          ki[..., rope:]], axis=-1)
    w = (z @ _up(ix["w_proj"])) * (hi ** -0.5 * di ** -0.5)
    return lambda blk: jnp.einsum("bqhn,bqh->bqn", jax.nn.relu(
        jnp.einsum("bqhd,bnd->bqhn", qi[:, blk], ki)), w[:, blk])


def _attention(a, ix, z, *, positions, allowed, sizes, selected=None, scores_out=None):
    """The attention sublayer, weights ``a`` and indexer ``ix``, on the
    normed input ``z`` (B, S, D): (out, this layer's selected sets (B, S, S)).
    ``scores_out``: a list that receives the index scores (B, S, S), on the
    host (a long sample's are 272 MB a layer: the device has no room)."""
    b, s, _ = z.shape
    nh = sizes["num_attention_heads"]
    nope, rope, vd = sizes["qk_nope_head_dim"], sizes["qk_rope_head_dim"], sizes["v_head_dim"]
    r = sizes["kv_lora_rank"]
    rs = sizes["rope_scaling"]
    inv_freq = yarn_inv_freq(rope, sizes["rope_theta"], rs)
    scale = (nope + rope) ** -0.5
    if rs["factor"] and rs["mscale_all_dim"]:
        scale *= (0.1 * rs["mscale_all_dim"] * math.log(rs["factor"]) + 1.0) ** 2

    cq = _query_latent(a, z, sizes)
    q = (cq @ _up(a["w_qb"])).reshape(b, s, nh, nope + rope)
    q_nope, q_rope = q[..., :nope], _rope_pairs(q[..., nope:], positions, inv_freq)
    del q
    ckr = z @ _up(a["w_kva"])
    c = _rms_norm(ckr[..., :r], a["kv_norm"], sizes["rms_norm_eps"])
    # heads in front of positions, as the blocks below read them (a long
    # sample's keys and values are the largest arrays here: made once, in
    # the layout they are used in, with no copy of both halves together)
    w_kvb = _up(a["w_kvb"]).reshape(r, nh, nope + vd)
    k_nope = jnp.einsum("bsr,rhd->bhsd", c, w_kvb[..., :nope])
    v = jnp.einsum("bsr,rhd->bhsd", c, w_kvb[..., nope:])
    del w_kvb
    k_rope = _rope_pairs(ckr[:, :, None, r:], positions, inv_freq)[:, :, 0]  # one a token

    index_of = _indexer(ix, cq, z, positions, sizes)

    outs, sets, index_scores = [], [], []
    for q0 in range(0, s, Q_BLOCK):  # blocks of queries, all keys
        blk = slice(q0, min(q0 + Q_BLOCK, s))
        index = index_of(blk)
        if scores_out is not None:
            index_scores.append(np.asarray(index))
        mine = select(index, allowed[:, blk], sizes["index_topk"])
        sets.append(mine)
        use = mine if selected is None else selected[:, blk]
        scores = (jnp.einsum("bqhd,bhkd->bhqk", q_nope[:, blk], k_nope)
                  + jnp.einsum("bqhd,bkd->bhqk", q_rope[:, blk], k_rope)) * scale
        scores = jnp.where(use[:, None], scores, -jnp.inf)
        probs = jax.nn.softmax(scores, axis=-1)
        outs.append(jnp.einsum("bhqk,bhkd->bqhd", probs, v))
    out = jnp.concatenate(outs, axis=1).reshape(b, s, nh * vd)
    if scores_out is not None:
        scores_out.append(np.concatenate(index_scores, axis=1))
    return out @ _up(a["wo"]), jnp.concatenate(sets, axis=1)


def _layer(params, layer: int):
    """Layer ``layer`` of the model (the dense stack, then the sparse one):
    its attention's and its indexer's weights."""
    for kind in ("dense", "sparse"):
        stack = params["layers"][kind]
        depth = stack["attn_norm"]["scale"].shape[0]
        if layer < depth:
            return jax.tree.map(lambda w: w[layer], (stack["attn"], stack["index"]))
        layer -= depth
    raise IndexError("no such layer")


def index_scores(params, layer: int, z, sizes: dict):
    """Layer ``layer``'s index scores (B, S, S) float32, on the host, of every
    query against every position, from the NORMED input ``z`` (B, S, D) handed in:
    ``benchmarks/dsa_check.py`` hands the program's own, to tell what the
    program's indexer does with its input from what its input has become."""
    b, s, _ = z.shape
    positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (b, s))
    with jax.default_matmul_precision("highest"):
        a, ix = _layer(params, layer)
        z = _up(z)
        index_of = _indexer(ix, _query_latent(a, z, sizes), z, positions, sizes)
        return np.concatenate([np.asarray(index_of(slice(q0, min(q0 + Q_BLOCK, s))))
                               for q0 in range(0, s, Q_BLOCK)], axis=1)


def _ffn(m, u):
    return (jax.nn.silu(u @ _up(m["w_gate"])) * (u @ _up(m["w_up"]))) @ _up(m["w_down"])


def choose(p_biased, sizes):
    """(…, E) biased scores -> the chosen experts as a 0/1 mask (…, E): the
    group-limited top-k of the docstring."""
    e = p_biased.shape[-1]
    g = sizes["n_group"]
    if g:
        grouped = p_biased.reshape(*p_biased.shape[:-1], g, e // g)
        group_score = jax.lax.top_k(grouped, 2)[0].sum(axis=-1)  # (…, G)
        _, best = jax.lax.top_k(group_score, sizes["topk_group"])
        keep = jax.nn.one_hot(best, g, dtype=F32).sum(axis=-2) > 0  # (…, G)
        p_biased = jnp.where(jnp.repeat(keep, e // g, axis=-1), p_biased, -jnp.inf)
    _, top_i = jax.lax.top_k(p_biased, sizes["num_experts_per_tok"])
    return jax.nn.one_hot(top_i, e, dtype=F32).sum(axis=-2)


def _experts(m, i, u, sizes):
    """(B, S, D) -> layer ``i``'s expert block's output here, and the chosen
    experts as a 0/1 mask (B, S, routed). ``m``: the stacked leaves."""
    first, count = sizes["experts_held"]
    p = jax.nn.sigmoid(u @ _up(m["router"][i]))
    chosen = choose(p + _up(m["router_bias"][i]), sizes)
    weight = chosen * p
    if sizes["norm_topk_prob"]:
        weight = weight / weight.sum(axis=-1, keepdims=True)
    weight = weight * sizes["routed_scaling_factor"]
    out = _ffn(jax.tree.map(lambda w: w[i], m["shared"]), u)
    for e in range(count):  # the held ones; a routed expert held elsewhere adds nothing
        y = ((jax.nn.silu(u @ _up(m["w_gate"][i, e])) * (u @ _up(m["w_up"][i, e])))
             @ _up(m["w_down"][i, e]))
        out = out + weight[..., first + e:first + e + 1] * y
    return out, chosen


def forward(params, input_ids, sizes: dict, *, positions=None, segment_ids=None,
            selected=None, with_index_scores=False):
    """Token ids (B, S) -> ``{"logits": float32 (B, S, V), "chosen": (expert
    layers, B, S, routed) 0/1, "selected": (L, B, S, S) bool}``, and with
    ``with_index_scores`` ``"index_scores"``: a list of each layer's (B, S, S),
    on the host."""
    b, s = input_ids.shape
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (b, s))
    idx = jnp.arange(s)
    allowed = jnp.broadcast_to(idx[None, :, None] >= idx[None, None, :], (b, s, s))
    if segment_ids is not None:
        allowed = allowed & (segment_ids[:, :, None] == segment_ids[:, None, :])
    chosen, sets = [], []
    scores = [] if with_index_scores else None
    eps = sizes["rms_norm_eps"]
    with jax.default_matmul_precision("highest"):
        x = _up(params["embed"]["embedding"][input_ids])
        layer = 0
        for kind in ("dense", "sparse"):
            stack = params["layers"][kind]
            for i in range(stack["attn_norm"]["scale"].shape[0]):
                at = lambda tree: jax.tree.map(lambda w: w[i], tree)  # noqa: E731,B023
                out, mine = _attention(
                    at(stack["attn"]), at(stack["index"]),
                    _rms_norm(x, stack["attn_norm"]["scale"][i], eps),
                    positions=positions, allowed=allowed, sizes=sizes,
                    selected=None if selected is None else selected[layer],
                    scores_out=scores)
                sets.append(mine)
                h = x + out
                u = _rms_norm(h, stack["mlp_norm"]["scale"][i], eps)
                if kind == "dense":
                    x = h + _ffn(at(stack["mlp"]), u)
                else:
                    y, c = _experts(stack["moe"], i, u, sizes)
                    chosen.append(c)
                    x = h + y
                layer += 1
        x = _rms_norm(x, params["final_norm"]["scale"], eps)
        logits = x @ _up(params["lm_head"]["kernel"])
    out = {"logits": logits, "chosen": jnp.stack(chosen), "selected": jnp.stack(sets)}
    return {**out, "index_scores": scores} if with_index_scores else out
