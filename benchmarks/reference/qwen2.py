"""Plain reference of the Qwen2 decoder (Qwen/Qwen2-*: arXiv:2407.10671, and
``modeling_qwen2.py`` in Hugging Face transformers), independent of the code
under test.

Straightforward ``jax.numpy`` in float32 at ``highest`` matmul precision: no
kernel, no cache, no batching tricks, no scan. One token row at a time would
do; a small batch is kept only so that the sample can hold packed documents.

What it computes, per layer: RMSNorm -> q/k/v projections WITH bias (o has
none) -> rotary embedding (theta from the config, rotate-half convention:
the two halves of each head, not interleaved pairs) -> grouped-query causal
attention (each kv head serves ``num_attention_heads / num_key_value_heads``
query heads, 7 in both published sizes) restricted to the token's own packed
document -> o projection -> residual -> RMSNorm -> SwiGLU MLP -> residual.
Then a final RMSNorm and the head: the transposed embedding when
``tie_word_embeddings``, its own matrix otherwise. The loss is the mean
next-token cross-entropy over the masked positions.

Departures from the published model: none in the mathematics. Weights are
seeded random values (the caller's), and sliding-window attention is not
implemented because the published configs switch it off
(``use_sliding_window: false``).

Parameters come as the pytree the program uses (layers stacked on axis 0),
because the same seeded values have to go through both sides; each layer is
sliced out and upcast on its own, so a 7B-wide model in bfloat16 fits one
chip beside its float32 working copy of one layer.

A family is its reference module. Besides ``forward`` and ``loss``, what
``reference_check.compare`` asks of the module a configuration's
``"reference"`` names:

- ``check_sizes(cfg, config) -> [problems]``: the program's ModelConfig
  against the published sizes of the configuration file;
- ``sizes(cfg, config) -> dict``: everything ``forward`` and ``loss`` need
  besides the weights, under the published config's keys;
- optional, ``perturb(params, cfg, seed) -> params``: what the program's
  initialiser leaves at a value that would hide a dropped term (here the
  zero biases);
- optional, ``forward_flops_per_token(config, context_mean)``: ``flops.py``
  uses it where a family's count is not the dense decoder's (Qwen2's is: no
  export).
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
}


def check_sizes(cfg, config: dict) -> list[str]:
    """The program's ModelConfig against the published sizes in the
    configuration file: a width that differs is an error, not a note. Two
    sizes config.json has no key for are fixed by the architecture: biases on
    q/k/v, and a head as wide as hidden / heads."""
    want = {field: config[key] for field, key in PUBLISHED.items()}
    want["head_dim"] = config["hidden_size"] // config["num_attention_heads"]
    want["attention_bias"] = True
    return [f"{k}: program {getattr(cfg, k)!r}, configuration file {v!r}"
            for k, v in want.items() if getattr(cfg, k) != v]


def perturb(params, cfg, seed: int):
    """The q/k/v biases made non-zero: the program's initialiser zeros them,
    and a zero bias would let a dropped bias pass."""
    attn = dict(params["layers"]["attn"])
    for i, name in enumerate(("bq", "bk", "bv")):
        k = jax.random.fold_in(jax.random.key(seed), 1000 + i)
        attn[name] = (0.1 * jax.random.normal(k, attn[name].shape, F32)
                      ).astype(attn[name].dtype)
    return {**params, "layers": {**params["layers"], "attn": attn}}


def sizes(cfg, config: dict) -> dict:
    """What ``forward`` needs besides the weights, as the program holds it
    (``check_sizes`` has held the program to the file)."""
    return {"num_attention_heads": cfg.num_heads,
            "num_key_value_heads": cfg.num_kv_heads, "head_dim": cfg.head_dim,
            "rope_theta": cfg.rope_theta, "rms_norm_eps": cfg.rms_norm_eps,
            "tie_word_embeddings": cfg.tie_embeddings}


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


def _layer(p, x, *, positions, allowed, n_heads, n_kv, head_dim, theta, eps):
    b, s, _ = x.shape
    a = p["attn"]
    h = _rms_norm(x, p["attn_norm"]["scale"], eps)
    q = (h @ a["wq"] + a["bq"]).reshape(b, s, n_heads, head_dim)
    k = (h @ a["wk"] + a["bk"]).reshape(b, s, n_kv, head_dim)
    v = (h @ a["wv"] + a["bv"]).reshape(b, s, n_kv, head_dim)
    q, k = _rope(q, positions, theta), _rope(k, positions, theta)
    group = n_heads // n_kv
    k = jnp.repeat(k, group, axis=2)  # kv head j serves query heads j*group..
    v = jnp.repeat(v, group, axis=2)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) / jnp.sqrt(F32(head_dim))
    scores = jnp.where(allowed[:, None, :, :], scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    attn = jnp.einsum("bhqk,bkhd->bqhd", probs, v).reshape(b, s, n_heads * head_dim)
    x = x + attn @ a["wo"]
    h = _rms_norm(x, p["mlp_norm"]["scale"], eps)
    m = p["mlp"]
    return x + (jax.nn.silu(h @ m["w_gate"]) * (h @ m["w_up"])) @ m["w_down"]


def forward(params, input_ids, sizes: dict, *, positions=None, segment_ids=None):
    """Token ids (B, S) -> float32 logits (B, S, V).

    ``sizes``: ``num_attention_heads``, ``num_key_value_heads``, ``head_dim``,
    ``rope_theta``, ``rms_norm_eps``, ``tie_word_embeddings`` — the keys of
    the published config.json (``head_dim`` = hidden / heads)."""
    up = lambda t: jax.tree.map(lambda w: w.astype(F32), t)  # noqa: E731
    b, s = input_ids.shape
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (b, s))
    idx = jnp.arange(s)
    allowed = (idx[None, :, None] >= idx[None, None, :])  # causal (1, S, S)
    allowed = jnp.broadcast_to(allowed, (b, s, s))
    if segment_ids is not None:
        allowed = allowed & (segment_ids[:, :, None] == segment_ids[:, None, :])
    with jax.default_matmul_precision("highest"):
        x = params["embed"]["embedding"][input_ids].astype(F32)
        n_layers = params["layers"]["attn_norm"]["scale"].shape[0]
        for i in range(n_layers):
            layer = up(jax.tree.map(lambda w: w[i], params["layers"]))
            x = _layer(
                layer, x, positions=positions, allowed=allowed,
                n_heads=sizes["num_attention_heads"],
                n_kv=sizes["num_key_value_heads"], head_dim=sizes["head_dim"],
                theta=sizes["rope_theta"], eps=sizes["rms_norm_eps"],
            )
        x = _rms_norm(x, params["final_norm"]["scale"].astype(F32),
                      sizes["rms_norm_eps"])
        if sizes["tie_word_embeddings"]:
            head = params["embed"]["embedding"].astype(F32).T
        else:
            head = params["lm_head"]["kernel"].astype(F32)
        return x @ head


def loss(outputs, input_ids, loss_mask, sizes: dict | None = None):
    """Mean next-token cross-entropy over the masked positions. ``outputs``
    is whatever ``forward`` returned (here the logits; a family whose loss
    has more terms returns a dict with ``"logits"`` and reads the rest)."""
    targets = input_ids[:, 1:]
    mask = loss_mask[:, 1:].astype(F32)
    lg = outputs[:, :-1]
    logz = jax.nn.logsumexp(lg, axis=-1)
    tgt = jnp.take_along_axis(lg, targets[..., None], axis=-1)[..., 0]
    return ((logz - tgt) * mask).sum() / jnp.maximum(mask.sum(), 1.0)
