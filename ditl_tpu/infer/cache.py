"""KV cache for incremental decoding (L1).

Layout mirrors the model's scanned-layer convention (models/llama.py): all
layers stacked on a leading ``layers`` axis so the decode forward scans
``(layer_params, layer_cache)`` together — one layer's HLO compiled once.

Shapes: ``k``/``v`` are ``(L, B, Smax, K, D)`` in the model's compute dtype
(bf16 on TPU — cache reads are the HBM-bandwidth cost of decoding, so half
the bytes is double the decode speed). With ``ModelConfig.kv_cache_dtype ==
"int8"`` the cache stores int8 values plus per-(layer, row, slot, head)
float32 scales — 8.25 bits/value vs bf16's 16, paying off exactly where
decode is cache-bandwidth-bound (long contexts, many slots). Quantization is
symmetric per-head absmax: one scale per (b, slot, kv_head) covering the D
lane values written together, so dequantization is a fused multiply on the
cache read.

Sharding: batch over the data/fsdp axes, KV heads over the tensor axis — the
same rule table as training (parallel/sharding.py), so a TP-sharded model
decodes with a TP-sharded cache and no resharding.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from ditl_tpu.config import ModelConfig

__all__ = ["init_cache", "cache_logical_axes", "write_kv", "read_kv", "scatter_tail"]


def init_cache(cfg: ModelConfig, batch_size: int, max_len: int) -> dict:
    """Zero-filled cache pytree for ``batch_size`` sequences of ≤ ``max_len``."""
    shape = (cfg.num_layers, batch_size, max_len, cfg.num_kv_heads, cfg.head_dim)
    if cfg.kv_cache_dtype == "int8":
        # Distinct scale arrays: sharing one buffer between both leaves breaks
        # donation (the same buffer would be donated twice per program call).
        return {
            "k": jnp.zeros(shape, jnp.int8),
            "v": jnp.zeros(shape, jnp.int8),
            "k_scale": jnp.ones(shape[:-1], jnp.float32),
            "v_scale": jnp.ones(shape[:-1], jnp.float32),
        }
    if cfg.kv_cache_dtype not in ("", "model"):
        raise ValueError(
            f"unknown kv_cache_dtype {cfg.kv_cache_dtype!r} ('', 'model', 'int8')"
        )
    dtype = jnp.dtype(cfg.dtype)
    return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}


def cache_logical_axes(cfg: ModelConfig, *, seq_sharded: bool = False) -> dict:
    """Logical axes for the cache pytree (same table as params/activations).
    ``seq_sharded`` splits the CONTEXT dim over the ``cache_seq`` rule
    (sequence mesh axis): per-device cache memory and attention reads drop
    by the shard factor, and decode merges per-shard partial softmax over
    ICI (ops/attention._seq_sharded_decode) — long-context serving beyond
    one chip's HBM."""
    seq = "cache_seq" if seq_sharded else None
    axes = ("layers", "batch", seq, "act_kv_heads", "head_dim")
    out = {"k": axes, "v": axes}
    if cfg.kv_cache_dtype == "int8":
        out["k_scale"] = axes[:-1]
        out["v_scale"] = axes[:-1]
    return out


def _scatter_rows(cache: jax.Array, chunk: jax.Array, idx: jax.Array) -> jax.Array:
    """Write ``chunk`` (B, S, ...) into ``cache`` (B, Smax, ...) at per-row
    slot offsets ``idx`` (B,). Used by the continuous-batching and
    speculative decode paths where each sequence sits at a different depth.

    Implemented as gather + select over the whole slot axis, NOT an XLA
    scatter: TPU lowers multi-row scatters poorly (serialized updates),
    while this form is a dense vectorized rewrite of the cache — and cache
    bytes are noise next to the weight reads that bound decode."""
    s = chunk.shape[1]
    smax = cache.shape[1]
    tail = (1,) * (cache.ndim - 2)  # broadcast over trailing (K, D, ...) dims
    rel = jnp.arange(smax, dtype=jnp.int32)[None, :] - idx[:, None]  # (B, Smax)
    in_chunk = (rel >= 0) & (rel < s)
    gathered = jnp.take_along_axis(
        chunk.astype(cache.dtype),
        jnp.clip(rel, 0, s - 1).reshape(rel.shape + tail),
        axis=1,
    )
    return jnp.where(in_chunk.reshape(in_chunk.shape + tail), gathered, cache)


@jax.named_scope("kv_write")
def scatter_tail(tail: jax.Array, chunk: jax.Array, off: jax.Array) -> jax.Array:
    """Write ``chunk`` (B, K, S, D) into the decode tail buffer ``tail``
    (B, K, T, D) at per-row column offsets ``off`` (B,) — the speculative
    verify's K+1-token write, where each slot sits at its own tail depth.
    Same dense gather+select formulation as ``_scatter_rows`` (axis moved
    to position 1; XLA fuses the transposes into the select)."""
    t = jnp.swapaxes(tail, 1, 2)  # (B, T, K, D)
    c = jnp.swapaxes(chunk, 1, 2)
    return jnp.swapaxes(_scatter_rows(t, c, off), 1, 2)


def _quantize(chunk: jax.Array) -> tuple[jax.Array, jax.Array]:
    """(B, S, K, D) -> int8 values + per-(B, S, K) float32 scales."""
    absmax = jnp.max(jnp.abs(chunk.astype(jnp.float32)), axis=-1)
    scale = jnp.where(absmax == 0.0, 1.0, absmax / 127.0)
    q = jnp.round(chunk.astype(jnp.float32) / scale[..., None])
    return jnp.clip(q, -127, 127).astype(jnp.int8), scale


def _write_one(cache: jax.Array, chunk: jax.Array, idx: jax.Array) -> jax.Array:
    if idx.ndim == 1:
        return _scatter_rows(cache, chunk, idx)
    pad = (0,) * (cache.ndim - 2)
    return jax.lax.dynamic_update_slice(
        cache, chunk.astype(cache.dtype), (0, idx) + pad
    )


@jax.named_scope("kv_write")
def write_kv(layer_cache: dict, k: jax.Array, v: jax.Array, idx: jax.Array) -> dict:
    """Write a (B, S, K, D) K/V chunk into one layer's cache slice at slot
    ``idx`` — scalar (lock-step decode: every row at the same depth) or (B,)
    (continuous batching: per-row depths, scatter write). Quantizes on the way
    in when the cache is int8."""
    idx = jnp.asarray(idx, jnp.int32)
    out = dict(layer_cache)
    if "k_scale" in layer_cache:
        k_q, k_s = _quantize(k)
        v_q, v_s = _quantize(v)
        out["k"] = _write_one(layer_cache["k"], k_q, idx)
        out["v"] = _write_one(layer_cache["v"], v_q, idx)
        out["k_scale"] = _write_one(layer_cache["k_scale"], k_s, idx)
        out["v_scale"] = _write_one(layer_cache["v_scale"], v_s, idx)
        return out
    out["k"] = _write_one(layer_cache["k"], k, idx)
    out["v"] = _write_one(layer_cache["v"], v, idx)
    return out


@jax.named_scope("kv_gather")
def read_kv(layer_cache: dict, dtype) -> tuple[jax.Array, jax.Array]:
    """One layer's full (B, Smax, K, D) K/V in the compute dtype; dequantizes
    int8 caches (XLA fuses the convert+scale into the attention matmul's
    operand read, so the HBM traffic stays int8-sized)."""
    k, v = layer_cache["k"], layer_cache["v"]
    if "k_scale" in layer_cache:
        k = (k.astype(jnp.float32) * layer_cache["k_scale"][..., None]).astype(dtype)
        v = (v.astype(jnp.float32) * layer_cache["v_scale"][..., None]).astype(dtype)
        return k, v
    return k.astype(dtype), v.astype(dtype)
