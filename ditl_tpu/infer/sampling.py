"""Token sampling (L1) — jit-compatible, static-config branching.

Greedy, temperature, top-k, and nucleus (top-p) sampling over a (B, V) logits
slab. All control flow branches on *static* Python config values, so each
``GenerateConfig`` compiles to a straight-line XLA program — no data-dependent
Python control flow inside jit (SURVEY.md §7 design stance).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

__all__ = ["sample_logits", "shaped_logits"]


def shaped_logits(
    logits: jax.Array,
    temperature,
    *,
    top_k: int = 0,
    top_p=1.0,
) -> jax.Array:
    """(B, V) raw logits -> shaped logits under per-row temperature / top-k /
    top-p — exactly the distribution ``sample_logits``' traced-temperature
    path draws from. Exposed for speculative rejection sampling, which needs
    the PROBABILITIES (acceptance = p[draft]) rather than one draw. Rows
    with ``temperature <= 0`` get the clamped 1e-6 scale (callers handle
    the greedy limit explicitly)."""
    logits = logits.astype(jnp.float32)
    temperature = jnp.asarray(temperature, jnp.float32)
    scaled = logits / jnp.maximum(temperature, 1e-6)[..., None]
    if top_k > 0:
        scaled = _apply_top_k(scaled, min(top_k, logits.shape[-1]))
    per_row_p = not isinstance(top_p, (int, float))
    if per_row_p or top_p < 1.0:
        scaled = _apply_top_p(scaled, top_p)
    return scaled


def _apply_top_k(logits: jax.Array, k: int) -> jax.Array:
    """Mask everything below the k-th largest logit (per row)."""
    kth = jax.lax.top_k(logits, k)[0][..., -1:]  # (B, 1)
    return jnp.where(logits < kth, -jnp.inf, logits)


def _apply_top_p(logits: jax.Array, p) -> jax.Array:
    """Nucleus sampling: keep the smallest prefix of the sorted distribution
    whose cumulative probability exceeds ``p`` (always keeping the top token).
    ``p`` may be a float or a per-row (B,) array."""
    sorted_logits = jnp.sort(logits, axis=-1)[..., ::-1]  # descending
    probs = jax.nn.softmax(sorted_logits, axis=-1)
    cum = jnp.cumsum(probs, axis=-1)
    if not isinstance(p, (int, float)):
        # Rows with p >= 1 mean "disabled": use +inf so float cumsum error
        # can never mask extreme-tail tokens on those rows.
        p = jnp.asarray(p, jnp.float32)[..., None]
        p = jnp.where(p >= 1.0, jnp.inf, p)
    # Token i is kept if the cumulative mass *before* it is still < p.
    keep_sorted = (cum - probs) < p
    # Threshold = smallest kept logit; everything below it is masked.
    threshold = jnp.min(
        jnp.where(keep_sorted, sorted_logits, jnp.inf), axis=-1, keepdims=True
    )
    return jnp.where(logits < threshold, -jnp.inf, logits)


@jax.named_scope("sample")
def sample_logits(
    logits: jax.Array,
    rng: jax.Array,
    *,
    temperature: float = 0.0,
    top_k: int = 0,
    top_p: float = 1.0,
) -> jax.Array:
    """(B, V) float logits -> (B,) int32 token ids.

    ``temperature`` may be a static float (``0`` compiles to pure greedy
    argmax) or a traced (B,) array — per-row temperatures for continuous
    batching, where rows with ``temperature <= 0`` are greedy and the rest
    sample; both paths are computed and selected with ``where`` (static
    shapes, no data-dependent control flow).
    """
    logits = logits.astype(jnp.float32)
    if isinstance(temperature, (int, float)):
        if temperature == 0.0:
            return jnp.argmax(logits, axis=-1).astype(jnp.int32)
        logits = logits / temperature
        if top_k > 0:
            logits = _apply_top_k(logits, min(top_k, logits.shape[-1]))
        if top_p < 1.0:
            logits = _apply_top_p(logits, top_p)
        return jax.random.categorical(rng, logits, axis=-1).astype(jnp.int32)

    greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    scaled = shaped_logits(logits, temperature, top_k=top_k, top_p=top_p)
    temperature = jnp.asarray(temperature, jnp.float32)
    if rng.ndim >= 1:  # per-row keys (continuous batching: per-request seeds)
        sampled = jax.vmap(
            lambda k, row: jax.random.categorical(k, row).astype(jnp.int32)
        )(rng, scaled)
    else:
        sampled = jax.random.categorical(rng, scaled, axis=-1).astype(jnp.int32)
    return jnp.where(temperature <= 0.0, greedy, sampled)
