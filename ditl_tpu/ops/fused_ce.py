"""Fused (blockwise) cross-entropy: lm_head matmul + log-softmax, chunked.

The reference computes no loss at all on device (its loss helper is dead code,
ref ``src/utils.py:12-23``). The naive TPU loss path (train/step.py) projects
the final hidden states to logits of shape ``(B, S, V)`` in float32 — at
bench shapes (8 x 1024 x 32768) that is a 1 GiB HBM tensor written by the
forward and read again by the backward, plus its bf16 twin from the matmul.
HBM bandwidth, not FLOPs, pays for that.

This op never materializes the full logits. Tokens are processed in blocks of
``block_tokens``: each block's ``(block, V)`` logits live only inside one
``lax.scan`` step, reduced immediately to the block's summed NLL;
``jax.checkpoint`` around the block recomputes those logits during the
backward instead of saving them. Peak logits memory drops from ``B*S*V`` to
``block_tokens*V`` (32 MiB at the default block), while the matmuls stay
``(block, D) @ (D, V)`` — large, static, MXU-shaped.

The gradient needs no custom VJP: autodiff of the blockwise scan yields
exactly the classic ``(softmax - onehot) @ Wᵀ`` per block, with the head
gradient accumulated across blocks by the scan's cotangent carry.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

__all__ = ["fused_cross_entropy"]


@functools.partial(jax.jit, static_argnames=("block_tokens", "compute_dtype"))
@jax.named_scope("loss")
def fused_cross_entropy(
    x: jax.Array,  # (N, D) final hidden states (already final-normed)
    head: jax.Array,  # (D, V) lm head weights
    targets: jax.Array,  # (N,) int target ids
    mask: jax.Array,  # (N,) float 0/1 loss mask
    *,
    block_tokens: int = 1024,
    compute_dtype: jnp.dtype = jnp.bfloat16,
) -> jax.Array:
    """Summed masked NLL over all N tokens, without full-logit materialization.

    Callers divide by ``mask.sum()`` themselves (keeping this a pure sum makes
    the gradient-accumulation and data-parallel reductions exact).
    """
    n, d = x.shape
    block = min(block_tokens, n) if n > 0 else block_tokens
    pad = (-n) % block
    if pad:
        x = jnp.pad(x, ((0, pad), (0, 0)))
        targets = jnp.pad(targets, (0, pad))
        mask = jnp.pad(mask, (0, pad))  # padded tokens are masked out
    nb = (n + pad) // block
    xb = x.reshape(nb, block, d)
    tb = targets.reshape(nb, block).astype(jnp.int32)
    mb = mask.reshape(nb, block).astype(jnp.float32)

    def block_nll(head, x_blk, t_blk, m_blk):
        logits = jnp.einsum(
            "td,dv->tv",
            x_blk.astype(compute_dtype),
            head.astype(compute_dtype),
            preferred_element_type=jnp.float32,
        )  # (block, V) — lives only inside this scan step
        lse = jax.nn.logsumexp(logits, axis=-1)
        target_logit = jnp.take_along_axis(logits, t_blk[:, None], axis=1)[:, 0]
        return jnp.sum((lse - target_logit) * m_blk)

    # Recompute the block's logits in the backward pass instead of saving them.
    block_nll = jax.checkpoint(block_nll)

    def scan_step(nll_sum, xs):
        x_blk, t_blk, m_blk = xs
        return nll_sum + block_nll(head, x_blk, t_blk, m_blk), None

    nll_sum, _ = jax.lax.scan(scan_step, jnp.zeros((), jnp.float32), (xb, tb, mb))
    return nll_sum
