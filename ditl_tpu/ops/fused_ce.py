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

Under a mesh that shards the head (``embed`` or ``vocab`` rule on axes of
total size k > 1) the loss is vocabulary-parallel, as Megatron's is: inside a
``shard_map`` each chip holds ``head[:, V/k]`` and every token of its block,
computes ``(block, V/k)`` logits, and the block's log-sum-exp and target
logit are merged from per-chip ``(block,)`` maxima and sums (``pmax``/``psum``).
What crosses chips a step: the head re-laid from its stored layout to
``(D, V/k)`` once and its gradient back (all-to-all), the hidden states
gathered over the batch axes that carry vocabulary here and their gradient
reduce-scattered, and ``(block,)`` vectors per block. Never a ``(block, V)``
logits block, which GSPMD left to itself all-reduces whole (the head's stored
split is along the contracting ``D``). Tokens stay sharded over the batch
axes that do not carry vocabulary.
"""

from __future__ import annotations

import functools
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ditl_tpu.parallel.sharding import DEFAULT_RULES, mesh_axes_size

__all__ = ["LossPartition", "fused_cross_entropy", "loss_partition"]


class LossPartition(NamedTuple):
    """How the fused loss meets a mesh that shards the head. Tokens arrive
    split over ``batch_axes``; the stored head's ``D`` is split over
    ``embed_axes`` and its ``V`` over ``vocab_axes``; the loss runs with ``V``
    split over both (``v_axes``, k ways) and ``D`` whole."""

    mesh: Any
    batch_axes: tuple[str, ...]
    embed_axes: tuple[str, ...]
    vocab_axes: tuple[str, ...]

    @property
    def v_axes(self) -> tuple[str, ...]:
        # vocab's own first: they are the major split of the stored V
        return self.vocab_axes + self.embed_axes

    @property
    def k(self) -> int:
        return mesh_axes_size(self.mesh, self.v_axes)

    def __str__(self) -> str:
        return f"vocab:{'+'.join(self.v_axes)}x{self.k}"


def _axes(mesh, rule) -> tuple[str, ...]:
    """A rule's mesh axes that actually split anything on this mesh."""
    names = (rule,) if isinstance(rule, str) else tuple(rule or ())
    return tuple(a for a in names if mesh.shape.get(a, 1) > 1)


def loss_partition(mesh, rules: dict[str, Any] | None) -> LossPartition | None:
    """The vocabulary-parallel layout this mesh and rule table call for, or
    None where the head is whole on every chip (no mesh, one chip, pure data
    parallelism, pipeline rules) and where tokens are sequence-sharded (ring
    attention meshes keep the GSPMD-partitioned loss). ``str()`` of the result
    (or ``"local"``) is the ``loss_partition`` a run records."""
    if mesh is None:
        return None
    rules = rules if rules is not None else DEFAULT_RULES
    vocab_axes = _axes(mesh, rules.get("vocab"))
    embed_axes = tuple(
        a for a in _axes(mesh, rules.get("embed")) if a not in vocab_axes
    )
    if not vocab_axes + embed_axes or _axes(mesh, rules.get("seq")):
        return None
    return LossPartition(
        mesh, _axes(mesh, rules.get("batch")), embed_axes, vocab_axes
    )


def _logits(head, x_blk, compute_dtype):
    """One block's float32 logits — they live only inside one scan step."""
    return jnp.einsum(
        "td,dv->tv",
        x_blk.astype(compute_dtype),
        head.astype(compute_dtype),
        preferred_element_type=jnp.float32,
    )


def _block_nll(head, x_blk, t_blk, m_blk, *, compute_dtype):
    logits = _logits(head, x_blk, compute_dtype)  # (block, V)
    lse = jax.nn.logsumexp(logits, axis=-1)
    target_logit = jnp.take_along_axis(logits, t_blk[:, None], axis=1)[:, 0]
    return jnp.sum((lse - target_logit) * m_blk)


def _block_nll_vocab(head, x_blk, t_blk, m_blk, *, compute_dtype, axes, k, vocab):
    """``_block_nll`` on one chip's ``(D, V/k)`` slice of the head: the same
    float32 logits and log-sum-exp, merged over ``axes`` from ``(block,)``
    vectors. The result is this chip's tokens' NLL, equal on every chip of
    ``axes``."""
    v_loc = head.shape[1]
    logits = _logits(head, x_blk, compute_dtype)  # (block, V/k)
    cols = jax.lax.axis_index(axes) * v_loc + jnp.arange(v_loc, dtype=jnp.int32)
    if v_loc * k != vocab:
        # columns the head was padded with to divide over the chips
        logits = jnp.where(cols < vocab, logits, -jnp.inf)
    m = jax.lax.pmax(jax.lax.stop_gradient(logits.max(axis=-1)), axes)
    sum_exp = jnp.sum(jnp.exp(logits - m[:, None]), axis=-1)
    # the chip whose slice holds the target contributes its logit, the rest 0
    target_logit = jnp.sum(
        jnp.where(cols == t_blk[:, None], logits, 0.0), axis=-1
    )
    sum_exp, target_logit = jax.lax.psum((sum_exp, target_logit), axes)
    return jnp.sum((m + jnp.log(sum_exp) - target_logit) * m_blk)


def _scan_blocks(block_nll, x, head, targets, mask, block_tokens):
    """Σ ``block_nll`` over blocks of ``block_tokens`` tokens, each block's
    logits recomputed in the backward pass instead of saved."""
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

    block_nll = jax.checkpoint(block_nll)

    def scan_step(nll_sum, xs):
        x_blk, t_blk, m_blk = xs
        return nll_sum + block_nll(head, x_blk, t_blk, m_blk), None

    nll_sum, _ = jax.lax.scan(scan_step, jnp.zeros((), jnp.float32), (xb, tb, mb))
    return nll_sum


@functools.partial(
    jax.jit,
    static_argnames=("block_tokens", "compute_dtype", "partition"),
)
@jax.named_scope("loss")
def fused_cross_entropy(
    x: jax.Array,  # (N, D) final hidden states (already final-normed)
    head: jax.Array,  # (D, V) lm head weights
    targets: jax.Array,  # (N,) int target ids
    mask: jax.Array,  # (N,) float 0/1 loss mask
    *,
    block_tokens: int = 1024,
    compute_dtype: jnp.dtype = jnp.bfloat16,
    partition: LossPartition | None = None,
) -> jax.Array:
    """Summed masked NLL over all N tokens, without full-logit materialization.

    Callers divide by ``mask.sum()`` themselves (keeping this a pure sum makes
    the gradient-accumulation and data-parallel reductions exact).

    ``partition`` is ``loss_partition(mesh, rules)``: None runs the whole head
    on every chip (and leaves any partitioning to GSPMD), otherwise the loss
    is vocabulary-parallel over its mesh as the module docstring describes.
    """
    if partition is None:
        return _scan_blocks(
            functools.partial(_block_nll, compute_dtype=compute_dtype),
            x, head, targets, mask, block_tokens,
        )
    mesh, batch_axes, embed_axes, vocab_axes = partition
    v_axes, k = partition.v_axes, partition.k
    shared = tuple(a for a in batch_axes if a in v_axes)
    token_axes = tuple(a for a in batch_axes if a not in v_axes)
    vocab = head.shape[1]
    # Every chip needs an equal share: tokens over the batch axes (padding is
    # masked out), head columns over the vocabulary axes (padding is -inf).
    pad = (-x.shape[0]) % mesh_axes_size(mesh, batch_axes)
    if pad:
        x = jnp.pad(x, ((0, pad), (0, 0)))
        targets = jnp.pad(targets, (0, pad))
        mask = jnp.pad(mask, (0, pad))
    if vocab % k:
        head = jnp.pad(head, ((0, 0), (0, (-vocab) % k)))

    def local(x, head, targets, mask):
        # The collectives, stated: tokens gathered over the axes that carry
        # vocabulary here (transpose: d_x reduce-scattered), the head re-laid
        # from (D/e, V/v) to (D, V/k) (transpose: d_head re-laid back).
        if shared:
            x, targets, mask = (
                jax.lax.all_gather(a, shared, axis=0, tiled=True)
                for a in (x, targets, mask)
            )
        if embed_axes:
            head = jax.lax.all_to_all(head, embed_axes, 1, 0, tiled=True)
        nll = _scan_blocks(
            functools.partial(
                _block_nll_vocab, compute_dtype=compute_dtype,
                axes=v_axes, k=k, vocab=vocab,
            ),
            x, head, targets, mask, block_tokens,
        )
        return jax.lax.psum(nll, token_axes) if token_axes else nll

    tokens = P(batch_axes or None)
    return jax.shard_map(
        local, mesh=mesh,
        in_specs=(
            P(batch_axes or None, None),
            P(embed_axes or None, vocab_axes or None),
            tokens, tokens,
        ),
        out_specs=P(), check_vma=False,
    )(x, head, targets.astype(jnp.int32), mask.astype(jnp.float32))
