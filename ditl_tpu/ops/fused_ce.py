"""Fused (blockwise) cross-entropy: lm_head matmul + log-softmax, chunked.

The reference computes no loss at all on device (its loss helper is dead code,
ref ``src/utils.py:12-23``). The naive TPU loss path (train/step.py) projects
the final hidden states to logits of shape ``(B, S, V)`` in float32 — at
bench shapes (8 x 1024 x 32768) that is a 1 GiB HBM tensor written by the
forward and read again by the backward, plus its bf16 twin from the matmul.
HBM bandwidth, not FLOPs, pays for that.

This op never materializes the full logits. Tokens are processed in blocks of
``block_tokens``: each block's ``(block, V)`` logits live only inside one
``lax.scan`` step, reduced immediately to the block's summed NLL. Peak logits
memory drops from ``B*S*V`` to ``block_tokens*V`` (32 MiB at the default
block), while the matmuls stay ``(block, D) @ (D, V)`` — large, static,
MXU-shaped.

The gradient is a ``jax.custom_vjp`` around the scan. The loss is a pure sum,
so its cotangent is one scalar ``g`` and everything the gradient needs is
known the first time a block's logits exist. Differentiated, the ONE scan
forms each block's logits once, its log-sum-exp and target logit (the NLL),
then ``dl = (softmax - onehot) * mask`` and from it ``d_x_blk = dl @ headᵀ``
(stacked, in ``x``'s dtype) and ``d_head += x_blkᵀ @ dl`` (carried, in the
head's dtype): three head matmuls a block, operands and accumulation as
autodiff's transposes of the logits matmul have them. The residuals are
``d_x`` ``(N, D)`` and ``d_head`` ``(D, V)``, both for ``g = 1``; the backward
rule multiplies them by ``g`` and does nothing else. (Autodiff of the scan
would have to save every block's logits or, with the blocks checkpointed,
form them a second time in a backward loop: four matmuls a block and two
loops.) Called for its value alone (``make_eval_step``, the benchmark's
reference check) the op is the plain scan, one matmul a block. Second
derivatives are given up.

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
axes that do not carry vocabulary. The VJP wraps the whole ``shard_map``: the
differentiated pass states the gradients' collectives itself (each chip's
``dl`` covers its own ``V/k`` columns, so ``d_head`` is local and ``d_x`` a
partial sum over the vocabulary axes) and hands back arrays laid out as ``x``
and the head arrived, so the cotangent is the caller's scalar and no
transpose rule of ``shard_map`` has a say in what it is worth on a chip.
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


def _columns(v_loc, axes):
    """The vocabulary ids of this chip's ``v_loc`` columns of the head."""
    cols = jnp.arange(v_loc, dtype=jnp.int32)
    return cols + jax.lax.axis_index(axes) * v_loc if axes else cols


def _lse_and_target(logits, t_blk):
    """A block's log-sum-exp and target logit over the whole vocabulary."""
    lse = jax.nn.logsumexp(logits, axis=-1)
    target_logit = jnp.take_along_axis(logits, t_blk[:, None], axis=1)[:, 0]
    return logits, lse, target_logit


def _lse_and_target_vocab(logits, t_blk, *, axes, k, vocab):
    """``_lse_and_target`` from one chip's ``(block, V/k)`` columns: the same
    float32 log-sum-exp, merged over ``axes`` from ``(block,)`` vectors, equal
    on every chip of ``axes``. Columns the head was padded with to divide over
    the chips come back as ``-inf``."""
    v_loc = logits.shape[1]
    cols = _columns(v_loc, axes)
    if v_loc * k != vocab:
        logits = jnp.where(cols < vocab, logits, -jnp.inf)
    m = jax.lax.pmax(jax.lax.stop_gradient(logits.max(axis=-1)), axes)
    sum_exp = jnp.sum(jnp.exp(logits - m[:, None]), axis=-1)
    # the chip whose slice holds the target contributes its logit, the rest 0
    target_logit = jnp.sum(
        jnp.where(cols == t_blk[:, None], logits, 0.0), axis=-1
    )
    sum_exp, target_logit = jax.lax.psum((sum_exp, target_logit), axes)
    return logits, m + jnp.log(sum_exp), target_logit


def _scan_blocks(lse_and_target, x, head, targets, mask, *, block_tokens,
                 compute_dtype, axes, grads):
    """Σ NLL over blocks of ``block_tokens`` tokens, one scan. With ``grads``
    also the loss's gradients for a cotangent of 1, formed from each block's
    logits while they exist: ``(nll_sum, d_x, d_head)``, ``d_head`` covering
    this chip's columns and tokens and ``d_x`` this chip's columns alone."""
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

    def scan_step(carry, xs):
        x_blk, t_blk, m_blk = xs
        if grads:
            # Two matmuls read the block's rows: sliced out of ``xb`` once,
            # both get a plain operand (left alone, the TPU compiler fuses
            # the slice into each and tiles them worse: 1.67 against 1.47 ms
            # a block for the logits, 1.95 against 1.61 for d_head, on four
            # v5e chips at 7B widths; PERF.md section 6, PR 50).
            x_blk = jax.lax.optimization_barrier(x_blk)
        logits, lse, target_logit = lse_and_target(
            _logits(head, x_blk, compute_dtype), t_blk)
        nll = jnp.sum((lse - target_logit) * m_blk)
        if not grads:
            return carry + nll, None
        nll_sum, d_head = carry
        hit = _columns(head.shape[1], axes) == t_blk[:, None]
        dl = (jnp.exp(logits - lse[:, None]) - hit) * m_blk[:, None]
        # The two products as autodiff's transposes of the logits matmul
        # state them: float32 ``dl`` against the ``compute_dtype`` operand,
        # float32 accumulation, rounded to the operand's dtype.
        d_x_blk = jax.lax.dot_general(
            dl, head.astype(compute_dtype), (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ).astype(compute_dtype).astype(x.dtype)
        d_head_blk = jax.lax.dot_general(
            dl, x_blk.astype(compute_dtype), (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        ).T.astype(compute_dtype).astype(head.dtype)
        return (nll_sum + nll, d_head + d_head_blk), d_x_blk

    zero = jnp.zeros((), jnp.float32)
    if not grads:
        return jax.lax.scan(scan_step, zero, (xb, tb, mb))[0]
    (nll_sum, d_head), d_x = jax.lax.scan(
        scan_step, (zero, jnp.zeros_like(head)), (xb, tb, mb))
    return nll_sum, d_x.reshape(nb * block, d)[:n], d_head


def _loss(x, head, targets, mask, block_tokens, compute_dtype, partition, *,
          grads):
    """The summed NLL, whole head or vocabulary-parallel; with ``grads`` the
    triple ``_scan_blocks`` describes, ``d_x`` and ``d_head`` summed over the
    chips and laid out as ``x`` and ``head`` arrived."""
    scan = functools.partial(
        _scan_blocks, block_tokens=block_tokens, compute_dtype=compute_dtype,
        grads=grads)
    if partition is None:
        return scan(_lse_and_target, x, head, targets, mask, axes=())
    mesh, batch_axes, embed_axes, vocab_axes = partition
    v_axes, k = partition.v_axes, partition.k
    shared = tuple(a for a in batch_axes if a in v_axes)
    token_axes = tuple(a for a in batch_axes if a not in v_axes)
    unshared = tuple(a for a in v_axes if a not in shared)
    n, vocab = x.shape[0], head.shape[1]
    # Every chip needs an equal share: tokens over the batch axes (padding is
    # masked out), head columns over the vocabulary axes (padding is -inf).
    pad = (-n) % mesh_axes_size(mesh, batch_axes)
    if pad:
        x = jnp.pad(x, ((0, pad), (0, 0)))
        targets = jnp.pad(targets, (0, pad))
        mask = jnp.pad(mask, (0, pad))
    if vocab % k:
        head = jnp.pad(head, ((0, 0), (0, (-vocab) % k)))

    def local(x, head, targets, mask):
        # The collectives, stated: tokens gathered over the axes that carry
        # vocabulary here (d_x reduce-scattered back), the head re-laid from
        # (D/e, V/v) to (D, V/k) (d_head re-laid back).
        if shared:
            x, targets, mask = (
                jax.lax.all_gather(a, shared, axis=0, tiled=True)
                for a in (x, targets, mask)
            )
        if embed_axes:
            head = jax.lax.all_to_all(head, embed_axes, 1, 0, tiled=True)
        out = scan(
            functools.partial(
                _lse_and_target_vocab, axes=v_axes, k=k, vocab=vocab),
            x, head, targets, mask, axes=v_axes,
        )
        if not grads:
            return jax.lax.psum(out, token_axes) if token_axes else out
        nll, d_x, d_head = out
        if shared:
            d_x = jax.lax.psum_scatter(
                d_x, shared, scatter_dimension=0, tiled=True)
        if unshared:
            d_x = jax.lax.psum(d_x, unshared)
        if token_axes:
            nll, d_head = jax.lax.psum((nll, d_head), token_axes)
        if embed_axes:
            d_head = jax.lax.all_to_all(d_head, embed_axes, 0, 1, tiled=True)
        return nll, d_x, d_head

    tokens = P(batch_axes or None)
    x_spec = P(batch_axes or None, None)
    head_spec = P(embed_axes or None, vocab_axes or None)
    out = jax.shard_map(
        local, mesh=mesh,
        in_specs=(x_spec, head_spec, tokens, tokens),
        out_specs=(P(), x_spec, head_spec) if grads else P(),
        check_vma=False,
    )(x, head, targets.astype(jnp.int32), mask.astype(jnp.float32))
    if not grads:
        return out
    nll, d_x, d_head = out
    return nll, d_x[:n], d_head[:, :vocab]


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6))
def _nll_sum(x, head, targets, mask, block_tokens, compute_dtype, partition):
    return _loss(x, head, targets, mask, block_tokens, compute_dtype,
                 partition, grads=False)


def _nll_sum_fwd(x, head, targets, mask, block_tokens, compute_dtype, partition):
    nll, d_x, d_head = _loss(x, head, targets, mask, block_tokens,
                             compute_dtype, partition, grads=True)
    return nll, (d_x, d_head)


def _nll_sum_bwd(block_tokens, compute_dtype, partition, residuals, g):
    # the loss is a sum, so its cotangent is one scalar: nothing is left to
    # do but scale; targets and mask get no gradient
    return (*((g * r).astype(r.dtype) for r in residuals), None, None)


_nll_sum.defvjp(_nll_sum_fwd, _nll_sum_bwd)


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

    Differentiable once, with respect to ``x`` and ``head``.
    """
    return _nll_sum(x, head, targets, mask, block_tokens, compute_dtype,
                    partition)
