"""GPipe-style pipeline parallelism over the ``stage`` mesh axis.

The reference has no model and therefore no pipeline parallelism (SURVEY.md §2
parallelism checklist: "TP, PP, SP, EP ... all absent"); this module is part
of the TPU build's full parallelism menu. Design, TPU-first:

- The stacked layer parameters (leading ``layers`` dim, models/llama.py) are
  sharded over ``stage``: each stage holds ``L / n_stages`` contiguous layers,
  fully materialized (GPipe memory layout — pipeline replaces FSDP as the
  weight-sharding strategy; see ``PIPELINE_RULES``).
- The batch is split into ``n_microbatches`` microbatches that flow through
  the stages. Every device runs the same compiled program (SPMD): a
  ``lax.scan`` over ``n_microbatches + n_stages - 1`` ticks, where each tick
  applies the stage's local layers (an inner ``lax.scan``) and rotates
  activations to the next stage with ``lax.ppermute`` — XLA lowers the
  neighbor permute to ICI/DCN sends, exactly like the ring-attention rotation
  (ops/ring_attention.py).
- Bubble fraction is the GPipe ``(n_stages-1)/(n_ticks)``; garbage flows
  through the bubble slots and is never read (stage 0 overwrites its inbox
  with the next microbatch; the last stage only records ticks that carry a
  finished microbatch).
- The whole schedule is differentiable (scan + ppermute + where), so the same
  code path serves training; the backward pass is the reverse pipeline XLA
  derives from the forward scan.

Composability: ``stage`` composes with the batch axes (``data``, ``fsdp`` —
the latter acting as plain data parallelism here, since ``PIPELINE_RULES``
un-shards parameters). It does not compose with ``tensor``/``sequence``/
``expert`` inside the pipelined region — those require GSPMD propagation,
which ``shard_map`` regions deliberately bypass; ``pipeline_apply`` validates
this at trace time.
"""

from __future__ import annotations

import functools
from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ditl_tpu.parallel.sharding import DEFAULT_RULES

__all__ = ["PIPELINE_RULES", "pipeline_rules", "pipeline_apply"]


def pipeline_rules(base: dict[str, Any] | None = None) -> dict[str, Any]:
    """Sharding rules for pipelined runs: layers -> stage, weights otherwise
    replicated (each stage holds its layers whole), batch axes untouched."""
    rules = dict(base if base is not None else DEFAULT_RULES)
    rules.update(
        layers="stage",
        embed=None,
        heads=None,
        kv_heads=None,
        mlp=None,
        vocab=None,
        expert=None,
        seq=None,
        act_heads=None,
        act_kv_heads=None,
        act_mlp=None,
        act_vocab=None,
    )
    return rules


PIPELINE_RULES = pipeline_rules()


def _batch_axes(rules: dict[str, Any]) -> Any:
    return rules.get("batch", ("data", "fsdp"))


def pipeline_apply(
    layer_fn: Callable[[jax.Array, Any, Any], tuple[jax.Array, jax.Array]],
    stacked_params: Any,
    x: jax.Array,  # (B, S, D) global activations entering the first layer
    extras: Any,  # pytree of (B, ...) arrays consumed by every layer (positions, segment_ids)
    *,
    mesh: jax.sharding.Mesh,
    rules: dict[str, Any] | None = None,
    n_microbatches: int | None = None,
    axis_name: str = "stage",
) -> tuple[jax.Array, jax.Array]:
    """Run ``x`` through all layers, pipelined over the ``stage`` mesh axis.

    ``layer_fn(x_mb, one_layer_params, extras_mb) -> (x_mb, aux_scalar)``
    applies a single decoder layer to one microbatch. ``stacked_params`` is
    the layer pytree with the leading ``layers`` dim (stage-sharded by the
    caller's train-state shardings). Returns the final activations (B, S, D)
    and the mean-over-microbatches of the summed per-layer aux scalars —
    matching the non-pipelined ``lax.scan``'s ``sum(aux)`` semantics.
    """
    rules = rules if rules is not None else PIPELINE_RULES
    n_stages = mesh.shape[axis_name]
    for ax in ("tensor", "sequence", "expert"):
        if ax in mesh.shape and mesh.shape[ax] > 1:
            raise ValueError(
                f"pipeline parallelism does not compose with mesh axis "
                f"{ax!r} > 1 (got {mesh.shape[ax]}) inside the pipelined region"
            )
    b = x.shape[0]
    m = n_microbatches or n_stages
    if b % m:
        raise ValueError(f"batch {b} not divisible by n_microbatches {m}")
    batch_ax = _batch_axes(rules)
    batch_ax = batch_ax if isinstance(batch_ax, tuple) else (batch_ax,)
    dp = 1
    for ax in batch_ax:
        dp *= mesh.shape.get(ax, 1)
    if (b // m) % dp:
        raise ValueError(
            f"microbatch size {b // m} (batch {b} / {m} microbatches) must be "
            f"divisible by the data-parallel size {dp}"
        )
    n_layers = jax.tree.leaves(stacked_params)[0].shape[0]
    if n_layers % n_stages:
        raise ValueError(
            f"num_layers {n_layers} not divisible by {n_stages} pipeline stages"
        )

    def split(a):
        return a.reshape((m, b // m) + a.shape[1:])

    x_mb = split(x)
    extras_mb = jax.tree.map(split, extras)

    batch = _batch_axes(rules)
    x_spec = P(None, batch, *([None] * (x.ndim - 1)))
    param_specs = jax.tree.map(
        lambda p: P(axis_name, *([None] * (p.ndim - 1))), stacked_params
    )
    extras_specs = jax.tree.map(
        lambda e: P(None, batch, *([None] * (e.ndim - 2))), extras_mb
    )

    stage_prog = functools.partial(
        _stage_program,
        layer_fn,
        axis_name=axis_name,
        n_stages=n_stages,
        m=m,
        batch_axes=tuple(ax for ax in batch_ax if mesh.shape.get(ax, 1) > 1),
    )
    out_mb, aux = jax.shard_map(
        stage_prog,
        mesh=mesh,
        in_specs=(param_specs, x_spec, extras_specs),
        out_specs=(x_spec, P()),
        check_vma=False,
    )(stacked_params, x_mb, extras_mb)
    return out_mb.reshape((b,) + x.shape[1:]), aux


def _stage_program(
    layer_fn, local_params, x_st, extras_st, *, axis_name, n_stages, m, batch_axes
):
    """The per-stage SPMD program: GPipe tick loop over the microbatch queue."""
    s_idx = jax.lax.axis_index(axis_name)
    n_ticks = m + n_stages - 1
    perm = [(i, (i + 1) % n_stages) for i in range(n_stages)]

    def tick(carry, t):
        buf, out, aux_sum = carry
        # Stage 0 pulls the next microbatch from its queue; other stages use
        # the activation rotated in from the previous stage.
        inj = jax.lax.dynamic_index_in_dim(
            x_st, jnp.clip(t, 0, m - 1), 0, keepdims=False
        )
        buf = jnp.where(s_idx == 0, inj, buf)
        # This tick, stage s works on microbatch t - s (bubble ticks work on
        # garbage that is masked out below and never emitted).
        my_mb = t - s_idx
        ex = jax.tree.map(
            lambda e: jax.lax.dynamic_index_in_dim(
                e, jnp.clip(my_mb, 0, m - 1), 0, keepdims=False
            ),
            extras_st,
        )

        def one_layer(h, lp):
            return layer_fn(h, lp, ex)

        buf, aux = jax.lax.scan(one_layer, buf, local_params)
        valid = (my_mb >= 0) & (my_mb < m)
        aux_sum = aux_sum + jnp.where(valid, jnp.sum(aux), 0.0)

        # The last stage records finished microbatches before the rotation.
        out_idx = jnp.clip(t - (n_stages - 1), 0, m - 1)
        write = (s_idx == n_stages - 1) & (t >= n_stages - 1)
        out = jnp.where(
            write, jax.lax.dynamic_update_index_in_dim(out, buf, out_idx, 0), out
        )
        buf = jax.lax.ppermute(buf, axis_name, perm)
        return (buf, out, aux_sum), None

    buf0 = jnp.zeros_like(x_st[0])
    out0 = jnp.zeros_like(x_st)
    (_, out, aux_sum), _ = jax.lax.scan(
        tick, (buf0, out0, jnp.zeros((), jnp.float32)), jnp.arange(n_ticks)
    )
    # Results live on the last stage only; broadcast them to every stage so
    # the loss head (outside the shard_map) sees stage-replicated activations.
    out = jnp.where(s_idx == n_stages - 1, out, jnp.zeros_like(out))
    out = jax.lax.psum(out, axis_name)
    # Each stage summed aux over its own layers; psum completes the layer sum,
    # /m converts the sum over microbatches into the batch-level aux. The aux
    # is declared replicated (out_specs P()), so it must also be reduced over
    # the data axes — each data shard computed aux on its own batch slice.
    aux = jax.lax.psum(aux_sum, axis_name) / m
    if batch_axes:
        aux = jax.lax.pmean(aux, batch_axes)
    return out, aux
