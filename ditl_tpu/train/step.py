"""Loss + jitted/sharded train and eval steps (L1/L5).

The reference's intended-but-dead loss loop (``process_batch``, ref
``src/utils.py:12-23``) fabricated random logits and cross-entropied them
against sentiment labels, never updating anything. Here the step is real:
next-token cross-entropy over the local model, value_and_grad, optax update —
compiled once with ``jax.jit`` against explicit NamedShardings so GSPMD emits
the DP gradient all-reduce / FSDP all-gather+reduce-scatter / TP collectives
implied by the mesh, and donated so state is updated in place in HBM.

Gradient accumulation (``TrainConfig.grad_accum_steps``) runs microbatches
through ``lax.scan`` inside the compiled step — device-resident, no host
round-trips between microbatches.
"""

from __future__ import annotations

from typing import Any, Callable

import jax
import jax.numpy as jnp

from ditl_tpu.config import ModelConfig, TrainConfig
from ditl_tpu.models import llama
from ditl_tpu.parallel.sharding import DEFAULT_RULES, named_sharding_tree
from ditl_tpu.train.state import TrainState, make_optimizer, state_logical_axes

__all__ = [
    "loss_fn",
    "compute_params",
    "make_train_step",
    "make_multi_step",
    "make_eval_step",
    "batch_logical_axes",
]


def loss_fn(
    params: Any,
    batch: dict[str, jax.Array],
    cfg: ModelConfig,
    *,
    mesh=None,
    rules=None,
) -> tuple[jax.Array, dict[str, jax.Array]]:
    """Masked next-token cross-entropy (float32 logits), plus the MoE router
    load-balancing aux term when the model is sparse.

    ``cfg.loss_impl == "fused"`` routes through the blockwise fused
    lm-head+CE (ops/fused_ce.py) — same value, no (B, S, V) logits tensor."""
    if cfg.loss_impl not in ("naive", "fused"):
        raise ValueError(f"unknown loss_impl {cfg.loss_impl!r} (naive|fused)")
    if cfg.retention_layer:
        raise ValueError(
            "a stack of retention layers (layer_types 'r', models/retention.py) is served, "
            "not trained: its prefill scan carries no packed documents and has no "
            "backward pass of its own yet (infer/server.py --engine continuous "
            "--cache-mode paged serves it)")
    targets = batch["input_ids"][:, 1:]
    mask = batch["loss_mask"][:, 1:].astype(jnp.float32)
    n_tokens = jnp.maximum(mask.sum(), 1.0)
    fused = cfg.loss_impl == "fused"
    sparse = cfg.num_experts > 0
    counted = "moe_load_max_over_mean" in moe_metric_names(cfg, mesh)
    out, aux, *moe_counts = llama.forward(
        params,
        batch["input_ids"],
        cfg,
        positions=batch.get("positions"),
        segment_ids=batch.get("segment_ids"),
        mesh=mesh,
        rules=rules,
        with_aux=True,
        return_hidden=fused,
        # the router's load-balancing term sees the tokens the loss counts
        token_mask=batch["loss_mask"] if sparse else None,
        with_moe_counts=counted,
    )
    with jax.named_scope("loss"):
        if fused:
            from ditl_tpu.ops.fused_ce import fused_cross_entropy, loss_partition

            d = out.shape[-1]
            nll_sum = fused_cross_entropy(
                out[:, :-1].reshape(-1, d),
                llama.head_weights(params, cfg),
                targets.reshape(-1).astype(jnp.int32),
                mask.reshape(-1),
                block_tokens=cfg.loss_block_tokens,
                compute_dtype=jnp.dtype(cfg.dtype),
                partition=loss_partition(mesh, rules),
            )
            ce = nll_sum / n_tokens
        else:
            logits = out[:, :-1]
            logz = jax.nn.logsumexp(logits, axis=-1)
            target_logit = jnp.take_along_axis(
                logits, targets[..., None].astype(jnp.int32), axis=-1
            )[..., 0]
            nll = (logz - target_logit) * mask
            ce = nll.sum() / n_tokens
    metrics = {"loss": ce, "n_tokens": mask.sum()}
    if not sparse:
        return ce, metrics
    # E * sum_e f_e P_e per layer, averaged over the layers (1 when balanced)
    metrics["router_aux_loss"] = aux / cfg.num_layers
    if counted:
        from ditl_tpu.models.moe import shares_experts, split_counts

        # (expert layers, E), or a share's held experts beside the zero-compute
        # and absent totals; the load ratio is over the experts held here
        held, n_zero, n_absent = split_counts(moe_counts[0].astype(jnp.float32), cfg)
        metrics["moe_load_max_over_mean"] = jnp.mean(
            held.max(axis=-1) / jnp.maximum(held.mean(axis=-1), 1e-9))
        if shares_experts(cfg):  # held pairs over T x k, live tokens
            metrics["moe_held_assign_share"] = held.sum() / jnp.maximum(
                held.sum() + n_zero.sum() + n_absent.sum(), 1.0)
    if not cfg.router_aux_coef:  # no auxiliary term (noaux_tc): the reading stays
        return ce, metrics
    return ce + cfg.router_aux_coef * metrics["router_aux_loss"], metrics


def compute_params(params: Any, cfg: ModelConfig) -> Any:
    """The float32 master parameters cast to the compute dtype ONCE a step:
    per-use casts inside the layers re-read the 4-byte masters at every matmul
    (forward and backward). Gradients flow back through the cast (bfloat16
    cotangents cast to float32), which is the precision the bfloat16 matmuls
    produced anyway. Norm scales stay float32: the model contract computes
    norms in float32 (``llama.rms_norm``) and they never pass through a
    matmul, so rounding them would be a pure precision loss and would make
    train numerics diverge from eval's."""
    cd = jnp.dtype(cfg.dtype)
    if cd == jnp.float32:
        return params

    def cast(path, p):
        if any(getattr(k, "key", None) and "norm" in k.key for k in path):
            return p
        return p.astype(cd) if p.dtype == jnp.float32 else p

    return jax.tree_util.tree_map_with_path(cast, params)


def moe_metric_names(cfg: ModelConfig, mesh) -> tuple[str, ...]:
    """What a model with experts adds to the step's metrics (and so to the
    ``metrics_file`` rows). The assignment counts behind the load ratio do
    not ride the pipeline schedule."""
    if cfg.num_experts == 0:
        return ()
    if mesh is not None and mesh.shape.get("stage", 1) > 1:
        return ("router_aux_loss",)
    from ditl_tpu.models.moe import shares_experts

    return ("router_aux_loss", "moe_load_max_over_mean",
            *(("moe_held_assign_share",) if shares_experts(cfg) else ()))


def flash_metric_names(cfg: ModelConfig, mesh, rules: dict, batch) -> tuple[str, ...]:
    """What a step adds to its metrics where its attention is the flash
    kernel over packed rows: the causally reachable blocks of the (query
    block, key block) rectangle over the step's batch, those the kernels'
    predicate keeps, and the grid steps the forward kernel takes, a head: the
    length of its work list, equal to the kept blocks since the grid visits
    nothing else (``ops/flash_attention.block_counts``; once a step, not once
    a layer).
    Nothing where another path runs: no segment ids, a sharded sequence (ring
    attention's own loop), a length the kernel cannot tile."""
    from ditl_tpu.ops import flash_attention as fa
    from ditl_tpu.parallel.sharding import mesh_axes_size

    seg = batch.get("segment_ids")
    if cfg.attention_impl != "flash" or seg is None:
        return ()
    if mesh is not None and mesh_axes_size(mesh, rules.get("seq")) > 1:
        return ()
    s = seg.shape[-1]
    if not fa.supports(s, s, cfg.head_dim, cfg.flash_block_q or 512,
                       cfg.flash_block_kv or 512, cfg.v_head_dim or None):
        return ()
    return ("flash_blocks_reachable", "flash_blocks_needed", "flash_steps_walked")


def batch_logical_axes(example_batch: dict[str, Any]) -> dict[str, tuple]:
    return {k: ("batch",) + (None,) * (v.ndim - 1) for k, v in example_batch.items()}


def _build_step_fn(
    model_cfg: ModelConfig,
    train_cfg: TrainConfig,
    mesh,
    rules: dict,
) -> Callable[[TrainState, dict], tuple[TrainState, dict]]:
    """The un-jitted single train step (loss -> grads -> optax update)."""
    tx = None

    def get_tx(params):
        nonlocal tx
        if tx is None:
            tx = make_optimizer(train_cfg, params)
        return tx

    accum = train_cfg.grad_accum_steps
    moe_names = moe_metric_names(model_cfg, mesh)

    def single_loss(params, batch):
        return loss_fn(compute_params(params, model_cfg), batch, model_cfg,
                       mesh=mesh, rules=rules)

    def train_step(state: TrainState, batch: dict) -> tuple[TrainState, dict]:
        tx = get_tx(state.params)
        if accum > 1:
            # (B, ...) -> (accum, B/accum, ...): scan microbatches on device.
            micro = jax.tree.map(
                lambda x: x.reshape((accum, x.shape[0] // accum) + x.shape[1:]), batch
            )

            def micro_step(carry, mb):
                grads_acc, loss_acc, tok_acc, moe_acc = carry
                (loss, aux), grads = jax.value_and_grad(single_loss, has_aux=True)(
                    state.params, mb
                )
                grads_acc = jax.tree.map(jnp.add, grads_acc, grads)
                moe_acc = tuple(a + aux[k] for a, k in zip(moe_acc, moe_names))
                return (grads_acc, loss_acc + loss, tok_acc + aux["n_tokens"],
                        moe_acc), None

            zero_grads = jax.tree.map(jnp.zeros_like, state.params)
            (grads, loss_sum, tokens, moe_sums), _ = jax.lax.scan(
                micro_step, (zero_grads, 0.0, 0.0, (0.0,) * len(moe_names)), micro
            )
            grads = jax.tree.map(lambda g: g / accum, grads)
            loss = loss_sum / accum
            moe_metrics = {k: v / accum for k, v in zip(moe_names, moe_sums)}
        else:
            (loss, aux), grads = jax.value_and_grad(single_loss, has_aux=True)(
                state.params, batch
            )
            tokens = aux["n_tokens"]
            moe_metrics = {k: aux[k] for k in moe_names}
        with jax.named_scope("optimizer"):
            updates, new_opt = tx.update(grads, state.opt_state, state.params)
            new_params = jax.tree.map(
                lambda p, u: (p + u.astype(p.dtype)), state.params, updates
            )
            grad_norm = optax_global_norm(grads)
        new_state = TrainState(step=state.step + 1, params=new_params, opt_state=new_opt)
        metrics = {"loss": loss, "n_tokens": tokens, "grad_norm": grad_norm,
                   **moe_metrics}
        flash_names = flash_metric_names(model_cfg, mesh, rules, batch)
        if flash_names:
            from ditl_tpu.ops.flash_attention import block_counts

            metrics.update(zip(flash_names, block_counts(
                batch["segment_ids"], block_q=model_cfg.flash_block_q or 512,
                block_kv=model_cfg.flash_block_kv or 512)))
        if train_cfg.fault_nan_step > 0:
            # Anomaly-plane drill (ISSUE 10): a real device NaN in the
            # REPORTED loss at exactly this step — it rides the compiled
            # metrics to the host flush like a genuine divergence would,
            # without perturbing gradients or parameters.
            metrics["loss"] = jnp.where(
                new_state.step == train_cfg.fault_nan_step,
                jnp.nan, metrics["loss"],
            )
        return new_state, metrics

    return train_step


def _shardings_for(model_cfg, train_cfg, mesh, example_batch, rules):
    from jax.sharding import NamedSharding, PartitionSpec as P

    state_shardings = named_sharding_tree(
        mesh, state_logical_axes(model_cfg, train_cfg), rules
    )
    batch_shardings = named_sharding_tree(mesh, batch_logical_axes(example_batch), rules)
    replicated = NamedSharding(mesh, P())
    metric_shardings = {
        k: replicated for k in ("loss", "n_tokens", "grad_norm",
                                *moe_metric_names(model_cfg, mesh),
                                *flash_metric_names(model_cfg, mesh, rules,
                                                    example_batch))
    }
    return state_shardings, batch_shardings, metric_shardings


def make_train_step(
    model_cfg: ModelConfig,
    train_cfg: TrainConfig,
    mesh,
    example_batch: dict[str, Any],
    rules: dict | None = None,
) -> Callable[[TrainState, dict], tuple[TrainState, dict]]:
    """Build the compiled train step with explicit in/out shardings. When the
    mesh has a pipeline axis (stage > 1), the stage-sharded rule table is
    selected automatically (parallel/pipeline.py)."""
    rules = rules if rules is not None else _default_rules(mesh)
    step = _build_step_fn(model_cfg, train_cfg, mesh, rules)
    state_sh, batch_sh, metric_sh = _shardings_for(
        model_cfg, train_cfg, mesh, example_batch, rules
    )
    return jax.jit(
        step,
        in_shardings=(state_sh, batch_sh),
        out_shardings=(state_sh, metric_sh),
        donate_argnums=(0,),
    )


def make_multi_step(
    model_cfg: ModelConfig,
    train_cfg: TrainConfig,
    mesh,
    example_batch: dict[str, Any],
    n_steps: int,
    rules: dict | None = None,
) -> Callable[[TrainState, dict], tuple[TrainState, dict]]:
    """Compiled ``n_steps`` optimizer steps per call: a ``lax.scan`` over a
    stacked batch window, so the device runs autonomously for the whole window
    with zero host dispatch between steps.

    Host-side per-step dispatch is pure overhead on TPU (the device idles
    while the host prepares and enqueues the next step).
    The reference's per-example host loop (ref
    ``src/distributed_inference.py:64-69``) is the extreme version of that
    anti-pattern. Input batches are stacked on a leading window dim
    ``(n_steps, B, ...)``; returned metrics carry the same leading dim (the
    caller logs the last row / aggregates)."""
    rules = rules if rules is not None else _default_rules(mesh)
    step = _build_step_fn(model_cfg, train_cfg, mesh, rules)

    def train_multi_step(state: TrainState, batches: dict) -> tuple[TrainState, dict]:
        return jax.lax.scan(step, state, batches)

    state_sh, batch_sh, metric_sh = _shardings_for(
        model_cfg, train_cfg, mesh, example_batch, rules
    )
    from jax.sharding import NamedSharding, PartitionSpec as P

    def window(sh):
        return jax.tree.map(lambda s: NamedSharding(mesh, P(None, *s.spec)), sh)

    return jax.jit(
        train_multi_step,
        in_shardings=(state_sh, window(batch_sh)),
        out_shardings=(state_sh, window(metric_sh)),
        donate_argnums=(0,),
    )


def _default_rules(mesh) -> dict:
    if mesh is not None and mesh.shape.get("stage", 1) > 1:
        from ditl_tpu.parallel.pipeline import PIPELINE_RULES

        return PIPELINE_RULES
    return DEFAULT_RULES


def optax_global_norm(tree: Any) -> jax.Array:
    leaves = jax.tree.leaves(tree)
    return jnp.sqrt(sum(jnp.sum(jnp.square(x.astype(jnp.float32))) for x in leaves))


def make_eval_step(model_cfg: ModelConfig, mesh, rules: dict | None = None):
    """Compiled forward-only step returning per-batch mean NLL."""
    rules = rules if rules is not None else _default_rules(mesh)

    @jax.jit
    def eval_step(params, batch):
        loss, aux = loss_fn(params, batch, model_cfg, mesh=mesh, rules=rules)
        return aux

    return eval_step
