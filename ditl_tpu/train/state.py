"""Train state: parameters + optimizer state + step counter.

The reference has no train state at all — no model, no optimizer, nothing is
ever updated or saved (SURVEY.md §2: the loss helper is dead code). This module
is the real thing: an optax AdamW state whose every leaf carries the same
logical sharding as its parameter, so FSDP shards optimizer moments alongside
weights (ZeRO-style) for free.
"""

from __future__ import annotations

from typing import Any

import flax.struct
import jax
import optax

from ditl_tpu.config import ModelConfig, TrainConfig
from ditl_tpu.models import llama

__all__ = ["TrainState", "create_train_state", "make_optimizer", "state_logical_axes"]


@flax.struct.dataclass
class TrainState:
    step: jax.Array
    params: Any
    opt_state: Any


BUFFERS = ("router_bias",)  # leaves that are state, not parameters


def lora_mask(params: Any, frozen: tuple[str, ...] = ()) -> Any:
    """True for trainable leaves. With LoRA enabled, only adapter params train
    (base weights frozen) — optimizer state for frozen leaves is zero-sized.
    A buffer (``BUFFERS``: a router's selection bias, which only chooses) never
    trains: no AdamW update and no weight decay, which a zero gradient alone
    would not spare it. Nor does a leaf ``frozen`` names (``train.frozen``)."""
    flat = jax.tree_util.tree_flatten_with_path(params)[0]

    def names(path):
        return [getattr(k, "key", None) for k in path]

    has_lora = any("lora" in names(path) for path, _ in flat)
    return jax.tree_util.tree_map_with_path(
        lambda path, _: ("lora" in names(path) if has_lora
                         else not {*BUFFERS, *frozen} & set(names(path))), params)


def make_optimizer(cfg: TrainConfig, params: Any) -> optax.GradientTransformation:
    schedule = optax.warmup_cosine_decay_schedule(
        init_value=0.0,
        peak_value=cfg.learning_rate,
        warmup_steps=cfg.warmup_steps,
        decay_steps=max(cfg.total_steps, cfg.warmup_steps + 1),
        end_value=cfg.learning_rate * 0.1,
    )
    if cfg.optimizer == "adamw":
        opt = optax.adamw(
            schedule,
            b1=cfg.beta1,
            b2=cfg.beta2,
            weight_decay=cfg.weight_decay,
            # bf16 first moment halves its HBM footprint/traffic; the
            # variance (nu) stays f32 — it is the precision-sensitive one
            # (sqrt of tiny values).
            mu_dtype=cfg.adam_mu_dtype,
        )
    elif cfg.optimizer == "adafactor":
        # Factored second moment: O(rows+cols) statistics instead of a full
        # parameter-shaped moment — the classic TPU big-model optimizer.
        # Factored stats are vectors, so they restore replicated (the
        # state_logical_axes ndim guard); that is by design, they're tiny.
        opt = optax.adafactor(
            learning_rate=schedule, weight_decay_rate=cfg.weight_decay or None
        )
    elif cfg.optimizer == "lion":
        opt = optax.lion(
            schedule, b1=cfg.beta1, b2=cfg.beta2,
            weight_decay=cfg.weight_decay, mu_dtype=cfg.adam_mu_dtype,
        )
    elif cfg.optimizer == "sgd":
        opt = optax.sgd(schedule, momentum=cfg.beta1)
    else:
        raise ValueError(
            f"unknown optimizer {cfg.optimizer!r} (adamw|adafactor|lion|sgd)"
        )
    tx = optax.chain(optax.clip_by_global_norm(cfg.grad_clip_norm), opt)
    frozen = tuple(n for n in cfg.frozen.split(",") if n)
    mask = lora_mask(params, frozen)
    named = {getattr(k, "key", None) for path, _ in
             jax.tree_util.tree_flatten_with_path(params)[0] for k in path}
    if set(frozen) - named:
        raise ValueError(f"train.frozen names no leaf of this model: "
                         f"{sorted(set(frozen) - named)}")
    if not all(jax.tree.leaves(mask)):
        # Freeze non-LoRA leaves: their updates are hard zeros (optax.masked
        # would pass raw gradients through for unmasked leaves, which is the
        # opposite of freezing).
        labels = jax.tree.map(lambda t: "train" if t else "freeze", mask)
        tx = optax.multi_transform({"train": tx, "freeze": optax.set_to_zero()}, labels)
    return tx


def create_train_state(
    rng: jax.Array, model_cfg: ModelConfig, train_cfg: TrainConfig
) -> TrainState:
    import jax.numpy as jnp

    params = llama.init_params(rng, model_cfg)
    tx = make_optimizer(train_cfg, params)
    opt_state = tx.init(params)
    return TrainState(step=jnp.zeros((), jnp.int32), params=params, opt_state=opt_state)


def state_logical_axes(model_cfg: ModelConfig, train_cfg: TrainConfig) -> Any:
    """Logical-axis tree matching ``create_train_state``'s output structure.

    Optimizer-state leaves inherit the logical axes of the parameter they
    shadow (adam moments are parameter-shaped), found by path-suffix matching:
    the leaf at ``opt_state/.../1/mu/embed/embedding`` gets the axes of
    ``params/embed/embedding``. Anything that isn't parameter-shadowing
    (step counts, schedule state) is replicated. Built by abstract evaluation,
    so no real parameters are ever allocated.
    """
    import jax.numpy as jnp

    param_axes = llama.param_logical_axes(model_cfg)
    axes_leaves, _ = jax.tree_util.tree_flatten_with_path(
        param_axes, is_leaf=lambda x: isinstance(x, tuple)
    )
    # param path (tuple of dict keys) -> logical axes
    by_path = {
        tuple(k.key for k in path): axes for path, axes in axes_leaves
    }

    def abstract_state():
        params = llama.init_params(jax.random.key(0), model_cfg)
        tx = make_optimizer(train_cfg, params)
        return TrainState(
            step=jnp.zeros((), jnp.int32), params=params, opt_state=tx.init(params)
        )

    shapes = jax.eval_shape(abstract_state)

    def leaf_axes(path, leaf):
        dict_keys = tuple(k.key for k in path if hasattr(k, "key") and isinstance(k.key, str))
        for start in range(len(dict_keys)):
            if dict_keys[start:] in by_path:
                axes = by_path[dict_keys[start:]]
                if len(axes) == leaf.ndim:
                    return axes
        return tuple([None] * leaf.ndim)

    opt_axes = jax.tree_util.tree_map_with_path(leaf_axes, shapes.opt_state)
    return TrainState(step=(), params=param_axes, opt_state=opt_axes)
