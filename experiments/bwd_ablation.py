"""Round-5 backward-residual ablation on the pinned 1b3 bench config.

The builders' r4 roofline (figures from before this round, not
re-measured) attributed ~39% of the step to XLA's backward scheduling,
outside every exposed knob. Before writing custom backward kernels, this
script localizes the in-step cost by adjacent A/B legs in ONE session
(session-to-session variance makes only adjacent pairs comparable):

  base        the pinned config's step (grad + adafactor), fresh anchor
  fwd_only    loss forward only (no grad, no optimizer)
  sg_mlp      stop_gradient on every MLP weight  -> MLP wgrads DCE'd
  sg_attn     stop_gradient on attn projections  -> attn wgrads DCE'd
  sg_embed    stop_gradient on the tied embedding -> head wgrad + embed
              scatter-add DCE'd
  unroll4     scan_unroll=4 (fusion across layer boundaries)
  remat_none  no rematerialization (may OOM; reported if so)

stop_gradient on a weight kills its wgrad GEMM but keeps the dgrad chain,
so (base - sg_X) is family X's in-step wgrad cost, to compare against the
isolated-rate ideal (~1/3 of the family's fwd+bwd GEMM budget).

Usage: python experiments/bwd_ablation.py [chunk windows]
"""

from __future__ import annotations

import dataclasses
import sys
import time

import numpy as np

sys.path.insert(0, ".")

import jax
import jax.numpy as jnp

import bench
from ditl_tpu.config import MeshConfig, TrainConfig
from ditl_tpu.data.loader import make_global_batch
from ditl_tpu.models import llama
from ditl_tpu.runtime.mesh import build_mesh
from ditl_tpu.train.state import create_train_state, make_optimizer, state_logical_axes
from ditl_tpu.train.step import loss_fn, batch_logical_axes
from ditl_tpu.parallel.sharding import DEFAULT_RULES, named_sharding_tree
from ditl_tpu.train.state import TrainState


def make_step(cfg, tcfg, mesh, example, *, sg_filter=None, grad=True):
    """A bench-equivalent multi-step (scan over a stacked window) with an
    optional stop-gradient filter on parameter paths (mirrors
    train/step._build_step_fn; experiment-local so the filter can be
    injected without touching the production step)."""
    rules = DEFAULT_RULES
    tx = None

    def single_loss(params, batch):
        cd = jnp.dtype(cfg.dtype)
        if sg_filter is not None:
            def sg(path, p):
                label = "/".join(str(getattr(k, "key", k)) for k in path)
                return jax.lax.stop_gradient(p) if sg_filter(label) else p

            params = jax.tree_util.tree_map_with_path(sg, params)
        if cd != jnp.float32:
            def cast(path, p):
                if any(getattr(k, "key", None) and "norm" in k.key for k in path):
                    return p
                return p.astype(cd) if p.dtype == jnp.float32 else p

            params = jax.tree_util.tree_map_with_path(cast, params)
        return loss_fn(params, batch, cfg, mesh=mesh, rules=rules)

    def step(state, batch):
        nonlocal tx
        if tx is None:
            tx = make_optimizer(tcfg, state.params)
        if not grad:
            loss, aux = single_loss(state.params, batch)
            return state, {"loss": loss}
        (loss, aux), grads = jax.value_and_grad(single_loss, has_aux=True)(
            state.params, batch
        )
        updates, new_opt = tx.update(grads, state.opt_state, state.params)
        new_params = jax.tree.map(
            lambda p, u: p + u.astype(p.dtype), state.params, updates
        )
        return (
            TrainState(step=state.step + 1, params=new_params, opt_state=new_opt),
            {"loss": loss},
        )

    def multi(state, batches):
        return jax.lax.scan(step, state, batches)

    from jax.sharding import NamedSharding, PartitionSpec as P

    state_sh = named_sharding_tree(mesh, state_logical_axes(cfg, tcfg), DEFAULT_RULES)
    batch_sh = named_sharding_tree(mesh, batch_logical_axes(example), DEFAULT_RULES)
    win = jax.tree.map(lambda s: NamedSharding(mesh, P(None, *s.spec)), batch_sh)
    rep = NamedSharding(mesh, P())
    return jax.jit(
        multi,
        in_shardings=(state_sh, win),
        out_shardings=(state_sh, {"loss": NamedSharding(mesh, P(None))}),
        donate_argnums=(0,),
    )


def main():
    chunk = int(sys.argv[1]) if len(sys.argv) > 1 else 10
    n_windows = int(sys.argv[2]) if len(sys.argv) > 2 else 3
    platform = jax.devices()[0].platform
    print(f"devices: {jax.devices()} platform={platform}", file=sys.stderr)

    # NOTE: bench._model_cfg carries the ADOPTED pinned config — after r5
    # that includes fused_gate_up + remat="dots_inputs", so re-running this
    # script measures the remaining headroom under the shipped schedule
    # (the sg_mlp path filter matches both w_gate/w_up/w_down and w_gu).
    cfg, batch, seq, optimizer = bench._model_cfg("1b3", platform)
    tcfg = TrainConfig(total_steps=1000, warmup_steps=10, optimizer=optimizer)
    mesh = build_mesh(MeshConfig())

    rng = np.random.default_rng(0)
    all_tokens = bench._bigram_batches(
        rng, chunk * (n_windows + 1), batch, seq, cfg.vocab_size
    )
    ones = np.ones((chunk, batch, seq), np.float32)
    segs = np.ones((chunk, batch, seq), np.int32)
    pos = np.tile(np.arange(seq, dtype=np.int32), (chunk, batch, 1))

    def window(i):
        toks = all_tokens[i * chunk:(i + 1) * chunk]
        return {
            "input_ids": toks, "loss_mask": ones,
            "labels": np.zeros((chunk, batch), np.int32),
            "segment_ids": segs, "positions": pos,
        }

    example = {k: v[0] for k, v in window(0).items()}

    legs = [
        ("base", cfg, None, True),
        ("fwd_only", cfg, None, False),
        ("sg_mlp", cfg, lambda p: "mlp/" in p or p.endswith("w_gate")
         or p.endswith("w_up") or p.endswith("w_down"), True),
        ("sg_attn", cfg, lambda p: "attn/" in p, True),
        ("sg_embed", cfg, lambda p: "embed" in p, True),
        ("unroll4", dataclasses.replace(cfg, scan_unroll=4), None, True),
        ("remat_none", dataclasses.replace(cfg, remat="none"), None, True),
    ]

    results = {}
    for name, leg_cfg, flt, grad in legs:
        try:
            t0 = time.perf_counter()
            state = create_train_state(jax.random.key(0), leg_cfg, tcfg)
            multi = make_step(leg_cfg, tcfg, mesh, example, sg_filter=flt,
                              grad=grad)
            state, m = multi(state, make_global_batch(mesh, window(0)))
            float(m["loss"][-1])  # full host sync: the value is on the host
            compile_s = time.perf_counter() - t0
            staged = [make_global_batch(mesh, window(w))
                      for w in range(1, n_windows + 1)]
            jax.block_until_ready(staged)
            times = []
            for gb in staged:
                t0 = time.perf_counter()
                state, m = multi(state, gb)
                float(m["loss"][-1])  # sync
                times.append((time.perf_counter() - t0) / chunk * 1e3)
            ms = float(np.median(times))
            results[name] = ms
            print(f"LEG {name}: {ms:.1f} ms/step (windows "
                  f"{[f'{t:.1f}' for t in times]}, compile {compile_s:.0f}s)",
                  flush=True)
            del state
        except Exception as e:  # noqa: BLE001 — report and continue
            print(f"LEG {name}: FAILED {type(e).__name__}: {e}", flush=True)
    if "base" in results:
        b = results["base"]
        for name, ms in results.items():
            if name != "base":
                print(f"DELTA {name}: {ms - b:+.1f} ms vs base", flush=True)


if __name__ == "__main__":
    main()
