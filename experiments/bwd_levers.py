"""Round-5 backward levers A/B on the pinned 1b3 config (follow-up to
bwd_ablation.py). This script's leg list evolved with the round (gu/di
lever sweep, attn_out/inner saves, flash-tile and CE-block re-sweeps, the
custom-VJP null — builders' results from before this round, not
re-measured). CURRENT legs (adjacent, one session):

  base         the ADOPTED pinned config (post-r5: fused_gate_up +
               remat="dots_inputs") — fresh anchor
  custom_vjp   ModelConfig.mlp_custom_vjp=True: the hand-written
               whole-block MLP backward (ops/mlp.py) instead of autodiff
  base_again   anchor repeat (brackets the A/B against drift)

plus `iso`: k-differenced ISOLATED rates of the exact backward GEMM
shapes (einsum over 8192 tokens, bf16) — only trustworthy on a quiet
host (concurrent load corrupts the k-difference).

Every finished leg lands as one cell in a versioned sweep record
(telemetry/perf.py format, `--out=PATH`, default bwd_levers_sweep.json)
so sessions are `perf_compare`-diffable (ISSUE 7).

Usage: python experiments/bwd_levers.py [chunk windows] [--out=PATH]
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
from ditl_tpu.runtime.mesh import build_mesh
from ditl_tpu.train.state import create_train_state
from ditl_tpu.train.step import make_multi_step


def time_step_leg(name, cfg, mesh, tcfg, window, example, chunk, n_windows):
    """Returns the leg's sweep-cell record (telemetry/perf.py format;
    ``step_ms`` is what perf_compare gates) or None on failure."""
    try:
        t0 = time.perf_counter()
        state = create_train_state(jax.random.key(0), cfg, tcfg)
        multi = make_multi_step(cfg, tcfg, mesh, example, chunk)
        state, m = multi(state, make_global_batch(mesh, window(0)))
        float(m["loss"][-1])  # full host sync
        compile_s = time.perf_counter() - t0
        staged = [make_global_batch(mesh, window(w))
                  for w in range(1, n_windows + 1)]
        jax.block_until_ready(staged)
        times = []
        for gb in staged:
            t0 = time.perf_counter()
            state, m = multi(state, gb)
            float(m["loss"][-1])
            times.append((time.perf_counter() - t0) / chunk * 1e3)
        ms = float(np.median(times))
        print(f"LEG {name}: {ms:.1f} ms/step (windows "
              f"{[f'{t:.1f}' for t in times]}, compile {compile_s:.0f}s)",
              flush=True)
        del state
        return {
            "step_ms": round(ms, 2),
            "window_ms": [round(t, 2) for t in times],
            "compile_s": round(compile_s, 1),
        }
    except Exception as e:  # noqa: BLE001
        print(f"LEG {name}: FAILED {type(e).__name__}: {e}", flush=True)
        # Recorded as an error cell: perf_compare gates measured->crashing,
        # and a resumed session retries it (telemetry/perf.py semantics).
        return {"error": f"{type(e).__name__}: {str(e)[:500]}"}


def iso_wgrad_rates():
    """k-differenced isolated rates for the backward GEMM shapes of the
    1b3 MLP/attn families (T=8192 tokens). Weights/activations are
    program ARGS; a data-dependence + ReLU barrier stops XLA folding the
    loop (ditl-tpu-env-gotchas)."""
    T, D, F = 8192, 2048, 5632
    shapes = {
        # wgrads: contraction over tokens
        "wgrad_gate (TxD)^T @ (TxF)": ((T, D), (T, F), "td,tf->df"),
        "wgrad_down (TxF)^T @ (TxD)": ((T, F), (T, D), "tf,td->fd"),
        "wgrad_gu   (TxD)^T @ (Tx2F)": ((T, D), (T, 2 * F), "td,tf->df"),
        "wgrad_qkvo (TxD)^T @ (TxD)": ((T, D), (T, D), "td,tf->df"),
        # dgrads: same shape family as forward
        "dgrad_gate (TxF) @ (FxD)": ((T, F), (F, D), "tf,fd->td"),
    }
    rng = jax.random.key(0)

    for name, (sa, sb, spec) in shapes.items():
        a = jax.random.normal(jax.random.fold_in(rng, 1), sa, jnp.bfloat16)
        b = jax.random.normal(jax.random.fold_in(rng, 2), sb, jnp.bfloat16)

        def run_k(k):
            @jax.jit
            def f(a, b):
                def body(i, carry):
                    s, a_ = carry
                    out = jnp.einsum(
                        spec, a_, b,
                        preferred_element_type=jnp.float32,
                    ).astype(jnp.bfloat16)
                    d = out.reshape(-1)[0].astype(jnp.float32)
                    # ReLU barrier + feed the scalar back into the input:
                    # the next iteration's operand depends on this one's
                    # output, so nothing hoists or folds.
                    a2 = a_ + (jax.nn.relu(d) * 0.0).astype(a_.dtype)
                    return (s + d, a2)

                return jax.lax.fori_loop(0, k, body, (jnp.float32(0), a))[0]

            f(a, b)  # compile + warm
            float(f(a, b))
            t0 = time.perf_counter()
            float(f(a, b))
            return time.perf_counter() - t0

        k1, k2 = 6, 30
        t1, t2 = run_k(k1), run_k(k2)
        per = (t2 - t1) / (k2 - k1)
        # 2 * contraction * rows * cols for every shape here.
        flops = 2 * sa[0] * sa[1] * sb[1]
        tf = flops / per / 1e12
        print(f"ISO {name}: {per * 1e3:.2f} ms  {tf:.0f} TF/s "
              f"({tf / 197 * 100:.0f}% of peak)", flush=True)


def main():
    from ditl_tpu.telemetry.perf import pop_out_arg, run_recorded_cells

    args = list(sys.argv[1:])
    out_path = pop_out_arg(args, "bwd_levers_sweep.json")
    chunk = int(args[0]) if len(args) > 0 else 10
    n_windows = int(args[1]) if len(args) > 1 else 3
    platform = jax.devices()[0].platform
    print(f"platform={platform}", file=sys.stderr)

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

    # cfg IS the adopted gu_di config post-r5-adoption; the custom-vjp leg
    # swaps the MLP block's autodiff backward for the hand-written one.
    legs = [
        ("base", cfg),
        ("custom_vjp", dataclasses.replace(cfg, mlp_custom_vjp=True)),
        ("base_again", cfg),
    ]
    cells = run_recorded_cells(
        out_path, "bwd_levers",
        meta={"platform": platform, "chunk": chunk,
              "n_windows": n_windows, "model": "1b3"},
        items=legs,
        runner=lambda name, leg_cfg: time_step_leg(
            name, leg_cfg, mesh, tcfg, window, example, chunk, n_windows,
        ),
    )
    results = {k: c["step_ms"] for k, c in cells.items() if "step_ms" in c}
    if "base" in results:
        for name, ms in results.items():
            if name != "base":
                print(f"DELTA {name}: {ms - results['base']:+.1f} ms",
                      flush=True)
    print(f"sweep record: {out_path} ({len(cells)} cell(s) this session); "
          f"diff sessions with python -m ditl_tpu.telemetry.perf_compare",
          flush=True)
    if platform == "tpu":
        iso_wgrad_rates()


if __name__ == "__main__":
    main()
