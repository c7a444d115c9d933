"""ISSUE 2 A/B: Pallas fused-backward kernels vs XLA's backward schedule,
adjacent legs on the pinned 1b3 bench config (bwd_levers.py rigor: anchor,
levers, anchor repeat — one chip, one session).

Legs:

  base         the ADOPTED pinned config (post-r5: fused_gate_up +
               remat="dots_inputs") — fresh anchor
  mlp_pallas   ModelConfig.mlp_bwd_impl="pallas": the fused MLP backward as
               hand-tiled Pallas kernels (ops/mlp_bwd.py) — targets the
               ~40 ms MLP-wgrad residual
  proj_pallas  ModelConfig.proj_bwd_impl="pallas": attention qkv/out
               projection backward as one Pallas kernel per projection
               (ops/projection.py) — targets the ~33 ms attn-proj residual
  both         both flags together (the candidate adoption config)
  base_again   anchor repeat (brackets the A/B against drift)

plus optional tile sweeps over mlp_bwd_block_* / proj_bwd_block_* (pass
`sweep` as argv[3]) — the (bd, 2F) pass-2 accumulator is the VMEM ceiling
term, so block_d is the lever most likely to move.

Decision rule (the VJP-null protocol): adopt into bench._model_cfg("1b3")
only on a step p50 clearly below the parent's across adjacent legs
(ROADMAP.md Speed 3 holds the bound); otherwise record a kernel-level
definitive null in PERF.md and leave the flags off. Every leg prints the EFFECTIVE backward impls
(bench._effective_bwd_impls) so a silent shape-fallback can never
masquerade as a null.

Every finished leg also lands as one cell in a versioned sweep record
(telemetry/perf.py format, `--out=PATH`, default
bwd_kernels_sweep.json) — ISSUE 7: the first real TPU session's numbers
are `perf_compare`-diffable JSON, not scraped stdout; a killed session
resumes at the first unrecorded leg.

Usage: python experiments/bwd_kernels.py [chunk windows [sweep]] [--out=PATH]
"""

from __future__ import annotations

import dataclasses
import sys
import time

import numpy as np

sys.path.insert(0, ".")

import jax

import bench
from ditl_tpu.config import MeshConfig, TrainConfig
from ditl_tpu.data.loader import make_global_batch
from ditl_tpu.runtime.mesh import build_mesh
from ditl_tpu.train.state import create_train_state
from ditl_tpu.train.step import make_multi_step


def time_step_leg(name, cfg, mesh, tcfg, window, example, chunk, n_windows,
                  batch, seq):
    """Returns the leg's cell record (telemetry/perf.py sweep-cell shape:
    ``step_ms`` is the key perf_compare gates on) or None on failure."""
    try:
        eff = bench._effective_bwd_impls(cfg, batch, seq, mesh)
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
              f"{[f'{t:.1f}' for t in times]}, compile {compile_s:.0f}s, "
              f"bwd_impl={eff})", flush=True)
        del state
        return {
            "step_ms": round(ms, 2),
            "window_ms": [round(t, 2) for t in times],
            "compile_s": round(compile_s, 1),
            "bwd_impl": eff,
        }
    except Exception as e:  # noqa: BLE001
        print(f"LEG {name}: FAILED {type(e).__name__}: {e}", flush=True)
        # Recorded as an error cell: perf_compare gates measured->crashing,
        # and a resumed session retries it (telemetry/perf.py semantics).
        return {"error": f"{type(e).__name__}: {str(e)[:500]}"}


def main():
    from ditl_tpu.telemetry.perf import pop_out_arg, run_recorded_cells

    args = list(sys.argv[1:])
    out_path = pop_out_arg(args, "bwd_kernels_sweep.json")
    chunk = int(args[0]) if len(args) > 0 else 10
    n_windows = int(args[1]) if len(args) > 1 else 3
    sweep = len(args) > 2 and args[2] == "sweep"
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

    legs = [
        ("base", cfg),
        ("mlp_pallas", dataclasses.replace(cfg, mlp_bwd_impl="pallas")),
        ("proj_pallas", dataclasses.replace(cfg, proj_bwd_impl="pallas")),
        ("both", dataclasses.replace(cfg, mlp_bwd_impl="pallas",
                                     proj_bwd_impl="pallas")),
        ("base_again", cfg),
    ]
    if sweep:
        # Tile sweep around the defaults; pass-2's (block_d, 2F) f32
        # accumulator is the VMEM ceiling, so block_d moves the most.
        for bn in (128, 256, 512):
            for bd in (128, 256):
                legs.insert(-1, (
                    f"mlp_pallas_n{bn}_d{bd}",
                    dataclasses.replace(cfg, mlp_bwd_impl="pallas",
                                        mlp_bwd_block_n=bn,
                                        mlp_bwd_block_d=bd),
                ))
        for bn in (128, 256, 512):
            legs.insert(-1, (
                f"proj_pallas_n{bn}",
                dataclasses.replace(cfg, proj_bwd_impl="pallas",
                                    proj_bwd_block_n=bn),
            ))
    # Record-as-you-go sweep cells (telemetry/perf.py): a killed session
    # reruns only unrecorded/errored legs. Mind the adjacency rigor — a
    # resumed base_again brackets a DIFFERENT session than its base; rerun
    # from scratch with a fresh --out when that matters.
    cells = run_recorded_cells(
        out_path, "bwd_kernels",
        meta={"platform": platform, "chunk": chunk, "n_windows": n_windows,
              "batch": batch, "seq": seq, "model": "1b3"},
        items=legs,
        runner=lambda name, leg_cfg: time_step_leg(
            name, leg_cfg, mesh, tcfg, window, example, chunk, n_windows,
            batch, seq,
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


if __name__ == "__main__":
    main()
