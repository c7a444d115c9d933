#!/usr/bin/env python3
"""Which initial draw a trainer cell of a held share starts from: the held
experts' share of the live tokens' choices at step 0, layer by layer, for a
range of ``train.init_seed``, on the chip at the configuration's widths.

    python benchmarks/init_draw_scan.py [--workload NAME] [--seeds 16]

Seeded weights are not a trained model's: every token's stream shares a large
common direction, so a seeded sigmoid router sends most tokens to the same few
experts, and whether those lie among the 16 held here is a lottery of the
draw (0.1% to 60% a layer). A trained router is balanced (that is what the
bias is for); the cell's one fixed draw stands for such a checkpoint, so it is
the draw whose held share lies nearest the even share in every expert layer.
The pick lasts only while the stream stays near the draw: the cell's job keeps
it there (a fine-tune's learning rate and frozen routers, the traffic file's
notes; at the trainer's default rate the share left the pick within ten
steps, whatever the draw).
A builder's instrument, run once when the cell is defined; PERF.md section 6
(PR 58) has the readings. Prints one JSON line.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, ROOT)
sys.path.insert(0, HERE)


def main(argv: list[str]) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", default="kanana-2-30b-a3b-cut1.train-ep8-8k")
    ap.add_argument("--seeds", type=int, default=16)
    ap.add_argument("--override", action="append", default=[], metavar="section.key=value",
                    help="a rehearsal at small sizes on the CPU: never a reading")
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp
    import numpy as np

    from train_grad_check import cell_model_config

    from ditl_tpu.config import Config, parse_overrides
    from ditl_tpu.data import load_text_dataset
    from ditl_tpu.data.loader import DataPipeline
    from ditl_tpu.data.tokenizer import get_tokenizer
    from ditl_tpu.models import llama
    from ditl_tpu.models.moe import split_counts
    from ditl_tpu.runtime.mesh import build_mesh
    from ditl_tpu.train.step import compute_params

    _, launch, cfg = cell_model_config(args.workload, args.override)
    data = parse_overrides(Config(), [a for a in launch if a.startswith("data.")]).data
    pipe = DataPipeline(load_text_dataset(data), get_tokenizer(data.tokenizer), data,
                        build_mesh(Config().mesh))
    hb = next(pipe._host_batches(0))
    ids, pos, seg = (jnp.asarray(hb[k]) for k in ("input_ids", "positions", "segment_ids"))

    @jax.jit
    def shares(key):
        params = compute_params(llama.init_params(key, cfg), cfg)
        *_, counts = llama.forward(params, ids, cfg, positions=pos, segment_ids=seg,
                                   with_aux=True, with_moe_counts=True,
                                   token_mask=jnp.ones(ids.shape, bool))[:3]
        held, _, _ = split_counts(counts.astype(jnp.float32), cfg)
        return held.sum(axis=-1) / counts.sum(axis=-1), held.max(axis=-1) / held.mean(axis=-1)

    even = cfg.experts_held_count / cfg.num_experts
    rows = []
    for seed in range(args.seeds):
        share, skew = (np.asarray(x) for x in shares(jax.random.key(seed)))
        rows.append({"init_seed": seed, "held_share_a_layer": [round(float(x), 4) for x in share],
                     "held_share": round(float(share.mean()), 4),
                     "max_over_mean_a_layer": [round(float(x), 2) for x in skew],
                     "worst_ratio_to_even": round(float(np.max(np.abs(np.log(
                         np.maximum(share, 1e-6) / even)))), 3)})
    best = min(rows, key=lambda r: r["worst_ratio_to_even"])
    print(json.dumps({"workload": args.workload, "even_share": even,
                      "device": jax.devices()[0].device_kind, "rows": rows,
                      "nearest_even": best["init_seed"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
