#!/usr/bin/env python3
"""The gradient check of a trainer cell, on the chip, at the configuration's
published widths: what the harness's ``correct`` does not compare.

    python benchmarks/train_grad_check.py [--workload NAME] [--seed N] [--tokens 1024]

``jax.grad`` of the PROGRAM's loss (``ditl_tpu.train.step.loss_fn`` on the
step's own bfloat16 copy of float32 masters, the cell's attention and loss
settings: the flash kernels forward and backward, ``gmm`` / ``tgmm``, the
fused loss) against ``jax.grad`` of the plain reference's (float32 at
``highest``), on the same seeded weights (the reference's ``perturb``) and a
seeded packed sample the kernels accept (2 rows of ``--tokens``, three
documents a row). Compared group by group in relative RMS over a group's
leaves together, ``||g - g_ref|| / ||g_ref||``.

Outside ``correct``, as ``dsa_check.py`` is: a builder's instrument, run by
hand through the chip tool. The program's gradient is taken twice: with its
OWN choice of experts, and with the reference's choice given to it
(``models/moe.py`` takes a ``choice`` leaf in place of its top-k), which
tells a bfloat16 rounding that flips a token's sixth choice from an error in
the expert path. It exits 0 only if the program passes both ways AND every
control FAILS, each run with the reference's choice so that the break is the
only difference: (a) one flash backward kernel broken on purpose
(``flash_bwd_dkv``'s ``dk`` without its 64 rotary lanes: what a kernel that
knew one width of 128 would return), (b) the held share's backward broken
(``tgmm`` one group short: the last held expert's weight gradient lost), (c)
the program's weights in a lower precision than the configuration states
(rounded through float8 e4m3, three mantissa bits, before the bfloat16
matmuls).

The tolerances, each between two chip readings at the published widths
(PERF.md section 6, PR 58) with about a factor of two of room on both sides.
``TOL``, the choice held: the program reads 1.9% on the head, 2.5-2.7% in
every dense group and on the held experts and 3.1-3.3% on the router (seeds 0,
1 and 2); the controls read 12.7-33.9% where (a) lands (attention, dense FFN,
norms, embedding; the head, which no attention gradient reaches, stays at
1.9%), 20.7-29.9% on the held experts alone under (b) and 17.3-31.4%
everywhere under (c): 7% sits between, 6% on the head. ``TOL_OWN_CHOICE``,
the program's own choice: 4.2-4.7% in the dense groups and 3.1-3.2% on the
head (a flipped token's stream changes by a fifth of an FFN output and every
leaf sees it), 24.3-24.9% on the router and 16.5-18.4% on the held experts,
whose gradients ride on the choice itself (a flipped token's rows leave one
expert's gradient and join another's); that the choice is the cause is what
the held reading shows (24.8 -> 3.3, 17.0 -> 2.7), so these bounds say only
"no worse than rounding's flips": 10%, 8% on the head, 45% and 32%, the first
tree's.
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

GROUPS = {
    "attention": lambda n: "/attn/" in n,
    "shared_expert": lambda n: "/shared/" in n,
    "router": lambda n: n.endswith("/router"),
    "held_experts": lambda n: "/moe/w_" in n,
    "dense_ffn": lambda n: "/mlp/" in n,
    "norms": lambda n: "norm" in n and "/attn/" not in n,
    "embedding": lambda n: n.startswith("embed"),
    "head": lambda n: n.startswith("lm_head"),
}
TOL = {"attention": 0.07, "shared_expert": 0.07, "router": 0.07, "held_experts": 0.07,
       "dense_ffn": 0.07, "norms": 0.07, "embedding": 0.07, "head": 0.06}
TOL_OWN_CHOICE = {"attention": 0.10, "shared_expert": 0.10, "router": 0.45, "held_experts": 0.32,
                  "dense_ffn": 0.10, "norms": 0.10, "embedding": 0.10, "head": 0.08}


def cell_model_config(workload: str, extra: list[str] = (), rehearse: bool = False):
    """(configuration file, the traffic file's launch arguments + ``extra``,
    the program's ModelConfig as the cell's command line builds it); with
    ``rehearse`` the configuration's ``rehearsal_overrides`` on top."""
    import manifest as manifest_mod
    import reference_check
    from harness import model_override_args

    manifest = manifest_mod.load()
    cell = manifest_mod.cell(manifest, workload)
    with open(os.path.join(ROOT, manifest_mod.config_entry(manifest, cell["config"])["file"])) as f:
        config = json.load(f)
    with open(manifest_mod.traffic_path(cell["traffic"])) as f:
        launch = json.load(f)["launch_args"] + list(extra)
    overrides = model_override_args(config, "train") + [
        a[len("model."):] for a in launch if a.startswith("model.")]
    if rehearse:
        overrides += config.get("rehearsal_overrides", [])
    return config, launch, reference_check.model_config(config, overrides)


def packed_sample(vocab: int, seed: int, tokens: int):
    """(batch of 2 rows, three documents a row at uneven cuts)."""
    import numpy as np

    rng = np.random.default_rng(seed)
    ids = rng.integers(3, vocab, size=(2, tokens)).astype(np.int32)
    seg = np.ones_like(ids)
    pos = np.broadcast_to(np.arange(tokens, dtype=np.int32), ids.shape).copy()
    for r in range(2):
        cuts = np.sort(rng.choice(np.arange(8, tokens - 8), 2, replace=False))
        seg[r] = np.searchsorted(cuts, np.arange(tokens), side="right") + 1
        pos[r] = np.arange(tokens) - np.concatenate([[0], cuts])[seg[r] - 1]
    return {"input_ids": ids, "positions": pos, "segment_ids": seg,
            "loss_mask": np.ones(ids.shape, np.float32)}


def group_errors(got, want) -> dict:
    """Relative RMS a group, the sums taken on the device (a leaf is up to
    100 M values: no float64 copy of it on the host)."""
    import jax
    import jax.numpy as jnp

    flat = jax.tree_util.tree_flatten_with_path(got)[0]
    sums = {g: [0.0, 0.0] for g in GROUPS}
    for (path, a), b in zip(flat, jax.tree.leaves(want)):
        name = "/".join(str(getattr(k, "key", k)) for k in path)
        if name.endswith("router_bias"):
            if bool(jnp.any(a != 0)):
                raise SystemExit(f"{name}: a buffer has a gradient")
            continue
        group = next(g for g, mine in GROUPS.items() if mine(name))
        a, b = a.astype(jnp.float32), b.astype(jnp.float32)
        sums[group][0] += float(jnp.sum((a - b) ** 2))
        sums[group][1] += float(jnp.sum(b ** 2))
    return {g: (e / w) ** 0.5 if w else float("nan") for g, (e, w) in sums.items()}


def main(argv: list[str]) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", default="kanana-2-30b-a3b-cut1.train-ep8-8k")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--tokens", type=int, default=1024)
    ap.add_argument("--allow-cpu", action="store_true",
                    help="a rehearsal at the configuration's rehearsal sizes: never a verdict")
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp

    import reference_check
    from harness import load_module

    from ditl_tpu.ops import flash_attention as fa
    from ditl_tpu.train.step import compute_params, loss_fn

    if jax.devices()[0].platform != "tpu" and not args.allow_cpu:
        print("train_grad_check: no TPU; a CPU run is a rehearsal (--allow-cpu)", file=sys.stderr)
        return 3
    rehearsal = []
    if args.allow_cpu:
        with open(os.path.join(HERE, "rehearsal.json")) as f:
            rehearsal = [a for a in json.load(f)["train_launch_args"] if a.startswith("model.")]
    config, _, cfg = cell_model_config(args.workload, rehearsal, rehearse=args.allow_cpu)
    ref = load_module(os.path.join(HERE, "reference", f"{config['reference']}.py"))
    if not args.allow_cpu and ref.check_sizes(cfg, config):
        print("sizes differ from the configuration file:", ref.check_sizes(cfg, config))
        return 1
    sizes = ref.sizes(cfg, config)
    params = reference_check.seeded_params(cfg, args.seed, ref)
    batch = {k: jnp.asarray(v) for k, v in
             packed_sample(cfg.vocab_size, args.seed, args.tokens).items()}

    def program_grads(choice=None, lower_precision: bool = False):
        def loss(p):
            p = compute_params(p, cfg)
            if lower_precision:
                p = jax.tree.map(lambda w: w.astype(jnp.float8_e4m3fn).astype(w.dtype)
                                 if w.dtype == jnp.dtype(cfg.dtype) else w, p)
            if choice is not None:  # models/moe.py takes these expert ids for its own
                sparse = p["layers"]["sparse"]
                p = {**p, "layers": {**p["layers"], "sparse": {
                    **sparse, "moe": {**sparse["moe"], "choice": choice}}}}
            return loss_fn(p, batch, cfg)[0]

        return jax.jit(jax.grad(loss))(params)

    # operation by operation, as reference_check.py runs the forward pass: one
    # program of the unrolled heads, experts and layers with its backward pass
    # took the host's 40 GiB to compile
    def reference_loss(p):
        out = ref.forward(p, batch["input_ids"], sizes, positions=batch["positions"],
                          segment_ids=batch["segment_ids"])
        return ref.loss(out, batch["input_ids"], batch["loss_mask"], sizes), out["chosen"]

    want, chosen = jax.grad(reference_loss, has_aux=True)(params)
    # the reference's choices as expert ids (expert layers, T, k), in any order
    choice = jax.lax.top_k(chosen.reshape(chosen.shape[0], -1, chosen.shape[-1]),
                           cfg.num_experts_per_tok)[1].astype(jnp.int32)
    readings = {"program_own_choice": group_errors(program_grads(), want),
                "program": group_errors(program_grads(choice), want)}

    whole = fa._bwd_impl

    def without_rotary_dk(*a, **kw):
        dq, dk, dv = whole(*a, **kw)
        return dq, dk.at[..., cfg.qk_nope_head_dim:].set(0), dv

    fa._bwd_impl = without_rotary_dk
    try:
        readings["control_broken_dkv"] = group_errors(program_grads(choice), want)
    finally:
        fa._bwd_impl = whole

    from jax.experimental.pallas.ops.tpu.megablox import ops as megablox_ops

    megablox = megablox_ops.backend  # the module gmm's backward rule takes tgmm from
    tgmm = megablox.tgmm

    def one_group_short(*a, **kw):  # (groups, k, n): the last held expert's product lost
        return tgmm(*a, **kw).at[-1].set(0)

    megablox.tgmm = one_group_short
    try:
        readings["control_broken_tgmm"] = group_errors(program_grads(choice), want)
    finally:
        megablox.tgmm = tgmm
    readings["control_float8_weights"] = group_errors(
        program_grads(choice, lower_precision=True), want)
    controls = [n for n in readings if n.startswith("control_")]

    over = {name: {g: round(e, 5) for g, e in errs.items()
                   if not e <= (TOL_OWN_CHOICE if name == "program_own_choice" else TOL)[g]}
            for name, errs in readings.items()}
    verdict = {"workload": args.workload, "seed": args.seed, "sample": [2, args.tokens],
               "device": jax.devices()[0].device_kind, "tolerances": TOL,
               "tolerances_own_choice": TOL_OWN_CHOICE,
               "readings": {n: {g: round(e, 5) for g, e in r.items()} for n, r in readings.items()},
               "over_tolerance": over,
               "ok": bool(not over["program"] and not over["program_own_choice"]
                          and all(over[c] for c in controls))}
    print(json.dumps(verdict), flush=True)
    return 0 if verdict["ok"] or args.allow_cpu else 1


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
