"""The comparison that decides the reference half of ``correct``.

On the chip, at the configuration's published widths, the program's forward
pass (``ditl_tpu.models.llama.forward``; for a trainer cell also its loss,
``ditl_tpu.train.step.loss_fn``) under the cell's dtype, attention and loss
settings is compared with the plain reference named in the configuration
file, on the same seeded weights and a seeded 256-token sample.

It costs a compile and a pass, so ``chip_child.py`` runs it in a cell's first
run in a checkout and leaves the verdict under ``benchmarks/out/``, keyed by
a hash of the sources under ``ditl_tpu/``, of the configuration file, of the
reference and of this file; later runs of the cell read the verdict.

This module is imported only in the process that holds the chip.
"""

from __future__ import annotations

import hashlib
import json
import os

from harness import load_module

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
REFERENCE_DIR = os.path.join(HERE, "reference")

SAMPLE_TOKENS = 256
SAMPLE_ROWS = 2

# Relative RMS error of the logits, rms(program - reference) / rms(reference),
# that a correct bfloat16 forward pass stays under and a pass computed in a
# lower precision than the configuration states does not.
#
# A bfloat16 rounding is uniform within 2^-9 relative, 0.11% rms; a product of
# a rounded activation and a rounded weight is off by 0.16% rms, and so is a
# sum of many such products whose errors are independent. The residual
# stream passes about four matmuls a layer in sequence, so 24 layers
# accumulate about sqrt(96) x 0.16% = 1.6%; 12 layers about 1.1%. Weight-only
# int8 (one scale per output column, absmax about four standard deviations)
# rounds each weight by 0.9% rms, 5.6 times bfloat16's product error: about
# 6-9% at these depths. 3% sits between the two with a factor of two on each
# side. The first chip run of this PR measured both sides; PERF.md section 6
# has the numbers. The loss, a mean over ~500 positions of a log-softmax, is
# steadier than a single logit: 1% relative.
LOGITS_REL_RMS_TOL = 3e-2
LOSS_REL_TOL = 1e-2


def sources_key(config_path: str, spec: dict) -> str:
    """Hash of everything the verdict depends on."""
    h = hashlib.sha256()
    paths = [config_path, os.path.abspath(__file__)]
    for base in (os.path.join(ROOT, "ditl_tpu"), os.path.join(HERE, "reference")):
        for dirpath, dirnames, filenames in os.walk(base):
            dirnames[:] = sorted(d for d in dirnames
                                 if d not in ("__pycache__", "_build"))
            paths += [os.path.join(dirpath, f) for f in sorted(filenames)
                      if f.endswith((".py", ".json"))]
    for p in paths:
        h.update(os.path.relpath(p, ROOT).encode())
        with open(p, "rb") as f:
            h.update(f.read())
    h.update(json.dumps(spec, sort_keys=True).encode())
    return h.hexdigest()[:20]


def model_config(config: dict, model_overrides: list[str]):
    """The program's ModelConfig exactly as the cell's command line builds
    it: the preset, then ``model.X=Y`` overrides in order."""
    from ditl_tpu.config import Config, parse_overrides
    from ditl_tpu.models.presets import get_preset

    cfg = Config(model=get_preset(config["preset"]))
    return parse_overrides(cfg, [f"model.{o}" for o in model_overrides]).model


def seeded_params(cfg, seed: int, ref):
    """The program's own initialiser (so the tree has the program's layout
    and dtype), then the family's ``perturb`` where it has one."""
    import jax

    from ditl_tpu.models import llama

    params = llama.init_params(jax.random.key(seed), cfg)
    return ref.perturb(params, cfg, seed) if hasattr(ref, "perturb") else params


def seeded_sample(vocab: int, seed: int, packed: bool):
    """(input_ids, positions, segment_ids, loss_mask) of SAMPLE_ROWS x
    SAMPLE_TOKENS. ``packed``: documents of uneven length in each row, with
    positions restarting at each, as the trainer's loader packs them."""
    import numpy as np

    rng = np.random.default_rng(seed)
    ids = rng.integers(3, vocab, size=(SAMPLE_ROWS, SAMPLE_TOKENS)).astype(np.int32)
    seg = np.ones_like(ids)
    pos = np.broadcast_to(np.arange(SAMPLE_TOKENS, dtype=np.int32), ids.shape).copy()
    if packed:
        for r in range(SAMPLE_ROWS):
            cuts = np.sort(rng.choice(np.arange(8, SAMPLE_TOKENS - 8), 2, replace=False))
            seg[r] = np.searchsorted(cuts, np.arange(SAMPLE_TOKENS), side="right") + 1
            starts = np.concatenate([[0], cuts])
            pos[r] = np.arange(SAMPLE_TOKENS) - starts[seg[r] - 1]
    return ids, pos, seg, np.ones(ids.shape, np.float32)


def rel_rms(got, ref) -> float:
    import numpy as np

    got = np.asarray(got, np.float64)
    ref = np.asarray(ref, np.float64)
    if not np.all(np.isfinite(got)):
        return float("inf")
    return float(np.sqrt(np.mean((got - ref) ** 2)) / (np.sqrt(np.mean(ref ** 2)) + 1e-30))


def compare(config: dict, spec: dict, seed: int = 0, quantize: bool = False,
            reference_dir: str = REFERENCE_DIR) -> dict:
    """Run both sides; returns the verdict record. ``quantize`` runs the
    program side on weight-only int8 weights instead: the demonstration
    that the tolerance refuses a lower precision (never a cell's own check).

    Everything that belongs to one family comes from its reference module,
    ``<reference_dir>/<config["reference"]>.py`` (its docstring lists the
    hooks): this file knows no architecture."""
    import jax
    import jax.numpy as jnp

    from ditl_tpu.models import llama

    cfg = model_config(config, spec["model_overrides"])
    ref = load_module(os.path.join(reference_dir, f"{config['reference']}.py"))
    # The rehearsal runs a tiny model on the CPU: its sizes are not the
    # configuration's, and its verdict never reaches a result line.
    problems = [] if spec.get("rehearsal") else ref.check_sizes(cfg, config)
    if problems:
        return {"ok": False, "error": "sizes differ from the configuration "
                "file: " + "; ".join(problems)}
    sizes = ref.sizes(cfg, config)
    train = spec["role"] == "train"
    params = seeded_params(cfg, seed, ref)
    ids, pos, seg, mask = (jnp.asarray(a) for a in
                           seeded_sample(cfg.vocab_size, seed, packed=train))
    kw = {"positions": pos, "segment_ids": seg} if train else {}
    run_params = params
    if quantize:
        from ditl_tpu.ops.quant import quantize_weights

        run_params = quantize_weights(params)
    got = jax.jit(lambda p: llama.forward(p, ids, cfg, **kw))(run_params)
    # the reference's logits, or a dict that holds them under "logits" beside
    # what else its loss needs (a router's auxiliary term)
    outputs = ref.forward(params, ids, sizes, **kw)
    want = outputs["logits"] if isinstance(outputs, dict) else outputs
    out = {
        "logits_rel_rms": rel_rms(got, want),
        "logits_rel_rms_tol": LOGITS_REL_RMS_TOL,
        "sample": [SAMPLE_ROWS, SAMPLE_TOKENS],
        "quantized": quantize,
        "attention_impl": cfg.attention_impl, "loss_impl": cfg.loss_impl,
        "dtype": cfg.dtype, "param_dtype": cfg.param_dtype,
        "num_layers": cfg.num_layers,
    }
    ok = out["logits_rel_rms"] <= LOGITS_REL_RMS_TOL
    if train and not quantize:
        from ditl_tpu.train.step import loss_fn

        batch = {"input_ids": ids, "positions": pos, "segment_ids": seg,
                 "loss_mask": mask}
        got_loss = float(jax.jit(lambda p: loss_fn(p, batch, cfg)[0])(params))
        want_loss = float(ref.loss(outputs, ids, mask, sizes))
        out.update(loss=got_loss, loss_reference=want_loss,
                   loss_rel=abs(got_loss - want_loss) / abs(want_loss),
                   loss_rel_tol=LOSS_REL_TOL)
        ok = ok and out["loss_rel"] <= LOSS_REL_TOL
    out["ok"] = bool(ok)
    return out


def verdict_path(out_dir: str, workload: str, key: str) -> str:
    return os.path.join(out_dir, f"{workload}.{key}.json")


def cached_or_run(out_dir: str, workload: str, config_path: str, spec: dict) -> dict:
    """The verdict for this cell, from ``benchmarks/out/`` when the sources
    have not changed since it was written."""
    with open(config_path) as f:
        config = json.load(f)
    key = sources_key(config_path, spec)
    path = verdict_path(out_dir, workload, key)
    if os.path.exists(path):
        with open(path) as f:
            return {**json.load(f), "cached": True}
    verdict = compare(config, spec)
    os.makedirs(os.path.dirname(path), exist_ok=True)
    tmp = path + ".tmp"
    with open(tmp, "w") as f:
        json.dump(verdict, f, indent=1, sort_keys=True)
    os.replace(tmp, path)
    return {**verdict, "cached": False}
