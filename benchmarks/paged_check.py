#!/usr/bin/env python3
"""Prefill, then decoding through the paged cache, against the plain
reference's full forward pass: the second half of what "supported" asks of a
serving configuration (``reference_check.py`` compares one full forward pass
and knows no cache).

    python benchmarks/paged_check.py --config olmoe-1b-7b-cut1 [--seed N]

On the chip, at the configuration's published widths and serving dtype, OUTSIDE
any timed window and in an engine of its own: a ``ContinuousEngine`` (paged
cache, pages of 256 tokens, 16-step ticks, as the serving cells run it) armed
with ``logprobs_k``, which makes the decode program return the
log-probabilities of each served token and of its top alternatives (and so is
not the timed program: arming it adds a log-softmax and a top-k to every
step). A few seeded prompts whose lengths reach several prefill buckets are
served greedily for a few ticks; for every served token, the engine's
log-probabilities (from a prefill of the prompt and then cached single-token
steps) are compared, at the same token ids, with the log-softmax of the
reference's logits from ONE uncached forward pass over prompt + answer.

Compare log-probabilities and not sampled tokens: with random weights the
largest logit changes on rounding. The error is the rms of the differences
over the rms of the reference's logits at those positions, so it reads on the
scale of ``reference_check``'s ``logits_rel_rms`` and takes its tolerance:
3%, which a bfloat16 pass stays under and a lower precision does not. A
wrong cache (a page off by one, a missing tail column, a norm or a rotation
applied at the wrong position) moves it to order 1.

Prints one JSON verdict as its last line; exits 0 when ``ok``. Not part of a
cell's ``correct`` (wiring it in edits ``chip_child.py``: a benchmark PR's).
Imported only in a process that may hold the chip.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
for p in (ROOT, HERE):
    if p not in sys.path:
        sys.path.insert(0, p)

import reference_check  # noqa: E402
from harness import load_module, model_override_args  # noqa: E402

PROMPT_TOKENS = (40, 200, 300, 700)  # prefill buckets 256, 256, 512, 1,024
NEW_TOKENS = 24  # a token from the prefill, then two 16-step ticks
LOGPROBS_K = 20  # the most alternatives an OpenAI-style request may ask for


def check(config: dict, overrides: list[str], seed: int = 0,
          prompt_tokens=PROMPT_TOKENS, new_tokens: int = NEW_TOKENS,
          page_size: int = 256, rehearsal: bool = False) -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from ditl_tpu.data.tokenizer import ByteTokenizer
    from ditl_tpu.infer.continuous import ContinuousEngine

    cfg = reference_check.model_config(config, overrides)
    ref = load_module(os.path.join(reference_check.REFERENCE_DIR, f"{config['reference']}.py"))
    problems = [] if rehearsal else ref.check_sizes(cfg, config)
    if problems:
        return {"ok": False, "error": "sizes differ from the configuration file: "
                + "; ".join(problems)}
    sizes = ref.sizes(cfg, config)
    params = reference_check.seeded_params(cfg, seed, ref)
    tok = ByteTokenizer()
    longest = max(prompt_tokens) + new_tokens
    eng = ContinuousEngine(
        params, cfg, tok, n_slots=8, decode_chunk=16, cache_mode="paged",
        page_size=page_size, max_cache_len=-(-longest // page_size) * page_size + page_size,
        logprobs_k=LOGPROBS_K,
    )
    rng = np.random.default_rng(seed)
    prompts = [[tok.bos_id] + [int(t) for t in rng.integers(3, cfg.vocab_size, n - 1)]
               for n in prompt_tokens]
    ids = [eng.submit(p, max_new_tokens=new_tokens, temperature=0.0, logprobs=LOGPROBS_K)
           for p in prompts]
    while eng.pending:
        eng.step()
    done = {r.req_id: r for r in eng.take_finished()}
    diffs, scale, argmax_same, served = [], [], 0, 0
    for prompt, rid in zip(prompts, ids):
        req = done[rid]
        full = jnp.asarray([prompt + req.tokens], jnp.int32)
        out = ref.forward(params, full, sizes)
        logits = np.asarray((out["logits"] if isinstance(out, dict) else out)[0], np.float64)
        logp = logits - np.logaddexp.reduce(logits, axis=-1, keepdims=True)
        for j, token in enumerate(req.tokens):
            at = len(prompt) + j - 1  # the position whose logits chose token j
            diffs.append(req.lp_token[j] - logp[at, token])
            diffs += [lp - logp[at, i] for i, lp in zip(req.lp_top_ids[j], req.lp_top[j])]
            scale.append(logits[at])
            argmax_same += int(token == int(np.argmax(logits[at])))
            served += 1
    err = float(np.sqrt(np.mean(np.square(diffs))))
    logit_rms = float(np.sqrt(np.mean(np.square(np.concatenate(scale)))))
    rel = err / logit_rms
    return {
        "ok": bool(served and np.isfinite(rel) and rel <= reference_check.LOGITS_REL_RMS_TOL),
        "logprob_err_over_logit_rms": rel, "tol": reference_check.LOGITS_REL_RMS_TOL,
        "logprob_err_rms": err, "logit_rms": logit_rms, "served_tokens": served,
        "compared": len(diffs), "argmax_same_share": argmax_same / max(served, 1),
        "prompt_tokens": list(prompt_tokens), "new_tokens": new_tokens,
        "page_size": page_size, "decode_chunk": 16, "logprobs_k": LOGPROBS_K,
        "dtype": cfg.dtype, "param_dtype": cfg.param_dtype, "num_layers": cfg.num_layers,
        "device": jax.devices()[0].device_kind, "seed": seed,
    }


def main(argv: list[str]) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", required=True, help="name of a file under configs/")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    with open(os.path.join(HERE, "configs", f"{args.config}.json")) as f:
        config = json.load(f)
    verdict = check(config, model_override_args(config, "serve"), seed=args.seed)
    print(json.dumps({"config": args.config, **verdict}), flush=True)
    return 0 if verdict["ok"] else 1


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
