#!/usr/bin/env python3
"""Rows longer than the window through both page pools, against the plain
reference's full forward pass: what ``paged_check.py`` (prompts of at most
700 tokens) and ``reference_check.py`` (a 256-token sample) cannot see of a
stack with window attention layers, because inside ``sliding_window`` tokens
a model with no window at all passes both.

    python benchmarks/window_check.py --config trinity-mini-cut1 [--seed N]
                                      [--quantize]

On the chip, at the configuration's published widths and serving dtype,
OUTSIDE any timed window and in an engine of its own (``ContinuousEngine``,
paged cache, pages of 256, 4-step ticks, ``logprobs_k`` armed as in
``paged_check.py``):

1. a document of ``--doc-tokens`` (8,192) is prefilled in chunks of
   ``--prefill-chunk`` (1,024) through both pools: the full layers' chunks
   over every page written before them, the window layers' over the last
   ``ceil(window / page_size)``; the window pages behind go back to the
   allocator chunk by chunk (``window_pages_freed``), with one answer token;
2. a question of ``--question-tokens`` behind the SAME document must hit its
   cached pages in BOTH pools at the whole length (asserted from the prefix
   cache's counters and ``prefix_hits_whole``), prefills only the question,
   and decodes ``--new-tokens`` (32) greedily;
3. a row of ``--short-prompt`` (1,900) tokens decodes ``--long-answer`` (700),
   so its own position crosses ``sliding_window`` and its first window pages
   fall wholly behind the window and are given up IN DECODE (asserted from
   ``window_pages_released_total`` around it: the row's references go; the
   pages themselves stay while the content cache, which published the prompt
   with them, holds them);
4. for every served token of 1-3 the engine's log-probabilities (chosen token
   and top alternatives) against the log-softmax of the reference's logits at
   the same ids, from ONE uncached float32 pass a request: rms of the
   differences over the rms of the reference's logits there, against ``TOL``;
5. the control: the same comparison against the reference with the window
   switched OFF (``window=False``) has to FAIL ``TOL``, which is what shows
   that the check sees the window. ``--quantize`` serves weight-only int8
   weights instead: the lower precision that 4 has to refuse.

``TOL`` is ``reference_check``'s 3%, for its reason: a bfloat16 pass of this
depth stays under it and a pass in a lower precision does not. The mask is a
function of positions alone, so unlike a top-k selection there is no boundary
that a rounding could flip: a wrong window, page or position moves the error
to order 1 (the control reads 0.5-0.9), and nothing between.

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

LOGPROBS_K = 20
TOL = reference_check.LOGITS_REL_RMS_TOL


def check(config: dict, overrides: list[str], seed: int = 0, doc_tokens: int = 8192,
          question_tokens: int = 64, new_tokens: int = 32, short_prompt: int = 1900,
          long_answer: int = 700, page_size: int = 256, prefill_chunk: int = 1024,
          window_pages: int = 0, quantize: bool = False, rehearsal: bool = False) -> dict:
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
    served = params
    if quantize:
        from ditl_tpu.ops.quant import quantize_weights

        served = quantize_weights(params)
    tok = ByteTokenizer()
    longest = max(doc_tokens + question_tokens + new_tokens, short_prompt + long_answer)
    eng = ContinuousEngine(
        served, cfg, tok, n_slots=2, decode_chunk=4, cache_mode="paged", page_size=page_size,
        max_cache_len=-(-longest // page_size) * page_size + page_size,
        prefill_chunk=prefill_chunk, window_pages=window_pages, logprobs_k=LOGPROBS_K)
    rng = np.random.default_rng(seed)
    draw = lambda n: [int(t) for t in rng.integers(3, cfg.vocab_size, n)]  # noqa: E731
    doc = [tok.bos_id] + draw(doc_tokens - 1)

    def serve(prompt, n):
        rid = eng.submit(prompt, max_new_tokens=n, temperature=0.0, logprobs=LOGPROBS_K)
        while eng.pending:
            eng.step()
        return {r.req_id: r for r in eng.take_finished()}[rid]

    stats = [eng.stats()]
    rows = [(doc, serve(doc, 1))]
    stats.append(eng.stats())
    question = doc + draw(question_tokens)
    rows.append((question, serve(question, new_tokens)))
    stats.append(eng.stats())
    short = [tok.bos_id] + draw(short_prompt - 1)
    rows.append((short, serve(short, long_answer)))
    stats.append(eng.stats())

    def compared(window: bool):
        diffs, scale = [], []
        for prompt, req in rows:
            full = jnp.asarray([prompt + req.tokens], jnp.int32)
            logits = np.asarray(ref.forward(params, full, sizes, window=window)["logits"][0],
                                np.float64)
            logp = logits - np.logaddexp.reduce(logits, axis=-1, keepdims=True)
            for j, token in enumerate(req.tokens):
                at = len(prompt) + j - 1  # the position whose logits chose token j
                diffs.append(req.lp_token[j] - logp[at, token])
                diffs += [lp - logp[at, i] for i, lp in zip(req.lp_top_ids[j], req.lp_top[j])]
                scale.append(logits[at])
        rms = float(np.sqrt(np.mean(np.square(np.concatenate(scale)))))
        return float(np.sqrt(np.mean(np.square(diffs)))) / rms, len(diffs)

    err, n = compared(True)
    err_no_window, _ = compared(False)
    hit = stats[2]["prefix_cache"]["hit_tokens"] - stats[1]["prefix_cache"]["hit_tokens"]
    freed, released = ([b[key] - a[key] for a, b in zip(stats, stats[1:])]
                       for key in ("window_pages_freed_total", "window_pages_released_total"))
    names = ("document", "question", "long_answer")
    reach = -(-cfg.sliding_window // page_size)
    whole_doc_pages = doc_tokens // page_size
    out = {
        "logprob_err_over_logit_rms": err, "tol": TOL, "compared": n,
        "logprob_err_without_the_window": err_no_window,
        "served_tokens": sum(len(r.tokens) for _, r in rows),
        "prefix_hit_tokens": hit,
        "prefix_hits_whole": stats[2]["prefix_hits_whole"] - stats[1]["prefix_hits_whole"],
        "window_pages_freed": dict(zip(names, freed)),
        "window_pages_released": dict(zip(names, released)),
        "window_pages_free_at_the_end": stats[3]["window_pages_free"],
        "window_pages_total": stats[3]["window_pages_total"],
        "doc_tokens": doc_tokens, "question_tokens": question_tokens,
        "new_tokens": new_tokens, "short_prompt": short_prompt, "long_answer": long_answer,
        "page_size": page_size, "prefill_chunk": prefill_chunk, "quantized": quantize,
        "dtype": cfg.dtype, "param_dtype": cfg.param_dtype, "num_layers": cfg.num_layers,
        "sliding_window": cfg.sliding_window,
        "device": jax.devices()[0].device_kind, "seed": seed,
    }
    # the document's chunks freed every page behind them but the last window's
    # and the last chunk's own; the long answer's row let go of the pages its
    # own position passed
    crossed = (short_prompt + long_answer - cfg.sliding_window) // page_size
    out["ok"] = bool(
        np.isfinite(err) and err <= TOL and err_no_window > TOL
        and hit == whole_doc_pages * page_size and out["prefix_hits_whole"] == 1
        and freed[0] >= whole_doc_pages - reach - prefill_chunk // page_size
        and released[2] >= crossed)
    return out


def main(argv: list[str]) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", required=True, help="name of a file under configs/")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--quantize", action="store_true")
    ap.add_argument("--doc-tokens", type=int, default=8192)
    ap.add_argument("--question-tokens", type=int, default=64)
    ap.add_argument("--new-tokens", type=int, default=32)
    ap.add_argument("--short-prompt", type=int, default=1900)
    ap.add_argument("--long-answer", type=int, default=700)
    ap.add_argument("--prefill-chunk", type=int, default=1024)
    args = ap.parse_args(argv)
    with open(os.path.join(HERE, "configs", f"{args.config}.json")) as f:
        config = json.load(f)
    verdict = check(config, model_override_args(config, "serve"), seed=args.seed,
                    quantize=args.quantize, doc_tokens=args.doc_tokens,
                    question_tokens=args.question_tokens, new_tokens=args.new_tokens,
                    short_prompt=args.short_prompt, long_answer=args.long_answer,
                    prefill_chunk=args.prefill_chunk)
    print(json.dumps({"config": args.config, **verdict}), flush=True)
    return 0 if verdict["ok"] else 1


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
