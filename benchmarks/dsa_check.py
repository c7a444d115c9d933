#!/usr/bin/env python3
"""A long document through both page pools, against the plain reference's
full forward pass: what ``paged_check.py`` (prompts of at most 700 tokens, no
length argument) and ``reference_check.py`` (a 256-token sample) cannot see
of DeepSeek-V3.2's sparse attention, because under ``index_topk`` tokens the
selection selects everything.

    python benchmarks/dsa_check.py --config deepseek-v3.2-cut1 [--seed N]
                                   [--quantize]

On the chip, at the configuration's published widths and serving dtype,
OUTSIDE any timed window and in an engine of its own (``ContinuousEngine``,
paged cache, pages of 256, 4-step ticks, ``logprobs_k`` armed as in
``paged_check.py``), with ``models/dsa.py``'s ``TAP`` set so that the ENGINE's
own prefill and decode programs report, for every query they serve, the
entries they chose and the normed stream their indexer read:

1. a document of ``--doc-tokens`` (8,192) is prefilled in chunks of
   ``--prefill-chunk`` through the latent pool AND the index-key pool (later
   chunks gather the earlier chunks' pages of both), with one answer token;
2. a question of ``--question-tokens`` behind the SAME document hits its
   cached pages (asserted from the prefix cache's counters), prefills only
   the question over 8,192 cached tokens, and decodes ``--new-tokens`` (24)
   greedily, every step selecting ``index_topk`` of its ~8,250 cached tokens;
3. ``logprob_err_given_the_engines_sets``: for every served token the
   engine's log-probabilities against the log-softmax of the reference's
   logits at the same ids, from ONE uncached float32 pass over document +
   question + answer in which the reference attends to the sets the ENGINE
   chose (``selected=``): rms of the differences over the rms of the
   reference's logits there, against ``GIVEN_SETS_TOL``. What it holds:
   pages, positions, the prefix path, the gather and attention's arithmetic
   over exactly the chosen entries, through both pools;
4. ``selected_overlap_same_stream``: the reference's indexer in float32 on
   the stream the ENGINE's indexer read (``reference.index_scores`` on the
   tapped ``h``), its top ``index_topk`` against the engine's, layer by
   layer, over the queries whose context exceeds ``index_topk``: the share of
   the reference's (query, key) pairs the engine chose too, at least
   ``MIN_OVERLAP``. What it holds: the indexer's projections, rotary,
   scores, masks, page arithmetic and top-k, GIVEN its input (a wrong key,
   position or page selects another set entirely; a bfloat16 score flips a
   pair at the boundary only);
5. reported, and held to nothing, the same two against the reference's OWN
   pass (its own stream, its own sets): ``logprob_err_over_logit_rms`` and
   ``selected_overlap`` a layer. On seeded weights they fail ISSUE 44's 3% and
   98% (13-15% and 93.6-99.8% on the chip), and the run's own numbers say
   why: layer 0 reads the same embedding on both sides and overlaps as in 4;
   from layer 1 on the program's bfloat16 stream has left the float32 one,
   which moves the index scores by ``score_err_from_the_stream`` of a query's
   spread (the reference's indexer on both streams) and so moves pairs across
   the boundary (``flip_margin_p50`` / ``_p99``: how far, in the reference's
   own scores and in units of that spread, the flipped pairs lay from the
   query's k-th score, beside ``all_pairs_margin_p50``); and seeded values do
   not follow the index scores as a trained model's do, so every swapped
   entry weighs like any other, most of all in layer 0, whose attention
   output is most of what its FFN's norm sees beside an embedding drawn at
   0.02.

``--quantize`` serves weight-only int8 weights instead: the lower precision
that 3 has to refuse.

Prints one JSON verdict as its last line; exits 0 when ``ok`` (3 within
tolerance, 4 at least ``MIN_OVERLAP`` in every layer, exactly ``index_topk``
entries a deep query, the prefix hit seen). Not part of a cell's ``correct``
(wiring it in edits ``chip_child.py``: a benchmark PR's). Imported only in a
process that may hold the chip.
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
# ``logprob_err_given_the_engines_sets`` that a bfloat16 engine stays under and
# a lower precision does not, from its two readings on the chip at the
# published widths (PERF.md section 6, PR 44): 2.96, 1.72, 2.17 and 3.54% over
# four seeds in bfloat16 (24 answer tokens x 21 log-probabilities a run, so a
# run scatters by a third around their root mean square of 2.7%; 96 tokens of
# the first seed read 2.69%), 6.83% with weight-only int8 weights. 5% has 1.4
# times of room on both sides. ``reference_check``'s 3%, which ISSUE 44 asked
# for, is made for 256 tokens with everything selected (2.0-2.3% there) and
# refuses one of these four seeds; the verdict carries both.
GIVEN_SETS_TOL = 5e-2
# ``selected_overlap_same_stream``: 99.81% in every layer on every seed in
# bfloat16 and 99.40% with int8 weights (a rounding moves pairs at the
# boundary only), against index_topk / context = 25% for a wrong key, position
# or page: the limit holds the indexer's logic, not its precision.
MIN_OVERLAP = 0.98


class Tapped:
    """``models/dsa.py``'s ``TAP``: what the traced programs chose and read,
    laid out by position: ``sets`` (L, rows, n, n) bool, ``h`` (L, rows, n, D)
    in the stream's dtype, ``seen`` (L, rows, n) calls a query. ``by_row``:
    a batch row is a sequence of its own (a forward without cache); else every
    row is sequence 0 (an engine serving one request at a time, whatever its
    slot). Only positions inside ``accept`` are taken."""

    def __init__(self, layers: int, n: int, rows: int = 1, by_row: bool = False):
        import numpy as np

        self.sets = np.zeros((layers, rows, n, n), bool)
        self.seen = np.zeros((layers, rows, n), np.int32)
        self.h, self.by_row, self.accept = None, by_row, range(n)

    def __call__(self, what: str, layer, a: dict) -> None:
        import numpy as np

        layer, h, pos = int(layer), np.asarray(a["h"]), np.asarray(a["positions"])
        real = np.ones(pos.shape, bool) if a["real"] is None else np.asarray(a["real"])
        if self.h is None:
            self.h = np.zeros((*self.seen.shape, h.shape[-1]), h.dtype)
        chosen = a["chosen"] and [np.asarray(x) for x in a["chosen"]]
        for b, j in zip(*np.nonzero(real)):
            t, row = int(pos[b, j]), int(b) if self.by_row else 0
            if t not in self.accept:
                continue
            self.h[layer, row, t] = h[b, j]
            self.seen[layer, row, t] += 1
            self.sets[layer, row, t] = False
            if not chosen:  # everything was selected
                self.sets[layer, row, t, :t + 1] = True
                continue
            keys = chosen[0][b, j][chosen[1][b, j]] if what == "chunk" else \
                chosen[0][b][chosen[1][b]]
            if what == "step":  # page positions in order, then the tail
                pages = int(a["pages"])
                keys = np.where(keys < pages, keys, int(a["starts"][b]) + keys - pages)
            self.sets[layer, row, t, keys] = True


def check(config: dict, overrides: list[str], seed: int = 0, doc_tokens: int = 8192,
          question_tokens: int = 40, new_tokens: int = 24, page_size: int = 256,
          prefill_chunk: int = 1024, rehearsal: bool = False,
          quantize: bool = False) -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from ditl_tpu.data.tokenizer import ByteTokenizer
    from ditl_tpu.infer.continuous import ContinuousEngine
    from ditl_tpu.models import dsa

    cfg = reference_check.model_config(config, overrides)
    ref = load_module(os.path.join(reference_check.REFERENCE_DIR, f"{config['reference']}.py"))
    problems = [] if rehearsal else ref.check_sizes(cfg, config)
    if problems:
        return {"ok": False, "error": "sizes differ from the configuration file: "
                + "; ".join(problems)}
    sizes = ref.sizes(cfg, config)
    params = reference_check.seeded_params(cfg, seed, ref)
    if quantize:  # the engine's weights only; the reference's are drawn again
        from ditl_tpu.ops.quant import quantize_weights

        params = quantize_weights(params)
    tok = ByteTokenizer()
    n = doc_tokens + question_tokens + new_tokens - 1  # the last token is never fed
    tap = Tapped(cfg.num_layers, n)
    dsa.TAP = tap
    try:
        eng = ContinuousEngine(
            params, cfg, tok, n_slots=2, decode_chunk=4, cache_mode="paged",
            page_size=page_size, max_cache_len=-(-n // page_size) * page_size + page_size,
            prefill_chunk=prefill_chunk, logprobs_k=LOGPROBS_K,
        )
        rng = np.random.default_rng(seed)
        doc = [tok.bos_id] + [int(t) for t in rng.integers(3, cfg.vocab_size, doc_tokens - 1)]
        question = [int(t) for t in rng.integers(3, cfg.vocab_size, question_tokens)]

        def serve(prompt, n_new, accept):
            tap.accept = accept
            rid = eng.submit(prompt, max_new_tokens=n_new, temperature=0.0,
                             logprobs=LOGPROBS_K)
            while eng.pending:
                eng.step()
            jax.effects_barrier()  # every call of the tap has landed
            return {r.req_id: r for r in eng.take_finished()}[rid]

        serve(doc, 1, range(doc_tokens))  # the document's pages, published
        hits0 = eng.stats()["prefix_cache"]["hit_tokens"]
        req = serve(doc + question, new_tokens, range(doc_tokens, n))
        hit = eng.stats()["prefix_cache"]["hit_tokens"] - hits0
    finally:
        dsa.TAP = None
    # the engine's pools and programs go: the reference needs the room
    eng.cache = eng.params = None
    del eng, serve, params
    jax.clear_caches()
    params = reference_check.seeded_params(cfg, seed, ref)
    prompt = doc + question
    full = jnp.asarray([(prompt + req.tokens)[:n]], jnp.int32)
    mine, seen = tap.sets[:, 0], tap.seen[:, 0]

    def against(**kw):
        out = ref.forward(params, full, sizes, **kw)
        logits = np.asarray(out.pop("logits")[0], np.float64)
        logp = logits - np.logaddexp.reduce(logits, axis=-1, keepdims=True)
        diffs, scale = [], []
        for j, token in enumerate(req.tokens):
            at = len(prompt) + j - 1  # the position whose logits chose token j
            diffs.append(req.lp_token[j] - logp[at, token])
            diffs += [lp - logp[at, i] for i, lp in zip(req.lp_top_ids[j], req.lp_top[j])]
            scale.append(logits[at])
        err = float(np.sqrt(np.mean(np.square(diffs))))
        return err / float(np.sqrt(np.mean(np.square(np.concatenate(scale))))), out

    def note(**kw):  # as each number lands: a later pass may not fit the device
        print("dsa_check:", json.dumps(kw), file=sys.stderr, flush=True)

    rel_given, _ = against(selected=jnp.asarray(mine[:, None]))
    note(logprob_err_given_the_engines_sets=rel_given)
    rel_own, own = against(with_index_scores=True)
    note(logprob_err_over_logit_rms=rel_own)
    theirs = np.asarray(own["selected"])[:, 0]
    k = cfg.index_topk
    deep = np.arange(n) >= k  # queries that had to choose
    allowed = np.tril(np.ones((n, n), bool))
    layers = {"selected_overlap": [], "selected_overlap_same_stream": [],
              "score_err_from_the_stream": [], "flip_margin_p50": [], "flip_margin_p99": [],
              "all_pairs_margin_p50": []}

    def share(a, b):
        return float((a & b)[deep].sum() / max(a[deep].sum(), 1))

    for layer in range(cfg.num_layers):
        s_own = own["index_scores"][layer][0]
        s_same = ref.index_scores(params, layer, jnp.asarray(tap.h[layer]), sizes)[0]
        same = np.asarray(ref.select(jnp.asarray(s_same), jnp.asarray(allowed), k))
        layers["selected_overlap"].append(share(theirs[layer], mine[layer]))
        layers["selected_overlap_same_stream"].append(share(same, mine[layer]))
        if not deep.any():
            continue
        # in the reference's own scores: each deep query's k-th score and spread
        a, o, d = allowed[deep], s_own[deep], s_same[deep] - s_own[deep]
        masked = np.where(a, o, -np.inf)
        kth = -np.partition(-masked, k - 1, axis=-1)[:, k - 1:k]
        count = a.sum(axis=-1, keepdims=True)
        mean = np.where(a, o, 0).sum(axis=-1, keepdims=True) / count
        sigma = np.sqrt(np.where(a, (o - mean) ** 2, 0).sum(axis=-1, keepdims=True) / count)
        layers["score_err_from_the_stream"].append(float(
            np.sqrt(np.where(a, d * d, 0).sum() / count.sum()) / np.sqrt(np.mean(sigma ** 2))))
        margin = np.abs(o - kth) / sigma
        flipped = (theirs[layer] ^ mine[layer])[deep] & a
        for name, q in (("flip_margin_p50", 50), ("flip_margin_p99", 99)):
            layers[name].append(float(np.percentile(margin[flipped], q))
                                if flipped.any() else None)
        layers["all_pairs_margin_p50"].append(float(np.median(margin[a])))
        note(layer=layer, **{name: values[-1] for name, values in layers.items()})
    per_query = mine.sum(axis=-1)
    counts_right = bool((per_query == np.minimum(np.arange(n) + 1, k)).all()
                        and (seen == 1).all())
    ok = (np.isfinite(rel_given) and rel_given <= GIVEN_SETS_TOL
          and min(layers["selected_overlap_same_stream"]) >= MIN_OVERLAP
          and counts_right and hit >= doc_tokens - page_size)
    return {
        "ok": bool(ok), "logprob_err_given_the_engines_sets": rel_given,
        "tol": GIVEN_SETS_TOL, "reference_checks_tol": reference_check.LOGITS_REL_RMS_TOL,
        "min_overlap": MIN_OVERLAP,
        "logprob_err_over_logit_rms": rel_own, **layers,
        "queries_that_chose": int(deep.sum()),
        "every_query_tapped_once_with_min_k_entries": counts_right,
        "selected_per_query_max": int(per_query.max()),
        "prefix_hit_tokens": int(hit), "served_tokens": len(req.tokens),
        "doc_tokens": doc_tokens, "question_tokens": question_tokens,
        "new_tokens": new_tokens, "page_size": page_size, "prefill_chunk": prefill_chunk,
        "index_topk": k, "dtype": cfg.dtype, "param_dtype": cfg.param_dtype,
        "quantized": quantize, "num_layers": cfg.num_layers,
        "device": jax.devices()[0].device_kind, "seed": seed,
    }


def main(argv: list[str]) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", required=True, help="name of a file under configs/")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--doc-tokens", type=int, default=8192)
    ap.add_argument("--question-tokens", type=int, default=40)
    ap.add_argument("--new-tokens", type=int, default=24)
    ap.add_argument("--prefill-chunk", type=int, default=1024)
    ap.add_argument("--quantize", action="store_true",
                    help="serve weight-only int8 weights: the control the tolerance refuses")
    args = ap.parse_args(argv)
    with open(os.path.join(HERE, "configs", f"{args.config}.json")) as f:
        config = json.load(f)
    verdict = check(config, model_override_args(config, "serve"), seed=args.seed,
                    doc_tokens=args.doc_tokens, question_tokens=args.question_tokens,
                    new_tokens=args.new_tokens, prefill_chunk=args.prefill_chunk,
                    quantize=args.quantize)
    print(json.dumps({"config": args.config, **verdict}), flush=True)
    return 0 if verdict["ok"] else 1


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
