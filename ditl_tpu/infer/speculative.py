"""Speculative decoding: verify K drafted tokens per forward pass, fully
on-device.

Sequential decode reads every weight byte per generated token; a K+1-token
verify forward reads them once for up to K+1 tokens — on a
weight-bandwidth-bound decoder accepted drafts are nearly free MXU work. Drafts come from **prompt-lookup** (n-gram lookup à la
prompt-lookup decoding / vLLM's ngram speculator; see PAPERS.md): the most
recent earlier occurrence of the trailing ``ngram`` tokens proposes the K
tokens that followed it — no second model, no extra HBM, high acceptance on
the repetitive spans (code, quotes, retrieval-stuffed prompts) where decode
time actually goes.

**The whole generation is one XLA program**: prefill, then a
``lax.while_loop`` whose body drafts (vectorized n-gram search over the
on-device token history), verifies (one K+1-token forward with per-row
scatter cache writes), and accepts — zero host round-trips between rounds.
A host-side loop would pay one dispatch and one fetch of host time per
round, on any machine, while the device idles; the reference's serving
story is one *HTTP* round-trip
per whole completion (ref ``src/distributed_inference.py:34-41``), and the
lock-step engine already runs its token loop on device — speculation follows
the same rule.

Exactness: greedy speculative output is IDENTICAL to lock-step greedy decode
in exact arithmetic — the verify step accepts exactly the longest draft
prefix the target model itself would have produced, and the first
non-matching position emits the target's own argmax (the "bonus" token).
Tested token-for-token against ``engine.Generator`` in float32 (bf16 can
legitimately flip near-ties between the chunked and 1-token schedules).

Cache note: rejected draft positions leave stale KV behind; they are masked
out (validity is ``slot <= pos[row]+q``) and the next round's K+1-slot write
(starting at ``pos+n+1 <= pos+K+1``) overwrites them, so no rollback pass is
needed.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from ditl_tpu.config import ModelConfig
from ditl_tpu.data.tokenizer import Tokenizer
from ditl_tpu.infer.cache import cache_logical_axes, init_cache
from ditl_tpu.infer.engine import _next_pow2
from ditl_tpu.models import llama
from ditl_tpu.utils.logging import get_logger

logger = get_logger(__name__)

__all__ = [
    "AutoSpeculativeGenerator", "SpeculativeGenerator", "lookup_draft",
    "device_lookup_draft", "spec_sample_tokens",
]


def spec_sample_tokens(
    logits: jax.Array,  # (B, K+1, V) raw verify logits, positions pos..pos+K
    draft: jax.Array,  # (B, K) drafted tokens for positions pos+1..pos+K
    keys: jax.Array,  # (B,) PRNG keys (consumed whole; split outside)
    temps: jax.Array,  # (B,) temperature; <= 0 rows take the greedy rule
    top_ps,  # (B,) or float nucleus parameter
    *,
    top_k: int = 0,
) -> tuple[jax.Array, jax.Array]:
    """Rejection-sampling acceptance for POINT-MASS (prompt-lookup) drafts —
    speculative decoding at temperature > 0 (Leviathan et al.; q is a delta
    at the drafted token, so the acceptance probability is simply
    ``p[draft]`` and the residual on rejection is ``p`` with the draft
    entry removed, renormalized). The emitted sequence is distributed
    EXACTLY as ancestral sampling from the target model under the same
    temperature/top-k/top-p shaping (pinned by a distributional test).

    Returns ``(n_acc, next_tok)``: per-row accepted-draft count and the
    pending token for position ``pos + n_acc + 1`` — the residual sample at
    the first rejected position, or the bonus sample from position K's
    distribution when every draft is accepted. Greedy rows (``temps <= 0``)
    reduce to the exact-match rule: accept while ``draft == argmax``,
    pending token = the argmax at the first mismatch — bit-identical to the
    greedy speculative program."""
    b, k1, v = logits.shape
    k = k1 - 1
    greedy_row = temps <= 0.0
    cand = jnp.argmax(logits, axis=-1).astype(jnp.int32)  # (B, K+1)

    # Shaped probabilities per position (flatten positions into rows so the
    # per-row temperature/top-p helpers broadcast correctly).
    from ditl_tpu.infer.sampling import shaped_logits

    flat = shaped_logits(
        logits.reshape(b * k1, v),
        jnp.repeat(temps, k1),
        top_k=top_k,
        top_p=(jnp.repeat(jnp.asarray(top_ps, jnp.float32), k1)
               if not isinstance(top_ps, (int, float)) else top_ps),
    )
    probs = jax.nn.softmax(flat, axis=-1).reshape(b, k1, v)

    split = jax.vmap(lambda kk: jax.random.split(kk, 2))(keys)
    u_key, cat_key = split[:, 0], split[:, 1]
    u = jax.vmap(lambda kk: jax.random.uniform(kk, (k,)))(u_key)  # (B, K)
    p_draft = jnp.take_along_axis(
        probs[:, :k], draft[..., None], axis=2
    )[..., 0]  # (B, K)
    acc_sampled = u < p_draft
    acc_greedy = draft == cand[:, :k]
    acc = jnp.where(greedy_row[:, None], acc_greedy, acc_sampled)
    n_acc = jnp.sum(jnp.cumprod(acc.astype(jnp.int32), axis=-1), axis=-1)

    # Pending-token distribution: position n_acc's shaped probs, with the
    # rejected draft's entry removed (residual) when a rejection happened.
    p_sel = jnp.take_along_axis(probs, n_acc[:, None, None], axis=1)[:, 0]
    rejected = n_acc < k
    d_sel = jnp.take_along_axis(
        draft, jnp.clip(n_acc, 0, k - 1)[:, None], axis=1
    )[:, 0]
    vocab = jnp.arange(v, dtype=jnp.int32)
    residual = jnp.where(
        rejected[:, None] & (vocab[None, :] == d_sel[:, None]), 0.0, p_sel
    )
    # Degenerate guard (float-only; p[draft] == 1 implies acceptance a.s.):
    # fall back to the unadjusted distribution rather than sampling NaNs.
    z = jnp.sum(residual, axis=-1, keepdims=True)
    residual = jnp.where(z > 0.0, residual / jnp.maximum(z, 1e-30), p_sel)
    next_sampled = jax.vmap(
        lambda kk, row: jax.random.categorical(kk, jnp.log(row + 1e-38))
    )(cat_key, residual).astype(jnp.int32)
    next_greedy = jnp.take_along_axis(cand, n_acc[:, None], axis=1)[:, 0]
    return n_acc, jnp.where(greedy_row, next_greedy, next_sampled)


def _emit_rows(buf: jax.Array, chunk: jax.Array, idx: jax.Array, count: jax.Array):
    """Write the first ``count[b]`` entries of ``chunk`` (B, S, ...) into
    ``buf`` (B, T, ...) at per-row offsets ``idx`` (B,) — trailing feature
    dims broadcast (the speculative logprob buffers are (B, T, N)). Same
    gather+select formulation as infer/cache._scatter_rows (TPU scatters
    serialize; dense selects don't), with the per-row prefix length
    bound."""
    s = chunk.shape[1]
    tail = (1,) * (buf.ndim - 2)
    rel = jnp.arange(buf.shape[1], dtype=jnp.int32)[None, :] - idx[:, None]
    in_chunk = (rel >= 0) & (rel < jnp.minimum(count, s)[:, None])
    gathered = jnp.take_along_axis(
        chunk.astype(buf.dtype),
        jnp.clip(rel, 0, s - 1).reshape(rel.shape + tail),
        axis=1,
    )
    return jnp.where(in_chunk.reshape(in_chunk.shape + tail), gathered, buf)


def lookup_draft(context: list[int], k: int, ngram: int,
                 min_ngram: int | None = None) -> list[int]:
    """Host reference implementation of prompt-lookup drafting (the device
    version below must match it — tests/test_speculative.py): find the most
    recent earlier occurrence of the trailing ``ngram`` of ``context`` and
    return the ``k`` tokens that followed it, 0-padded when no match or the
    history runs out. With ``min_ngram < ngram``, BACKS OFF to shorter
    n-grams when the longer one has no earlier occurrence — a 1-gram floor
    is a "most recent successor" bigram predictor, which keeps drafting on
    merely statistically repetitive text where exact long n-grams are
    rare."""
    min_n = ngram if min_ngram is None else min_ngram
    n = len(context)
    for level in range(ngram, min_n - 1, -1):
        draft: list[int] = []
        if n > level:
            tail = context[n - level:]
            fallback: list[int] | None = None
            for start in range(n - level - 1, -1, -1):
                if context[start:start + level] == tail:
                    follow = list(context[start + level: start + level + k])
                    if len(follow) == k:  # prefer a full continuation
                        draft = follow
                        break
                    if fallback is None:
                        fallback = follow
            if not draft and fallback is not None:
                draft = fallback
        if draft:
            return (draft + [0] * (k - len(draft)))[:k]
    return [0] * k


def _device_lookup_level(
    tokens: jax.Array,  # (B, T) token history buffer
    ctx_len: jax.Array,  # (B,) valid length per row
    *,
    k: int,
    ngram: int,
) -> tuple[jax.Array, jax.Array]:
    """One n-gram level of the device lookup: ((B, k) draft, (B,) found)."""
    b, t = tokens.shape
    # Trailing ngram per row: tokens[ctx_len-ngram : ctx_len].
    tail_idx = ctx_len[:, None] - ngram + jnp.arange(ngram)  # (B, ngram)
    tail = jnp.take_along_axis(tokens, jnp.clip(tail_idx, 0, t - 1), axis=1)
    # Candidate window starts i: tokens[i : i+ngram] == tail, i strictly
    # before the trailing occurrence itself. Built from ngram STATIC slices
    # (shifted compares), not a (B, W, ngram) gather — TPU lowers computed-
    # index gathers poorly, and this runs inside every decode round.
    w = t - ngram
    starts = jnp.arange(w, dtype=jnp.int32)  # (W,)
    eq = jnp.ones((b, w), bool)
    for j in range(ngram):
        eq &= tokens[:, j: j + w] == tail[:, j][:, None]
    valid = (starts[None, :] < (ctx_len - ngram)[:, None]) & (
        ctx_len[:, None] > ngram
    )
    hit = eq & valid
    # Prefer the most recent match whose k-token continuation fits inside the
    # context (a tail-adjacent match drafts mostly padding — e.g. a constant
    # token would cap acceptance at 1/round); fall back to the most recent.
    hit_full = hit & ((starts[None, :] + ngram + k) <= ctx_len[:, None])
    best_any = jnp.max(jnp.where(hit, starts[None, :], -1), axis=-1)  # (B,)
    best_full = jnp.max(jnp.where(hit_full, starts[None, :], -1), axis=-1)
    best = jnp.where(best_full >= 0, best_full, best_any)
    found = best >= 0
    src = (best + ngram)[:, None] + jnp.arange(k, dtype=jnp.int32)[None, :]
    draft = jnp.take_along_axis(tokens, jnp.clip(src, 0, t - 1), axis=1)
    in_ctx = src < ctx_len[:, None]
    return jnp.where(found[:, None] & in_ctx, draft, 0).astype(jnp.int32), found


def device_lookup_draft(
    tokens: jax.Array,  # (B, T) token history buffer
    ctx_len: jax.Array,  # (B,) valid length per row
    *,
    k: int,
    ngram: int,
    min_ngram: int | None = None,
) -> jax.Array:
    """Vectorized on-device prompt-lookup with n-gram BACKOFF: per row, the
    longest n-gram level (``ngram`` down to ``min_ngram``) with an earlier
    occurrence supplies the draft. O(T·ngram·levels) compares per row — VPU
    noise next to the verify forward. Matches ``lookup_draft``."""
    min_n = ngram if min_ngram is None else min_ngram
    draft = jnp.zeros((tokens.shape[0], k), jnp.int32)
    taken = jnp.zeros((tokens.shape[0],), bool)
    for level in range(ngram, min_n - 1, -1):
        d, f = _device_lookup_level(tokens, ctx_len, k=k, ngram=level)
        use = f & ~taken
        draft = jnp.where(use[:, None], d, draft)
        taken = taken | f
    return draft


class SpeculativeGenerator:
    """Greedy batch generation with on-device prompt-lookup speculation.

    Drop-in for ``engine.Generator`` restricted to greedy decoding
    (temperature 0) — the rejection-sampling extension for temperature > 0
    changes acceptance from exact-match to probability-ratio and is out of
    scope here."""

    def __init__(
        self,
        params: llama.Params,
        model_cfg: ModelConfig,
        tokenizer: Tokenizer,
        *,
        k: int = 8,
        ngram: int = 3,
        min_ngram: int = 1,
        rounds_per_check: int = 8,
        mesh=None,
        rules=None,
    ):
        if k < 1:
            raise ValueError(f"k must be >= 1, got {k}")
        if ngram < 1:
            raise ValueError(f"ngram must be >= 1, got {ngram}")
        if not (1 <= min_ngram <= ngram):
            raise ValueError(
                f"min_ngram must be in [1, ngram], got {min_ngram}"
            )
        if rounds_per_check < 1:
            raise ValueError(f"rounds_per_check must be >= 1, got {rounds_per_check}")
        self.rounds_per_check = rounds_per_check
        self.params = params
        self.cfg = model_cfg
        self.tokenizer = tokenizer
        self.k = k
        self.ngram = ngram
        self.min_ngram = min_ngram
        # Per-ROW tokens per verify forward of the latest call (None before
        # the first): the number that must clear the verify/decode step-cost
        # ratio for speculation to win. Per-row, not batch-aggregate — plain
        # decode also produces one token per row per forward, so the
        # breakeven ratio is batch-size-independent.
        self.last_acceptance: float | None = None
        self.last_rounds: int = 0
        self.mesh = mesh
        self.rules = rules
        # LRU-bounded: the compile key includes client-controlled max_new
        # (same rationale as engine.Generator's cache — unbounded would be
        # an unbounded memory leak on a public server).
        import collections

        self._compiled: collections.OrderedDict = collections.OrderedDict()
        self._compile_cache_size = 32

    # -- the one compiled program --------------------------------------------

    def _build(self, batch: int, prompt_len: int, max_new: int):
        cfg, mesh, rules, k, ngram = self.cfg, self.mesh, self.rules, self.k, self.ngram
        min_ngram = self.min_ngram
        rounds_per_check = max(1, min(self.rounds_per_check, max_new))
        max_len = prompt_len + max_new + k + 1  # KV slots incl. overshoot slack
        if max_len > cfg.max_seq_len:
            raise ValueError(
                f"prompt {prompt_len} + max_new {max_new} + k {k} exceeds "
                f"model max_seq_len {cfg.max_seq_len}"
            )
        t_buf = prompt_len + max_new + 1  # token history: prompt + first + out
        pad_id = jnp.int32(self.tokenizer.pad_id)
        eos_id = jnp.int32(self.tokenizer.eos_id)
        slots = jnp.arange(max_len, dtype=jnp.int32)
        q_idx = jnp.arange(k + 1, dtype=jnp.int32)
        rows = jnp.arange(batch, dtype=jnp.int32)[:, None]

        def shard_cache(cache):
            if mesh is None:
                return cache
            from ditl_tpu.parallel.sharding import named_sharding_tree

            return jax.lax.with_sharding_constraint(
                cache, named_sharding_tree(mesh, cache_logical_axes(cfg), rules)
            )

        def spec_generate(params, input_ids, lengths, n_real):
            # ---- prefill ----
            cache = shard_cache(init_cache(cfg, batch, max_len))
            p_pos = jnp.arange(prompt_len, dtype=jnp.int32)
            # Empty-cache prefill = causal self-attention: flash-kernel path
            # (validity via segment ids), same as the lock-step engine.
            seg = (p_pos[None, :] < lengths[:, None]).astype(jnp.int32)
            logits, cache = llama.forward(
                params, input_ids, cfg,
                positions=jnp.broadcast_to(p_pos, (batch, prompt_len)),
                segment_ids=seg, mesh=mesh, rules=rules,
                cache=cache, cache_index=jnp.int32(0), prefill_causal=True,
            )
            first = jnp.argmax(
                jnp.take_along_axis(logits, (lengths - 1)[:, None, None], axis=1)[:, 0],
                axis=-1,
            ).astype(jnp.int32)
            # Pad rows (batch bucketing) start DONE: they would otherwise
            # decode to the full budget, inflating the round count that the
            # acceptance metric divides by.
            is_pad_row = jnp.arange(batch, dtype=jnp.int32) >= n_real

            tokens_buf = jnp.zeros((batch, t_buf), jnp.int32)
            tokens_buf = jax.lax.dynamic_update_slice(
                tokens_buf, input_ids, (0, 0)
            )
            done0 = (first == eos_id) | is_pad_row
            tokens_buf = tokens_buf.at[rows[:, 0], lengths].set(
                jnp.where(done0, 0, first)
            )
            out_buf = jnp.full((batch, max_new), pad_id, jnp.int32)
            out_buf = out_buf.at[:, 0].set(jnp.where(done0, pad_id, first))
            n_out = jnp.where(done0, 0, 1)
            ctx_len = lengths + n_out
            state = dict(
                cache=cache,
                tokens=tokens_buf,
                out=out_buf,
                cur=jnp.where(done0, pad_id, first),
                pos=lengths,  # KV depth; cur's KV is written next round
                ctx_len=ctx_len,
                n_out=n_out,
                done=done0 | (n_out >= max_new),
                rounds=jnp.int32(0),
            )

            # ---- speculative rounds, all on device ----
            def cond(s):
                return ~jnp.all(s["done"])

            def body(s):
                draft = device_lookup_draft(
                    s["tokens"], s["ctx_len"], k=k, ngram=ngram,
                    min_ngram=min_ngram,
                )  # (B, k)
                tokens_in = jnp.concatenate([s["cur"][:, None], draft], axis=1)
                positions = s["pos"][:, None] + q_idx[None, :]
                mask = slots[None, None, :] <= positions[:, :, None]
                logits, cache = llama.forward(
                    params, tokens_in, cfg,
                    positions=positions, mesh=mesh, rules=rules,
                    cache=s["cache"], cache_index=s["pos"], attn_mask=mask,
                )
                cand = jnp.argmax(logits, axis=-1).astype(jnp.int32)  # (B, K+1)
                eq = tokens_in[:, 1:] == cand[:, :k]
                n_acc = jnp.sum(
                    jnp.cumprod(eq.astype(jnp.int32), axis=-1), axis=-1
                )  # (B,)

                # Emit the accepted prefix + bonus, truncated at EOS/budget.
                in_span = q_idx[None, :] <= n_acc[:, None]
                is_eos = cand == eos_id
                eos_before = (jnp.cumsum(is_eos, axis=1) - is_eos.astype(jnp.int32)) > 0
                budget_ok = (s["n_out"][:, None] + q_idx[None, :]) < max_new
                emit = (
                    in_span & ~is_eos & ~eos_before & budget_ok
                    & ~s["done"][:, None]
                )
                e = jnp.sum(emit, axis=1)  # emitted this round (B,)
                hit_eos = jnp.any(in_span & is_eos & ~eos_before, axis=1)

                # Emitted tokens are a per-row prefix of cand: dense
                # select-writes, no TPU scatter.
                out = _emit_rows(s["out"], cand, s["n_out"], e)
                tokens = _emit_rows(s["tokens"], cand, s["ctx_len"], e)

                n_out = s["n_out"] + e
                done = s["done"] | hit_eos | (n_out >= max_new)
                take = n_acc + 1
                pos = jnp.where(
                    s["done"], s["pos"],
                    jnp.minimum(s["pos"] + take, max_len - k - 2),
                )
                cur = jnp.where(
                    done, pad_id, jnp.take_along_axis(cand, n_acc[:, None], 1)[:, 0]
                )
                return dict(
                    cache=cache, tokens=tokens, out=out, cur=cur, pos=pos,
                    ctx_len=s["ctx_len"] + e, n_out=n_out, done=done,
                    # Count only rounds where some row was still live: the
                    # chunked while-loop runs whole R-round chunks, and
                    # phantom tail rounds would deflate measured acceptance.
                    rounds=s["rounds"]
                    + jnp.any(~s["done"]).astype(jnp.int32),
                )

            # Chunked loop: R rounds per while iteration. A bare while_loop
            # costs ~4.5 ms/iteration extra on this chip (no cross-iteration
            # pipelining with an unknown trip count); scanning R rounds per
            # check amortizes that to noise. Rows that finish mid-chunk
            # no-op (emission masked, pos frozen) for <= R-1 wasted rounds.
            def chunk(s):
                def sbody(c, _):
                    return body(c), None
                s, _ = jax.lax.scan(sbody, s, None, length=rounds_per_check)
                return s

            state = jax.lax.while_loop(cond, chunk, state)
            return state["out"], state["rounds"], state["n_out"]

        logger.info(
            "compiling speculative program: batch=%d prompt_len=%d max_new=%d k=%d",
            batch, prompt_len, max_new, k,
        )
        return jax.jit(spec_generate)

    # -- public surface -------------------------------------------------------

    def generate_tokens(
        self, token_lists: list[list[int]], max_new_tokens: int = 64
    ) -> list[list[int]]:
        """Greedy speculative decode; token-id prompts in, EOS-trimmed
        generated ids out. Token-identical to ``Generator.generate_tokens``
        at temperature 0 (exact arithmetic)."""
        n = len(token_lists)
        if n == 0:
            return []
        tok = self.tokenizer
        token_lists = [t if t else [tok.bos_id] for t in token_lists]
        batch = _next_pow2(n, floor=1)
        prompt_len = _next_pow2(max(len(t) for t in token_lists))
        ids = np.full((batch, prompt_len), tok.pad_id, np.int32)
        lengths = np.ones((batch,), np.int32)
        for i, toks in enumerate(token_lists):
            ids[i, : len(toks)] = toks
            lengths[i] = len(toks)

        from ditl_tpu.infer.engine import lru_program

        key = (batch, prompt_len, max_new_tokens)
        program = lru_program(
            self._compiled, key,
            lambda: self._build(batch, prompt_len, max_new_tokens),
            bound=self._compile_cache_size,
        )
        out, rounds, n_out = program(
            self.params, jnp.asarray(ids), jnp.asarray(lengths), jnp.int32(n)
        )
        out = np.asarray(jax.device_get(out))
        rounds = int(jax.device_get(rounds))
        self.last_rounds = rounds
        self.last_acceptance = None
        if rounds:
            total = int(np.asarray(jax.device_get(n_out))[:n].sum())
            self.last_acceptance = total / rounds / n
            logger.info(
                "speculative decode: %d tokens, %d rows, %d rounds "
                "(%.2f tokens/forward/row)",
                total, n, rounds, self.last_acceptance,
            )
        results = []
        for i in range(n):
            trimmed = []
            for t in out[i].tolist():
                if t == tok.eos_id or t == tok.pad_id:
                    break
                trimmed.append(t)
            results.append(trimmed)
        return results

    def generate(self, prompts: list[str], max_new_tokens: int = 64) -> list[str]:
        return _generate_text(self, prompts, max_new_tokens)


def _generate_text(gen, prompts: list[str], max_new_tokens: int) -> list[str]:
    """Shared text round-trip (BOS + encode -> generate_tokens -> decode)."""
    encoded = [
        [gen.tokenizer.bos_id] + gen.tokenizer.encode(p) for p in prompts
    ]
    return [
        gen.tokenizer.decode(t)
        for t in gen.generate_tokens(encoded, max_new_tokens)
    ]


class AutoSpeculativeGenerator:
    """Per-request speculation auto-enable driven by MEASURED acceptance.

    Speculation pays only when accepted tokens per verify forward PER ROW
    exceed the verify/decode step-cost ratio (builders' ~2-2.5x from before
    this round, not re-measured) — and acceptance is a property of the WORKLOAD (repetitive
    continuations accept; high-entropy text does not). This wrapper serves
    each request speculatively while the exponentially-averaged acceptance
    clears ``threshold``, falls back to the plain lock-step ``Generator``
    when it does not, and re-probes with a speculative request every
    ``probe_every`` requests so a workload shift back to repetitive text is
    re-detected. Greedy only (the speculative path's restriction)."""

    def __init__(
        self,
        params: llama.Params,
        model_cfg: ModelConfig,
        tokenizer: Tokenizer,
        *,
        threshold: float = 2.5,
        probe_every: int = 16,
        ema: float = 0.7,
        mesh=None,
        rules=None,
        plain=None,
        **spec_kw,
    ):
        from ditl_tpu.infer.engine import Generator

        if probe_every < 1:
            raise ValueError(f"probe_every must be >= 1, got {probe_every}")
        if not (0.0 <= ema < 1.0):
            raise ValueError(f"ema must be in [0, 1), got {ema}")
        self.spec = SpeculativeGenerator(
            params, model_cfg, tokenizer, mesh=mesh, rules=rules, **spec_kw
        )
        # Reuse the caller's Generator when given (the server already holds
        # one): a second instance would keep a second 32-program compile
        # cache for the same shapes.
        self.plain = plain if plain is not None else Generator(
            params, model_cfg, tokenizer, mesh=mesh, rules=rules
        )
        self.tokenizer = tokenizer
        self.threshold = threshold
        self.probe_every = probe_every
        self._ema_w = ema
        self.acceptance_ema: float | None = None
        self._n_requests = 0

    @property
    def speculating(self) -> bool:
        """Would the next (non-probe) request use the speculative path?"""
        return (
            self.acceptance_ema is None
            or self.acceptance_ema >= self.threshold
        )

    def generate_tokens(
        self, token_lists: list[list[int]], max_new_tokens: int = 64
    ) -> list[list[int]]:
        probe = self._n_requests % self.probe_every == 0
        self._n_requests += 1
        if self.speculating or probe:
            out = self.spec.generate_tokens(token_lists, max_new_tokens)
            acc = self.spec.last_acceptance
            if acc is not None:
                self.acceptance_ema = (
                    acc if self.acceptance_ema is None
                    else self._ema_w * self.acceptance_ema
                    + (1.0 - self._ema_w) * acc
                )
            return out
        from ditl_tpu.infer.engine import GenerateConfig

        return self.plain.generate_tokens(
            token_lists, GenerateConfig(max_new_tokens=max_new_tokens)
        )

    def generate(self, prompts: list[str], max_new_tokens: int = 64) -> list[str]:
        return _generate_text(self, prompts, max_new_tokens)
