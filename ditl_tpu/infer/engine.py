"""KV-cache generation engine (L1/L5) — the local, TPU-native "inference" the
reference only reaches over HTTP (ref ``src/distributed_inference.py:34-41``).

Design (TPU-first):
- **Prefill + decode split**: the prompt is processed in one batched forward
  (MXU-friendly big matmuls) writing the KV cache; decode then feeds one token
  per step through a ``lax.scan`` — the whole generation loop is a single XLA
  program, no host round-trips between tokens.
- **Static shapes**: prompts are right-padded to a power-of-two bucket and the
  decode loop has a static ``max_new_tokens``, so each (batch, bucket,
  GenerateConfig) compiles once and is cached.
- **Masked-slot validity instead of causal masks**: every (b, slot) pair in
  the cache carries an implicit validity rule — prompt slots ``< lengths[b]``
  plus generated slots — so right-padding, per-example prompt lengths, and
  EOS freezing all work inside one jitted program.
- **Sharding-aware**: with a mesh, the cache is sharded batch-over-data/fsdp
  and KV-heads-over-tensor via the same rule table as training
  (parallel/sharding.py), so a TP/FSDP-sharded model decodes without
  resharding its weights.
"""

from __future__ import annotations

import dataclasses
from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np

from ditl_tpu.config import ModelConfig
from ditl_tpu.data.tokenizer import Tokenizer
from ditl_tpu.infer.cache import cache_logical_axes, init_cache
from ditl_tpu.infer.sampling import sample_logits
from ditl_tpu.models import llama
from ditl_tpu.utils.logging import get_logger

logger = get_logger(__name__)

__all__ = ["GenerateConfig", "Generator"]


@dataclass(frozen=True)
class GenerateConfig:
    """Per-request sampling parameters (static: part of the compile key)."""

    max_new_tokens: int = 64
    temperature: float = 0.0  # 0 => greedy
    top_k: int = 0  # 0 => disabled
    top_p: float = 1.0  # 1.0 => disabled
    seed: int = 0
    # >0 => also return the chosen token's logprob and the top-N
    # alternatives per step (OpenAI `logprobs` semantics; engine
    # `generate_with_logprobs`). Part of the compile key.
    logprobs: int = 0


def lru_program(cache, key, build, bound: int = 32):
    """Bounded compile-cache access: move-to-front on hit, build on miss,
    evict oldest past ``bound``. Compile keys include client-controlled
    fields (max_tokens, temperature...), so every program cache on a
    serving path must be bounded or it is an unbounded memory leak."""
    if key in cache:
        cache.move_to_end(key)
        return cache[key]
    prog = build()
    cache[key] = prog
    while len(cache) > bound:
        cache.popitem(last=False)
    return prog


def _next_pow2(n: int, floor: int = 16) -> int:
    p = floor
    while p < n:
        p *= 2
    return p


class Generator:
    """Batch text generation over a (possibly sharded) Llama-family model."""

    def __init__(
        self,
        params: llama.Params,
        model_cfg: ModelConfig,
        tokenizer: Tokenizer,
        *,
        mesh=None,
        rules=None,
    ):
        from ditl_tpu.data.tokenizer import check_vocab

        check_vocab(tokenizer, model_cfg.vocab_size, "Generator")
        self.params = params
        self.cfg = model_cfg
        self.tokenizer = tokenizer
        self.mesh = mesh
        self.rules = rules
        # Multi-LoRA serving tree (models/lora.stack_adapters): full-tree
        # adapter leaves are (L, K, d, r); requests then pick adapters by id.
        lora = params.get("layers", {}).get("lora") or {}
        self.multi_lora = bool(lora) and next(iter(lora.values()))["a"].ndim == 4
        # LRU: the compile key includes client-controlled GenerateConfig
        # fields (temperature, top_p, max_new_tokens...), so an unbounded
        # cache is an unbounded memory leak on a public server — a client
        # sweeping temperatures would pin one program per distinct float.
        import collections

        self._compiled: collections.OrderedDict = collections.OrderedDict()
        self._compile_cache_size = 32

    # -- compiled program ---------------------------------------------------

    def _build(self, batch: int, prompt_len: int, gen: GenerateConfig):
        """Compile the full prefill+decode program for one shape bucket."""
        cfg, mesh, rules = self.cfg, self.mesh, self.rules
        if set(cfg.layer_types) & {"m", "r"}:
            # at its first program and not at construction: the server holds
            # a Generator beside every engine (tokenizer, lock-step fallbacks)
            raise ValueError(
                "the lock-step engine (--engine lockstep) keeps keys and values "
                "only: a state-space or retention layer's state a slot is carried "
                "by --engine continuous --cache-mode paged")
        max_len = prompt_len + gen.max_new_tokens
        if max_len > cfg.max_seq_len:
            raise ValueError(
                f"prompt {prompt_len} + max_new {gen.max_new_tokens} exceeds "
                f"model max_seq_len {cfg.max_seq_len}"
            )
        from ditl_tpu.parallel.sharding import seq_shards

        seq_n = seq_shards(mesh, rules)
        if seq_n > 1:
            # Round the cache up so the context dim always divides the
            # sequence axis — sequence-sharded serving must never silently
            # fall back to a replicated cache (the continuous engine raises
            # for the same condition; here the bucket is internal, so
            # padding it is the kinder fix).
            max_len = -(-max_len // seq_n) * seq_n
        pad_id = jnp.int32(self.tokenizer.pad_id)
        eos_id = jnp.int32(self.tokenizer.eos_id)
        slots = jnp.arange(max_len, dtype=jnp.int32)

        def generate(params, input_ids, lengths, rng, adapter_ids=None):
            cache = init_cache(cfg, batch, max_len)
            if mesh is not None:
                from ditl_tpu.parallel.sharding import named_sharding_tree

                cache = jax.lax.with_sharding_constraint(
                    cache,
                    named_sharding_tree(
                        mesh,
                        cache_logical_axes(cfg, seq_sharded=seq_n > 1),
                        rules,
                    ),
                )
            # Prefill: causal over real (non-pad) prompt slots — pure causal
            # self-attention from an empty cache, so the flash kernel
            # applies (prefill_causal; pad validity rides segment ids).
            q_pos = jnp.arange(prompt_len, dtype=jnp.int32)
            seg = (q_pos[None, :] < lengths[:, None]).astype(jnp.int32)
            positions = jnp.broadcast_to(q_pos, (batch, prompt_len))
            logits, cache = llama.forward(
                params,
                input_ids,
                cfg,
                positions=positions,
                segment_ids=seg,
                mesh=mesh,
                rules=rules,
                cache=cache,
                cache_index=jnp.int32(0),
                adapter_ids=adapter_ids,
                prefill_causal=True,
            )
            last = jnp.take_along_axis(
                logits, (lengths - 1)[:, None, None], axis=1
            )[:, 0]  # (B, V)
            rng, sub = jax.random.split(rng)
            first = sample_logits(
                last, sub, temperature=gen.temperature, top_k=gen.top_k,
                top_p=gen.top_p,
            )
            done0 = first == eos_id
            n_lp = gen.logprobs

            def lp_stats(step_logits, tok):
                """Chosen-token logprob + top-N alternatives (OpenAI
                `logprobs` semantics: of the raw distribution, before any
                temperature/top-k/top-p shaping)."""
                lp = jax.nn.log_softmax(step_logits.astype(jnp.float32), -1)
                chosen = jnp.take_along_axis(lp, tok[:, None], 1)[:, 0]
                top_lp, top_id = jax.lax.top_k(lp, n_lp)
                return chosen, top_id.astype(jnp.int32), top_lp

            stats0 = lp_stats(last, first) if n_lp else None

            def body(carry, t):
                cache, cur, cur_stats, done, rng = carry
                rng, sub = jax.random.split(rng)
                write_idx = prompt_len + t
                # Attend to: real prompt slots + generated slots so far
                # (including the one being written at write_idx).
                mask = (
                    (slots[None, :] < lengths[:, None])
                    | ((slots[None, :] >= prompt_len) & (slots[None, :] <= write_idx))
                )[:, None, :]
                step_logits, cache = llama.forward(
                    params,
                    cur[:, None],
                    cfg,
                    positions=(lengths + t)[:, None],
                    mesh=mesh,
                    rules=rules,
                    cache=cache,
                    cache_index=write_idx,
                    attn_mask=mask,
                    adapter_ids=adapter_ids,
                )
                nxt = sample_logits(
                    step_logits[:, 0], sub, temperature=gen.temperature,
                    top_k=gen.top_k, top_p=gen.top_p,
                )
                # cur's stats were computed when cur was sampled (previous
                # iteration / prefill); emit them alongside cur.
                nxt_stats = lp_stats(step_logits[:, 0], nxt) if n_lp else None
                new_done = done | (cur == eos_id)
                nxt = jnp.where(new_done, pad_id, nxt)
                return (cache, nxt, nxt_stats, new_done, rng), (cur, cur_stats)

            _, (tokens, stats) = jax.lax.scan(
                body,
                (cache, first, stats0, done0, rng),
                jnp.arange(gen.max_new_tokens, dtype=jnp.int32),
            )
            out = {"tokens": tokens.T}  # (steps, B) -> (B, steps)
            if n_lp:
                chosen, top_id, top_lp = stats
                out["token_logprobs"] = chosen.T  # (B, steps)
                out["top_ids"] = jnp.swapaxes(top_id, 0, 1)  # (B, steps, N)
                out["top_logprobs"] = jnp.swapaxes(top_lp, 0, 1)
            return out

        jitted = jax.jit(generate)
        logger.info(
            "compiling generate program: batch=%d prompt_len=%d max_new=%d",
            batch, prompt_len, gen.max_new_tokens,
        )
        return jitted

    def _get_compiled(self, batch: int, prompt_len: int, gen: GenerateConfig):
        # seed is runtime data (the rng argument), not part of the program —
        # keep it out of the compile key or every new seed recompiles.
        key = (batch, prompt_len, dataclasses.replace(gen, seed=0))
        return lru_program(
            self._compiled, key, lambda: self._build(batch, prompt_len, gen),
            bound=self._compile_cache_size,
        )

    # -- public surface -----------------------------------------------------

    def generate_tokens(
        self,
        token_lists: list[list[int]],
        gen: GenerateConfig | None = None,
        adapter_ids: list[int] | None = None,
    ) -> list[list[int]]:
        """Token-id prompts in, generated token ids out (EOS-trimmed).
        ``adapter_ids`` selects each prompt's LoRA adapter when the params
        tree is a multi-adapter stack (0 = the conventional base slot)."""
        return self._generate(token_lists, gen, adapter_ids)[0]

    def generate_tokens_with_logprobs(
        self,
        token_lists: list[list[int]],
        gen: GenerateConfig,
        adapter_ids: list[int] | None = None,
    ) -> tuple[list[list[int]], list[dict]]:
        """Like ``generate_tokens`` but also returns, per prompt, a dict of
        ``token_logprobs`` (chosen token, raw distribution) and aligned
        ``top_ids``/``top_logprobs`` (N = ``gen.logprobs``) lists."""
        if gen.logprobs < 1:
            raise ValueError("generate_tokens_with_logprobs needs gen.logprobs >= 1")
        results, lps = self._generate(token_lists, gen, adapter_ids)
        return results, lps

    def _generate(
        self,
        token_lists: list[list[int]],
        gen: GenerateConfig | None,
        adapter_ids: list[int] | None = None,
    ) -> tuple[list[list[int]], list[dict]]:
        gen = gen or GenerateConfig()
        n = len(token_lists)
        if n == 0:
            return [], []
        if adapter_ids is not None and not self.multi_lora:
            raise ValueError(
                "adapter_ids given but params are not a multi-adapter stack "
                "(models/lora.stack_adapters)"
            )
        token_lists = [t if t else [self.tokenizer.bos_id] for t in token_lists]
        batch = _next_pow2(n, floor=1)
        prompt_len = _next_pow2(max(len(t) for t in token_lists))
        ids = np.full((batch, prompt_len), self.tokenizer.pad_id, np.int32)
        lengths = np.ones((batch,), np.int32)  # dummy rows attend to slot 0
        for i, toks in enumerate(token_lists):
            ids[i, : len(toks)] = toks
            lengths[i] = len(toks)
        run = self._get_compiled(batch, prompt_len, gen)
        rng = jax.random.key(gen.seed)
        args = [self.params, jnp.asarray(ids), jnp.asarray(lengths), rng]
        if self.multi_lora:
            aid = np.zeros((batch,), np.int32)
            if adapter_ids is not None:
                if len(adapter_ids) != n:
                    raise ValueError(
                        f"adapter_ids has {len(adapter_ids)} entries for {n} prompts"
                    )
                lora = self.params["layers"]["lora"]
                k = next(iter(lora.values()))["a"].shape[1]
                bad = [i for i in adapter_ids if not 0 <= i < k]
                if bad:
                    # JAX gathers clamp out-of-range indices under jit, which
                    # would silently serve the wrong adapter.
                    raise ValueError(f"adapter ids {bad} out of range [0, {k})")
                aid[:n] = adapter_ids
            args.append(jnp.asarray(aid))
        out = jax.device_get(run(*args))
        tokens = np.asarray(out["tokens"])
        results = []
        keep: list[int] = []
        for i in range(n):
            row = tokens[i].tolist()
            trimmed = []
            for tok in row:
                if tok == self.tokenizer.eos_id or tok == self.tokenizer.pad_id:
                    break
                trimmed.append(tok)
            results.append(trimmed)
            keep.append(len(trimmed))
        lps: list[dict] = []
        if gen.logprobs:
            lps = [
                {
                    "token_logprobs": np.asarray(out["token_logprobs"])[i, : keep[i]].tolist(),
                    "top_ids": np.asarray(out["top_ids"])[i, : keep[i]].tolist(),
                    "top_logprobs": np.asarray(out["top_logprobs"])[i, : keep[i]].tolist(),
                }
                for i in range(n)
            ]
        return results, lps

    def generate(
        self,
        prompts: list[str],
        gen: GenerateConfig | None = None,
        adapter_ids: list[int] | None = None,
    ) -> list[str]:
        """Text prompts in, generated continuations out."""
        encoded = [
            [self.tokenizer.bos_id] + self.tokenizer.encode(p) for p in prompts
        ]
        out = self.generate_tokens(encoded, gen, adapter_ids)
        return [self.tokenizer.decode(toks) for toks in out]
