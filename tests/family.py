"""What a model family's test files share (ISSUE 60): a tiny instance of a
preset, its seeded weights, an engine over them. A helper beside
``tests/tpu_compile.py``, not a test file.

A new configuration's tests ask this module: weights are drawn once a
``(reference, cfg, seed)`` a worker (``seeded``: an eager draw is seconds,
leaf by leaf, and was made anew by every case), and an engine is built once a
``(model, options)`` a module (the ``engines`` fixture of
``tests/conftest.py``: an engine's programs are closures over it, so a second
engine from equal arguments compiles them all again). A case that builds an
engine of its own (``engine``) says in one line why sharing would change what
it checks.
"""

from __future__ import annotations

import dataclasses
import functools
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmarks")
for _p in (ROOT, BENCH):
    if _p not in sys.path:
        sys.path.insert(0, _p)

from harness import load_module  # noqa: E402

from ditl_tpu.data.tokenizer import ByteTokenizer  # noqa: E402
from ditl_tpu.infer.continuous import ContinuousEngine  # noqa: E402
from ditl_tpu.models import llama  # noqa: E402
from ditl_tpu.models.presets import get_preset  # noqa: E402

# What every family file asks of an engine unless the case is about one of them.
PAGED = dict(n_slots=2, cache_mode="paged", page_size=16, max_cache_len=128)


@functools.cache
def reference(name: str):
    """``benchmarks/reference/<name>.py``, one module object a worker: it is
    part of ``seeded``'s key, so two files of one family share one draw."""
    return load_module(os.path.join(BENCH, "reference", name + ".py"))


def tiny(preset: str, base: dict, **kw):
    return dataclasses.replace(get_preset(preset), **{**base, **kw})


@functools.cache
def _seeded(ref, cfg, seed):
    with jax.ensure_compile_time_eval():  # arrays, were it asked under a trace
        params = llama.init_params(jax.random.key(seed), cfg)
        return params if ref is None else ref.perturb(params, cfg, seed)


def seeded(ref, cfg, seed: int = 0):
    """The weights of ``(ref, cfg, seed)``: the program's own draw, perturbed
    as the family's reference does it (``ref=None``: the draw alone). Drawn
    once a worker and handed to every caller as THE SAME immutable arrays.

    One hazard: a program that DONATES its weights deletes them for the next
    caller. The trainer's steps donate their state (``train/step.py``,
    ``donate_argnums=(0,)``): a case that puts these weights into a train
    state, or under any ``donate_argnums``, hands over ``copy_of(seeded(...))``.
    The engine's programs donate the pools, never the weights."""
    return _seeded(ref, cfg, seed)


def model(ref, cfg, seed: int = 0):
    """A model as ``engine`` takes it: its seeded weights and its configuration."""
    return seeded(ref, cfg, seed), cfg


def copy_of(tree):
    """A tree of fresh buffers: what a donating program may be handed."""
    return jax.tree.map(jnp.copy, tree)


def rel(got, want) -> float:
    """The rms of the difference over the rms of ``want``, in float64."""
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return float(np.sqrt(np.mean((got - want) ** 2)) / np.sqrt(np.mean(want ** 2)))


def engine(model, **kw) -> ContinuousEngine:
    """A new engine over ``model`` (its weights and its configuration)."""
    params, cfg = model
    return ContinuousEngine(params, cfg, ByteTokenizer(), **{**PAGED, **kw})


class Engines:
    """An engine a ``(model, options)``: what the module-scoped ``engines``
    fixture hands out. The cases of a module share what it builds, so each
    leaves an engine with nothing pending, reads a counter as the DIFFERENCE
    across its own requests, and draws prompts no other case of the module
    draws where it asserts that nothing was found in the cache."""

    def __init__(self):
        self._built = {}

    def __call__(self, model, **kw) -> ContinuousEngine:
        params, cfg = model
        key = (id(params), cfg, tuple(sorted(kw.items())))
        if key not in self._built:
            self._built[key] = engine(model, **kw)
        return self._built[key]

    def close(self):
        for eng in self._built.values():
            assert at_rest(eng), eng.stats()


def at_rest(eng) -> bool:
    """Nothing pending: no request queued or seated, and every page of the
    pool free or the prefix cache's alone (what a shared engine is left as)."""
    st = eng.stats()
    held = st.get("pages_total", 0) - st.get("pages_free", 0) - st.get(
        "pages_cached_evictable", 0)
    return eng.pending == 0 and st["slots_busy"] == 0 and held == 0


def prompt_of(rng, n: int, vocab: int = 512) -> list[int]:
    """``n`` tokens: the tokenizer's bos, then draws of ``rng``."""
    return [ByteTokenizer().bos_id] + [int(t) for t in rng.integers(3, vocab, n - 1)]


def ask(eng, prompt, n: int = 3) -> list[int]:
    rid = eng.submit(prompt, max_new_tokens=n, temperature=0.0)
    return eng.run()[rid]


# The engine's builders a test may ask for, by their program's name: the one
# place that names them (``ROADMAP.md`` Design 2 moves them; this table follows).
BUILDERS = {
    "paged_prefill": "_build_paged_prefill",
    "paged_decode": "_build_paged_decode",
    "spec_paged_decode": "_build_spec_paged_decode",
}


def build_program(eng, name: str, *key):
    """The jitted program ``name`` of ``BUILDERS`` as ``eng`` builds it."""
    return getattr(eng, BUILDERS[name])(*key)


def patch_builder(monkeypatch, name: str, wrap, target=ContinuousEngine):
    """Put ``wrap(build)`` in the place of ``target``'s builder of ``name``
    while ``monkeypatch`` holds: on the class for every engine built meanwhile
    (the builder then takes ``self`` first), or on one engine."""
    monkeypatch.setattr(target, BUILDERS[name], wrap(getattr(target, BUILDERS[name])))
