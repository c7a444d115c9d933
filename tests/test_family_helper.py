"""``tests/family.py`` itself (ISSUE 60): the memo of seeded weights and the
copy a donating program is handed, the engine a ``(model, options)`` a module
and the state a case leaves it in, the one place that names the engine's
builders."""

from __future__ import annotations

import dataclasses
import types

import jax
import numpy as np
import pytest

from ditl_tpu.infer.continuous import ContinuousEngine
from tests import family


@pytest.fixture(scope="module")
def cfg(tiny_model_cfg):
    return dataclasses.replace(tiny_model_cfg, dtype="float32", param_dtype="float32")


def test_tiny_is_the_preset_under_the_files_sizes_under_the_cases_own():
    got = family.tiny("tiny-llama", dict(num_layers=3, hidden_size=64), hidden_size=32)
    assert (got.num_layers, got.hidden_size) == (3, 32)
    assert got == family.tiny("tiny-llama", dict(num_layers=3, hidden_size=32))
    assert hash(got) == hash(dataclasses.replace(got))  # what the memo's key leans on


def test_seeded_hands_every_caller_the_same_tree_and_another_for_another_key(cfg):
    assert family.reference("qwen2") is family.reference("qwen2")  # one module a worker
    # a reference's part of the key is the object: what it does to the draw is its own
    ref = types.ModuleType("a_reference")
    ref.perturb = lambda params, cfg, seed: jax.tree.map(lambda w: w + (seed + 1), params)
    first = family.seeded(ref, cfg)
    assert family.seeded(ref, cfg, 0) is first  # the default seed is no key of its own
    assert family.seeded(ref, dataclasses.replace(cfg), seed=0) is first  # an equal cfg
    assert family.model(ref, cfg) == (first, cfg)
    same = jax.tree.leaves(first)
    for other in (family.seeded(ref, cfg, 1), family.seeded(None, cfg),
                  family.seeded(ref, dataclasses.replace(cfg, num_layers=3))):
        assert other is not first
        leaves = jax.tree.leaves(other)
        assert len(leaves) == len(same)
        assert any(a.shape != b.shape or not np.array_equal(a, b)
                   for a, b in zip(same, leaves))


def test_seeded_asked_under_a_trace_still_keeps_arrays(cfg):
    seen = []

    @jax.jit
    def f(x):
        seen.append(family.seeded(None, cfg, 5))
        return x

    f(0)
    assert all(isinstance(leaf, jax.Array) and not isinstance(leaf, jax.core.Tracer)
               for leaf in jax.tree.leaves(seen[0]))
    assert family.seeded(None, cfg, 5) is seen[0]


def test_a_donating_program_is_handed_a_copy_and_the_memo_stays_readable(cfg):
    """What ``train/step.py``'s steps do to their state (``donate_argnums=(0,)``)."""
    kept = family.seeded(None, cfg)
    want = [np.asarray(leaf) for leaf in jax.tree.leaves(kept)]
    step = jax.jit(lambda p: jax.tree.map(lambda w: w * 0.5, p), donate_argnums=(0,))
    handed = family.copy_of(kept)
    assert all(a is not b for a, b in zip(jax.tree.leaves(handed), jax.tree.leaves(kept)))
    halved = step(handed)
    # the copy is gone (where the backend donates at all); the memo is not
    assert all(leaf.is_deleted() for leaf in jax.tree.leaves(handed))
    assert not any(leaf.is_deleted() for leaf in jax.tree.leaves(kept))
    for leaf, w, h in zip(jax.tree.leaves(family.seeded(None, cfg)), want,
                          jax.tree.leaves(halved)):
        np.testing.assert_array_equal(leaf, w)
        np.testing.assert_array_equal(h, w * 0.5)


def test_an_engine_a_model_and_options_and_what_a_case_leaves_it_as(cfg, engines):
    """The invariant that the module fixtures lean on (and assert once more
    when the module is done): nothing pending is no request queued or seated
    and no page held by a row."""
    model = family.model(None, cfg)
    eng = engines(model, prefill_chunk=0)
    assert engines(model, prefill_chunk=0) is eng
    assert engines((model[0], dataclasses.replace(cfg)), prefill_chunk=0) is eng
    assert engines(model, prefill_chunk=16) is not eng
    assert family.engine(model, prefill_chunk=0) is not eng  # a case's own
    assert (eng.cache_mode, eng.page_size, eng.n_slots) == ("paged", 16, 2)
    assert family.at_rest(eng)
    prompt = family.prompt_of(np.random.default_rng(0), 40)
    assert len(prompt) == 40 and prompt[0] == eng.tokenizer.bos_id
    rid = eng.submit(prompt, max_new_tokens=12, temperature=0.0)
    assert not family.at_rest(eng)  # queued
    eng.step()
    st = eng.stats()
    assert st["slots_busy"] == 1 and st["pages_total"] - st["pages_free"] >= 3
    assert not family.at_rest(eng)  # seated, three pages held
    first = eng.run()[rid]
    st = eng.stats()
    assert family.at_rest(eng) and st["slots_busy"] == 0 and eng.pending == 0
    # what is not free is the prefix cache's, which a later request may find
    assert st["pages_total"] - st["pages_free"] == st["pages_cached_evictable"] > 0
    hit_was = st["prefix_cache"]["hit_tokens"]
    assert family.ask(eng, prompt, 12) == first
    assert eng.stats()["prefix_cache"]["hit_tokens"] - hit_was == 32
    with pytest.raises(AssertionError):  # the fixture's teardown, on an engine left busy
        busy = family.Engines()
        busy(model).submit(prompt, max_new_tokens=4)
        busy.close()


def test_the_builders_are_named_in_one_table_and_a_patch_is_taken_back(cfg, monkeypatch):
    assert set(family.BUILDERS) == {"paged_prefill", "paged_decode", "spec_paged_decode"}
    eng = family.engine(family.model(None, cfg))
    for name, attr in family.BUILDERS.items():
        assert callable(getattr(ContinuousEngine, attr))
    assert family.build_program(eng, "paged_decode", False, False).__name__ == "paged_decode"
    built = []

    def counting(build):
        def builder(self, *key):
            built.append(key)
            return build(self, *key)

        return builder

    with monkeypatch.context() as patch:
        family.patch_builder(patch, "paged_prefill", counting)
        family.build_program(eng, "paged_prefill", 16, 1)
    family.build_program(eng, "paged_prefill", 16, 1)
    assert built == [(16, 1)]
