"""The stable names of the work (ops/names.py) are in the programs: every
scope in the lowered trainer step or the paged serving programs, every
kernel's ``name`` on its ``pallas_call``, and a name of its own on every
jitted entry point. A refactor may move code; it fails here if it renames
or drops one of these.
"""

from __future__ import annotations

import dataclasses
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ditl_tpu.config import ModelConfig, TrainConfig
from ditl_tpu.data.tokenizer import ByteTokenizer
from ditl_tpu.infer.continuous import ContinuousEngine
from ditl_tpu.infer.engine import GenerateConfig, Generator
from ditl_tpu.models import llama
from ditl_tpu.ops import names
from ditl_tpu.parallel.sharding import DEFAULT_RULES
from ditl_tpu.train.state import create_train_state
from ditl_tpu.train.step import _build_step_fn, loss_fn
from tests import family

TRAIN_SCOPES = ("embed", "attn_qkv", "attn_core", "attn_out", "mlp", "layer_scan",
                "lm_head", "loss", "optimizer")
SERVE_SCOPES = ("kv_gather", "kv_write", "sample")

# Tile-able for every kernel in interpret mode: flash (head_dim 64, 128-token
# rows), the MLP and projection backward kernels (128-multiples).
CFG = ModelConfig(
    vocab_size=512, hidden_size=256, intermediate_size=128, num_layers=2,
    num_heads=4, num_kv_heads=2, head_dim=64, max_seq_len=256,
    dtype="float32", param_dtype="float32", fused_gate_up=True,
    attention_impl="flash", loss_impl="fused",
)
KERNEL_CFG = dataclasses.replace(
    CFG, mlp_bwd_impl="pallas", proj_bwd_impl="pallas",
    mlp_bwd_block_n=32, mlp_bwd_block_f=128, mlp_bwd_block_d=128,
    proj_bwd_block_n=32, proj_bwd_block_d=128,
)


def _batch(seq: int = 128) -> dict:
    rng = np.random.default_rng(0)
    return {
        "input_ids": jnp.asarray(rng.integers(3, 500, size=(2, seq)), jnp.int32),
        "loss_mask": jnp.ones((2, seq), jnp.float32),
    }


def _has_segment(text: str, name: str) -> bool:
    """``name`` as a whole segment of a scope path in a lowered module's
    location text (``"jit(train_step)/jvp(loss)/slice"``)."""
    return re.search(r'(?<=[/("])' + re.escape(name) + r'(?=[/)"])', text) is not None


def _pallas_names(jaxpr, out: set) -> set:
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            out.add(eqn.params["name"])
        for value in eqn.params.values():
            for sub in value if isinstance(value, (list, tuple)) else [value]:
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    _pallas_names(inner, out)
    return out


class _Recorder:
    """Stands in for a jitted program: keeps the shapes of its first call,
    so that the test can lower the same program again."""

    def __init__(self, prog):
        self.prog = prog
        self.avals = None

    def __call__(self, *args):
        if self.avals is None:
            self.avals = jax.tree.map(
                lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype)
                if hasattr(x, "shape") else x, args)
        return self.prog(*args)


@pytest.fixture(scope="module")
def train_text() -> str:
    tcfg = TrainConfig(total_steps=4, warmup_steps=1)
    state = jax.eval_shape(
        lambda: create_train_state(jax.random.key(0), CFG, tcfg))
    step = jax.jit(_build_step_fn(CFG, tcfg, None, DEFAULT_RULES))
    return step.lower(state, _batch()).as_text(debug_info=True)


@pytest.fixture(scope="module")
def paged() -> dict:
    """The paged prefill and decode programs of a CPU engine that served one
    request: their lowered text and the kernel names in their jaxprs."""
    cfg = dataclasses.replace(CFG, attention_impl="xla", head_dim=16,
                              hidden_size=64, intermediate_size=128)
    params = llama.init_params(jax.random.key(0), cfg)
    eng = ContinuousEngine(
        params, cfg, ByteTokenizer(), n_slots=2, decode_chunk=4,
        cache_mode="paged", page_size=16, gen=GenerateConfig(max_new_tokens=4))
    recorded = {}

    def recording(name):
        def wrap(build):
            def wrapped(*a):
                recorded[name] = _Recorder(build(*a))
                return recorded[name]

            return wrapped

        return wrap

    with pytest.MonkeyPatch.context() as patch:
        for name in ("paged_prefill", "paged_decode"):
            family.patch_builder(patch, name, recording(family.BUILDERS[name]), eng)
        eng.submit(list(range(1, 21)), max_new_tokens=4)
        eng.run()
    out = {"kernels": set()}
    for builder, rec in recorded.items():
        out[builder] = rec.prog.lower(*rec.avals).as_text(debug_info=True)
        _pallas_names(jax.make_jaxpr(rec.prog)(*rec.avals).jaxpr, out["kernels"])
    return out


@pytest.fixture(scope="module")
def train_kernels() -> set:
    params = jax.eval_shape(
        lambda: llama.init_params(jax.random.key(0), KERNEL_CFG))
    grad = jax.grad(lambda p, b: loss_fn(p, b, KERNEL_CFG)[0])
    return _pallas_names(jax.make_jaxpr(grad)(params, _batch()).jaxpr, set())


def test_the_table_is_the_scopes_of_both_programs_and_seven_kernels():
    assert names.SCOPES == TRAIN_SCOPES + SERVE_SCOPES
    assert len(names.KERNELS) == 7 and not set(names.KERNELS) & set(names.SCOPES)


@pytest.mark.parametrize("name", TRAIN_SCOPES)
def test_scope_is_in_the_lowered_train_step(name, train_text):
    assert _has_segment(train_text, name)


@pytest.mark.parametrize("name", SERVE_SCOPES + ("attn_qkv", "attn_core", "mlp",
                                                  "layer_scan"))
def test_scope_is_in_the_lowered_paged_programs(name, paged):
    found = [b for b in ("_build_paged_prefill", "_build_paged_decode")
             if _has_segment(paged[b], name)]
    assert found, name
    # A prefill gathers its context pages; decode's kernel reads the pages
    # itself, and what its layer loop slices out of the pool is layer_scan's.
    want = "_build_paged_prefill" if name == "kv_gather" else "_build_paged_decode"
    assert want in found


def test_the_decode_programs_work_list_is_built_inside_attn_core(paged):
    """``attn_steps`` sits inside ``attn_core``, so a reader that knows only
    ``SCOPES`` still books the list's time to a name (PR 42), and in front of
    the program's scan: once a tick, not once a step."""
    assert names.ATTN_SCOPES == ("attn_steps",)
    text = paged["_build_paged_decode"]
    assert re.search(r"attn_core/attn_steps/", text)
    assert not re.search(r"while/body[^\n\"]*attn_steps", text)
    assert not _has_segment(paged["_build_paged_prefill"], "attn_steps")


def test_backward_and_rematerialised_parts_keep_their_scope(train_text):
    """Scopes survive ``jax.checkpoint`` and autodiff as path segments: the
    rematerialised forward and the backward of a part are found under the
    part's own name."""
    assert re.search(r"rematted_computation/mlp/", train_text)
    assert re.search(r"transpose\(jvp\(layer_scan\)\)", train_text)
    assert re.search(r"transpose\(jvp\(loss\)\)", train_text)


@pytest.mark.parametrize("name", names.KERNELS)
def test_kernel_name_is_on_its_pallas_call(name, train_kernels, paged):
    assert name in (paged["kernels"] if name == "paged_attention" else train_kernels)


def test_module_names_of_the_lowered_programs(train_text, paged):
    assert "module @jit_train_step" in train_text
    assert "module @jit_paged_prefill" in paged["_build_paged_prefill"]
    assert "module @jit_paged_decode" in paged["_build_paged_decode"]


@pytest.fixture(scope="module")
def spec_engines() -> dict:
    """Engines that can build every serving program, speculative ones with a
    draft model included. Building a program compiles nothing."""
    cfg = dataclasses.replace(CFG, attention_impl="xla", head_dim=16,
                              hidden_size=64, intermediate_size=128)
    params = llama.init_params(jax.random.key(0), cfg)
    kw = dict(n_slots=2, decode_chunk=4, speculative=True,
              draft_params=params, draft_cfg=cfg,
              gen=GenerateConfig(max_new_tokens=4))
    return {
        "contiguous": ContinuousEngine(params, cfg, ByteTokenizer(), **kw),
        "paged": ContinuousEngine(params, cfg, ByteTokenizer(), cache_mode="paged",
                                  page_size=16, **kw),
        "lockstep": Generator(params, cfg, ByteTokenizer()),
    }


@pytest.mark.parametrize("engine,builder,args,name", [
    ("contiguous", "_build_prefill", (16,), "prefill"),
    ("contiguous", "_build_decode", (False, False), "decode"),
    ("contiguous", "_build_draft_prefill", (16,), "draft_prefill"),
    ("contiguous", "_build_draft_suffix_prefill", (16,), "draft_suffix_prefill"),
    ("contiguous", "_build_spec_decode", (False,), "spec_decode"),
    ("contiguous", "_build_prefix_prefill", (16,), "prefix_prefill"),
    ("contiguous", "_build_seed", (16,), "seed"),
    ("contiguous", "_build_suffix_prefill", (16,), "suffix_prefill"),
    ("paged", "_build_paged_prefill", (16, 1), "paged_prefill"),
    ("paged", "_build_paged_decode", (False, False), "paged_decode"),
    ("paged", "_build_spec_paged_decode", (False,), "spec_paged_decode"),
    ("lockstep", "_build", (1, 16, GenerateConfig(max_new_tokens=4)), "generate"),
])
def test_each_jitted_entry_point_has_a_name_of_its_own(
        spec_engines, engine, builder, args, name):
    eng = spec_engines[engine]
    if name in family.BUILDERS:  # the paged programs: asked for in one place
        assert family.BUILDERS[name] == builder
        prog = family.build_program(eng, name, *args)
    else:
        prog = getattr(eng, builder)(*args)
    assert prog.__name__ == name  # the trace's XLA Modules line: jit_<name>


def test_the_trainer_programs_are_named():
    from ditl_tpu.config import MeshConfig
    from ditl_tpu.runtime.mesh import build_mesh
    from ditl_tpu.train.step import make_multi_step, make_train_step

    mesh = build_mesh(MeshConfig())
    tcfg = TrainConfig(total_steps=4, warmup_steps=1)
    example = _batch()
    assert make_train_step(CFG, tcfg, mesh, example).__name__ == "train_step"
    assert make_multi_step(CFG, tcfg, mesh, example, 2).__name__ == "train_multi_step"
