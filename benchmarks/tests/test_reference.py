"""The plain reference against the program's forward pass and loss at a tiny
Qwen2-shaped size in float32, where the two must agree to rounding."""
import json
import os

import jax
import numpy as np
import pytest
import reference_check as rc
from conftest import BENCH

TINY = ["vocab_size=512", "hidden_size=64", "intermediate_size=160", "num_layers=3",
        "num_heads=14", "num_kv_heads=2", "head_dim=8", "dtype=float32"]


def config():
    with open(os.path.join(BENCH, "configs", "qwen2-0.5b.json")) as f:
        return json.load(f)


@pytest.mark.parametrize("role", ["serve", "train"])
def test_reference_agrees_with_the_program_in_float32(role):
    spec = {"role": role, "model_overrides": TINY, "rehearsal": True}
    with jax.default_matmul_precision("highest"):
        v = rc.compare(config(), spec, seed=3)
    assert v["logits_rel_rms"] < 1e-4, v
    if role == "train":
        assert v["loss_rel"] < 1e-5, v
    assert v["ok"]


def test_the_tolerance_refuses_int8_weights_at_a_tiny_size():
    spec = {"role": "serve", "model_overrides": TINY, "rehearsal": True}
    v = rc.compare(config(), spec, seed=3, quantize=True)
    assert v["logits_rel_rms"] > v["logits_rel_rms_tol"] / 3  # far from rounding
    assert v["logits_rel_rms"] > 100 * 1e-4


def test_a_width_that_differs_from_the_configuration_file_fails_the_check():
    v = rc.compare(config(), {"role": "serve", "model_overrides": ["hidden_size=64"]})
    assert not v["ok"] and "hidden_size" in v["error"]


def test_the_sample_packs_documents_with_restarting_positions():
    ids, pos, seg, mask = rc.seeded_sample(512, 0, packed=True)
    assert ids.shape == (rc.SAMPLE_ROWS, rc.SAMPLE_TOKENS)
    for r in range(rc.SAMPLE_ROWS):
        starts = np.flatnonzero(np.diff(seg[r])) + 1
        assert len(starts) == 2 and (pos[r][starts] == 0).all()
