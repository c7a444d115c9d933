"""The Granite-4.0-H family through the benchmark's own doors, at a tiny size
on the CPU: its configuration against the catalog row, the reference check in
float32 and with a state-space layer's own terms dropped, the paged check,
the cell's entries in the manifest."""
import json
import os

import manifest as M
import pytest
import reference_check as rc
from conftest import BENCH

TINY = ["vocab_size=512", "hidden_size=32", "intermediate_size=64", "num_layers=6",
        "layer_types=mmamma", "num_heads=4", "num_kv_heads=2", "head_dim=8", "ssm_heads=4",
        "ssm_head_dim=16", "ssm_state=8", "ssm_chunk=16", "attention_multiplier=0.0625",
        "max_seq_len=1024", "dtype=float32"]
CELL = "granite-4.0-h-micro.chat-wide-ssm"
NAME = "granite-4.0-h-micro"


def config():
    with open(os.path.join(BENCH, "configs", f"{NAME}.json")) as f:
        return json.load(f)


def test_the_configuration_holds_the_catalog_row_whole():
    """Every key of the published config.json as the model-configs catalog
    holds it (copied here: the guide is not part of the repository)."""
    period = ["mamba"] * 5 + ["attention"] + ["mamba"] * 4
    catalog = {
        "attention_bias": False, "attention_multiplier": 0.015625, "embedding_multiplier": 12,
        "hidden_act": "silu", "hidden_size": 2048, "intermediate_size": 8192,
        "layer_types": period * 4, "logits_scaling": 8, "mamba_chunk_size": 256,
        "mamba_conv_bias": True, "mamba_d_conv": 4, "mamba_d_head": 64, "mamba_d_state": 128,
        "mamba_expand": 2, "mamba_n_groups": 1, "mamba_n_heads": 64, "mamba_proj_bias": False,
        "max_position_embeddings": 131072, "model_type": "granitemoehybrid",
        "normalization_function": "rmsnorm", "num_attention_heads": 32, "num_experts_per_tok": 0,
        "num_hidden_layers": 40, "num_key_value_heads": 8, "num_local_experts": 0,
        "position_embedding_type": "nope", "residual_multiplier": 0.22, "rms_norm_eps": 1e-05,
        "rope_scaling": None, "rope_theta": 10000, "shared_intermediate_size": 8192,
        "tie_word_embeddings": True, "vocab_size": 100352}
    body = config()
    assert [k for k, v in catalog.items() if body.get(k, "missing") != v] == []
    assert body["reduced"] == [] and body["model_overrides"] == {}
    entry = M.config_entry(M.load(), NAME)
    assert entry["reduced"] == [] and entry["source"] == body["source"]
    assert all(1 <= len(entry[k]) <= 200 and entry[k].isascii() for k in ("why", "source"))


def test_the_program_is_held_to_the_configuration_file():
    ref = rc.load_module(os.path.join(rc.REFERENCE_DIR, "granite_hybrid.py"))
    cfg = rc.model_config(config(), ["param_dtype=bfloat16"])
    assert ref.check_sizes(cfg, config()) == []
    wrong = rc.model_config(config(), ["ssm_state=64", "residual_multiplier=1.0"])
    assert len(ref.check_sizes(wrong, config())) == 2
    # the whole published model: 40 of 40 layers
    # two operations a weight (the embedding's rows are the tied head's), and
    # six on each of a mixer's 524,288 state values
    flops = ref.forward_flops_per_token(config(), 0.0)
    assert flops == pytest.approx(2 * 3_191_396_096 + 36 * 6 * 524_288, rel=1e-3)


def test_the_cell_and_its_metrics():
    m = M.load()
    assert M.validate(m) == []
    cell = M.cell(m, CELL)
    assert (cell["chips"], cell["config"], cell["traffic"]) == (1, NAME, "chat-wide-ssm")
    assert {"setup_s", "tpot_p50_ms"} == {e["name"] for e in M.metrics_for(m, "end_to_end", CELL)}
    per_layer = {p["name"] for p in M.metrics_for(m, "per_layer", CELL)}
    assert {"ssm_time_share_chat", "ssm_scan_time_share_chat",
            "ssm_state_roofline_decode"} <= per_layer
    assert not [n for n in per_layer if n.startswith(("moe_", "mla_"))]
    assert len(m["workloads"]) == 6 and sum(w["chips"] == 4 for w in m["workloads"]) == 1
    with open(M.traffic_path("chat-wide-ssm")) as f:
        ssm = json.load(f)
    with open(M.traffic_path("chat-wide-mla")) as f:
        mla = json.load(f)
    with open(M.traffic_path("chat-steady-7b")) as f:
        steady = json.load(f)
    assert ssm["prompt_tokens"] == steady["prompt_tokens"] == mla["prompt_tokens"]
    assert ssm["max_tokens"] == mla["max_tokens"] and ssm["sharing"] == "none"
    assert ssm["generator"] == "open_loop" and ssm["rate_per_s"] % 0.5 == 0
    args = ssm["server_args"]
    assert args[args.index("--slots") + 1] == "64" and int(args[args.index("--pages") + 1]) >= 512


@pytest.mark.parametrize("role", ["serve", "train"])
def test_the_reference_check_passes_in_float32_and_refuses_a_dropped_term(role, monkeypatch):
    spec = {"role": role, "model_overrides": TINY, "rehearsal": True}
    verdict = rc.compare(config(), spec, seed=5)
    assert verdict["ok"] and verdict["logits_rel_rms"] < 1e-4
    from ditl_tpu.ops import ssd

    scan = ssd.ssd_scan
    monkeypatch.setattr(ssd, "ssd_scan", lambda *a, **k: scan(*a, **{**k, "doc": None}))
    broken = rc.compare(config(), spec, seed=5)
    # without its document resets a packed trainer sample moves a
    # thousandfold; a serving sample has one document a row and does not notice
    if role == "train":
        assert broken["logits_rel_rms"] > 1000 * verdict["logits_rel_rms"]
    else:
        assert broken["logits_rel_rms"] == verdict["logits_rel_rms"]


def test_the_paged_check_holds_the_engine_to_the_reference():
    import paged_check

    verdict = paged_check.check(config(), TINY, seed=2, prompt_tokens=(5, 20, 33),
                                new_tokens=40, page_size=16, rehearsal=True)
    assert verdict["served_tokens"] == 120 and verdict["logprob_err_over_logit_rms"] < 1e-4
