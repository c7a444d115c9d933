"""The Brumby family through the benchmark's own doors, at a tiny size on the
CPU: its configuration against the catalog row, the count functions against
hand arithmetic, the generator, the reference check in float32 and with the
state's term dropped, the paged check, the cell's entries in the manifest."""
import json
import os
import types

import manifest as M
import pytest
import reference_check as rc
import retention_counts
from conftest import BENCH

TINY = ["vocab_size=512", "hidden_size=64", "intermediate_size=128", "num_layers=3",
        "layer_types=rrr", "num_heads=4", "num_kv_heads=2", "head_dim=16", "ret_chunk=16",
        "max_seq_len=1024", "dtype=float32"]
CELL = "brumby-14b-cut1.streams-16-ret"
NAME = "brumby-14b-cut1"


def config():
    with open(os.path.join(BENCH, "configs", f"{NAME}.json")) as f:
        return json.load(f)


def test_the_configuration_holds_the_catalog_row_but_for_what_is_reduced():
    """Every key of the published config.json as the model-configs catalog
    holds it (copied here: the guide is not part of the repository)."""
    catalog = {
        "attention_bias": False, "head_dim": 128, "hidden_act": "silu", "hidden_size": 5120,
        "intermediate_size": 17408, "max_position_embeddings": 32768, "max_window_layers": 40,
        "model_type": "brumby", "num_attention_heads": 40, "num_hidden_layers": 40,
        "num_key_value_heads": 8, "rms_norm_eps": 1e-06, "rope_scaling": None,
        "rope_theta": 1000000, "sliding_window": None, "tie_word_embeddings": False,
        "use_sliding_window": False, "vocab_size": 151936}
    body = config()
    assert [k for k, v in catalog.items() if body.get(k, "missing") != v] == ["num_hidden_layers"]
    assert body["reduced"] == ["num_hidden_layers"] and body["num_hidden_layers"] == 8
    assert body["model_overrides"] == {"num_layers": 8, "layer_types": "r" * 8}
    assert body["reference"] == "brumby" and body["deployment"] and body["dtype"]["serve"]
    assert {"degree", "feature_map", "gate", "normaliser", "scale", "qk", "chunk",
            "random_weights_start"} <= set(body["assumed"])
    assert body["assumed_values"]["features_a_head"] <= 9216  # never the full square
    entry = M.config_entry(M.load(), NAME)
    assert entry["reduced"] == ["num_hidden_layers"] and entry["source"] == body["source"]
    assert all(1 <= len(entry[k]) <= 200 and entry[k].isascii() for k in ("why", "source"))


def test_the_program_is_held_to_the_configuration_file():
    ref = rc.load_module(os.path.join(rc.REFERENCE_DIR, "brumby.py"))
    cfg = rc.model_config(config(), ["num_layers=8", "layer_types=rrrrrrrr",
                                     "param_dtype=bfloat16"])
    assert ref.check_sizes(cfg, config()) == []
    wrong = rc.model_config(config(), ["num_layers=8", "layer_types=rrrrrrrr", "ret_eps=1e-3",
                                       "rope_theta=10000.0"])
    assert len(ref.check_sizes(wrong, config())) == 2
    # two operations a weight (the embedding is a gather), and on each of a
    # layer's 8 x 9,216 x 128 state values a decay, an update and 5 read-outs
    flops = ref.forward_flops_per_token(config(), 0.0)
    weights = 4_198_652_992 - 777_912_320 - 8 * 10_496 - 5_120 - 8 * 8
    assert flops == pytest.approx(2 * weights + 8 * 8 * 9216 * 128 * 14, rel=1e-4)


def test_the_counts_against_hand_arithmetic():
    c = config()
    assert retention_counts.state_bytes(c) == 8 * 9216 * (128 + 1) * 4
    assert retention_counts.state_bytes(c) / 2**20 == pytest.approx(36.3, abs=0.05)
    assert retention_counts.state_bytes(c, features=8256) / 2**20 == pytest.approx(32.5, abs=0.05)
    assert 16 * 8 * retention_counts.state_bytes(c) / 2**30 == pytest.approx(4.54, abs=0.01)


def test_the_generator_is_seeded_and_runs_exactly_the_sessions():
    from generators import stream_sessions as gen

    with open(M.traffic_path("streams-16-ret")) as f:
        t = json.load(f)
    sz = gen.sizes(types.SimpleNamespace(traffic=t, rehearsal=None))
    assert sz["sessions"] == t["sessions"] == 16
    a, b = (gen.session_turns(2**31 + 5, 7, sz, t, 151936) for _ in range(2))
    for _ in range(5):
        (ia, ma, ta), (ib, mb, tb) = next(a), next(b)
        assert ia.tolist() == ib.tolist() and (ma, ta) == (mb, tb)
    tiny = gen.sizes(types.SimpleNamespace(traffic=t, rehearsal={"length_scale": 0.0625}))
    assert tiny["sessions"] == 4 and tiny["prompt"]["max"] == 128


def test_the_cell_and_its_metrics():
    m = M.load()
    assert M.validate(m) == []
    cell = M.cell(m, CELL)
    assert (cell["chips"], cell["config"], cell["traffic"]) == (1, NAME, "streams-16-ret")
    assert ".chat-" not in CELL and len(cell["why"]) <= 200
    assert {"setup_s", "tpot_p50_ms"} == {e["name"] for e in M.metrics_for(m, "end_to_end", CELL)}
    per_layer = {p["name"] for p in M.metrics_for(m, "per_layer", CELL)}
    mine = {"ret_time_share_chat", "ret_state_time_share_chat", "ret_state_roofline_decode",
            "ret_state_bytes_share_decode"}
    assert mine <= per_layer and mine == {
        p["name"] for p in m["per_layer"] if p.get("workloads") == [CELL]}
    assert all(p["moves"] == "tpot_p50_ms" for p in m["per_layer"] if p["name"] in mine)
    # no pool: none of the pool's readers, no K/V kernel, no experts
    assert not per_layer & {"pool_live_share_chat", "pool_cached_share_chat",
                            "prefix_hit_share_chat", "kv_write_time_share_chat",
                            "attn_steps_walked_share_chat", "paged_attn_time_share_chat"}
    assert not [n for n in per_layer if n.startswith(("moe_", "mla_", "ssm_", "dsa_", "window_"))]
    assert {"slots_busy_mean_chat", "device_idle_share_chat", "compiles_in_window_chat",
            "layer_scan_time_share_chat", "scoped_time_share_chat",
            "tick_overlap_share_chat", "setup_params_s"} <= per_layer
    assert len(m["workloads"]) == 9 and sum(w["chips"] == 4 for w in m["workloads"]) == 1


def test_the_reference_check_passes_in_float32_and_refuses_a_wrong_decay(monkeypatch):
    spec = {"role": "serve", "model_overrides": TINY, "rehearsal": True}
    verdict = rc.compare(config(), spec, seed=5)
    assert verdict["ok"] and verdict["logits_rel_rms"] < 1e-4
    from ditl_tpu.ops import retention as ret

    scan = ret.ret_scan
    # a gate that forgets twice as fast: every far weight is wrong
    monkeypatch.setattr(ret, "ret_scan", lambda q, k, v, log_g, **kw: scan(q, k, v, 2 * log_g, **kw))
    broken = rc.compare(config(), spec, seed=5)
    assert not broken["ok"] and broken["logits_rel_rms"] > 1000 * verdict["logits_rel_rms"]


def test_the_paged_check_holds_the_engine_to_the_reference():
    import paged_check

    verdict = paged_check.check(config(), TINY, seed=2, prompt_tokens=(5, 20, 33),
                                new_tokens=40, page_size=16, rehearsal=True)
    assert verdict["served_tokens"] == 120 and verdict["logprob_err_over_logit_rms"] < 1e-4
