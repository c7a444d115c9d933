"""The Kanana-2 family through the benchmark's own doors, on the CPU: its
configuration against the catalog row, the count functions against hand
arithmetic, the reference's operation count, the readers on a hand-made run
record, the cell's entries in the manifest."""
import json
import os

import manifest as M
import mla_train_counts as counts
import pytest
import reference_check as rc
from conftest import BENCH
from harness import load_module

CELL = "kanana-2-30b-a3b-cut1.train-ep8-8k"
NAME = "kanana-2-30b-a3b-cut1"
MINE = ("moe_time_share_train", "moe_experts_roofline_train", "mla_flash_roofline_train",
        "mla_proj_time_share_train", "moe_held_assign_share_train",
        "moe_load_max_over_mean_train")


def config():
    with open(os.path.join(BENCH, "configs", f"{NAME}.json")) as f:
        return json.load(f)


def test_the_configuration_holds_the_catalog_row_but_for_what_is_reduced():
    """Every key of the published config.json as the model-configs catalog
    holds it (copied here: the guide is not part of the repository)."""
    catalog = {
        "attention_bias": False, "first_k_dense_replace": 1, "head_dim": 64, "hidden_act": "silu",
        "hidden_size": 2048, "intermediate_size": 6144, "kv_lora_rank": 512,
        "max_position_embeddings": 32768, "model_type": "deepseek_v3",
        "moe_intermediate_size": 768, "moe_layer_freq": 1, "n_group": 1, "n_routed_experts": 128,
        "n_shared_experts": 2, "norm_topk_prob": True, "num_attention_heads": 32,
        "num_experts_per_tok": 6, "num_hidden_layers": 48, "num_key_value_heads": 32,
        "q_lora_rank": None, "qk_head_dim": 192, "qk_nope_head_dim": 128,
        "qk_rope_head_dim": 64, "rms_norm_eps": 1e-06, "rope_interleave": True,
        "rope_scaling": None, "rope_theta": 1000000, "routed_scaling_factor": 2.448,
        "scoring_func": "sigmoid", "tie_word_embeddings": False, "topk_group": 1,
        "topk_method": "noaux_tc", "v_head_dim": 128, "vocab_size": 128256}
    body = config()
    assert [k for k, v in catalog.items() if body.get(k, "missing") != v] == []
    assert body["reduced"] == ["num_hidden_layers", "n_routed_experts", "vocab_size"]
    cut = body["cut"]
    assert (cut["num_hidden_layers"], cut["experts_held"], cut["vocab_size"],
            cut["chips_sharing_a_layer"]) == (5, [0, 16], 16032, 8)
    assert cut["parameters"] == 575_955_968 and cut["published_parameters"] == 30_670_815_104
    assert body["model_overrides"] == {"num_layers": 5, "vocab_size": 16032,
                                       "experts_held_first": 0, "experts_held_count": 16}
    assert body["reference"] == "kanana2" and body["deployment"] and body["dtype"]["train"]
    assert {"block", "rope", "softmax_scale", "router", "router_bias_frozen",
            "no_auxiliary_term", "shared_expert", "head", "bytes_a_parameter",
            "initial_weights", "random_weights_start"} <= set(body["assumed"])
    entry = M.config_entry(M.load(), NAME)
    assert entry["reduced"] == body["reduced"] and entry["source"] == body["source"]
    assert all(1 <= len(entry[k]) <= 200 and entry[k].isascii() for k in ("why", "source"))


def test_the_program_is_held_to_the_configuration_file_and_the_counts_to_hand_arithmetic():
    ref = load_module(os.path.join(rc.REFERENCE_DIR, "kanana2.py"))
    body = config()
    own = [f"{k}={v}" for k, v in body["model_overrides"].items()]
    assert ref.check_sizes(rc.model_config(body, own), body) == []
    assert len(ref.check_sizes(rc.model_config(body, own + ["rope_theta=10000.0",
                                                            "router_aux_coef=0.01"]), body)) == 2
    with open(os.path.join(BENCH, "reference", "kanana2.py")) as f:
        assert "ditl_tpu" not in "".join(line for line in f if line.startswith(("import", "from")))
    # a layer's attention: four projections and 192 + 128 a head a key
    attn = 2 * (2048 * 6144 + 2048 * 576 + 512 * 32 * 256 + 4096 * 2048) + 1366 * 32 * 2 * 320
    sparse = 2 * 2048 * 128 + (2 + 0.75) * 6 * 2048 * 768
    want = 5 * attn + 6 * 2048 * 6144 + 4 * sparse + 2 * 2048 * 16032
    assert ref.forward_flops_per_token(body, 1366.0) == pytest.approx(want)
    assert 3 * want == pytest.approx(1.95e9, rel=0.01)
    # a 512 x 512 block of a head: 2 forwards, dq's three products, dkv's four
    assert counts.flash_flops_per_block(body) == 2 * 512 * 512 * (
        2 * (192 + 128) + (192 + 128 + 192) + (192 + 128 + 128 + 192))
    assert counts.flash_flops_per_step(body, 10.0) == 10 * 32 * 5 * counts.flash_flops_per_block(body)
    assert counts.expert_flops_per_held_pair(body) == 12 * 2 * 2048 * 768
    assert counts.held_pairs_per_step(body, 32768, 0.125) == 0.125 * 32768 * 6 * 4


def test_the_readers_read_a_hand_made_run_and_fall_silent_without_their_counters():
    from layer_metrics import _mla_train

    rows = [{"step": i, "moe_held_assign_share": s, "moe_load_max_over_mean": 1.5,
             "flash_blocks_needed": 204} for i, s in enumerate((0.10, 0.12, 0.14))]
    with open(M.traffic_path("train-ep8-8k")) as f:
        traffic = json.load(f)
    run = {"rows": rows, "trace": None, "traffic": traffic, "config": config(),
           "workload": CELL, "peaks": {"bf16_flops_per_s": 197e12}}
    read = lambda name: load_module(M.reader_path("per_layer", name)).read  # noqa: E731
    assert read("moe_held_assign_share_train")(run) == pytest.approx(12.0)
    assert read("moe_load_max_over_mean_train")(run) == 1.5
    assert _mla_train.tokens_per_step(run) == 32768
    for name in MINE[:4]:  # no trace: nothing to read, and no exception
        assert read(name)(run) is None
    bare = {**run, "rows": [{"step": 0, "loss": 1.0}]}  # a program without the counters
    assert all(read(name)(bare) is None for name in MINE)
    # device time by scope inside whole runs of the step, on a hand-made trace
    meta = {"1": ["fusion.1", "jit(train_step)/jvp(layer_scan)/while/body/mlp/moe_experts/jit(gmm)/x"],
            "2": ["flash_fwd.3", "jit(train_step)/x/attn_core/mla_attn/flash_fwd/pallas_call"],
            "3": ["fusion.2", "jit(train_step)/x/attn_qkv/mla_q/dot_general"]}
    trace = {"devices": {"0": [[3, 0, 500_000], [1, 2_000_000, 4_000_000], [2, 7_000_000, 2_000_000],
                               [3, 10_000_000, 1_000_000], [1, 30_000_000, 9_000_000]]},
             "meta": {"0": meta},
             "modules": {"0": [["jit_train_step", 1_500_000, 10_000_000],
                               ["jit_train_step", 20_000_000, 30_000_000]]}}
    by = _mla_train.seconds_by_scope(trace)
    assert by["steps"] == 1.0  # the second run touches the trace's end: not whole
    assert by["moe_experts"] == pytest.approx(4e-6) and by["flash_fwd"] == pytest.approx(2e-6)
    assert by["mla_q"] == pytest.approx(1e-6)


def test_the_cell_is_listed_under_what_it_can_report():
    m = M.load()
    assert M.validate(m) == []
    cell = M.cell(m, CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (NAME, "train-ep8-8k", 1)
    assert len(cell["why"]) <= 200 and cell["why"].isascii() and "1/8" in cell["why"]
    mine = {x["name"] for x in m["per_layer"] if x.get("workloads") == [CELL]}
    assert mine == set(MINE)
    listed = {x["name"] for x in M.metrics_for(m, "per_layer", CELL)}
    assert {"mfu", "step_p50_ms", "data_wait_share", "device_idle_share_train",
            "flash_time_share_train", "flash_blocks_needed_share_train",
            "loss_time_share_train", "optimizer_time_share_train", "scoped_time_share_train",
            "compiles_in_window_train", "setup_reference_check_s"} <= listed
    assert "mlp_time_share_train" not in listed  # it would book the whole expert layer
    assert {x["name"] for x in M.metrics_for(m, "end_to_end", CELL)} == {
        "setup_s", "train_tokens_per_s_per_chip"}
    with open(M.traffic_path("train-ep8-8k")) as f:
        traffic = json.load(f)
    args = dict(a.split("=", 1) for a in traffic["launch_args"])
    assert traffic["generator"] == "train_job" and traffic["attention_context_mean"] == 1366.0
    assert args["data.synthetic_doc_tokens"] == "4096,2048,1024,512,256,128,64,64"
    assert sum(map(int, args["data.synthetic_doc_tokens"].split(","))) == int(args["data.seq_len"])
    assert (args["data.batch_size"], args["data.shuffle"], args["train.init_seed"]) == (
        "4", "false", "12")
    assert (traffic["log_every"], traffic["warmup_flushes"]) == (4, 2)
