"""Trinity-Mini's family (PR 48): the configuration file holds every number of
the catalog's row and states its cut and what it assumed; the traffic is
docs-32k-dsa's letter for letter, so the two cells differ in the model alone;
the cell is a closed loop listed under its own readers; and the manifest now
has eight cells on eight configurations, one of them on four chips."""
import json
import os

from conftest import BENCH

import manifest as M

NAME = "trinity-mini-cut1"
CELL = f"{NAME}.docs-32k-swa"
MINE = {"window_attn_time_share_chat", "full_attn_time_share_chat",
        "window_attn_roofline_decode", "full_attn_roofline_decode",
        "window_pages_walked_share_chat", "window_pool_live_share_chat"}


def load(folder, name):
    with open(os.path.join(BENCH, folder, f"{name}.json")) as f:
        return json.load(f)


def test_the_configuration_file_holds_every_number_of_the_catalogs_row():
    config = load("configs", NAME)
    want = {"hidden_size": 2048, "intermediate_size": 6144, "moe_intermediate_size": 1024,
            "num_attention_heads": 32, "num_key_value_heads": 4, "head_dim": 128,
            "num_experts": 128, "num_experts_per_tok": 8, "num_shared_experts": 1,
            "num_dense_layers": 2, "num_hidden_layers": 32, "sliding_window": 2048,
            "global_attn_every_n_layers": 4, "vocab_size": 200192, "route_scale": 2.826,
            "rope_theta": 10000, "rms_norm_eps": 1e-05, "max_position_embeddings": 131072,
            "n_group": 1, "topk_group": 1, "load_balance_coeff": 0.001,
            "num_expert_groups": 1, "num_limited_groups": 1}
    assert {k: config[k] for k in want} == want
    assert (config["score_func"], config["route_norm"], config["mup_enabled"],
            config["rope_scaling"], config["model_type"], config["tie_word_embeddings"]) == (
        "sigmoid", True, True, None, "afmoe", False)
    assert config["layer_types"] == (["sliding_attention"] * 3 + ["full_attention"]) * 8
    assert config["reduced"] == ["num_hidden_layers", "num_dense_layers", "num_experts",
                                 "vocab_size", "max_position_embeddings"]
    assert not [k for k in config["reduced"] if M.WIDTH_KEY.search(k)]
    cut = config["cut"]
    assert (cut["num_hidden_layers"], cut["num_dense_layers"], cut["experts_held"],
            cut["vocab_size"], cut["chips_sharing_a_layer"]) == (16, 1, [0, 16], 25024, 8)
    assert cut["layers"] == {"window": 12, "full": 4, "dense_ffn": 1, "expert": 15}
    assert cut["parameters"] == 2_184_847_232
    assert cut["cache_entry_bytes"] == {"a_layer": 2048, "a_token_full_pool": 8192,
                                        "a_token_window_pool": 24576}
    assert {"attention_gate", "qk_norm", "positional_term", "norms", "embedding", "router",
            "window", "random_weights_start"} <= set(config["assumed"])
    assert config["reference"] == "trinity_mini" and config["preset"] == "trinity-mini"
    assert len(config["why"]) <= 200 and config["why"].isascii()


def test_the_traffic_is_the_dsa_cells_letter_for_letter():
    mine, theirs = load("traffic", "docs-32k-swa"), load("traffic", "docs-32k-dsa")
    for key in ("generator", "tokenizer", "documents", "doc_tokens", "sessions",
                "question_tokens", "max_tokens", "think_s", "preroll_s",
                "session_start_spread_s", "drain_s", "warmup", "rehearsal", "trace"):
        assert mine[key] == theirs[key], key
    args, other = mine["server_args"], theirs["server_args"]
    value = lambda a, name: a[a.index(name) + 1]  # noqa: E731
    for name in ("--engine", "--cache-mode", "--slots", "--page-size", "--max-cache-len",
                 "--pages", "--prefill-chunk"):
        assert value(args, name) == value(other, name), name
    assert value(args, "--window-pages") == "384" and "--window-pages" not in other
    assert os.path.exists(os.path.join(os.path.dirname(BENCH), value(args, "--tokenizer"),
                                       "tokenizer.json"))
    with open(os.path.join(os.path.dirname(BENCH), value(args, "--tokenizer"),
                           "tokenizer.json")) as f:
        vocab = json.load(f)["model"]["vocab"]
    assert len(vocab) == 25024 and vocab["t25023"] == 25023 and vocab["<|endoftext|>"] == 2
    # every document's last window and every row's own span fit the window pool
    assert int(value(args, "--window-pages")) >= 12 * 8 + 32 * 5


def test_the_cell_is_a_closed_loop_with_its_own_readers():
    m = M.load()
    assert M.validate(m) == []
    cell = M.cell(m, CELL)
    assert (cell["chips"], cell["config"], cell["traffic"]) == (1, NAME, "docs-32k-swa")
    assert {"setup_s", "tpot_p50_ms"} == {e["name"] for e in M.metrics_for(m, "end_to_end", CELL)}
    per_layer = {p["name"] for p in M.metrics_for(m, "per_layer", CELL)}
    assert MINE <= per_layer
    assert {p["name"] for p in m["per_layer"] if p.get("workloads") == [CELL]} == MINE
    # what the deepseek cell is under, less the latent and the indexer's readers,
    # and the K/V kernel's two, which this configuration does run
    theirs = {p["name"] for p in M.metrics_for(m, "per_layer", "deepseek-v3.2-cut1.docs-32k-dsa")}
    assert per_layer - MINE == {n for n in theirs if not n.startswith(("mla_", "dsa_"))} | {
        "paged_attn_time_share_chat", "attn_steps_walked_share_chat"}
    assert all(p["moves"] == "tpot_p50_ms" for p in m["per_layer"] if p["name"] in MINE)
    t = load("traffic", "docs-32k-swa")
    assert t["generator"] == "doc_sessions" and "rate_per_s" not in t and t["warmup"] == []


def test_eight_cells_on_eight_configurations_one_of_them_on_four_chips():
    m = M.load()
    assert len(m["workloads"]) == 8 and len(m["configs"]) == 8
    assert [w["name"] for w in m["workloads"] if w["chips"] == 4] == [
        "qwen2-7b-cut4.train-fsdp4-4k"]
    assert m["workloads"][-1]["name"] == CELL and m["configs"][-1]["name"] == NAME
    assert m["run_seconds"] == 51


def test_the_count_functions_at_the_published_widths():
    import window_counts

    config = load("configs", NAME)
    assert (window_counts.layers_of(config, "window"), window_counts.layers_of(config, "full")) == (
        12, 4)
    assert window_counts.page_bytes(config, 256) == 524_288
    assert window_counts.page_flops(config, 256) == 32 * 4 * 128 * 256
    peaks = {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12}
    # 8 operations a byte against the v5e's 240: the bytes bound both
    assert abs(window_counts.attn_floor_s(config, "full", 1000, 256, peaks)
               - 1000 * 4 * 524_288 / 819e9) < 1e-12
    assert abs(window_counts.attn_floor_s(config, "window", 1000, 256, peaks)
               - 1000 * 12 * 524_288 / 819e9) < 1e-12
