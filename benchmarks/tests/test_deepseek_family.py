"""DeepSeek-V3.2's family (PR 44): the closed loop of document sessions is
deterministic in the seed and inside its clips, the configuration file holds
every number of the catalog's row, and the cell is listed as a closed loop."""
import json
import os

import numpy as np
from conftest import BENCH

import manifest as M
from generators import doc_sessions, serving

NAME = "deepseek-v3.2-cut1"
CELL = f"{NAME}.docs-32k-dsa"
VOCAB = 16160


def traffic():
    with open(os.path.join(BENCH, "traffic", "docs-32k-dsa.json")) as f:
        return json.load(f)


def ctx(seed=7, rehearsal=None):
    import types

    return types.SimpleNamespace(traffic=traffic(), seed=seed, rehearsal=rehearsal)


def test_documents_and_sessions_are_deterministic_in_the_seed():
    sz = doc_sessions.sizes(ctx())
    assert (sz["documents"], sz["doc_tokens"], sz["sessions"]) == (12, 32768, 32)
    small = {**sz, "doc_tokens": 64}
    a, b, c = (doc_sessions.documents(s, 3, 64, VOCAB) for s in (7, 7, 2**31 + 8))
    assert a == b != c and len(set(a)) == 3
    assert all(len(d.split()) == 63 for d in a)  # the server prepends its bos id
    t = traffic()
    turns = [[next(g) for _ in range(50)]
             for g in (doc_sessions.session_turns(s, i, small, t, VOCAB)
                       for s, i in ((7, 0), (7, 0), (7, 1)))]
    same = lambda x, y: all(p[0] == q[0] and (p[1] == q[1]).all() and p[2:] == q[2:]  # noqa: E731
                            for p, q in zip(x, y))
    assert same(turns[0], turns[1]) and not same(turns[0], turns[2])


def test_turns_stay_inside_the_traffic_files_clips():
    t, sz = traffic(), doc_sessions.sizes(ctx())
    g = doc_sessions.session_turns(3, 5, sz, t, VOCAB)
    turns = [next(g) for _ in range(2000)]
    docs, qs, outs, thinks = zip(*turns)
    assert set(docs) == set(range(12))  # uniform over the twelve
    q = [len(x) for x in qs]
    assert min(q) >= 16 and max(q) <= 256 and 55 <= np.median(q) <= 75
    assert min(outs) >= 64 and max(outs) <= 512 and 235 <= np.median(outs) <= 280
    assert 0 <= min(thinks) and max(thinks) <= 1.0 and 0.15 <= np.mean(thinks) <= 0.25
    assert all((x >= 3).all() and (x < VOCAB).all() for x in qs)
    # a prompt and its answer fit a row of the server's cache
    args = t["server_args"]
    assert sz["doc_tokens"] + 256 + 512 <= int(args[args.index("--max-cache-len") + 1])


def test_the_rehearsal_fits_the_rehearsals_server():
    with open(os.path.join(BENCH, "rehearsal.json")) as f:
        rehearsal = json.load(f)
    sz = doc_sessions.sizes(ctx(rehearsal=rehearsal))
    assert sz["doc_tokens"] % rehearsal["server_args"]["--page-size"] == 0
    assert (sz["doc_tokens"] + sz["question"]["max"] + sz["answer"]["max"]
            <= rehearsal["server_args"]["--max-cache-len"])
    g = doc_sessions.session_turns(1, 0, sz, traffic(), 512)
    assert all(len(next(g)[1]) <= sz["question"]["max"] for _ in range(100))


def test_the_configuration_file_holds_every_number_of_the_catalogs_row():
    with open(os.path.join(BENCH, "configs", f"{NAME}.json")) as f:
        config = json.load(f)
    want = {"hidden_size": 7168, "intermediate_size": 18432, "moe_intermediate_size": 2048,
            "num_attention_heads": 128, "q_lora_rank": 1536, "kv_lora_rank": 512,
            "qk_nope_head_dim": 128, "qk_rope_head_dim": 64, "v_head_dim": 128,
            "index_n_heads": 64, "index_head_dim": 128, "index_topk": 2048,
            "n_routed_experts": 256, "num_experts_per_tok": 8, "n_group": 8, "topk_group": 4,
            "n_shared_experts": 1, "first_k_dense_replace": 3, "num_hidden_layers": 61,
            "vocab_size": 129280, "num_nextn_predict_layers": 1, "routed_scaling_factor": 2.5,
            "rope_theta": 10000, "rms_norm_eps": 1e-06, "max_position_embeddings": 163840}
    assert {k: config[k] for k in want} == want
    assert config["rope_scaling"] == {"beta_fast": 32, "beta_slow": 1, "factor": 40, "mscale": 1,
                                      "mscale_all_dim": 1,
                                      "original_max_position_embeddings": 4096, "type": "yarn"}
    assert not [k for k in config["reduced"] if M.WIDTH_KEY.search(k)]
    cut = config["cut"]
    assert (cut["num_hidden_layers"], cut["first_k_dense_replace"], cut["experts_held"],
            cut["vocab_size"], cut["num_nextn_predict_layers"], cut["chips_sharing_a_layer"]) == (
        5, 1, [0, 16], 16160, 0, 16)
    assert {"indexer_rotary", "indexer_precision", "indexer_key_norm"} <= set(config["assumed"])


def test_the_cell_is_a_closed_loop_with_its_own_readers():
    m = M.load()
    assert M.validate(m) == []
    cell = M.cell(m, CELL)
    assert (cell["chips"], cell["config"], cell["traffic"]) == (1, NAME, "docs-32k-dsa")
    assert {"setup_s", "tpot_p50_ms"} == {e["name"] for e in M.metrics_for(m, "end_to_end", CELL)}
    per_layer = {p["name"] for p in M.metrics_for(m, "per_layer", CELL)}
    assert {"dsa_time_share_chat", "dsa_select_time_share_chat", "dsa_index_roofline_decode",
            "dsa_attn_roofline_decode", "dsa_selected_share_chat",
            "moe_shared_time_share_chat"} <= per_layer
    t = traffic()
    assert t["generator"] == "doc_sessions" and "rate_per_s" not in t and t["warmup"] == []
    record = serving.new_record(10, 5, 123.0, counted=True)
    assert record["due"] == 123.0  # a turn is due the instant it is sent
