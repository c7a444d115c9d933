"""The OLMoE family through the benchmark's own doors, at a tiny size on the
CPU: its configuration against the catalog row, the reference check in
float32 (both roles: the trainer's loss carries the router term), the paged
check on both serving families, the byte count and the expert readers on
events known by construction."""
import json
import os

import jax
import manifest as M
import pytest
import reference_check as rc
from conftest import BENCH
from harness import load_module
from layer_metrics import _moe, _scopes

TINY = ["vocab_size=512", "hidden_size=64", "intermediate_size=32", "num_layers=3",
        "num_heads=4", "num_kv_heads=4", "head_dim=16", "num_experts=16",
        "num_experts_per_tok=4", "max_seq_len=512", "dtype=float32"]
TINY_QWEN = ["vocab_size=512", "hidden_size=64", "intermediate_size=160", "num_layers=3",
             "num_heads=14", "num_kv_heads=2", "head_dim=8", "max_seq_len=512", "dtype=float32"]
CELL = "olmoe-1b-7b-cut1.chat-steady-moe"


def config(name="olmoe-1b-7b-cut1"):
    with open(os.path.join(BENCH, "configs", f"{name}.json")) as f:
        return json.load(f)


def test_the_configuration_holds_the_catalog_row_but_for_its_depth():
    """Every key of the published config.json as the model-configs catalog
    holds it (copied here: the guide is not part of the repository)."""
    catalog = {
        "attention_bias": False, "clip_qkv": None, "hidden_act": "silu", "hidden_size": 2048,
        "intermediate_size": 1024, "max_position_embeddings": 4096, "model_type": "olmoe",
        "norm_topk_prob": False, "num_attention_heads": 16, "num_experts": 64,
        "num_experts_per_tok": 8, "num_hidden_layers": 16, "num_key_value_heads": 16,
        "rms_norm_eps": 1e-05, "rope_scaling": None, "rope_theta": 10000,
        "tie_word_embeddings": False, "vocab_size": 50304}
    body = config()
    differs = [k for k, v in catalog.items() if body.get(k, "missing") != v]
    assert differs == ["num_hidden_layers"] == body["reduced"] and body["num_hidden_layers"] == 10
    entry = M.config_entry(M.load(), "olmoe-1b-7b-cut1")
    assert entry["reduced"] == ["num_hidden_layers"] and entry["source"] == body["source"]


def test_the_cell_and_its_metrics():
    m = M.load()
    cell = M.cell(m, CELL)
    assert (cell["chips"], cell["config"], cell["traffic"]) == (1, "olmoe-1b-7b-cut1", "chat-steady-moe")
    assert {"setup_s", "tpot_p50_ms"} <= {
        e["name"] for e in M.metrics_for(m, "end_to_end", CELL)}
    per_layer = {p["name"] for p in M.metrics_for(m, "per_layer", CELL)}
    other = {p["name"] for p in M.metrics_for(m, "per_layer", "qwen2-7b-cut1.chat-steady-7b")}
    assert per_layer - other == {"moe_time_share_chat", "moe_dispatch_time_share_chat",
                                 "moe_experts_roofline_decode", "moe_load_max_over_mean_chat"}
    assert len(other) >= 26 and other <= per_layer  # it asserted 16: red from PR 29 to PR 39
    with open(M.traffic_path("chat-steady-moe")) as f:
        moe = json.load(f)
    with open(M.traffic_path("chat-steady-7b")) as f:
        dense = json.load(f)
    for key in ("prompt_tokens", "max_tokens", "sharing", "preroll_s", "postroll_s", "generator"):
        assert moe[key] == dense[key], key  # one traffic on two architectures
    pages = int(moe["server_args"][moe["server_args"].index("--pages") + 1])
    assert pages >= 160
    assert moe["rate_per_s"] == moe["knee"]["cell_rate_per_s"] and moe["rate_per_s"] % 0.5 == 0
    assert moe["rate_per_s"] <= 0.8 * moe["knee"]["knee_rate_per_s"] < moe["rate_per_s"] + 0.5


@pytest.mark.parametrize("role", ["serve", "train"])
def test_reference_agrees_with_the_program_in_float32(role):
    spec = {"role": role, "model_overrides": TINY, "rehearsal": True}
    with jax.default_matmul_precision("highest"):
        v = rc.compare(config(), spec, seed=5)
    assert v["logits_rel_rms"] < 1e-4, v
    if role == "train":
        assert v["loss_rel"] < 1e-5, v
    assert v["ok"]


def test_the_sizes_check_knows_what_makes_it_olmoe():
    ref = load_module(os.path.join(BENCH, "reference", "olmoe.py"))
    cfg = rc.model_config(config(), ["num_layers=10"])
    assert ref.check_sizes(cfg, config()) == []
    for override, word in (("qk_norm=false", "qk_norm"), ("norm_topk_prob=true", "norm_topk_prob"),
                           ("num_experts_per_tok=2", "num_experts_per_tok"),
                           ("attention_bias=true", "attention_bias"),
                           ("intermediate_size=2048", "intermediate_size")):
        bad = rc.model_config(config(), ["num_layers=10", override])
        assert any(word in p for p in ref.check_sizes(bad, config())), override
    # 8 of 64 experts are active: 1.28 B parameters of 6.92 B, of which the
    # embedding table (0.10 B) is a lookup and not a matmul
    whole = dict(config(), num_hidden_layers=16)
    flops = ref.forward_flops_per_token(whole, 0.0)
    assert 2 * 1.17e9 < flops < 2 * 1.19e9


@pytest.mark.parametrize("name,tiny", [("olmoe-1b-7b-cut1", TINY), ("qwen2-7b-cut1", TINY_QWEN)])
def test_prefill_then_paged_decode_agrees_with_the_reference_in_float32(name, tiny):
    paged = load_module(os.path.join(BENCH, "paged_check.py"))
    with jax.default_matmul_precision("highest"):
        v = paged.check(config(name), tiny, seed=2, prompt_tokens=(5, 21, 38), new_tokens=9,
                        page_size=16, rehearsal=True)
    assert v["ok"] and v["served_tokens"] >= 3 * 2, v
    assert v["logprob_err_over_logit_rms"] < 1e-4, v
    assert v["argmax_same_share"] == 1.0


def test_the_byte_count():
    mb = load_module(os.path.join(BENCH, "moe_bytes.py"))
    assert mb.expert_weight_bytes(config()) == 3 * 2048 * 1024 * 2 == 12_582_912
    # 16 steps of 10 layers touching 40 experts each: 80.5 GB, 98 ms at 819 GB/s
    assert mb.decode_expert_bytes(config(), 16, 40.0) == 16 * 10 * 40 * 12_582_912


MS = 10**9  # ps


def known_run(tmp_path, monkeypatch):
    """One chip, one recorded run of jit_paged_decode of 100 ms (a while whose
    body holds, per the paths below, 10 ms of router, 15 of dispatch, 40 of
    the grouped matmuls, 5 of combine, 10 of the mlp's own norm and 20 of the
    whole-pool slice), 8 ms of experts in a decode run cut off at the trace's
    start and 2 ms of a run cut off at its end; the journal beside it: three
    ticks of 16 steps."""
    pre = "jit(paged_decode)/layer_scan/while/body/mlp/"
    names = {"1": ["while.1", "jit(paged_decode)/layer_scan/while:"],
             "2": ["fusion.1", pre + "moe_router/dot_general:"],
             "3": ["sort.1", pre + "moe_dispatch/sort:"],
             "4": ["gmm.1", pre + "moe_experts/jit(gmm)/pallas_call:"],
             "5": ["fusion.2", pre + "moe_combine/reduce_sum:"],
             "6": ["fusion.3", pre + "mul:"],
             "7": ["fusion.4", "jit(paged_decode)/layer_scan/while/body/dynamic_slice:"],
             "8": ["gmm.2", pre + "moe_experts/jit(gmm)/pallas_call:"],
             "9": ["fusion.5", "jit(paged_decode)/sample/argmax:"]}
    t0 = 20 * MS
    events = [[8, 0, 8 * MS], [1, t0, 100 * MS], [2, t0, 10 * MS], [3, t0 + 10 * MS, 15 * MS],
              [4, t0 + 25 * MS, 40 * MS], [5, t0 + 65 * MS, 5 * MS], [6, t0 + 70 * MS, 10 * MS],
              [7, t0 + 80 * MS, 20 * MS], [9, t0 + 104 * MS, 2 * MS]]
    run_dir = tmp_path / "runs" / f"{CELL}.s1.t1"
    trace = run_dir / "trace" / "plugins" / "profile" / "t"
    trace.mkdir(parents=True)
    path = trace / "host.xplane.pb"
    path.write_bytes(b"")
    (run_dir / "spans").mkdir()
    ticks = [{"event": "trace.span", "name": "engine.tick", "ts": 100.0 + i, "dur_s": 0.5,
              "moe_steps": 16, "moe_touched": 16 * 10 * 40, "moe_assignments": 40000,
              "moe_load_max_over_mean": 1.5 + 0.1 * i} for i in range(3)]
    ticks.append({"event": "trace.span", "name": "engine.tick", "ts": 50.0, "dur_s": 0.5,
                  "moe_steps": 16, "moe_touched": 0, "moe_assignments": 0,
                  "moe_load_max_over_mean": 0.0})  # before the window
    ticks.append({"event": "trace.span", "name": "engine.tick", "ts": 101.5, "dur_s": 0.1})
    with open(run_dir / "spans" / "events-server-1.jsonl", "w") as f:
        f.writelines(json.dumps(t) + "\n" for t in ticks)
    # the line clips a run to the trace: the first began before it, the last
    # (4 ms of experts would follow) ends with it; only the middle one is whole
    loaded = {"devices": {"0": events}, "meta": {"0": names},
              "modules": {"0": [["jit_paged_decode", 0, 8 * MS], ["jit_paged_decode", t0, 100 * MS],
                                ["jit_paged_decode", t0 + 104 * MS, 2 * MS]]}}
    monkeypatch.setattr(_scopes, "trace_file", lambda run: str(path))
    monkeypatch.setattr(_scopes, "_loaded", lambda p: loaded)
    _moe._seconds_of.cache_clear()
    run = {"workload": CELL, "trace": {"busy_s": 0.108}, "config": config(),
           "window_wall": [99.0, 130.0], "peaks": {"hbm_bytes_per_s": 819e9}}
    return run, _moe


def reader(name):
    return load_module(os.path.join(BENCH, "layer_metrics", f"{name}.py"))


def test_the_expert_readers_on_events_known_by_construction(tmp_path, monkeypatch):
    run, moe = known_run(tmp_path, monkeypatch)
    by = moe.seconds_by_scope(moe._scopes._loaded(""))
    assert by["moe_experts"] == pytest.approx(0.048) and by["mlp"] == pytest.approx(0.010)
    assert by["layer_scan"] == pytest.approx(0.020) and by["moe_dispatch"] == pytest.approx(0.015)
    for name, want in (("moe_time_share_chat", 100 * (10 + 15 + 48 + 5) / 108),
                       ("moe_dispatch_time_share_chat", 100 * (10 + 15 + 5) / 108),
                       ("moe_load_max_over_mean_chat", 1.6)):
        assert reader(name).read(run) == pytest.approx(want), name
    # the roofline share: only the recorded run's 40 ms and its 16 steps count
    least_s = 16 * 10 * 40 * 12_582_912 / 819e9
    assert reader("moe_experts_roofline_decode").read(run) == pytest.approx(100 * least_s / 0.040)
    # to the old table an expert layer's time is mlp's, as before
    sc = moe._scopes
    assert sc.seconds_by_name(sc._loaded(""))["mlp"] == pytest.approx(0.088)


def test_a_program_without_experts_gives_the_expert_readers_nothing(tmp_path, monkeypatch):
    run, moe = known_run(tmp_path, monkeypatch)
    dense = moe._scopes._loaded("")
    dense["meta"]["0"] = {k: [n, p.replace("moe_", "xx_")] for k, (n, p) in dense["meta"]["0"].items()}
    os.remove(os.path.join(tmp_path, "runs", f"{CELL}.s1.t1", "spans", "events-server-1.jsonl"))
    moe._seconds_of.cache_clear()
    for name in ("moe_time_share_chat", "moe_dispatch_time_share_chat",
                 "moe_experts_roofline_decode", "moe_load_max_over_mean_chat"):
        assert reader(name).read(run) is None, name
    assert reader("moe_time_share_chat").read({**run, "trace": None}) is None


RECORDED = os.path.join(BENCH, "tests", "data", "moe_scopes.json.gz")


def test_the_recorded_cut_of_the_cells_first_traced_run_gives_its_known_shares(
        tmp_path, monkeypatch):
    """One whole decode tick and a prefill of the cell's first traced run on
    the chip with the experts addressed in place (PR 26), and that run's
    ``engine.tick`` spans: reduced as ``chat7b_scopes.json.gz`` was."""
    with open(RECORDED.replace(".json.gz", ".expected.json")) as f:
        want = json.load(f)
    trace = _scopes.load(RECORDED)
    assert sum(len(v) for v in trace["devices"].values()) == want["events"]
    by = _moe.seconds_by_scope(trace)
    for name, s in want["seconds_by_scope"].items():
        assert by[name or None] == pytest.approx(s, rel=1e-9), name
    # the journal beside a trace, as a run leaves them
    run_dir = tmp_path / "runs" / f"{CELL}.s2147492103.t1"
    path = run_dir / "trace" / "plugins" / "profile" / "t" / "host.xplane.pb"
    path.parent.mkdir(parents=True)
    path.write_bytes(b"")
    (run_dir / "spans").mkdir()
    with open(os.path.join(BENCH, "tests", "data", "moe_ticks.jsonl")) as f:
        (run_dir / "spans" / "events-server-1.jsonl").write_text(f.read())
    monkeypatch.setattr(_scopes, "trace_file", lambda run: str(path))
    monkeypatch.setattr(_scopes, "_loaded", lambda p: trace)
    _moe._seconds_of.cache_clear()
    run = {"workload": CELL, "trace": {"busy_s": want["busy_s"]}, "config": config(),
           "window_wall": want["window_wall"], "peaks": {"hbm_bytes_per_s": 819e9}}
    rows = _moe.tick_rows(run)
    assert len(rows) == want["ticks"]
    assert _moe.touched_mean(rows, 10) == pytest.approx(want["touched_mean"], rel=1e-12)
    for name, value in want["readers"].items():
        assert reader(name).read(run) == pytest.approx(value, rel=1e-9), name
    assert set(want["readers"]) == {"moe_time_share_chat", "moe_dispatch_time_share_chat",
                                    "moe_experts_roofline_decode", "moe_load_max_over_mean_chat"}
    # what the trace showed: the grouped matmuls are nearly all of an expert
    # layer's time, under the memory roofline, and the layer loop copies no
    # expert weights any more (its time is the page pool's slice)
    assert by["moe_experts"] > 30 * (by["moe_router"] + by["moe_dispatch"] + by["moe_combine"])
    assert 50 < want["readers"]["moe_experts_roofline_decode"] < 100
    # to the PR 23 table the expert layer is mlp, whole
    old = _scopes.seconds_by_name(trace)
    assert old["mlp"] == pytest.approx(sum(by[n] for n in _moe.MOE_SCOPES) + by["mlp"], rel=1e-9)
