"""``BENCHMARK.json`` is sound as the tree stands: the checks of the driver's
contract that need no chip (``benchmarks/manifest.validate``), every cell
resolves to the files it is run from, and the ration of four-chip cells.
The benchmark's own tests (``benchmarks/tests``, outside tier-1) hold the
rest; this one is in tier-1 so that a PR that edits the program cannot leave
a manifest no run can start from (asked by ISSUE 25).
"""

from __future__ import annotations

import importlib.util
import json
import os

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmarks")


@pytest.fixture(scope="module")
def manifest_mod():
    spec = importlib.util.spec_from_file_location(
        "benchmarks_manifest", os.path.join(BENCH, "manifest.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_the_manifest_is_sound(manifest_mod):
    assert manifest_mod.validate(manifest_mod.load()) == []


def test_every_cell_resolves_to_its_files(manifest_mod):
    m = manifest_mod.load()
    assert m["workloads"]
    for cell in m["workloads"]:
        entry = manifest_mod.config_entry(m, cell["config"])
        with open(os.path.join(ROOT, entry["file"])) as f:
            config = json.load(f)
        with open(manifest_mod.traffic_path(cell["traffic"])) as f:
            traffic = json.load(f)
        for folder, name in (("generators", traffic["generator"]),
                             ("reference", config["reference"])):
            assert os.path.exists(os.path.join(BENCH, folder, f"{name}.py")), (cell["name"], name)
        for section in ("end_to_end", "per_layer"):
            metrics = manifest_mod.metrics_for(m, section, cell["name"])
            assert metrics, (cell["name"], section)
            for metric in metrics:
                assert os.path.exists(manifest_mod.reader_path(section, metric["name"]))
        # the preset the cell's command line names is one the program has
        from ditl_tpu.models.presets import PRESETS

        assert config["preset"] in PRESETS, cell["name"]


def test_at_most_a_quarter_of_the_cells_ask_for_four_chips(manifest_mod):
    cells = manifest_mod.load()["workloads"]
    four = sum(c["chips"] == 4 for c in cells)
    assert four <= max(1, len(cells) // 4)
    assert all(c["chips"] in (1, 4) for c in cells)


def test_every_why_and_source_is_1_to_200_printable_ascii_characters(manifest_mod):
    """The driver reads a configuration's ``why`` and ``source`` and a cell's
    ``why`` under one rule: 1 to 200 characters, ASCII and printable, one
    line. ``manifest.validate`` checks only a cell's ``why``, so a manifest it
    passed was refused for a configuration's (ledger, PR 31)."""
    m = manifest_mod.load()
    texts = [(f"{kind} {row['name']}: {key}", row[key])
             for kind, rows, keys in (("configuration", m["configs"], ("why", "source")),
                                      ("cell", m["workloads"], ("why",)))
             for row in rows for key in keys]
    assert len(texts) == 2 * len(m["configs"]) + len(m["workloads"])
    for where, text in texts:
        assert 1 <= len(text) <= 200, (where, len(text))
        assert text.isascii() and text.isprintable(), where


def test_the_longcat_cell_is_listed_only_under_readers_that_are_never_absent(manifest_mod):
    """On a NEW cell a metric missing from the traced line is a refusal, not
    a note (ledger, PR 30: "metrics lacks harvest_idle_share_chat"). So the
    cell stays off the readers that may be absent from its line, and off
    those that read another kernel or keys this configuration does not have."""
    m = manifest_mod.load()
    cell = "longcat-flash-cut1.chat-wide-mla"
    listed = {x["name"] for x in manifest_mod.metrics_for(m, "per_layer", cell)}
    # (``schedule_idle_share_chat`` and ``prefill_device_share`` read 0.0 on any
    # run with a trace since PR 39: a benchmark PR may list the cell there)
    for name, why in {
        "paged_attn_time_share_chat": "the K/V kernel, which this configuration never runs",
        "moe_experts_roofline_decode": "reads intermediate_size and num_hidden_layers",
    }.items():
        assert name not in listed, (name, why)
    mine = {x["name"] for x in m["per_layer"]
            if x.get("workloads", [None])[0] == cell and x["name"].startswith(("mla_", "moe_"))}
    assert mine == {"mla_attn_time_share_chat", "mla_attn_roofline_decode",
                    "mla_proj_time_share_chat", "moe_held_roofline_decode",
                    "moe_zero_assign_share_chat", "moe_held_assign_share_chat"}
    # three of the six read DeepSeek-V3.2's cell too (PR 44): the scopes and the
    # span keys are the same; the two rooflines' counts are LongCat's alone
    shared = {x["name"] for x in m["per_layer"] if x["name"] in mine and len(x["workloads"]) > 1}
    assert shared == {"mla_proj_time_share_chat", "mla_attn_time_share_chat",
                      "moe_held_assign_share_chat"}
    # 24 of PR 33, tick_overlap_share_chat, PR 36's six, PR 39's
    # ttft_client_p95_ms; "at least", so the next reader does not redden it
    assert mine <= listed and len(listed) >= 32
    assert {x["name"] for x in manifest_mod.metrics_for(m, "end_to_end", cell)} == {
        "setup_s", "tpot_p50_ms"}


def test_the_deepseek_cell_is_a_closed_loop_listed_under_what_it_can_report(manifest_mod):
    """``deepseek-v3.2-cut1.docs-32k-dsa`` (PR 44): its own six readers, the
    ``*_chat`` readers that are never absent for it, the server's and the
    scheduler's readers that count from the server's own spans, no reader
    whose counts are another configuration's, and none that counts from
    ``due`` of an open loop (a closed loop's ``due`` is the instant a turn was
    sent). Every reader it is NOT under is named here with the reason."""
    m = manifest_mod.load()
    cell = "deepseek-v3.2-cut1.docs-32k-dsa"
    listed = {x["name"] for x in manifest_mod.metrics_for(m, "per_layer", cell)}
    # (``moe_shared_time_share_chat`` reads Trinity-Mini's cell too since PR 48)
    mine = {x["name"] for x in m["per_layer"] if x.get("workloads", [None])[0] == cell}
    assert mine == {"dsa_time_share_chat", "dsa_select_time_share_chat",
                    "dsa_index_roofline_decode", "dsa_attn_roofline_decode",
                    "dsa_selected_share_chat", "moe_shared_time_share_chat"}
    assert all(x["moves"] == "tpot_p50_ms" for x in m["per_layer"] if x["name"] in mine)
    for name, why in {
        "mla_attn_roofline_decode": "counts LongCat's 8 sublayers of whole contexts",
        "moe_held_roofline_decode": "reads expert_ffn_hidden_size",
        "moe_experts_roofline_decode": "reads OLMoE's keys",
        "moe_zero_assign_share_chat": "no zero-compute experts",
        "paged_attn_time_share_chat": "the K/V kernel, which this configuration never runs",
        "attn_steps_walked_share_chat": "the sparse path walks no list of (row, page) steps",
        "ttft_p50_ms": "counts from due", "ttft_client_p95_ms": "counts from due",
        "generator_late_p95_ms": "a schedule to be late on",
        "ssm_time_share_chat": "no state-space mixer",
        "ssm_scan_time_share_chat": "no state-space mixer",
        "ssm_state_roofline_decode": "no state-space mixer",
    }.items():
        assert name not in listed, (name, why)
    serving = {x["name"] for x in m["per_layer"]
               if any(".chat-" in w for w in x.get("workloads", []))}
    assert serving - listed == {
        "mla_attn_roofline_decode", "moe_held_roofline_decode", "moe_experts_roofline_decode",
        "moe_zero_assign_share_chat", "paged_attn_time_share_chat",
        "attn_steps_walked_share_chat", "ttft_p50_ms", "ttft_client_p95_ms",
        "generator_late_p95_ms", "ssm_time_share_chat", "ssm_scan_time_share_chat",
        "ssm_state_roofline_decode"}
    assert mine <= listed and len(listed) == len(mine) + 36
    assert {x["name"] for x in manifest_mod.metrics_for(m, "end_to_end", cell)} == {
        "setup_s", "tpot_p50_ms"}
    with open(manifest_mod.traffic_path("docs-32k-dsa")) as f:
        traffic = json.load(f)
    assert traffic["generator"] == "doc_sessions" and "rate_per_s" not in traffic
    assert (traffic["documents"], traffic["doc_tokens"], traffic["sessions"]) == (12, 32768, 32)
    args = traffic["server_args"]
    assert args[args.index("--slots") + 1] == "32"
    pages = int(args[args.index("--pages") + 1])
    assert pages >= 12 * 128 + 32 * 4  # every document and every row's own pages
    assert int(args[args.index("--max-cache-len") + 1]) >= 32768 + 256 + 512
    assert os.path.exists(os.path.join(ROOT, args[args.index("--tokenizer") + 1],
                                       "tokenizer.json"))


def test_the_trinity_cell_is_listed_under_what_it_can_report(manifest_mod):
    """``trinity-mini-cut1.docs-32k-swa`` (PR 48): its own six readers, every
    reader ``deepseek-v3.2-cut1.docs-32k-dsa`` is under that reads no latent
    attention and no indexer, and the K/V decode kernel's two (this
    configuration runs ``paged_attention`` and walks ``decode_steps``' lists).
    Ten cells on ten configurations (PR 58), one of them on four chips."""
    m = manifest_mod.load()
    cell = "trinity-mini-cut1.docs-32k-swa"
    assert len(m["workloads"]) == len(m["configs"]) == 10
    assert sum(w["chips"] == 4 for w in m["workloads"]) == 1
    listed = {x["name"] for x in manifest_mod.metrics_for(m, "per_layer", cell)}
    mine = {x["name"] for x in m["per_layer"] if x.get("workloads") == [cell]}
    assert mine == {"window_attn_time_share_chat", "full_attn_time_share_chat",
                    "window_attn_roofline_decode", "full_attn_roofline_decode",
                    "window_pages_walked_share_chat", "window_pool_live_share_chat"}
    for name, why in {
        "mla_attn_time_share_chat": "no latent attention", "mla_proj_time_share_chat": "same",
        "dsa_time_share_chat": "no indexer", "moe_zero_assign_share_chat": "no zero experts",
        "moe_held_roofline_decode": "reads LongCat's keys",
        "moe_experts_roofline_decode": "reads OLMoE's keys",
        "ttft_p50_ms": "counts from due", "ttft_client_p95_ms": "counts from due",
        "generator_late_p95_ms": "a schedule to be late on",
        "ssm_time_share_chat": "no state-space mixer",
    }.items():
        assert name not in listed, (name, why)
    assert {"paged_attn_time_share_chat", "attn_steps_walked_share_chat", "moe_time_share_chat",
            "moe_shared_time_share_chat", "moe_held_assign_share_chat", "prefix_hit_share_chat",
            "preemptions_chat", "compiles_in_window_chat", "tpot_p95_ms"} <= listed
    assert len(listed) == len(mine) + 37
    assert {x["name"] for x in manifest_mod.metrics_for(m, "end_to_end", cell)} == {
        "setup_s", "tpot_p50_ms"}
    with open(manifest_mod.traffic_path("docs-32k-swa")) as f:
        traffic = json.load(f)
    args = traffic["server_args"]
    assert args[args.index("--window-pages") + 1] == "384"
    assert os.path.exists(os.path.join(ROOT, args[args.index("--tokenizer") + 1],
                                       "tokenizer.json"))
