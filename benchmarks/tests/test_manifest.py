"""BENCHMARK.json against the contract's checks that need no chip, and each
reader file against its manifest entry."""
import copy
import json
import os

import manifest as M
from conftest import BENCH, ROOT
from harness import load_module


def test_the_manifest_is_sound():
    assert M.validate(M.load()) == []


def test_every_cell_resolves_to_its_files():
    m = M.load()
    for w in m["workloads"]:
        assert os.path.exists(os.path.join(ROOT, M.config_entry(m, w["config"])["file"]))
        with open(M.traffic_path(w["traffic"])) as f:
            gen = json.load(f)["generator"]
        assert os.path.exists(os.path.join(BENCH, "generators", f"{gen}.py"))
        with open(os.path.join(ROOT, M.config_entry(m, w["config"])["file"])) as f:
            ref = json.load(f)["reference"]
        assert os.path.exists(os.path.join(BENCH, "reference", f"{ref}.py"))


def test_readers_agree_with_their_manifest_entries():
    m = M.load()
    for section in ("end_to_end", "per_layer"):
        for entry in m[section]:
            mod = load_module(M.reader_path(section, entry["name"]))
            assert mod.UNIT == entry["unit"], entry["name"]
            if section == "per_layer":
                assert (mod.LAYER, mod.MOVES, mod.SOURCE) == (
                    entry["layer"], entry["moves"], entry["source"]), entry["name"]


SERVING = ("qwen2-7b-cut1.chat-steady-7b", "olmoe-1b-7b-cut1.chat-steady-moe",
           "longcat-flash-cut1.chat-wide-mla")


def test_the_serving_cell_and_its_metrics():
    """Written so that a later PR's new cell or reader does not turn it red
    (it asserted ONE serving cell and 16 metrics, and was red from PR 26 on)."""
    m = M.load()
    cell = M.cell(m, "qwen2-7b-cut1.chat-steady-7b")
    assert (cell["chips"], cell["config"], cell["traffic"]) == (1, "qwen2-7b-cut1", "chat-steady-7b")
    e2e = {e["name"]: e for e in M.metrics_for(m, "end_to_end", cell["name"])}
    assert {"setup_s", "tpot_p50_ms"} <= set(e2e)
    assert set(SERVING) <= set(e2e["tpot_p50_ms"]["workloads"])
    assert 0.01 <= e2e["tpot_p50_ms"]["bound"] <= 0.1
    per_layer = M.metrics_for(m, "per_layer", cell["name"])
    assert len(per_layer) >= 26  # PR 39's count: harvest_idle_share_chat gone, ttft_client_p95_ms in
    # every arrow ends at an end-to-end metric the cell reports: tpot_p50_ms is its only one
    assert {p["moves"] for p in per_layer} == {"tpot_p50_ms"}
    # the trainer cells report none of them, and keep their own
    for trainer, least in (("qwen2-0.5b.train-2k", 12), ("qwen2-7b-cut4.train-fsdp4-4k", 13)):
        mine = M.metrics_for(m, "per_layer", trainer)
        assert len(mine) >= least
        assert {p["moves"] for p in mine} == {"train_tokens_per_s_per_chip", "setup_s"}
    assert sum(w["chips"] == 4 for w in m["workloads"]) <= max(1, len(m["workloads"]) // 4)


def test_harvest_idle_share_chat_is_gone_and_nothing_lists_it():
    """Since PR 35 the harvest runs under the next tick's program: no idle
    gap lies under ``engine.tick.harvest`` (ledger, PR 36: 4.7e-05 and null),
    and ``tick_overlap_share_chat`` says so as a number."""
    m = M.load()
    assert "harvest_idle_share_chat" not in {p["name"] for p in m["per_layer"]}
    assert not os.path.exists(M.reader_path("per_layer", "harvest_idle_share_chat"))
    overlap = next(p for p in m["per_layer"] if p["name"] == "tick_overlap_share_chat")
    assert set(SERVING) <= set(overlap["workloads"])


def test_the_ttft_tail_is_read_in_every_serving_cell_and_held_to_no_bound():
    """A p95 over the 230-306 requests of a 51 s window spreads by 3.4-5.5% from
    the draw alone, over half the widest bound there is (PERF.md section 2): the
    driver's check refused it end to end, so it is a per-layer metric of every
    serving cell under another name, its reader what ``end_to_end/ttft_p95_ms.py``
    was."""
    m = M.load()
    assert not [e for e in m["end_to_end"] if e["name"].startswith("ttft")]
    assert not os.path.exists(M.reader_path("end_to_end", "ttft_p95_ms"))
    tail = next(p for p in m["per_layer"] if p["name"] == "ttft_client_p95_ms")
    assert tail["workloads"] == list(SERVING) and tail["source"] == "host_clock"
    for cell in SERVING:
        assert {e["name"] for e in M.metrics_for(m, "end_to_end", cell)} == {"setup_s", "tpot_p50_ms"}


def test_every_open_loop_cell_counts_two_hundred_requests_a_window():
    """A p95 wants ten samples beyond it: 200 requests. ``run_seconds`` is one
    number for every cell, so the slowest rate decides (4.5 req/s: 45 s)."""
    m = M.load()
    seen = 0
    for w in m["workloads"]:
        with open(M.traffic_path(w["traffic"])) as f:
            t = json.load(f)
        if t["generator"] == "open_loop":
            seen += 1
            assert t["rate_per_s"] * m["run_seconds"] >= 200, w["name"]
    assert seen >= 3


def broken(change):
    m = copy.deepcopy(M.load())
    change(m)
    return M.validate(m)


def test_a_bad_name_unit_or_arrow_is_refused():
    assert broken(lambda m: m["workloads"][0].update(name="has space"))
    assert broken(lambda m: m["end_to_end"][0].update(unit="tokens per second"))
    assert broken(lambda m: m["per_layer"][0].update(moves="no_such_metric"))
    # an arrow at a metric the cell does not report
    assert broken(lambda m: m["per_layer"][0].update(moves="tpot_p50_ms"))
    assert broken(lambda m: m["end_to_end"][0].update(bound=0.5))
    assert broken(lambda m: m.update(extra=1))
    assert broken(lambda m: m["configs"][1]["reduced"].append("hidden_size"))


def test_more_than_a_quarter_of_the_cells_on_four_chips_is_refused():
    assert broken(lambda m: m["workloads"][0].update(chips=4))


def published_path(name):
    return os.path.join(BENCH, "tests", "data", "published", f"{name}.json")


def width_problems(name, body):
    """One data file per configuration, ``tests/data/published/<name>.json``,
    holds the published value of every width its configuration file states:
    a new configuration adds a file and edits none, and one without a file
    fails."""
    if not os.path.exists(published_path(name)):
        return [f"{name}: no tests/data/published/{name}.json"]
    with open(published_path(name)) as f:
        published = {k: v for k, v in json.load(f).items() if not k.startswith("_")}
    missing = [k for k in body if M.WIDTH_KEY.search(k) and k not in published
               and not isinstance(body[k], (dict, list, str))]
    return ([f"{name}: width {k!r} is not in its published file" for k in missing]
            + [f"{name}: {k} is {body.get(k)!r}, published {v!r}"
               for k, v in published.items() if body.get(k) != v]
            + ([] if published else [f"{name}: its published file is empty"]))


def test_no_width_differs_from_the_published_config():
    for c in M.load()["configs"]:
        with open(os.path.join(ROOT, c["file"])) as f:
            assert width_problems(c["name"], json.load(f)) == []
    # every configuration file of the tree, in a cell or not
    for name in sorted(os.listdir(os.path.join(BENCH, "configs"))):
        with open(os.path.join(BENCH, "configs", name)) as f:
            assert width_problems(name[:-len(".json")], json.load(f)) == []


def test_a_changed_width_or_a_configuration_without_its_file_fails():
    with open(os.path.join(BENCH, "configs", "qwen2-0.5b.json")) as f:
        body = json.load(f)
    assert width_problems("qwen2-0.5b", {**body, "intermediate_size": 4096})
    assert width_problems("qwen2-0.5b", {**body, "latent_dim": 64})  # a width nobody vouches for
    assert width_problems("no-such-configuration", body)
