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


def test_the_serving_cell_and_its_metrics():
    m = M.load()
    cell = M.cell(m, "qwen2-7b-cut1.chat-steady-7b")
    assert (cell["chips"], cell["config"], cell["traffic"]) == (1, "qwen2-7b-cut1", "chat-steady-7b")
    e2e = {e["name"]: e for e in M.metrics_for(m, "end_to_end", cell["name"])}
    assert set(e2e) == {"setup_s", "ttft_p95_ms", "tpot_p50_ms"}
    for name in ("ttft_p95_ms", "tpot_p50_ms"):
        assert e2e[name]["workloads"] == [cell["name"]] and 0.01 <= e2e[name]["bound"] <= 0.1
    per_layer = M.metrics_for(m, "per_layer", cell["name"])
    assert len(per_layer) == 16
    assert {p["moves"] for p in per_layer} == {"ttft_p95_ms", "tpot_p50_ms"}
    # the trainer cells report none of them, and keep their own thirteen
    assert len(M.metrics_for(m, "per_layer", "qwen2-0.5b.train-2k")) == 12
    assert len(M.metrics_for(m, "per_layer", "qwen2-7b-cut4.train-fsdp4-4k")) == 13
    assert sum(w["chips"] == 4 for w in m["workloads"]) <= max(1, len(m["workloads"]) // 4)


def broken(change):
    m = copy.deepcopy(M.load())
    change(m)
    return M.validate(m)


def test_a_bad_name_unit_or_arrow_is_refused():
    assert broken(lambda m: m["workloads"][0].update(name="has space"))
    assert broken(lambda m: m["end_to_end"][0].update(unit="tokens per second"))
    assert broken(lambda m: m["per_layer"][0].update(moves="no_such_metric"))
    # an arrow at a metric the cell does not report
    assert broken(lambda m: m["per_layer"][0].update(moves="ttft_p95_ms"))
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
