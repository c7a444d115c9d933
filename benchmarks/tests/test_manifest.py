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


def test_no_width_differs_from_the_published_config():
    published = {  # Qwen/Qwen2-0.5B and Qwen/Qwen2-7B config.json
        "qwen2-0.5b": (896, 4864, 14, 2, 151936, True),
        "qwen2-7b-cut1": (3584, 18944, 28, 4, 152064, False),
        "qwen2-7b-cut4": (3584, 18944, 28, 4, 152064, False),
    }
    for c in M.load()["configs"]:
        with open(os.path.join(ROOT, c["file"])) as f:
            body = json.load(f)
        got = (body["hidden_size"], body["intermediate_size"],
               body["num_attention_heads"], body["num_key_value_heads"],
               body["vocab_size"], body["tie_word_embeddings"])
        assert got == published[c["name"]]
