"""PR 40's reader, ``flash_blocks_needed_share_train``, on rows known by
construction, and its manifest entry."""
import os

import manifest as M
import pytest
from conftest import BENCH
from harness import load_module

NAME = "flash_blocks_needed_share_train"
TRAINERS = ["qwen2-0.5b.train-2k", "qwen2-7b-cut4.train-fsdp4-4k"]


@pytest.fixture(scope="module")
def reader():
    return load_module(os.path.join(BENCH, "layer_metrics", f"{NAME}.py"))


def rows(*counts):
    return [{"step": i, "n_tokens": 32768.0, "loss": 5.0,
             **({} if c is None else {"flash_blocks_reachable": c[0], "flash_blocks_needed": c[1]})}
            for i, c in enumerate(counts)]


@pytest.mark.parametrize("counts,want", [
    (((160.0, 112.0), (160.0, 116.0), (160.0, 112.0)), 100.0 * 340 / 480),  # kept over reachable
    (((160.0, 160.0),), 100.0),  # one document a row keeps every causal block
    ((None, None), 100.0),  # a program that does not count computes every reachable block
    ((None, (72.0, 30.0)), 100.0 * 30 / 72),  # only the rows that count are read
], ids=["packed", "one-document", "no-counter", "mixed"])
def test_the_share_is_kept_over_reachable_and_100_without_the_counter(reader, counts, want):
    assert reader.read({"rows": rows(*counts)}) == pytest.approx(want)


def test_the_manifest_entry_is_the_readers_and_lists_the_trainer_cells(reader):
    m = M.load()
    assert M.validate(m) == []
    (entry,) = [x for x in m["per_layer"] if x["name"] == NAME]
    assert (entry["layer"], entry["unit"], entry["moves"], entry["source"]) == (
        reader.LAYER, reader.UNIT, reader.MOVES, reader.SOURCE) == (
        "Kernels", "%", "train_tokens_per_s_per_chip", "program_counter")
    assert entry["better"] == "lower" and entry["workloads"] == TRAINERS
    for cell in TRAINERS:
        assert NAME in {x["name"] for x in M.metrics_for(m, "per_layer", cell)}
