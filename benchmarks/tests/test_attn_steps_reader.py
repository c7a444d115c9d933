"""PR 42's reader, ``attn_steps_walked_share_chat``, on journals known by
construction, and its manifest entry."""
import json
import os

import manifest as M
import pytest
from conftest import BENCH
from harness import load_module

NAME = "attn_steps_walked_share_chat"
SERVERS = ["qwen2-7b-cut1.chat-steady-7b", "olmoe-1b-7b-cut1.chat-steady-moe",
           "longcat-flash-cut1.chat-wide-mla", "granite-4.0-h-micro.chat-wide-ssm"]
WINDOW = (1000.0, 1051.0)


@pytest.fixture(scope="module")
def reader():
    return load_module(os.path.join(BENCH, "layer_metrics", f"{NAME}.py"))


def journal(tmp_path, ticks):
    """A server's ``--trace-dir`` journal: ``engine.tick`` spans at the
    given instants, with (walked, rect) or, for None, without the counter."""
    path = tmp_path / "events-server-1.jsonl"
    with open(path, "w") as f:
        for ts, counts in ticks:
            rec = {"event": "trace.span", "name": "engine.tick", "ts": ts, "dur_s": 0.06}
            if counts is not None:
                rec.update(attn_steps_walked=counts[0], attn_steps_rect=counts[1])
            f.write(json.dumps(rec) + "\n")
        f.write(json.dumps({"event": "trace.span", "name": "engine.decode", "ts": 1001.0,
                            "attn_steps_walked": 9, "attn_steps_rect": 9}) + "\n")
        f.write("a torn line\n")
    return [str(path)]


@pytest.mark.parametrize("ticks,want", [
    ([(1001.0, (40, 1088)), (1002.0, (56, 1088)), (1003.0, (0, 1088))], 100.0 * 96 / 3264),
    ([(1001.0, (1088, 1088))], 100.0),  # every slot live at full width: the rectangle's own
    ([(1001.0, None), (1002.0, None)], 100.0),  # a program that does not count walks it all
    ([(999.0, (1, 1088)), (1001.0, (68, 2176)), (1051.0, (1, 1088))], 100.0 * 68 / 2176),
    ([(1001.0, None), (1002.0, (30, 1088))], 100.0 * 30 / 1088),  # only ticks that count
    ([], 100.0),
], ids=["sparse", "full", "no-counter", "window", "mixed", "no-tick"])
def test_the_share_is_walked_over_rect_and_100_without_the_counter(reader, tmp_path, ticks, want):
    assert reader.walked_share(journal(tmp_path, ticks), *WINDOW) == pytest.approx(want)


def test_an_untraced_run_gives_nothing(reader):
    assert reader.read({"trace": None, "window_wall": WINDOW}) is None


def test_the_manifest_entry_is_the_readers_and_lists_the_serving_cells(reader):
    m = M.load()
    assert M.validate(m) == []
    (entry,) = [x for x in m["per_layer"] if x["name"] == NAME]
    assert (entry["layer"], entry["unit"], entry["moves"], entry["source"]) == (
        reader.LAYER, reader.UNIT, reader.MOVES, reader.SOURCE) == (
        "Kernels", "%", "tpot_p50_ms", "program_counter")
    assert entry["better"] == "lower" and entry["workloads"] == SERVERS
    assert m["per_layer"][-1] is entry  # appended: nothing the benchmark had moved
    for cell in SERVERS:
        assert NAME in {x["name"] for x in M.metrics_for(m, "per_layer", cell)}
