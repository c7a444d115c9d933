"""PR 29's two readers on events known by construction: the decode program's
``kv_write`` (the ``kv_flush`` kernel sits inside the scope and is booked
there; a prefill's page writes are another program's) and every table name
together, which the flush's whole-pool copies used to fall out of."""
import os

import pytest
from conftest import BENCH
from harness import load_module

sc = load_module(os.path.join(BENCH, "layer_metrics", "_scopes.py"))
MS = 10**9  # ps


def reader(name):
    return load_module(os.path.join(BENCH, "layer_metrics", f"{name}.py"))


def trace():
    """One chip, 100 ms busy: a decode run of 80 ms (3 ms of tail writes, a
    2 ms flush kernel, 55 ms of mlp, 20 ms of layout copies under no name)
    and a prefill run of 20 ms (4 ms of page writes, 16 ms of mlp)."""
    decode = "jit(paged_decode)/"
    return {
        "devices": {"0": [[1, 0, 3 * MS], [2, 3 * MS, 2 * MS], [3, 5 * MS, 55 * MS],
                          [4, 60 * MS, 20 * MS], [5, 100 * MS, 4 * MS], [6, 104 * MS, 16 * MS]]},
        "meta": {"0": {
            "1": ["dynamic_update_slice.85", decode + "while/body/closed_call/layer_scan/while/"
                                             "body/attn_core/kv_write/dynamic_update_slice:"],
            "2": ["kv_flush.2", decode + "kv_write/kv_flush/pallas_call:"],
            "3": ["fusion.4", decode + "while/body/closed_call/layer_scan/while/body/mlp/"
                              "dot_general:"],
            "4": ["copy.87", ""],
            "5": ["fusion.5", "jit(paged_prefill)/kv_write/dynamic_update_slice:"],
            "6": ["fusion.6", "jit(paged_prefill)/layer_scan/while/body/mlp/dot_general:"],
        }},
        "modules": {"0": [["jit_paged_decode", 0, 80 * MS], ["jit_paged_prefill", 100 * MS, 20 * MS]]},
    }


def run_of(monkeypatch, tr):
    monkeypatch.setattr(sc, "trace_file", lambda run: "flush.pb")
    monkeypatch.setattr(sc, "load", lambda path: tr)
    sc._loaded.cache_clear()
    sc._seconds_of.cache_clear()
    return {"workload": "w", "trace": {"busy_s": 0.1, "window_s": 0.125}}


@pytest.mark.parametrize("name,want", [
    ("kv_write_time_share_chat", 5.0),  # the decode program's 3 + 2 ms, not the prefill's 4
    ("scoped_time_share_chat", 80.0),  # all but the 20 ms of copies
])
def test_flush_readers_on_a_known_trace(monkeypatch, name, want):
    mod = reader(name)
    monkeypatch.setattr(mod, "_scopes", sc)
    assert mod.read(run_of(monkeypatch, trace())) == pytest.approx(want)


@pytest.mark.parametrize("name", ["kv_write_time_share_chat", "scoped_time_share_chat"])
def test_flush_readers_leave_out_what_they_cannot_read(monkeypatch, name):
    """None, never 0: an untraced run, and a trace none of whose operations
    carries a name (a program from before the names)."""
    mod = reader(name)
    monkeypatch.setattr(mod, "_scopes", sc)
    assert mod.read({"workload": "w", "trace": None}) is None
    unnamed = trace()
    for m in unnamed["meta"]["0"].values():
        m[1] = "jit(step)/while/body/add:"
    assert mod.read(run_of(monkeypatch, unnamed)) is None


def test_a_trainers_trace_gives_the_decode_reader_nothing(monkeypatch):
    mod = reader("kv_write_time_share_chat")
    monkeypatch.setattr(mod, "_scopes", sc)
    trainer = trace()
    for m in trainer["meta"]["0"].values():
        m[1] = m[1].replace("jit(paged_decode)", "jit(train_step)").replace(
            "jit(paged_prefill)", "jit(train_step)")
    assert mod.read(run_of(monkeypatch, trainer)) is None
