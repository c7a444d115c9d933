"""The six readers of DeepSeek-V3.2's cell (benchmarks/layer_metrics/_dsa.py)
and their count functions (benchmarks/dsa_counts.py): on a trace known by
construction each returns the hand-reckoned number; where the program has no
indexer (the parent commit, any other configuration) each returns None and
does not raise, so the line leaves the metric out."""

from __future__ import annotations

import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmarks")
for _p in (ROOT, BENCH):
    if _p not in sys.path:
        sys.path.insert(0, _p)

from harness import load_module  # noqa: E402
from layer_metrics import _dsa, _mla, _moe, _scopes  # noqa: E402

import dsa_counts  # noqa: E402
from ditl_tpu.ops import names  # noqa: E402

READERS = ("dsa_time_share_chat", "dsa_select_time_share_chat", "dsa_index_roofline_decode",
           "dsa_attn_roofline_decode", "dsa_selected_share_chat", "moe_shared_time_share_chat")
MS = 10**9  # ps
DECODE = "jit(paged_decode)/while/body/closed_call/layer_scan/while/body/"
PREFILL = "jit(paged_prefill)/layer_scan/while/body/"
PEAKS = {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12}


def config():
    with open(os.path.join(BENCH, "configs", "deepseek-v3.2-cut1.json")) as f:
        return json.load(f)


def reader(name):
    return load_module(os.path.join(BENCH, "layer_metrics", f"{name}.py"))


def test_the_name_tables_equal_the_programs():
    assert _dsa.DSA_SCOPES == names.DSA_SCOPES
    assert _dsa.MOE_SHARED_SCOPES == names.MOE_SHARED_SCOPES
    known = names.SCOPES + names.MOE_SCOPES + names.MLA_SCOPES + names.MOE_ZERO_SCOPES
    assert not set(names.DSA_SCOPES + names.MOE_SHARED_SCOPES) & set(known)


def test_innermost_tells_the_indexers_projections_from_its_scores():
    assert _dsa.innermost(DECODE + "attn_qkv/dsa_index/dot_general:") == "dsa_index.proj"
    assert _dsa.innermost(DECODE + "attn_core/dsa_index/gather:") == "dsa_index.scores"
    assert _dsa.innermost(DECODE + "attn_core/dsa_select/top_k:") == "dsa_select"
    assert _dsa.innermost(DECODE + "attn_core/mla_attn/dot_general:") == "mla_attn"
    assert _dsa.innermost(DECODE + "mlp/moe_shared/dot_general:") == "moe_shared"
    assert _dsa.innermost(DECODE + "mlp/moe_experts/gmm:") == "moe_experts"
    # the tables of the files that came before book the same time one scope out
    assert _mla.innermost(DECODE + "attn_core/dsa_gather/gather:") == "attn_core"
    assert _moe.innermost(DECODE + "mlp/moe_shared/dot_general:") == "mlp"


def test_the_counts_at_the_published_widths():
    c = config()
    assert dsa_counts.index_key_bytes(c) == 256
    assert dsa_counts.index_flops_per_key(c) == 64 * 258
    assert dsa_counts.entry_bytes(c) == 1280
    assert dsa_counts.attn_flops_per_entry(c) == 278_528
    # 64.5 and 218 operations a byte against the v5e's 240: the bytes bound both
    assert dsa_counts.index_floor_s(c, 1e6, PEAKS) == pytest.approx(1e6 * 256 / 819e9)
    assert dsa_counts.attn_floor_s(c, 1e6, PEAKS) == pytest.approx(1e6 * 1280 / 819e9)


def known_trace():
    """One chip. A WHOLE decode run of 80 ms (index scores 20 ms, top-k 10 ms,
    the gather 8 ms, the attention 2 ms, the indexer's projections 5 ms, the
    shared expert 5 ms, other mlp 30 ms) between two runs the trace clips, and
    a prefill whose index scores (4 ms) count in shares and in no roofline."""
    meta = {
        "1": ["fusion.1", DECODE + "attn_core/dsa_index/dot_general:"],
        "2": ["sort.2", DECODE + "attn_core/dsa_select/top_k:"],
        "3": ["gather.3", DECODE + "attn_core/dsa_gather/gather:"],
        "4": ["fusion.4", DECODE + "attn_core/mla_attn/dot_general:"],
        "5": ["fusion.5", DECODE + "attn_qkv/dsa_index/dot_general:"],
        "6": ["fusion.6", DECODE + "mlp/moe_shared/dot_general:"],
        "7": ["fusion.7", DECODE + "mlp/dot_general:"],
        "8": ["fusion.8", PREFILL + "attn_core/dsa_index/dot_general:"],
        "9": ["fusion.1", DECODE + "attn_core/dsa_index/dot_general:"],
    }
    events = [[9, 0, 1 * MS], [1, 10 * MS, 20 * MS], [2, 30 * MS, 10 * MS], [3, 40 * MS, 8 * MS],
              [4, 48 * MS, 2 * MS], [5, 50 * MS, 5 * MS], [6, 55 * MS, 5 * MS],
              [7, 60 * MS, 30 * MS], [8, 95 * MS, 4 * MS], [9, 100 * MS, 1 * MS]]
    modules = [["jit_paged_decode", 0, 1 * MS], ["jit_paged_decode", 10 * MS, 80 * MS],
               ["jit_paged_prefill", 95 * MS, 4 * MS], ["jit_paged_decode", 100 * MS, 1 * MS]]
    return {"devices": {"0": events}, "meta": {"0": meta}, "modules": {"0": modules}}


TICKS = [  # wall = trace + 1000 s: the first tick holds the whole run's middle
    {"ts": 1000.005, "dur_s": 0.09, "moe_steps": 4, "dsa_ctx_tokens": 20_000_000,
     "dsa_selected_tokens": 1_300_000},
    {"ts": 1000.2, "dur_s": 0.1, "moe_steps": 4, "dsa_ctx_tokens": 22_000_000,
     "dsa_selected_tokens": 1_220_000},
]


def a_run(monkeypatch, trace, ticks, offset=1000.0):
    monkeypatch.setattr(_scopes, "trace_file", lambda run: "a.xplane.pb")
    monkeypatch.setattr(_scopes, "_loaded", lambda path: trace)
    monkeypatch.setattr(_moe, "tick_rows", lambda run: ticks)
    monkeypatch.setattr(_mla, "_clock_offset_s", lambda path: offset)
    _dsa._seconds_of.cache_clear()
    _mla._seconds_of.cache_clear()
    return {"workload": "w", "trace": {"busy_s": 0.086}, "config": config(), "peaks": PEAKS}


WANT = {
    # scores 20 + 1 + 1 (the clipped runs' too: a share) + the prefill's 4, top-k 10,
    # gather 8, projections 5
    "dsa_time_share_chat": 100 * 0.049 / 0.086,
    "dsa_select_time_share_chat": 100 * 0.010 / 0.086,
    # 20e6 context tokens x 256 B over 819 GB/s = 6.252 ms of the 20 in the whole run
    "dsa_index_roofline_decode": 100 * (20e6 * 256 / 819e9) / 0.020,
    # 1.3e6 entries x 1,280 B = 2.032 ms of the gather's 8 + the attention's 2
    "dsa_attn_roofline_decode": 100 * (1.3e6 * 1280 / 819e9) / 0.010,
    "dsa_selected_share_chat": 100 * 2_520_000 / 42_000_000,  # the window's ticks, both
    "moe_shared_time_share_chat": 100 * 0.005 / 0.086,
}


@pytest.mark.parametrize("name", READERS)
def test_readers_on_a_known_trace(monkeypatch, name):
    run = a_run(monkeypatch, known_trace(), TICKS)
    assert reader(name).read(run) == pytest.approx(WANT[name], rel=1e-9)


@pytest.mark.parametrize("name", READERS)
def test_readers_find_nothing_to_read_where_the_program_has_no_indexer(monkeypatch, name):
    """LongCat-Flash's decode: latent attention, no indexer, no shared
    expert, ticks without the counts; and a run with no trace at all."""
    meta = {"1": ["mla_paged_attention.2", DECODE + "attn_core/mla_attn/mla_paged_attention:"],
            "2": ["fusion.2", DECODE + "mlp/dot_general:"]}
    other = {"devices": {"0": [[1, 0, 10 * MS], [2, 10 * MS, 10 * MS]]}, "meta": {"0": meta},
             "modules": {"0": [["jit_paged_decode", 0, 20 * MS]]}}
    run = a_run(monkeypatch, other, [{"ts": 1000.0, "dur_s": 0.02, "moe_steps": 4}])
    assert reader(name).read(run) is None
    assert reader(name).read({"workload": "w", "trace": None}) is None


@pytest.mark.parametrize("name", READERS)
def test_readers_give_a_number_where_no_tick_matches(monkeypatch, name):
    """The cell's own trace with no clock mark: shares as they are, 0.0 for
    what needs the ticks."""
    run = a_run(monkeypatch, known_trace(), [], offset=None)
    got = reader(name).read(run)
    if name == "dsa_selected_share_chat":
        assert got is None  # no tick carries the counts
    else:
        assert isinstance(got, float)
