"""The six readers of LongCat-Flash's cell (benchmarks/layer_metrics/_mla.py)
and their count functions (benchmarks/mla_counts.py): on a run record whose
trace matches nothing every one returns a NUMBER (a traced line that lacks a
metric refuses a new cell: ledger, PR 30), and on a trace known by
construction each returns the hand-reckoned share."""

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
from layer_metrics import _mla, _moe, _scopes  # noqa: E402

import mla_counts  # noqa: E402
from ditl_tpu.ops import names  # noqa: E402

READERS = ("mla_attn_time_share_chat", "mla_attn_roofline_decode", "mla_proj_time_share_chat",
           "moe_held_roofline_decode", "moe_zero_assign_share_chat",
           "moe_held_assign_share_chat")
MS = 10**9  # ps
DECODE = "jit(paged_decode)/while/body/closed_call/layer_scan/while/body/"
PEAKS = {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12}


def config():
    with open(os.path.join(BENCH, "configs", "longcat-flash-cut1.json")) as f:
        return json.load(f)


def reader(name):
    return load_module(os.path.join(BENCH, "layer_metrics", f"{name}.py"))


def test_the_name_tables_equal_the_programs():
    assert _mla.MLA_SCOPES == names.MLA_SCOPES
    assert _mla.MOE_ZERO_SCOPES == names.MOE_ZERO_SCOPES
    assert _mla.DECODE == "jit_paged_decode"
    assert names.MLA_KERNELS == ("mla_paged_attention",)
    assert not set(names.MLA_SCOPES + names.MOE_ZERO_SCOPES) & set(names.SCOPES + names.MOE_SCOPES)


def test_the_counts_at_the_published_widths():
    c = config()
    assert mla_counts.sublayers(c) == 8
    assert mla_counts.entry_bytes(c) == 1280  # 576 values stored as 640
    assert mla_counts.attn_flops_per_entry(c) == 139_264
    assert mla_counts.held_expert_bytes(c) == 75_497_472
    # 109 operations a byte: the bytes bound it on a v5e (240)
    assert mla_counts.decode_attn_floor_s(c, 1e6, PEAKS) == pytest.approx(8e6 * 1280 / 819e9)


def known_trace():
    """One chip. A WHOLE decode run of 80 ms (the latent kernel 20 ms, a
    grouped matmul 10 ms, mla_q 5 ms, mla_kv inside attn_out 5 ms, mlp 40 ms)
    between two runs the trace clips, whose operations must not count."""
    meta = {
        "1": ["mla_paged_attention.2", DECODE + "attn_core/mla_attn/mla_paged_attention/pallas_call:"],
        "2": ["gmm.3", DECODE + "mlp/while/body/moe_experts/gmm/pallas_call:"],
        "3": ["fusion.4", DECODE + "attn_qkv/mla_q/dot_general:"],
        "4": ["fusion.5", DECODE + "attn_out/mla_kv/dot_general:"],
        "5": ["fusion.6", DECODE + "mlp/dot_general:"],
        "6": ["mla_paged_attention.2", DECODE + "attn_core/mla_attn/mla_paged_attention/pallas_call:"],
        "7": ["fusion.9", "jit(paged_prefill)/layer_scan/while/body/attn_qkv/mla_kv/dot_general:"],
    }
    events = [[6, 0, 1 * MS], [1, 10 * MS, 20 * MS], [2, 30 * MS, 10 * MS], [3, 40 * MS, 5 * MS],
              [4, 45 * MS, 5 * MS], [5, 50 * MS, 40 * MS], [7, 95 * MS, 2 * MS],
              [6, 100 * MS, 1 * MS]]
    modules = [["jit_paged_decode", 0, 1 * MS], ["jit_paged_decode", 10 * MS, 80 * MS],
               ["jit_paged_prefill", 95 * MS, 2 * MS], ["jit_paged_decode", 100 * MS, 1 * MS]]
    return {"devices": {"0": events}, "meta": {"0": meta}, "modules": {"0": modules}}


TICKS = [  # wall = trace + 1000 s: the first tick holds the whole run's middle
    {"ts": 1000.005, "dur_s": 0.09, "moe_steps": 16, "moe_assignments": 1200, "moe_touched": 40,
     "moe_assign_zero": 400, "moe_assign_held": 24, "moe_assign_absent": 776,
     "decode_ctx_tokens": 1_000_000},
    {"ts": 1000.2, "dur_s": 0.1, "moe_steps": 16, "moe_assignments": 2400, "moe_touched": 64,
     "moe_assign_zero": 800, "moe_assign_held": 51, "moe_assign_absent": 1549,
     "decode_ctx_tokens": 5_000_000},
]


def a_run(monkeypatch, trace, ticks, offset=1000.0):
    monkeypatch.setattr(_scopes, "trace_file", lambda run: "a.xplane.pb")
    monkeypatch.setattr(_scopes, "_loaded", lambda path: trace)
    monkeypatch.setattr(_moe, "tick_rows", lambda run: ticks)
    monkeypatch.setattr(_mla, "_clock_offset_s", lambda path: offset)
    _mla._seconds_of.cache_clear()
    return {"workload": "w", "trace": {"busy_s": 0.084}, "config": config(), "peaks": PEAKS}


WANT = {
    "mla_attn_time_share_chat": 100 * 0.022 / 0.084,  # the clipped runs' kernels too: a share
    "mla_proj_time_share_chat": 100 * 0.012 / 0.084,  # mla_q + mla_kv, decode and prefill
    # 1e6 tokens x 8 sublayers x 1,280 B over 819 GB/s = 12.503 ms of the 20 in the whole run
    "mla_attn_roofline_decode": 100 * (8e6 * 1280 / 819e9) / 0.020,
    # 40 experts x 75,497,472 B = 3.687 ms of 10
    "moe_held_roofline_decode": 100 * (40 * 75_497_472 / 819e9) / 0.010,
    "moe_zero_assign_share_chat": 100 * 1200 / 3600,  # the window's ticks, both
    "moe_held_assign_share_chat": 100 * 75 / 3600,
}


@pytest.mark.parametrize("name", READERS)
def test_readers_on_a_known_trace(monkeypatch, name):
    run = a_run(monkeypatch, known_trace(), TICKS)
    assert reader(name).read(run) == pytest.approx(WANT[name], rel=1e-9)


@pytest.mark.parametrize("name", READERS)
def test_readers_give_a_number_where_nothing_matches(monkeypatch, name):
    """A trainer's trace, no tick, no clock mark: 0.0, never None (None is
    for a run with no trace at all, where the line has no per-layer metric)."""
    trainer = {"devices": {"0": [[1, 0, 10 * MS]]},
               "meta": {"0": {"1": ["fusion.1", "jit(train_step)/mlp/dot_general:"]}},
               "modules": {"0": [["jit_train_step", 0, 10 * MS]]}}
    run = a_run(monkeypatch, trainer, [], offset=None)
    assert reader(name).read(run) == 0.0
    empty = a_run(monkeypatch, {"devices": {}, "meta": {}, "modules": {}}, [], offset=None)
    assert reader(name).read(empty) == 0.0
    assert reader(name).read({"workload": "w", "trace": None}) is None


def test_ticks_are_matched_to_whole_runs_through_the_clock():
    assert _mla.match_ticks(known_trace(), 1000.0, TICKS) == [TICKS[0]]
    assert _mla.match_ticks(known_trace(), 1000.2 - 0.05, TICKS) == [TICKS[1]]
    assert _mla.match_ticks(known_trace(), None, TICKS) == []
    assert _mla.whole_runs(known_trace(), "0") == [(10 * MS, 90 * MS)]
