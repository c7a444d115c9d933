"""The six readers of Trinity-Mini's cell (benchmarks/layer_metrics/_swa.py)
and their count functions (benchmarks/window_counts.py): on a trace and a
journal known by construction each returns the hand-reckoned number; where
the program has no window layer (the parent commit, any other configuration)
each returns None and does not raise, so the line leaves the metric out."""

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
from layer_metrics import _mla, _moe, _scopes, _swa  # noqa: E402

import window_counts  # noqa: E402
from ditl_tpu.ops import names  # noqa: E402

READERS = ("window_attn_time_share_chat", "full_attn_time_share_chat",
           "window_attn_roofline_decode", "full_attn_roofline_decode",
           "window_pages_walked_share_chat", "window_pool_live_share_chat")
MS = 10**9  # ps
DECODE = "jit(paged_decode)/while/body/closed_call/layer_scan/while/body/"
PREFILL = "jit(paged_prefill)/layer_scan/while/body/"
PEAKS = {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12}


def config():
    with open(os.path.join(BENCH, "configs", "trinity-mini-cut1.json")) as f:
        return json.load(f)


def traffic():
    with open(os.path.join(BENCH, "traffic", "docs-32k-swa.json")) as f:
        return json.load(f)


def reader(name):
    return load_module(os.path.join(BENCH, "layer_metrics", f"{name}.py"))


def test_the_name_table_equals_the_programs():
    assert _swa.SWA_SCOPES == names.SWA_SCOPES
    assert _swa.KERNEL in names.KERNELS
    known = (names.SCOPES + names.MOE_SCOPES + names.MLA_SCOPES + names.MOE_ZERO_SCOPES
             + names.DSA_SCOPES + names.SSM_SCOPES + names.ATTN_SCOPES)
    assert not set(names.SWA_SCOPES) & set(known)


def test_kind_of_tells_a_window_layers_attention_from_a_full_layers():
    cond = "attn_core/cond/branch_1_fun/"
    assert _swa.kind_of(DECODE + cond + "attn_window/paged_attention/pallas_call:") == (
        "attn_window.kernel")
    assert _swa.kind_of(DECODE + cond + "attn_full/paged_attention/pallas_call:") == (
        "attn_full.kernel")
    assert _swa.kind_of(DECODE + cond + "attn_window/mul:") == "attn_window"  # the gate
    assert _swa.kind_of(PREFILL + cond + "attn_full/dot_general:") == "attn_full"
    assert _swa.kind_of(DECODE + "attn_core/attn_steps/cumsum:") is None
    assert _swa.kind_of(DECODE + "mlp/moe_experts/gmm:") is None
    # the tables of the files that came before book the same time one scope out
    assert _scopes.innermost(DECODE + cond + "attn_window/paged_attention/pallas_call:") == (
        "paged_attention")
    assert _scopes.innermost(DECODE + cond + "attn_full/mul:") == "attn_core"


def test_the_counts_at_the_published_widths():
    c = config()
    assert window_counts.page_bytes(c, 256) == 2 * 4 * 256 * 128 * 2
    assert (window_counts.layers_of(c, "window"), window_counts.layers_of(c, "full")) == (12, 4)
    assert window_counts.attn_floor_s(c, "full", 1e3, 256, PEAKS) == pytest.approx(
        1e3 * 4 * 524_288 / 819e9)


def known_trace():
    """One chip. A WHOLE decode run of 80 ms (the full layers' kernel 40 ms,
    the window layers' 12 ms, the gate's products 2 + 1 ms, mlp 25 ms) between
    two runs the trace clips, and a prefill whose full-layer scores (6 ms) and
    window-layer scores (1 ms) count in shares and in no roofline."""
    cond = "attn_core/cond/branch_0_fun/"
    meta = {
        "1": ["paged_attention.1", DECODE + cond + "attn_full/paged_attention/pallas_call:"],
        "2": ["paged_attention.2", DECODE + cond + "attn_window/paged_attention/pallas_call:"],
        "3": ["fusion.3", DECODE + cond + "attn_window/mul:"],
        "4": ["fusion.4", DECODE + cond + "attn_full/mul:"],
        "5": ["fusion.5", DECODE + "mlp/dot_general:"],
        "6": ["fusion.6", PREFILL + cond + "attn_full/dot_general:"],
        "7": ["fusion.7", PREFILL + cond + "attn_window/dot_general:"],
        "9": ["paged_attention.1", DECODE + cond + "attn_full/paged_attention/pallas_call:"],
    }
    events = [[9, 0, 1 * MS], [1, 10 * MS, 40 * MS], [2, 50 * MS, 12 * MS], [3, 62 * MS, 2 * MS],
              [4, 64 * MS, 1 * MS], [5, 65 * MS, 25 * MS], [6, 92 * MS, 6 * MS],
              [7, 98 * MS, 1 * MS], [9, 100 * MS, 1 * MS]]
    modules = [["jit_paged_decode", 0, 1 * MS], ["jit_paged_decode", 10 * MS, 80 * MS],
               ["jit_paged_prefill", 92 * MS, 7 * MS], ["jit_paged_decode", 100 * MS, 1 * MS]]
    return {"devices": {"0": events}, "meta": {"0": meta}, "modules": {"0": modules}}


TICKS = [  # wall = trace + 1000 s: the first tick holds the whole run's middle
    {"ts": 1000.005, "dur_s": 0.09, "moe_steps": 4, "window_pages_walked": 1100,
     "full_pages_walked": 16_500, "window_pages_live": 200, "window_pages_total": 383},
    {"ts": 1000.2, "dur_s": 0.1, "moe_steps": 4, "window_pages_walked": 1000,
     "full_pages_walked": 13_500, "window_pages_live": 220, "window_pages_total": 383},
]


def a_run(monkeypatch, trace, ticks, offset=1000.0):
    monkeypatch.setattr(_scopes, "trace_file", lambda run: "a.xplane.pb")
    monkeypatch.setattr(_scopes, "_loaded", lambda path: trace)
    monkeypatch.setattr(_moe, "tick_rows", lambda run: ticks)
    monkeypatch.setattr(_mla, "_clock_offset_s", lambda path: offset)
    _swa._seconds_of.cache_clear()
    return {"workload": "w", "trace": {"busy_s": 0.089}, "config": config(), "peaks": PEAKS,
            "traffic": traffic()}


WANT = {
    # the kernel in all three runs (12), the gate (2), the prefill's scores (1)
    "window_attn_time_share_chat": 100 * 0.015 / 0.089,
    "full_attn_time_share_chat": 100 * 0.049 / 0.089,  # 40 + 1 + 1, the gate 1, the prefill 6
    # 1,100 page steps x 12 layers x 524,288 B over 819 GB/s = 8.45 ms of the kernel's 12
    "window_attn_roofline_decode": 100 * (1100 * 12 * 524_288 / 819e9) / 0.012,
    # 16,500 x 4 x 524,288 B = 42.25 ms... of 40: the construction's own numbers, held to no bound here
    "full_attn_roofline_decode": 100 * (16_500 * 4 * 524_288 / 819e9) / 0.040,
    "window_pages_walked_share_chat": 100 * 2100 / 30_000,  # the window's ticks, both
    "window_pool_live_share_chat": 100 * (200 / 383 + 220 / 383) / 2,
}


@pytest.mark.parametrize("name", READERS)
def test_readers_on_a_known_trace(monkeypatch, name):
    run = a_run(monkeypatch, known_trace(), TICKS)
    assert reader(name).read(run) == pytest.approx(WANT[name], rel=1e-9)


@pytest.mark.parametrize("name", READERS)
def test_readers_find_nothing_to_read_where_the_program_has_no_window_layer(monkeypatch, name):
    """OLMoE's decode: the same kernel under no such scope, ticks without the
    counts; and a run with no trace at all."""
    meta = {"1": ["paged_attention.2", DECODE + "attn_core/paged_attention/pallas_call:"],
            "2": ["fusion.2", DECODE + "mlp/dot_general:"]}
    other = {"devices": {"0": [[1, 0, 10 * MS], [2, 10 * MS, 10 * MS]]}, "meta": {"0": meta},
             "modules": {"0": [["jit_paged_decode", 0, 20 * MS]]}}
    run = a_run(monkeypatch, other, [{"ts": 1000.0, "dur_s": 0.02, "moe_steps": 4}])
    assert reader(name).read(run) is None
    assert reader(name).read({"workload": "w", "trace": None}) is None


@pytest.mark.parametrize("name", READERS)
def test_readers_give_a_number_where_no_tick_matches(monkeypatch, name):
    """The cell's own trace with no clock mark: shares as they are, 0.0 for
    what needs the traced ticks."""
    run = a_run(monkeypatch, known_trace(), TICKS, offset=None)
    got = reader(name).read(run)
    assert isinstance(got, float)
    if "roofline" in name:
        assert got == 0.0
