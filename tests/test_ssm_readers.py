"""The three readers of Granite-4.0-H's cell (benchmarks/layer_metrics/_ssm.py)
and their count function (benchmarks/ssm_counts.py): on a run record whose
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
from layer_metrics import _mla, _scopes, _ssm  # noqa: E402

import ssm_counts  # noqa: E402
from ditl_tpu.ops import names  # noqa: E402

READERS = ("ssm_time_share_chat", "ssm_scan_time_share_chat", "ssm_state_roofline_decode")
MS = 10**9  # ps
DECODE = "jit(paged_decode)/while/body/closed_call/layer_scan/while/body/closed_call/"
PREFILL = "jit(paged_prefill)/layer_scan/while/body/closed_call/"
PEAKS = {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12}


def config():
    with open(os.path.join(BENCH, "configs", "granite-4.0-h-micro.json")) as f:
        return json.load(f)


def reader(name):
    return load_module(os.path.join(BENCH, "layer_metrics", f"{name}.py"))


def test_the_name_tables_equal_the_programs():
    assert _ssm.SSM_SCOPES == names.SSM_SCOPES == ("ssm_in", "ssm_scan", "ssm_out")
    assert names.SSM_KERNELS == ("ssd_step",)
    assert not set(names.SSM_SCOPES) & set(
        names.SCOPES + names.MOE_SCOPES + names.MLA_SCOPES + names.MOE_ZERO_SCOPES)
    assert not set(names.SSM_KERNELS) & set(
        names.KERNELS + names.MOE_KERNELS + names.MLA_KERNELS + names.CACHE_KERNELS)


def test_the_counts_at_the_published_widths():
    c = config()
    assert ssm_counts.mixers(c) == 36
    assert ssm_counts.state_values(c) * 4 == 2_097_152  # one mixer's state, a row
    assert ssm_counts.conv_window_bytes(c) == 3 * 4352 * 2
    # state and window, in and out, 36 mixers: 152.9 MB a live row a step
    assert ssm_counts.row_step_bytes(c) == 36 * 2 * (2_097_152 + 26_112) == 152_875_008
    # 0.74 operations a byte: the bytes bound it on a v5e (240)
    assert ssm_counts.decode_state_floor_s(c, 96, PEAKS) == pytest.approx(
        96 * 152_875_008 / 819e9)


def known_trace():
    """One chip. A WHOLE decode run of 80 ms (the step kernel 30 ms, the input
    projection 10 ms, the output projection 5 ms, the FFN 35 ms) between two
    runs the trace clips, whose operations must not count in the roofline,
    and a prefill whose chunked scan is 4 ms."""
    meta = {
        "1": ["ssd_step.2", DECODE + "attn_core/ssm_scan/ssd_step/pallas_call:"],
        "2": ["fusion.3", DECODE + "attn_qkv/ssm_in/dot_general:"],
        "3": ["fusion.4", DECODE + "attn_out/ssm_out/dot_general:"],
        "4": ["fusion.5", DECODE + "mlp/dot_general:"],
        "5": ["fusion.6", PREFILL + "attn_core/ssm_scan/while/body/dot_general:"],
        "6": ["paged_attention.7", DECODE + "attn_core/paged_attention/pallas_call:"],
    }
    events = [[1, 0, 1 * MS], [1, 10 * MS, 30 * MS], [2, 40 * MS, 10 * MS], [3, 50 * MS, 5 * MS],
              [4, 55 * MS, 35 * MS], [5, 95 * MS, 4 * MS], [6, 99 * MS, 1 * MS],
              [1, 100 * MS, 1 * MS]]
    modules = [["jit_paged_decode", 0, 1 * MS], ["jit_paged_decode", 10 * MS, 80 * MS],
               ["jit_paged_prefill", 95 * MS, 4 * MS], ["jit_paged_decode", 99 * MS, 2 * MS]]
    return {"devices": {"0": events}, "meta": {"0": meta}, "modules": {"0": modules}}


TICKS = [  # wall = trace + 1000 s: the first tick holds the whole run's middle
    {"ts": 1000.005, "dur_s": 0.09, "ssm_steps": 4, "ssm_row_steps": 96},
    {"ts": 1000.2, "dur_s": 0.1, "ssm_steps": 4, "ssm_row_steps": 120},
]


def a_run(monkeypatch, trace, ticks, offset=1000.0):
    monkeypatch.setattr(_scopes, "trace_file", lambda run: "/r/trace/plugins/profile/t/a.xplane.pb")
    monkeypatch.setattr(_scopes, "_loaded", lambda path: trace)
    monkeypatch.setattr(_ssm, "read_ticks", lambda paths, w0, w1: ticks)
    monkeypatch.setattr(_mla, "_clock_offset_s", lambda path: offset)
    _ssm._seconds_of.cache_clear()
    return {"workload": "w", "trace": {"busy_s": 0.087}, "config": config(), "peaks": PEAKS,
            "window_wall": (1000.0, 1051.0)}


WANT = {
    # every run's, clipped ones and the prefill's too: a share of the window
    "ssm_time_share_chat": 100 * (0.032 + 0.010 + 0.005 + 0.004) / 0.087,
    "ssm_scan_time_share_chat": 100 * (0.032 + 0.004) / 0.087,
    # 96 row steps x 152,875,008 B over 819 GB/s = 17.92 ms of the 30 in the whole run
    "ssm_state_roofline_decode": 100 * (96 * 152_875_008 / 819e9) / 0.030,
}


@pytest.mark.parametrize("name", READERS)
def test_readers_on_a_known_trace(monkeypatch, name):
    run = a_run(monkeypatch, known_trace(), TICKS)
    assert reader(name).read(run) == pytest.approx(WANT[name], rel=1e-9)
    assert reader(name).read(run) <= 100.0


@pytest.mark.parametrize("name", READERS)
def test_readers_give_a_number_where_nothing_matches(monkeypatch, name):
    """The parent's trace of another cell (no such scope), no tick, no clock
    mark: 0.0, never None (None is for a run with no trace at all)."""
    other = {"devices": {"0": [[1, 0, 10 * MS]]},
             "meta": {"0": {"1": ["fusion.1", "jit(paged_decode)/layer_scan/mlp/dot_general:"]}},
             "modules": {"0": [["jit_paged_decode", 0, 10 * MS]]}}
    run = a_run(monkeypatch, other, [], offset=None)
    assert reader(name).read(run) == 0.0
    empty = a_run(monkeypatch, {"devices": {}, "meta": {}, "modules": {}}, [], offset=None)
    assert reader(name).read(empty) == 0.0
    assert reader(name).read({"workload": "w", "trace": None}) is None


def test_ticks_are_read_from_the_journal_by_their_own_key(tmp_path):
    """``_moe.read_ticks`` keeps only spans with ``moe_steps``: this cell's
    ticks carry ``ssm_steps``, and the window bounds them."""
    rows = [{"event": "trace.span", "name": "engine.tick", "ts": 10.0, "dur_s": 0.1,
             "ssm_steps": 4, "ssm_row_steps": 80},
            {"event": "trace.span", "name": "engine.tick", "ts": 11.0, "dur_s": 0.1},
            {"event": "trace.span", "name": "engine.tick", "ts": 99.0, "dur_s": 0.1,
             "ssm_steps": 4, "ssm_row_steps": 8},
            {"event": "trace.span", "name": "engine.prefill", "ts": 12.0, "ssm_steps": 4}]
    path = tmp_path / "events-server-0.jsonl"
    path.write_text("\n".join(json.dumps(r) for r in rows) + "\nnot json\n")
    got = _ssm.read_ticks([str(path)], 5.0, 50.0)
    assert [r["ssm_row_steps"] for r in got] == [80]
