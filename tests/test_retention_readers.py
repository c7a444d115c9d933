"""The four readers of Brumby's cell (benchmarks/layer_metrics/_ret.py), their
count functions (benchmarks/retention_counts.py) and the cell's generator
(benchmarks/generators/stream_sessions.py): on a run record whose trace
matches nothing every reader returns a NUMBER (a traced line that lacks a
metric refuses a new cell: ledger, PR 30), and on a trace known by
construction each returns the hand-reckoned share."""

from __future__ import annotations

import json
import os
import sys
import types

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmarks")
for _p in (ROOT, BENCH):
    if _p not in sys.path:
        sys.path.insert(0, _p)

from harness import load_module  # noqa: E402
from layer_metrics import _mla, _ret, _scopes, _ssm  # noqa: E402

import retention_counts  # noqa: E402
from ditl_tpu.ops import names  # noqa: E402

READERS = ("ret_time_share_chat", "ret_state_time_share_chat", "ret_state_roofline_decode",
           "ret_state_bytes_share_decode")
MS = 10**9  # ps
DECODE = "jit(paged_decode)/while/body/closed_call/layer_scan/while/body/closed_call/"
PREFILL = "jit(paged_prefill)/layer_scan/while/body/closed_call/"
PEAKS = {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12}
LAYER_STATE = 8 * 9216 * 129 * 4  # one layer's state, a row: 36.3 MiB


def config():
    with open(os.path.join(BENCH, "configs", "brumby-14b-cut1.json")) as f:
        return json.load(f)


def reader(name):
    return load_module(os.path.join(BENCH, "layer_metrics", f"{name}.py"))


def test_the_name_tables_equal_the_programs():
    assert _ret.RET_SCOPES == names.RET_SCOPES == ("ret_in", "ret_state", "ret_out")
    assert names.RET_KERNELS == ("ret_step", "ret_step_read")
    assert not set(names.RET_SCOPES) & set(
        names.SCOPES + names.MOE_SCOPES + names.MLA_SCOPES + names.SSM_SCOPES)
    assert not set(names.RET_KERNELS) & set(
        names.KERNELS + names.MOE_KERNELS + names.MLA_KERNELS + names.SSM_KERNELS
        + names.CACHE_KERNELS)


def test_the_counts_at_the_published_widths():
    c = config()
    assert retention_counts.state_bytes(c) == LAYER_STATE == 38_043_648
    # the distinct products alone: 32.5 MiB a layer a row (ISSUE 56's arithmetic)
    assert retention_counts.state_bytes(c, features=8256) / 2**20 == pytest.approx(32.5, abs=0.05)
    assert retention_counts.row_step_bytes(c) == 8 * LAYER_STATE  # READ once a step
    # 14 operations on 4 bytes read: the bytes bound it on a v5e (240 a byte)
    assert retention_counts.row_step_flops(c) == 8 * 8 * 9216 * 128 * 14
    assert retention_counts.decode_state_floor_s(c, 64, PEAKS) == pytest.approx(
        64 * 8 * LAYER_STATE / 819e9)
    # 8 layers' matrices and the head in bf16: 6.37 GiB a step
    layer = 2 * 5120 * 5120 + 2 * 5120 * 1024 + 5120 * 8 + 3 * 5120 * 17408
    assert retention_counts.step_weight_bytes(c) == (8 * layer + 5120 * 151936) * 2
    assert retention_counts.step_weight_bytes(c) / 2**30 == pytest.approx(6.37, abs=0.01)
    # 16 live rows: the state, read and rewritten, is most of a step's bytes
    assert retention_counts.state_bytes_share(c, 64, 4) == pytest.approx(
        100 * 2 * 16 * 8 * LAYER_STATE / (2 * 16 * 8 * LAYER_STATE + 6.37 * 2**30), abs=0.1)
    assert 55 < retention_counts.state_bytes_share(c, 64, 4) < 62
    assert retention_counts.state_bytes_share(c, 0, 0) == 0.0


def known_trace():
    """One chip. A WHOLE decode run of 100 ms (the step kernel 50 ms and the
    feature maps around it 5 ms, the projections 10 ms, the output projection
    5 ms, the FFN 30 ms) between two runs the trace clips, whose operations
    must not count in the roofline, and a prefill whose scan is 4 ms."""
    meta = {
        "1": ["ret_step.2", DECODE + "attn_core/ret_state/ret_step/pallas_call:"],
        "2": ["fusion.3", DECODE + "attn_qkv/ret_in/dot_general:"],
        "3": ["fusion.4", DECODE + "attn_out/ret_out/dot_general:"],
        "4": ["fusion.5", DECODE + "mlp/dot_general:"],
        "5": ["fusion.6", PREFILL + "attn_core/ret_state/while/body/dot_general:"],
        "6": ["fusion.7", DECODE + "attn_core/ret_state/mul:"],
    }
    events = [[1, 0, 1 * MS], [1, 10 * MS, 50 * MS], [6, 60 * MS, 5 * MS], [2, 65 * MS, 10 * MS],
              [3, 75 * MS, 5 * MS], [4, 80 * MS, 30 * MS], [5, 115 * MS, 4 * MS],
              [1, 120 * MS, 1 * MS]]
    modules = [["jit_paged_decode", 0, 1 * MS], ["jit_paged_decode", 10 * MS, 100 * MS],
               ["jit_paged_prefill", 115 * MS, 4 * MS], ["jit_paged_decode", 120 * MS, 2 * MS]]
    return {"devices": {"0": events}, "meta": {"0": meta}, "modules": {"0": modules}}


TICKS = [  # wall = trace + 1000 s: the first tick holds the whole run's middle
    {"ts": 1000.005, "dur_s": 0.11, "ssm_steps": 4, "ssm_row_steps": 64},
    {"ts": 1000.2, "dur_s": 0.1, "ssm_steps": 4, "ssm_row_steps": 60},
]


def a_run(monkeypatch, trace, ticks, offset=1000.0):
    monkeypatch.setattr(_scopes, "trace_file", lambda run: "/r/trace/plugins/profile/t/a.xplane.pb")
    monkeypatch.setattr(_scopes, "_loaded", lambda path: trace)
    monkeypatch.setattr(_ssm, "read_ticks", lambda paths, w0, w1: ticks)
    monkeypatch.setattr(_mla, "_clock_offset_s", lambda path: offset)
    _ret._seconds_of.cache_clear()
    return {"workload": "w", "trace": {"busy_s": 0.106}, "config": config(), "peaks": PEAKS,
            "window_wall": (1000.0, 1051.0)}


WEIGHTS = retention_counts.step_weight_bytes(config())
WANT = {
    # every run's, clipped ones and the prefill's too: a share of the window
    "ret_time_share_chat": 100 * (0.052 + 0.005 + 0.010 + 0.005 + 0.004) / 0.106,
    "ret_state_time_share_chat": 100 * (0.052 + 0.005 + 0.004) / 0.106,
    # 64 row steps x 8 layers x 36.3 MiB READ over 819 GB/s = 23.8 ms of the 55
    # under the scope in the whole run: under 50%, as a kernel that rewrites must be
    "ret_state_roofline_decode": 100 * (64 * 8 * LAYER_STATE / 819e9) / 0.055,
    # the whole window's ticks: 124 row steps read and rewritten, 8 steps' weights
    "ret_state_bytes_share_decode": 100 * 2 * 124 * 8 * LAYER_STATE / (
        2 * 124 * 8 * LAYER_STATE + 8 * WEIGHTS),
}


@pytest.mark.parametrize("name", READERS)
def test_readers_on_a_known_trace(monkeypatch, name):
    run = a_run(monkeypatch, known_trace(), TICKS)
    assert reader(name).read(run) == pytest.approx(WANT[name], rel=1e-9)
    assert reader(name).read(run) <= 100.0
    assert WANT["ret_state_roofline_decode"] < 50.0


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


def traffic():
    with open(os.path.join(BENCH, "traffic", "streams-16-ret.json")) as f:
        return json.load(f)


def test_the_generator_gives_the_same_turns_for_the_same_seed_and_sixteen_streams():
    from generators import stream_sessions as gen

    t = traffic()
    sz = gen.sizes(types.SimpleNamespace(traffic=t, rehearsal=None))
    assert sz["sessions"] == 16 == int(t["server_args"][t["server_args"].index("--slots") + 1])

    def first(seed, session, n=6):
        turns = gen.session_turns(seed, session, sz, t, 151936)
        return [(ids.tolist(), m, th) for ids, m, th in (next(turns) for _ in range(n))]

    big = 2**31 + 77  # the driver's seeds are more than 32 signed bits hold
    assert first(big, 3) == first(big, 3)
    assert first(big, 3) != first(big, 4) and first(big, 3) != first(big + 1, 3)
    every = [turn for s in range(16) for turn in first(big, s, 12)]
    assert all(64 <= len(ids) + 1 <= 2048 and 128 <= m <= 1024 and 0 <= th <= 1.0
               for ids, m, th in every)
    # unique from the first token: no two prompts start alike (nothing is shared)
    assert len({tuple(ids[:4]) for ids, _, _ in every}) == len(every)
    assert all(3 <= i < 151936 and i not in (151643, 151644, 151645)
               for ids, _, _ in every for i in ids)


def test_the_traffic_file_is_the_cell_of_the_issue():
    t = traffic()
    assert t["generator"] == "stream_sessions" and "rate_per_s" not in t and "--pages" not in t[
        "server_args"]
    assert t["prompt_tokens"] == {"dist": "lognormal", "median": 512, "sigma": 0.8,
                                  "min": 64, "max": 2048}
    assert t["max_tokens"] == {"dist": "lognormal", "median": 512, "sigma": 0.5,
                               "min": 128, "max": 1024}
    assert t["think_s"] == {"dist": "exponential", "mean": 0.2, "max": 1.0}
    assert (t["preroll_s"], t["session_start_spread_s"], t["drain_s"]) == (12, 8, 90)
    args = t["server_args"]
    assert args[args.index("--max-cache-len") + 1] == "4096"
    # one warm-up request a prefill bucket the prompts reach
    buckets = {max(256, 1 << (n).bit_length()) for n in t["warmup"][0]["suffix_tokens"]}
    assert buckets == {256, 512, 1024, 2048}
