"""The scope helper (layer_metrics/_scopes.py): which name an operation's
path ends in, the wire-format reader against a trace the profiler writes
here, seconds by name on events known by construction and on the cut
recorded on the chip, the readers built on it, and its copy of the name
table against the program's."""
import glob
import json
import os

import pytest
from conftest import BENCH
from harness import load_module

sc = load_module(os.path.join(BENCH, "layer_metrics", "_scopes.py"))
MS = 10**9  # ps


def test_the_name_table_equals_the_programs():
    from ditl_tpu.ops import names

    assert sc.SCOPES == names.SCOPES and sc.KERNELS == names.KERNELS


@pytest.mark.parametrize("tf_op,want", [
    # as a v5e trace spells them (PR 23's traced train-2k run)
    ("jit(train_step)/transpose(jvp(loss))/jit(fused_cross_entropy)/loss/while/body/"
     "closed_call/checkpoint/td,dv->tv/dot_general:", "loss"),
    ("jit(train_step)/transpose(jvp())/while/body/closed_call/checkpoint/attn_core/"
     "attn_core/flash_bwd_dq/pallas_call:", "flash_bwd_dq"),
    ("jit(train_step)/jvp()/while/body/closed_call/attn_core/attn_core/flash_fwd/"
     "pallas_call:", "flash_fwd"),
    ("jit(train_step)/optimizer/add:", "optimizer"),
    ("jit(train_step)/jvp(embed)/gather:", "embed"),
    # a wrapper around a path of two scopes: the innermost wins
    ("jit(paged_decode)/while/body/transpose(jvp(attn_core/kv_write))/mul:", "kv_write"),
    ("jit(paged_decode)/layer_scan/while/body/mlp/dot_general:", "mlp"),
    ("jit(paged_decode)/layer_scan/while/body/dynamic_slice:", "layer_scan"),
    ("jit(paged_prefill)/kv_gather/gather:", "kv_gather"),
    ("jit(train_step)/transpose(jvp(layer_scan))/while/body/closed_call/checkpoint/mlp/"
     "mul:", "mlp"),
    ("jit(train_step)/jvp(layer_scan)/while:", "layer_scan"),
    # no table name: what the loop itself does, a function that is only NAMED like a scope
    ("jit(train_step)/jvp()/while:", None),
    ("jit(loss)/jit(mlp)/add:", None),
    ("jit(train_step)/mlp_norm/mul:", None),
    ("", None),
])
def test_innermost_table_name_of_a_scope_path(tf_op, want):
    assert sc.innermost(tf_op) == want


def known_trace():
    """One chip, 100 ms: a while (id 1, no name of its own) of 60 ms holding an
    mlp fusion of 30 ms and a flash kernel of 20 ms; then 40 ms under loss."""
    return {
        "devices": {"0": [[1, 0, 60 * MS], [2, 5 * MS, 30 * MS], [3, 36 * MS, 20 * MS],
                          [4, 60 * MS, 40 * MS]]},
        "meta": {"0": {
            "1": ["while.1", "jit(train_step)/jvp()/while:"],
            "2": ["fusion.7", "jit(train_step)/jvp()/while/body/closed_call/mlp/dot_general:"],
            "3": ["flash_fwd.2", "jit(train_step)/jvp()/while/body/closed_call/attn_core/"
                                 "flash_fwd/pallas_call:"],
            "4": ["fusion.9", "jit(train_step)/jvp(loss)/jit(fused_cross_entropy)/loss/exp:"],
        }},
    }


def test_seconds_by_name_use_self_time_and_keep_the_unnamed_rest():
    by = sc.seconds_by_name(known_trace())
    assert by == {None: pytest.approx(0.010), "mlp": pytest.approx(0.030),
                  "flash_fwd": pytest.approx(0.020), "loss": pytest.approx(0.040)}


def test_a_second_chip_halves_what_only_one_ran():
    tr = known_trace()
    tr["devices"]["1"] = [[4, 0, 100 * MS]]
    tr["meta"]["1"] = tr["meta"]["0"]
    by = sc.seconds_by_name(tr)
    assert by["loss"] == pytest.approx((0.040 + 0.100) / 2)
    assert by["mlp"] == pytest.approx(0.030 / 2)


def test_dump_round_trip_keeps_only_events_inside_the_cut(tmp_path):
    path = str(tmp_path / "cut.json.gz")
    sc.dump(known_trace(), path, 0.0, 0.060)
    back = sc.load(path)
    assert [e[0] for e in back["devices"]["0"]] == [1, 2, 3]
    assert set(back["meta"]["0"]) == {"1", "2", "3"}


RECORDED = os.path.join(BENCH, "tests", "data", "train2k_scopes.json.gz")


def test_the_recorded_chip_cut_gives_its_known_seconds():
    with open(RECORDED.replace(".json.gz", ".expected.json")) as f:
        want = json.load(f)
    trace = sc.load(RECORDED)
    assert sum(len(v) for v in trace["devices"].values()) == want["events"]
    by = sc.seconds_by_name(trace)
    assert {k or "" for k in by} == set(want["seconds_by_name"])
    for name, s in want["seconds_by_name"].items():
        assert by[name or None] == pytest.approx(s, rel=1e-9), name
    # every Mosaic call of the cut reads as its kernel's name, in the
    # instruction's own name too (what the ledger's breakdown prints)
    shorts = {m[0].rsplit(".", 1)[0] for m in trace["meta"]["0"].values()}
    assert {"flash_fwd", "flash_bwd_dq", "flash_bwd_dkv"} <= shorts
    assert not {"closed_call", "checkpoint", "rematted_computation"} & shorts


def test_reads_the_xplane_the_profiler_writes_here_without_a_device(tmp_path):
    """The wire-format reader walks a real .xplane.pb (a CPU one has no TPU
    plane, so it holds no device event): no error, nothing to read."""
    import jax
    import jax.numpy as jnp

    f = jax.jit(lambda a: (a @ a).sum())
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    f(x).block_until_ready()
    jax.profiler.stop_trace()
    (path,) = glob.glob(str(tmp_path / "plugins" / "profile" / "*" / "*.xplane.pb"))
    assert sc.load(path) == {"devices": {}, "meta": {}, "modules": {}}
    assert sc.seconds_by_name(sc.load(path)) == {}


def test_the_plane_reader_finds_events_and_their_tf_op():
    """A hand-built XSpace: one device plane, one XLA Ops line, two events
    whose metadata carry tf_op as a string and as a reference."""
    def varint(n):
        out = bytearray()
        while True:
            b, n = n & 0x7F, n >> 7
            out.append(b | (0x80 if n else 0))
            if not n:
                return bytes(out)

    def field(no, payload):
        if isinstance(payload, int):
            return varint(no << 3) + varint(payload)
        return varint(no << 3 | 2) + varint(len(payload)) + payload

    def entry(key, value):
        return field(1, key) + field(2, value)

    stat_md = [field(5, entry(7, field(1, 7) + field(2, b"tf_op"))),
               field(5, entry(8, field(1, 8) + field(2, b"jit(f)/mlp/add:")))]
    ev_md = [
        field(4, entry(1, field(1, 1) + field(2, b"%fusion.1 = f32[] fusion()")
                       + field(5, field(1, 7) + field(5, b"jit(f)/loss/exp:")))),
        field(4, entry(2, field(1, 2) + field(2, b"%fusion.2 = f32[] fusion()")
                       + field(5, field(1, 7) + field(7, 8)))),
    ]
    line = field(2, b"XLA Ops") + field(3, 5) \
        + field(4, field(1, 1) + field(2, 1000) + field(3, 2000)) \
        + field(4, field(1, 2) + field(2, 4000) + field(3, 500))
    other = field(2, b"Steps") + field(4, field(1, 1) + field(2, 0) + field(3, 9))
    plane = field(2, b"/device:TPU:0") + field(3, line) + field(3, other) \
        + b"".join(ev_md) + b"".join(stat_md)
    host = field(2, b"/host:CPU") + field(3, line)
    space = field(1, plane) + field(1, host)
    got = sc._plane(memoryview(field(2, b"/host:CPU")))
    assert got is None
    out = {"devices": {}, "meta": {}}
    for no, v in sc._fields(memoryview(space)):
        p = sc._plane(v)
        if p:
            out["devices"][p["device"]] = p["events"]
            out["meta"][p["device"]] = p["meta"]
    assert out["devices"] == {"0": [[1, 5000 + 1000, 2000], [2, 5000 + 4000, 500]]}
    assert out["meta"]["0"] == {"1": ["fusion.1", "jit(f)/loss/exp:"],
                                "2": ["fusion.2", "jit(f)/mlp/add:"]}


# --------------------------------------------------------------------------
# The readers
# --------------------------------------------------------------------------


def reader(name):
    return load_module(os.path.join(BENCH, "layer_metrics", f"{name}.py"))


@pytest.mark.parametrize("name,want", [
    ("flash_time_share_train", 20.0), ("loss_time_share_train", 40.0),
    ("mlp_time_share_train", 30.0), ("optimizer_time_share_train", None),
    ("scoped_time_share_train", 90.0),
])
def test_time_share_readers(monkeypatch, name, want):
    """Shares of busy time; a reader whose names the trace lacks gives None,
    never 0."""
    mod = reader(name)
    by = sc.seconds_by_name(known_trace())
    monkeypatch.setattr(mod._scopes, "run_seconds_by_name", lambda run, program=None: by)
    got = mod.read({"workload": "w", "trace": {"busy_s": 0.1}})
    assert got == (pytest.approx(want) if want is not None else None)


@pytest.mark.parametrize("name", [
    "flash_time_share_train", "loss_time_share_train", "mlp_time_share_train",
    "optimizer_time_share_train", "scoped_time_share_train"])
def test_time_share_readers_leave_out_what_has_no_names(monkeypatch, name, tmp_path):
    """An untraced run, a run with no trace file, and a trace from a program
    without the names (the parent commit): None, and no error."""
    mod = reader(name)
    assert mod.read({"workload": "w", "trace": None}) is None
    monkeypatch.setattr(mod._scopes, "OUT", str(tmp_path))
    assert mod.read({"workload": "w", "trace": {"busy_s": 1.0}}) is None
    unnamed = known_trace()
    for m in unnamed["meta"]["0"].values():
        m[1] = "jit(step)/jvp()/while/body/closed_call/checkpoint/add:"
    monkeypatch.setattr(mod._scopes, "trace_file", lambda run: "x.pb")
    monkeypatch.setattr(mod._scopes, "load", lambda path: unnamed)
    mod._scopes._seconds_of.cache_clear()
    assert mod.read({"workload": "w", "trace": {"busy_s": 1.0}}) is None


def test_compile_readers_take_the_rows_counters_and_skip_a_program_without_them():
    rows = [{"step": 9, "compile_s_cum": 41.5, "compile_count_cum": 7},
            {"step": 10, "compile_s_cum": 41.5, "compile_count_cum": 7},
            {"step": 11, "compile_s_cum": 43.0, "compile_count_cum": 8}]
    assert reader("compile_s_in_window_train").read({"rows": rows}) == pytest.approx(1.5)
    assert reader("setup_compile_s_train").read({"rows": rows}) == pytest.approx(41.5)
    old = [{"step": 9, "loss": 1.0}]
    assert reader("compile_s_in_window_train").read({"rows": old}) is None
    assert reader("setup_compile_s_train").read({"rows": old}) is None


# --------------------------------------------------------------------------
# Serving: by program, by gap label, and the five readers of the serving cell
# --------------------------------------------------------------------------


def known_serving_trace():
    """One chip, 100 ms busy: a decode run of 80 ms (a 50 ms whole-pool slice
    under layer_scan, a 20 ms paged kernel, 10 ms of mlp) and a prefill run of
    20 ms (5 ms under ITS layer_scan, 15 ms of mlp)."""
    return {
        "devices": {"0": [[1, 0, 50 * MS], [2, 50 * MS, 20 * MS], [3, 70 * MS, 10 * MS],
                          [4, 100 * MS, 5 * MS], [5, 105 * MS, 15 * MS]]},
        "meta": {"0": {
            "1": ["dynamic-slice_bitcast_fusion.8",
                  "jit(paged_decode)/while/body/closed_call/layer_scan/while/body/squeeze:"],
            "2": ["paged_attention.3", "jit(paged_decode)/while/body/closed_call/layer_scan/"
                                       "while/body/attn_core/paged_attention/pallas_call:"],
            "3": ["fusion.4", "jit(paged_decode)/while/body/closed_call/layer_scan/while/"
                              "body/mlp/dot_general:"],
            "4": ["fusion.5", "jit(paged_prefill)/layer_scan/while/body/dynamic_slice:"],
            "5": ["fusion.6", "jit(paged_prefill)/layer_scan/while/body/mlp/dot_general:"],
        }},
        "modules": {"0": [["jit_paged_decode", 0, 80 * MS], ["jit_paged_prefill", 100 * MS, 20 * MS],
                          ["jit_scatter", 121 * MS, 0]]},
    }


def test_seconds_by_name_inside_one_program_and_seconds_by_program():
    tr = known_serving_trace()
    assert sc.seconds_by_name(tr)["layer_scan"] == pytest.approx(0.055)
    assert sc.seconds_by_name(tr, "paged_decode") == {
        "layer_scan": pytest.approx(0.050), "paged_attention": pytest.approx(0.020),
        "mlp": pytest.approx(0.010)}
    assert sc.seconds_by_name(tr, "no_such_program") == {}
    by = sc.seconds_by_program(tr)
    assert by["jit_paged_decode"] == pytest.approx(0.080)
    assert by["jit_paged_prefill"] == pytest.approx(0.020)
    assert sc.seconds_by_program(known_trace()) == {}  # a cut from before `modules`


def serving_run(monkeypatch, trace, gaps):
    monkeypatch.setattr(sc, "trace_file", lambda run: "serving.pb")
    monkeypatch.setattr(sc, "load", lambda path: trace)
    sc._loaded.cache_clear()
    sc._seconds_of.cache_clear()
    return {"workload": "w", "trace": {"busy_s": 0.1, "window_s": 0.125,
                                       "gap_s_by_label": gaps}}


@pytest.mark.parametrize("name,want", [
    ("layer_scan_time_share_chat", 50.0),  # the decode program's, not the prefill's 5 ms
    ("paged_attn_time_share_chat", 20.0),
    ("prefill_device_share", 20.0),
    ("schedule_idle_share_chat", 4.0),
])
def test_serving_readers_on_a_known_trace(monkeypatch, name, want):
    mod = reader(name)
    monkeypatch.setattr(mod, "_scopes", sc)
    run = serving_run(monkeypatch, known_serving_trace(),
                      {"engine.tick.harvest": 0.010, "engine.tick.schedule": 0.005,
                       "unattributed": 0.010})
    assert mod.read(run) == pytest.approx(want)


@pytest.mark.parametrize("name", ["layer_scan_time_share_chat", "paged_attn_time_share_chat"])
def test_serving_readers_find_nothing_in_a_trainers_trace(monkeypatch, name):
    """None, never 0, for a share of busy time by NAME: a trainer's trace has
    no decode program and no paged kernel; an untraced run has no trace."""
    mod = reader(name)
    monkeypatch.setattr(mod, "_scopes", sc)
    assert mod.read(serving_run(monkeypatch, known_trace(), {"unattributed": 0.004})) is None
    assert mod.read({"workload": "w", "trace": None}) is None


@pytest.mark.parametrize("name", ["prefill_device_share", "schedule_idle_share_chat"])
def test_a_share_of_the_trace_reads_zero_where_the_trace_holds_none(monkeypatch, name):
    """A traced window that happens to hold no prefill run, or no idle gap
    under the span, HAS a share of them: 0.0. A listed metric missing from a
    traced line refuses a PR (ledger, PR 30, PR 32 notes), so None is kept for
    the run without a trace."""
    mod = reader(name)
    monkeypatch.setattr(mod, "_scopes", sc)
    assert mod.read(serving_run(monkeypatch, known_trace(), {"unattributed": 0.004})) == 0.0
    assert mod.read({"workload": "w", "trace": None}) is None


SERVING = os.path.join(BENCH, "tests", "data", "chat7b_scopes.json.gz")


def test_the_recorded_serving_cut_gives_its_known_shares(monkeypatch):
    """A cut of the serving cell's first traced run on the chip (PR 25): one
    whole decode tick and the prefills beside it, reduced as
    ``train2k_scopes.json.gz`` was."""
    with open(SERVING.replace(".json.gz", ".expected.json")) as f:
        want = json.load(f)
    trace = sc.load(SERVING)
    assert sum(len(v) for v in trace["devices"].values()) == want["events"]
    by = sc.seconds_by_name(trace)
    for name, s in want["seconds_by_name"].items():
        assert by[name or None] == pytest.approx(s, rel=1e-9), name
    for name, s in want["seconds_by_program"].items():
        assert sc.seconds_by_program(trace)[name] == pytest.approx(s, rel=1e-9), name
    run = serving_run(monkeypatch, trace, want["gap_s_by_label"])
    run["trace"].update(busy_s=want["busy_s"], window_s=want["window_s"])
    for name, value in want["readers"].items():
        mod = reader(name)
        monkeypatch.setattr(mod, "_scopes", sc)
        assert mod.read(run) == pytest.approx(value, rel=1e-9), name
    assert set(want["readers"]) == {
        "layer_scan_time_share_chat", "paged_attn_time_share_chat", "prefill_device_share",
        "schedule_idle_share_chat"}
    # the whole-pool slice is nearly all of the decode program's layer_scan
    decode = sc.seconds_by_name(trace, "paged_decode")
    assert decode["layer_scan"] <= by["layer_scan"]
    assert decode["paged_attention"] == pytest.approx(by["paged_attention"])
