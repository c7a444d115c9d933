"""PR 54's eight readers of a start (``layer_metrics/_setup.py``) on made-up
run directories of either kind, and their manifest entries."""
import json
import os

import manifest as M
import pytest
from conftest import BENCH
from harness import load_module
from layer_metrics import _setup

CELLS = ["qwen2-0.5b.train-2k", "qwen2-7b-cut4.train-fsdp4-4k",
         "qwen2-7b-cut1.chat-steady-7b", "olmoe-1b-7b-cut1.chat-steady-moe",
         "longcat-flash-cut1.chat-wide-mla", "granite-4.0-h-micro.chat-wide-ssm",
         "deepseek-v3.2-cut1.docs-32k-dsa", "trinity-mini-cut1.docs-32k-swa"]
# name: (unit, source, cells)
READERS = {
    "setup_before_program_s": ("s", "program_span", CELLS),
    "setup_reference_check_s": ("s", "host_clock", CELLS),
    "setup_program_s": ("s", "program_span", CELLS),
    "setup_imports_s": ("s", "program_span", CELLS),
    "setup_params_s": ("s", "program_span", CELLS),
    "setup_warm_s": ("s", "program_span", CELLS),
    "setup_compile_s_chat": ("s", "program_span", CELLS[2:]),
    "setup_cache_misses": ("count", "program_counter", CELLS),
}
STRETCHES = ("setup_before_program_s", "setup_program_s", "setup_warm_s")


def reader(name):
    return load_module(os.path.join(BENCH, "layer_metrics", f"{name}.py"))


def read_all(run):
    return {name: reader(name).read(run) for name in READERS}


@pytest.fixture
def at(tmp_path, monkeypatch):
    """The readers find the traced run's directory in ``tmp_path``."""
    monkeypatch.setattr(_setup._ttft, "run_dir_of", lambda run: str(tmp_path))
    return tmp_path


# -- serving ---------------------------------------------------------------

T0 = 1_000_000.0  # the harness's start on the wall clock
SERVE_LEGS = {"imports": 0.5, "runtime": 0.25, "tokenizer": 7.0, "params": 12.0,
              "engine": 3.0, "listen": 0.25}


def serve_run():
    return {"kind": "serve", "setup_s": 60.0, "window_wall": [T0 + 60.0, T0 + 111.0],
            "health_s": 33.5, "reference": {"ok": True, "seconds": 0.004}}


def write_journal(run_dir, with_startup=True, with_cache=True):
    entry = T0 + 10.0
    recs, t = [], entry
    if with_startup:
        for name, dur in SERVE_LEGS.items():
            recs.append({"event": "trace.span", "name": f"startup.{name}", "ts": t,
                         "dur_s": dur, "parent": "p0", "span": name, "pid": 7})
            t += dur
        recs.append({"event": "trace.span", "name": "startup", "ts": entry,
                     "dur_s": t - entry, "parent": "", "span": "p0", "pid": 7})
    cache = lambda c, **kw: ({"cache": c, **kw} if with_cache else {})  # noqa: E731
    recs += [
        {"event": "jit.compile", "ts": T0 + 20.0, "program": "jit(init)", "compile_s": 2.0,
         "pid": 7, **cache("hit", retrieval_s=0.5)},
        {"event": "jit.compile", "ts": T0 + 40.0, "program": "jit(paged_prefill)",
         "compile_s": 9.0, "pid": 7, **cache("miss")},
        {"event": "jit.compile", "ts": T0 + 45.0, "program": "jit(paged_decode)",
         "compile_s": 1.0, "pid": 7, **cache("miss")},
        # inside the window, and another process's: neither is the start's
        {"event": "jit.compile", "ts": T0 + 70.0, "program": "jit(late)", "compile_s": 5.0,
         "pid": 7, **cache("miss")},
        {"event": "jit.compile", "ts": T0 + 30.0, "program": "jit(other)", "compile_s": 4.0,
         "pid": 8, **cache("miss")},
        {"event": "trace.span", "name": "server.request", "ts": T0 + 61.0, "dur_s": 0.1,
         "span": "r", "parent": "", "pid": 7},
    ]
    os.makedirs(os.path.join(run_dir, "spans"))
    with open(os.path.join(run_dir, "spans", "events-server-7.jsonl"), "w") as f:
        f.write("not json\n")
        for r in recs:
            f.write(json.dumps(r) + "\n")


def test_every_reader_on_a_serving_run(at):
    write_journal(at)
    got = read_all(serve_run())
    assert got == {
        "setup_before_program_s": pytest.approx(10.0),
        "setup_reference_check_s": 0.004,
        "setup_program_s": pytest.approx(23.0),
        "setup_imports_s": pytest.approx(7.5),
        "setup_params_s": pytest.approx(12.0),
        "setup_warm_s": pytest.approx(27.0),
        "setup_compile_s_chat": pytest.approx(12.0),
        "setup_cache_misses": 2.0,
    }
    assert sum(got[n] for n in STRETCHES) == pytest.approx(60.0, abs=_setup.TOLERANCE_S)


@pytest.mark.parametrize("kind", ["no-startup", "no-journal"])
def test_a_server_without_the_spans_reads_none(at, kind):
    if kind == "no-startup":
        write_journal(at, with_startup=False, with_cache=False)
    got = read_all(serve_run())
    assert got.pop("setup_reference_check_s") == 0.004  # the harness's own clock
    assert set(got.values()) == {None}


def test_compile_events_without_the_cache_field_count_no_miss(at):
    write_journal(at, with_cache=False)
    got = read_all(serve_run())
    assert got["setup_cache_misses"] == 0.0
    assert got["setup_compile_s_chat"] == pytest.approx(12.0)


# -- the trainer -----------------------------------------------------------

TRAIN_LEGS = {"config": 0.1, "runtime": 1.4, "data": 0.5, "state": 3.0, "restore": 0.0,
              "loop_prep": 1.0, "first_flush": 4.0}


def train_run(warm=2):
    return {"kind": "train", "setup_s": 24.0, "traffic": {"warmup_flushes": warm},
            "reference": {"ok": True, "seconds": 5.5}, "rows": []}


def write_rows(run_dir, with_startup=True, with_misses=True):
    rows = []
    for step in range(13):
        row = {"step": step, "loss": 5.0, "compile_count_cum": 9, "compile_s_cum": 1.75}
        if with_misses:
            row["compile_miss_count_cum"] = 3 if step < 8 else 4
        if step % 4 == 0:
            row["flush_step_s"] = 3.9 if step == 0 else 1.25
        rows.append(row)
    if with_startup:
        rows[0]["startup"] = {"entry_wall": T0 + 9.0, "legs": TRAIN_LEGS}
    with open(os.path.join(run_dir, "metrics.jsonl"), "w") as f:
        for r in rows:
            f.write(json.dumps(r) + "\n")


def test_every_reader_on_a_trainer_run(at):
    write_rows(at)
    got = read_all(train_run())
    # the window opens at the second flush (step 4): four steps of 1.25 s behind the first
    assert got == {
        "setup_before_program_s": pytest.approx(24.0 - 6.0 - 4.0 - 5.0),
        "setup_reference_check_s": 5.5,
        "setup_program_s": pytest.approx(6.0),
        "setup_imports_s": pytest.approx(1.5),
        "setup_params_s": pytest.approx(3.0),
        "setup_warm_s": pytest.approx(9.0),
        "setup_compile_s_chat": pytest.approx(1.75),  # not listed for a trainer cell
        "setup_cache_misses": 3.0,
    }
    assert sum(got[n] for n in STRETCHES) == pytest.approx(24.0, abs=_setup.TOLERANCE_S)


def test_a_later_window_counts_every_warm_up_flush(at):
    write_rows(at)
    run = train_run(warm=3)  # opens at step 8: two flush intervals behind the first
    assert reader("setup_warm_s").read(run) == pytest.approx(4.0 + 2 * 5.0)
    assert reader("setup_cache_misses").read(run) == 4.0


@pytest.mark.parametrize("kind", ["no-startup", "no-file"])
def test_a_trainer_without_the_block_reads_none(at, kind):
    if kind == "no-startup":
        write_rows(at, with_startup=False, with_misses=False)
    got = read_all(train_run())
    assert got.pop("setup_reference_check_s") == 5.5
    assert set(got.values()) == {None}


def test_an_untraced_run_reads_none(monkeypatch):
    monkeypatch.setattr(_setup._ttft, "run_dir_of", lambda run: None)
    for run in (serve_run(), train_run()):
        got = read_all(run)
        got.pop("setup_reference_check_s")
        assert set(got.values()) == {None}
    assert reader("setup_reference_check_s").read({"reference": {"ok": False}}) is None


# -- the command -----------------------------------------------------------


@pytest.mark.parametrize("kind", ["serve", "train"])
def test_the_command_prints_the_legs_and_the_difference(tmp_path, capsys, kind):
    run = serve_run() if kind == "serve" else train_run()
    (write_journal if kind == "serve" else write_rows)(str(tmp_path))
    with open(tmp_path / "run.json", "w") as f:
        json.dump(run, f)
    assert _setup.main([str(tmp_path)]) == 0
    out = capsys.readouterr().out
    for leg in (_setup.SERVE_LEGS if kind == "serve" else _setup.TRAIN_LEGS):
        assert f" {leg} " in out
    assert "difference" in out and "OVER" not in out
    assert "reference check" in out and "not in the cache" in out
    if kind == "serve":
        assert "jit(paged_prefill)" in out and "miss" in out and "jit(late)" not in out
        assert "/health seen after" in out


def test_the_command_says_so_where_there_is_nothing_to_read(tmp_path, capsys):
    assert _setup.main([str(tmp_path)]) == 2  # no run record
    with open(tmp_path / "run.json", "w") as f:
        json.dump(serve_run(), f)
    assert _setup.main([str(tmp_path)]) == 1  # a run record, no start-up spans
    assert "no start-up record" in capsys.readouterr().err


# -- the manifest ----------------------------------------------------------


def test_the_manifest_lists_the_eight_readers_last_and_in_their_cells():
    m = M.load()
    assert M.validate(m) == []
    entries = m["per_layer"][-len(READERS):]
    assert [e["name"] for e in entries] == list(READERS)
    for e in entries:
        unit, source, cells = READERS[e["name"]]
        r = reader(e["name"])
        assert (e["layer"], e["unit"], e["moves"], e["source"]) == (
            r.LAYER, r.UNIT, r.MOVES, r.SOURCE) == ("Runtime", unit, "setup_s", source)
        assert e["better"] == "lower" and e["workloads"] == cells
        for cell in cells:
            assert e["name"] in {x["name"] for x in M.metrics_for(m, "per_layer", cell)}
    # 8 on the serving cells, 7 on the trainer's, on top of what each had
    mine = set(READERS)
    for cell in CELLS:
        listed = {x["name"] for x in M.metrics_for(m, "per_layer", cell)}
        assert len(listed & mine) == (7 if "train-" in cell else 8)
