"""A program's start, leg by leg (ISSUE 54): ``telemetry/tracing.py``
``StartupRecorder`` in ``serve()`` and ``launch.main`` -> ``train()``, and the
``cache`` field of ``jit.compile`` events (``utils/profiling.CompileCounter``).

Counts and identities only: the legs telescope, a span's place in the journal,
which row carries the block. Never that one CPU reading is smaller than another.
"""

from __future__ import annotations

import json
import os
import signal
import socket
import subprocess
import sys
import threading
import time
import types
import urllib.request

import jax
import pytest

from ditl_tpu.telemetry.journal import EventJournal, read_journal, worker_journal_path
from ditl_tpu.telemetry.tracing import StartupRecorder, Tracer

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SERVE_LEGS = ["imports", "runtime", "tokenizer", "params", "engine", "listen"]
TRAIN_LEGS = ["config", "runtime", "data", "state", "restore", "loop_prep", "first_flush"]


def _spans(path) -> list[dict]:
    return [r for r in read_journal(str(path)) if r["event"] == "trace.span"]


def _single_device_env(**extra) -> dict:
    env = {k: v for k, v in os.environ.items()
           if k not in ("XLA_FLAGS", "JAX_NUM_CPU_DEVICES", "JAX_COMPILATION_CACHE_DIR")}
    env.update(JAX_PLATFORMS="cpu", PYTHONPATH=REPO, **extra)
    return env


# ---------------------------------------------------------------------------
# The recorder
# ---------------------------------------------------------------------------


def test_legs_telescope_to_the_total():
    rec = StartupRecorder()
    for name in ("a", "b", "c"):
        time.sleep(0.002)
        assert rec.mark(name) > 0
    legs = rec.totals()
    assert list(legs) == ["a", "b", "c"]
    assert sum(legs.values()) == pytest.approx(rec.total(), abs=1e-9)
    block = rec.block()
    assert block["entry_wall"] == round(rec.entry_wall, 6)
    assert sum(block["legs"].values()) == pytest.approx(rec.total(), abs=1e-5)


def test_an_entry_instant_handed_in_backdates_the_first_leg():
    rec = StartupRecorder(time.time() - 5.0)
    assert rec.mark("first") >= 5.0
    assert rec.total() >= 5.0


@pytest.mark.parametrize("tracer", [None, Tracer(None)], ids=["none", "unarmed"])
def test_unarmed_writes_nothing_and_evaluates_no_attribute(tracer):
    called = []
    rec = StartupRecorder(tracer=tracer)
    rec.mark("params", synced=0, param_bytes=lambda: called.append(1) or 7)
    rec.close()
    assert not rec.armed and not called
    # a leg is a name, a start and its seconds: no attribute is kept
    assert [len(leg) for leg in rec._legs] == [3]


def test_a_tracer_armed_after_the_first_legs_gets_them_backdated(tmp_path):
    path = tmp_path / "events-x.jsonl"
    rec = StartupRecorder()
    time.sleep(0.002)
    rec.mark("imports", dropped="an attribute of a leg closed unarmed")
    rec.attach(Tracer(EventJournal(str(path))))
    assert rec.armed
    time.sleep(0.002)
    rec.mark("params", synced=1, param_bytes=lambda: 7)
    assert [s["name"] for s in _spans(path)] == ["startup.imports", "startup.params"]
    rec.close()
    rec.close()  # idempotent
    spans = _spans(path)
    imports, params, parent = spans
    assert parent["name"] == "startup" and parent["parent"] == ""
    assert {imports["parent"], params["parent"]} == {parent["span"]}
    assert {s["trace"] for s in spans} == {parent["trace"]}
    # contiguous on one clock, from the entry on
    assert imports["ts"] == parent["ts"] == rec.entry_wall
    assert params["ts"] == pytest.approx(imports["ts"] + imports["dur_s"], abs=1e-5)
    assert parent["dur_s"] == pytest.approx(imports["dur_s"] + params["dur_s"], abs=1e-5)
    assert parent["dur_s"] == pytest.approx(rec.total(), abs=1e-5)
    assert "dropped" not in imports
    assert params["synced"] == 1 and params["param_bytes"] == 7


def test_a_second_tracer_is_not_attached(tmp_path):
    a, b = tmp_path / "events-a.jsonl", tmp_path / "events-b.jsonl"
    rec = StartupRecorder(tracer=Tracer(EventJournal(str(a))))
    rec.attach(Tracer(EventJournal(str(b))))
    rec.mark("only")
    rec.close()
    assert [s["name"] for s in _spans(a)] == ["startup.only", "startup"]
    assert _spans(b) == []


# ---------------------------------------------------------------------------
# jit.compile events: did the persistent cache hold the program
# ---------------------------------------------------------------------------


class _Journal:
    def __init__(self):
        self.events = []

    def event(self, name, **attrs):
        self.events.append({"event": name, **attrs})


def _counter(monkeypatch, cache_dir):
    from ditl_tpu.utils import profiling

    monkeypatch.setattr(profiling, "jax", types.SimpleNamespace(config=types.SimpleNamespace(
        jax_compilation_cache_dir=cache_dir, jax_enable_compilation_cache=True)))
    counter = profiling.CompileCounter()
    counter.journal = _Journal()
    return profiling, counter


@pytest.mark.parametrize("cache_dir,retrieval,expect", [
    ("/somewhere", 0.25, {"cache": "hit", "retrieval_s": 0.25}),
    ("/somewhere", None, {"cache": "miss"}),
    (None, None, {"cache": "off"}),
], ids=["hit", "miss", "off"])
def test_a_compile_event_says_whether_the_cache_held_the_program(
        monkeypatch, cache_dir, retrieval, expect):
    profiling, counter = _counter(monkeypatch, cache_dir)
    if retrieval is not None:
        counter.on_duration(profiling._CACHE_RETRIEVAL_EVENT, retrieval)
    counter.on_duration(profiling._BACKEND_COMPILE_EVENT, 1.5, fun_name="jit(f)")
    (event,) = counter.journal.events
    assert event == {"event": "jit.compile", "program": "jit(f)", "compile_s": 1.5, **expect}
    snap = counter.snapshot()
    assert snap["compile_count"] == 1
    assert snap["cache_miss_count"] == (expect["cache"] == "miss")
    # the retrieval belongs to ONE program: the next one is a miss again
    counter.on_duration(profiling._BACKEND_COMPILE_EVENT, 0.5, fun_name="jit(g)")
    assert "retrieval_s" not in counter.journal.events[1]


def test_a_retrieval_is_paired_with_its_own_threads_compile(monkeypatch):
    profiling, counter = _counter(monkeypatch, "/somewhere")
    t = threading.Thread(
        target=counter.on_duration, args=(profiling._CACHE_RETRIEVAL_EVENT, 0.1))
    t.start()
    t.join(10)
    assert not t.is_alive()
    counter.on_duration(profiling._BACKEND_COMPILE_EVENT, 1.0, fun_name="jit(f)")
    assert counter.journal.events[0]["cache"] == "miss"
    assert counter.snapshot()["cache_miss_count"] == 1


_COMPILE_CHILD = r"""
import json, sys
import jax, jax.numpy as jnp
from ditl_tpu.runtime.distributed import enable_compile_cache
from ditl_tpu.telemetry.journal import EventJournal
from ditl_tpu.train.metrics import MetricsLogger
from ditl_tpu.utils.profiling import compile_counter

enable_compile_cache()
compile_counter().journal = EventJournal(sys.argv[1])
@jax.jit
def start_up_legs_probe(x):
    return jnp.tanh(x @ x.T).sum()
out = start_up_legs_probe(jnp.ones((64, 64)))
log = MetricsLogger(log_every=1, metrics_file=sys.argv[2])
log.start_step()
log.end_step(0, {"loss": out, "n_tokens": jnp.float32(8)})
log.close()
print(json.dumps(compile_counter().snapshot()))
"""


def test_miss_then_hit_over_two_processes_sharing_a_cache(tmp_path):
    snaps = []
    for i in (0, 1):
        out = subprocess.run(
            [sys.executable, "-c", _COMPILE_CHILD, str(tmp_path / f"events-{i}.jsonl"),
             str(tmp_path / f"rows-{i}.jsonl")],
            env=_single_device_env(JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cache")),
            capture_output=True, text=True, timeout=240, cwd=str(tmp_path))
        assert out.returncode == 0, out.stderr[-2000:]
        snaps.append(json.loads(out.stdout.strip().splitlines()[-1]))
    probe = [[r for r in read_journal(str(tmp_path / f"events-{i}.jsonl"))
              if r["event"] == "jit.compile" and "start_up_legs_probe" in r["program"]]
             for i in (0, 1)]
    assert [len(p) for p in probe] == [1, 1]
    assert probe[0][0]["cache"] == "miss" and "retrieval_s" not in probe[0][0]
    assert probe[1][0]["cache"] == "hit" and probe[1][0]["retrieval_s"] >= 0
    # every program of the first process was new to the cache, none of the second's
    assert snaps[0]["cache_miss_count"] == snaps[0]["compile_count"] > 0
    assert snaps[1]["cache_miss_count"] == 0 and snaps[1]["compile_count"] > 0
    rows = [json.loads((tmp_path / f"rows-{i}.jsonl").read_text().splitlines()[0])
            for i in (0, 1)]
    assert rows[0]["compile_miss_count_cum"] == snaps[0]["cache_miss_count"]
    assert rows[1]["compile_miss_count_cum"] == 0
    assert rows[1]["compile_count_cum"] == snaps[1]["compile_count"]


# ---------------------------------------------------------------------------
# serve()
# ---------------------------------------------------------------------------


def _get(port: int, path: str) -> dict:
    with urllib.request.urlopen(f"http://127.0.0.1:{port}{path}", timeout=10) as r:
        return json.loads(r.read())


@pytest.fixture(scope="module")
def served(tmp_path_factory):
    """One tiny server process with ``--trace-dir``: (health, stats, journal records)."""
    tmp = tmp_path_factory.mktemp("serve")
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
    proc = subprocess.Popen(
        [sys.executable, "-m", "ditl_tpu.infer.server", "--preset", "tiny-llama",
         "--engine", "continuous", "--cache-mode", "paged", "--host", "127.0.0.1",
         "--port", str(port), "--max-cache-len", "128", "--slots", "2",
         "--tokenizer", os.path.join(REPO, "tests", "fixtures", "llama3_tokenizer"),
         "--trace-dir", str(tmp / "spans")],
        env=_single_device_env(JAX_COMPILATION_CACHE_DIR=str(tmp / "cache")),
        cwd=REPO, stdout=subprocess.DEVNULL, stderr=subprocess.PIPE, text=True)
    try:
        health = None
        deadline = time.monotonic() + 240
        while health is None:
            assert proc.poll() is None, proc.stderr.read()[-2000:]
            assert time.monotonic() < deadline, "the server never answered /health"
            try:
                health = _get(port, "/health")
            except OSError:
                time.sleep(0.2)
        stats = _get(port, "/v1/stats")
    finally:
        proc.send_signal(signal.SIGTERM)
        try:
            proc.wait(60)
        except subprocess.TimeoutExpired:
            proc.kill()
            proc.wait(10)
    (path,) = (tmp / "spans").glob("events-server-*.jsonl")
    return health, stats, read_journal(str(path))


def test_serve_writes_its_six_legs_in_order_under_one_parent(served):
    _, _, records = served
    spans = [r for r in records
             if r["event"] == "trace.span" and r["name"].startswith("startup")]
    assert [s["name"] for s in spans] == [f"startup.{n}" for n in SERVE_LEGS] + ["startup"]
    parent = spans[-1]
    assert {s["parent"] for s in spans[:-1]} == {parent["span"]}
    for a, b in zip(spans, spans[1:-1]):
        assert b["ts"] == pytest.approx(a["ts"] + a["dur_s"], abs=1e-5)
    by_name = {s["name"]: s for s in spans}
    assert by_name["startup.params"]["synced"] == 1
    assert by_name["startup.params"]["restored"] is False
    assert by_name["startup.params"]["param_bytes"] > 0
    assert by_name["startup.engine"]["pool_bytes"] > 0
    assert by_name["startup.listen"]["port"] > 0


def test_the_tokenizer_leg_names_its_loader(served):
    """A directory with a tokenizer.json is read without `transformers`: the
    span and the stats block say which loader ran."""
    _, stats, records = served
    (leg,) = [r for r in records if r.get("name") == "startup.tokenizer"]
    assert leg["loader"] == stats["startup"]["tokenizer_loader"] == "tokenizers"


def test_cold_start_s_is_the_legs_sum_in_health_stats_and_journal(served):
    health, stats, records = served
    block = stats["startup"]
    assert list(block["legs"]) == SERVE_LEGS
    total = sum(block["legs"].values())
    assert health["cold_start_s"] == pytest.approx(total, abs=2e-3)  # /health rounds to ms
    (parent,) = [r for r in records if r.get("name") == "startup"]
    assert parent["dur_s"] == pytest.approx(total, abs=1e-4)
    assert parent["ts"] == pytest.approx(block["entry_wall"], abs=1e-5)


def test_the_servers_compile_events_and_stats_count_cache_misses(served):
    _, stats, records = served
    compiles = [r for r in records if r["event"] == "jit.compile"]
    assert compiles and {c["cache"] for c in compiles} <= {"hit", "miss"}
    # a fresh cache directory: what the process built before /v1/stats answered
    assert stats["compile_miss_count_cum"] > 0
    assert stats["compile_miss_count_cum"] <= stats["compile_count_cum"]


# ---------------------------------------------------------------------------
# launch.main -> train()
# ---------------------------------------------------------------------------

_TINY = ["data.synthetic=true", "data.synthetic_examples=64", "data.batch_size=8",
         "data.seq_len=32", "data.num_epochs=1", "train.total_steps=5", "train.log_every=2",
         "train.warmup_steps=1", "model.vocab_size=512", "model.hidden_size=64",
         "model.intermediate_size=128", "model.num_layers=2", "model.num_heads=4",
         "model.num_kv_heads=2", "model.head_dim=16", "model.max_seq_len=64"]


def _launch(capsys, *overrides) -> None:
    from ditl_tpu import launch

    assert launch.main(_TINY + list(overrides)) == 0
    capsys.readouterr()  # the summary line


def test_the_first_metrics_row_and_no_other_carries_the_seven_legs(tmp_path, capsys):
    rows_file = tmp_path / "rows.jsonl"
    _launch(capsys, f"train.metrics_file={rows_file}")
    rows = [json.loads(ln) for ln in rows_file.read_text().splitlines()]
    assert [r["step"] for r in rows] == list(range(5))
    assert ["startup" in r for r in rows] == [True] + [False] * 4
    block = rows[0]["startup"]
    assert sorted(block["legs"]) == sorted(TRAIN_LEGS)
    assert all(v >= 0 for v in block["legs"].values())
    assert block["entry_wall"] <= time.time()
    assert all("compile_miss_count_cum" in r for r in rows)
    # a second process appending to the same file writes its own block
    _launch(capsys, f"train.metrics_file={rows_file}")
    rows = [json.loads(ln) for ln in rows_file.read_text().splitlines()]
    assert ["startup" in r for r in rows] == ([True] + [False] * 4) * 2
    assert rows[5]["startup"]["entry_wall"] > block["entry_wall"]


def test_a_journaled_trainer_writes_the_same_legs_as_spans(tmp_path, capsys):
    rows_file = tmp_path / "rows.jsonl"
    _launch(capsys, f"train.metrics_file={rows_file}",
            f"train.telemetry_dir={tmp_path / 'telemetry'}")
    spans = [s for s in _spans(worker_journal_path(str(tmp_path / "telemetry"), 0))
             if s["name"].startswith("startup")]
    assert [s["name"] for s in spans] == [f"startup.{n}" for n in TRAIN_LEGS] + ["startup"]
    parent = spans[-1]
    assert parent["dur_s"] == pytest.approx(sum(s["dur_s"] for s in spans[:-1]), abs=1e-4)
    by_name = {s["name"][len("startup."):]: s for s in spans[:-1]}
    assert by_name["state"]["synced"] == 1 and by_name["state"]["n_params"] > 0
    assert by_name["data"]["examples"] == 64
    assert by_name["restore"]["resumed"] is False and by_name["restore"]["step"] == 0
    block = json.loads(rows_file.read_text().splitlines()[0])["startup"]
    for name, span in by_name.items():
        assert block["legs"][name] == pytest.approx(span["dur_s"], abs=1e-5)


def test_an_unjournaled_trainer_start_adds_no_device_sync(tmp_path, monkeypatch):
    from ditl_tpu.config import Config, DataConfig, ModelConfig, TrainConfig
    from ditl_tpu.train.trainer import train

    waits = []
    real = jax.block_until_ready
    monkeypatch.setattr(jax, "block_until_ready", lambda x: waits.append(1) or real(x))
    out = train(Config(
        model=ModelConfig(vocab_size=512, hidden_size=64, intermediate_size=128,
                          num_layers=2, num_heads=4, num_kv_heads=2, head_dim=16,
                          max_seq_len=64),
        data=DataConfig(synthetic=True, synthetic_examples=64, batch_size=8,
                        seq_len=32, num_epochs=1),
        train=TrainConfig(total_steps=3, warmup_steps=1, log_every=2,
                          metrics_file=str(tmp_path / "rows.jsonl"))))
    assert out["steps"] == 3 and not waits
    # called without a launcher, train() counts its start from its own entry
    block = json.loads((tmp_path / "rows.jsonl").read_text().splitlines()[0])["startup"]
    assert sorted(block["legs"]) == sorted(set(TRAIN_LEGS) - {"config"})
    # the goodput report's startup bucket is fed from the recorder's legs
    before_loop = sum(v for k, v in block["legs"].items() if k != "first_flush")
    assert out["goodput"]["startup_s"] == pytest.approx(before_loop, abs=1e-3)
