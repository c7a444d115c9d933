"""Profiling subsystem tests (SURVEY.md §5: the reference has no profiler at
all; this asserts ours actually produces a trace)."""

import glob
import os

import jax
import jax.numpy as jnp

import pytest

from ditl_tpu.utils.profiling import StepProfiler


def test_step_profiler_writes_trace(tmp_path):
    prof = StepProfiler(str(tmp_path), start_step=1, num_steps=2)

    @jax.jit
    def step(x):
        return x @ x.T

    x = jnp.ones((64, 64))
    for s in range(4):
        prof.maybe_start(s)
        with prof.annotate(s):
            x = step(x)
        prof.maybe_stop(s)
    x.block_until_ready()
    assert not prof._active
    traces = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    assert traces, f"no trace files under {tmp_path}: {list(tmp_path.rglob('*'))}"
    assert os.path.getsize(traces[0]) > 0


def test_step_profiler_disabled_is_noop(tmp_path):
    prof = StepProfiler("", start_step=0, num_steps=3)
    for s in range(3):
        prof.maybe_start(s)
        with prof.annotate(s):
            pass
        prof.maybe_stop(s)
    prof.close()


def test_step_profiler_span_records_size_and_wall(tmp_path):
    """ISSUE 7 satellite: the profiler.capture span carries the capture's
    wall seconds and the on-disk trace size, so profiling overhead is
    attributable on the timeline instead of vanishing into `other`."""
    import json

    from ditl_tpu.telemetry import EventJournal, Tracer

    jpath = str(tmp_path / "events.jsonl")
    journal = EventJournal(jpath, source="test")
    prof = StepProfiler(
        str(tmp_path / "trace"), start_step=0, num_steps=2,
        tracer=Tracer(journal),
    )

    @jax.jit
    def step(x):
        return x @ x.T

    x = jnp.ones((64, 64))
    for s in range(2):
        prof.maybe_start(s)
        with prof.annotate(s):
            x = step(x)
        prof.maybe_stop(s)
    x.block_until_ready()
    journal.close()
    recs = [json.loads(ln) for ln in open(jpath)]
    spans = [r for r in recs if r.get("event") == "trace.span"
             and r.get("name") == "profiler.capture"]
    assert len(spans) == 1
    span = spans[0]
    assert span["trace_bytes"] > 0, span
    assert span["capture_s"] > 0, span
    assert span["partial"] is False
    assert not prof._active


def test_trainer_profile_config_end_to_end(tmp_path):
    """Full trainer run with profiling enabled on simulated devices."""
    from ditl_tpu.config import Config, DataConfig, ModelConfig, TrainConfig
    from ditl_tpu.train.trainer import train

    cfg = Config(
        model=ModelConfig(
            vocab_size=512, hidden_size=64, intermediate_size=128,
            num_layers=2, num_heads=4, num_kv_heads=2, head_dim=16,
            max_seq_len=64,
        ),
        data=DataConfig(
            synthetic=True, synthetic_examples=64, batch_size=8, seq_len=32,
            num_epochs=1,
        ),
        train=TrainConfig(
            total_steps=5, warmup_steps=1, log_every=2,
            profile_dir=str(tmp_path), profile_start_step=1, profile_num_steps=2,
        ),
    )
    summary = train(cfg)
    assert summary["steps"] == 5
    traces = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    assert traces, "trainer did not write a profiler trace"


def test_metrics_jsonl_stream(tmp_path):
    """train.metrics_file writes a tail-able JSONL scalar stream."""
    import json

    import numpy as np

    from ditl_tpu.config import Config, DataConfig, ModelConfig, TrainConfig
    from ditl_tpu.train.trainer import train

    out = train(
        Config(
            model=ModelConfig(
                vocab_size=512, hidden_size=64, intermediate_size=128,
                num_layers=2, num_heads=4, num_kv_heads=2, head_dim=16,
                max_seq_len=64,
            ),
            data=DataConfig(synthetic=True, synthetic_examples=64, batch_size=8,
                            seq_len=32, num_epochs=1),
            train=TrainConfig(total_steps=4, warmup_steps=1, log_every=2,
                              metrics_file=str(tmp_path / "metrics.jsonl")),
        )
    )
    assert out["steps"] == 4
    lines = [json.loads(l) for l in (tmp_path / "metrics.jsonl").read_text().splitlines()]
    assert lines, "no metrics rows written"
    for row in lines:
        assert {"step", "loss", "step_time_s", "tokens_per_sec_per_chip"} <= row.keys()
        assert np.isfinite(row["loss"])


# ---------------------------------------------------------------------------
# ISSUE 23: the profiler waits for the device, every step is marked, compiles
# are counted where they happen, and a flush gives the honest step time
# ---------------------------------------------------------------------------


def test_maybe_stop_and_close_block_on_what_they_are_given(tmp_path, monkeypatch):
    """The trace stops only when the traced steps' arrays are ready (not after
    ``jax.effects_barrier()``, which does not wait for the device)."""
    waited, order = [], []
    monkeypatch.setattr(jax, "block_until_ready",
                        lambda x: (waited.append(x), order.append("wait")))
    monkeypatch.setattr(jax.profiler, "start_trace", lambda d: order.append("start"))
    monkeypatch.setattr(jax.profiler, "stop_trace", lambda: order.append("stop"))
    monkeypatch.setattr(
        jax, "effects_barrier",
        lambda: pytest.fail("effects_barrier does not wait for the device"))
    prof = StepProfiler(str(tmp_path), start_step=0, num_steps=2)
    prof.maybe_start(0)
    prof.maybe_stop(0, {"loss": 0})  # inside the window: nothing happens
    assert order == ["start"]
    prof.maybe_stop(1, {"loss": 1})
    assert order == ["start", "wait", "stop"] and waited == [{"loss": 1}]
    again = StepProfiler(str(tmp_path), start_step=0, num_steps=5)
    again.maybe_start(0)
    again.close("state")
    assert order[-2:] == ["wait", "stop"] and waited[-1] == "state"


def test_close_finalises_the_trace_when_the_last_step_failed(tmp_path, monkeypatch):
    """``close`` runs in the trainer's ``finally``: a step that failed on the
    device raises again in the wait, and that must neither leave the trace
    open nor raise over the first error and the clean-up that follows."""
    order = []

    def failed(x):
        raise RuntimeError("device step failed")

    monkeypatch.setattr(jax, "block_until_ready", failed)
    monkeypatch.setattr(jax.profiler, "start_trace", lambda d: order.append("start"))
    monkeypatch.setattr(jax.profiler, "stop_trace", lambda: order.append("stop"))
    prof = StepProfiler(str(tmp_path), start_step=0, num_steps=5)
    prof.maybe_start(0)
    prof.close({"loss": 0})
    assert order == ["start", "stop"]
    prof.maybe_start(1)  # finalised: no second window
    prof.close()
    assert order == ["start", "stop"]


def test_every_step_is_annotated_whoever_started_the_trace(tmp_path):
    """A trace started from outside the StepProfiler (a benchmark's launcher
    thread, the profiler server) still holds the trainer's step marks and
    the flush mark."""
    import gzip
    import json as _json

    from ditl_tpu.train.metrics import MetricsLogger

    prof = StepProfiler("", start_step=0, num_steps=3)  # disabled: no window
    logger = MetricsLogger(log_every=2, n_chips=1)
    jax.profiler.start_trace(str(tmp_path), create_perfetto_trace=True)
    for s in range(2):
        logger.start_step()
        with prof.annotate(s):
            m = {"loss": jnp.ones(()), "n_tokens": jnp.ones(())}
        logger.end_step(s, m)
    logger.close()
    jax.profiler.stop_trace()
    (path,) = glob.glob(str(tmp_path / "**" / "perfetto_trace.json.gz"), recursive=True)
    with gzip.open(path, "rt") as f:
        names = {e.get("name") for e in _json.load(f)["traceEvents"]}
    assert "train_step" in names and "train.flush" in names


def _train_rows(tmp_path, **data):
    import json

    from ditl_tpu.config import Config, DataConfig, ModelConfig, TrainConfig
    from ditl_tpu.train.trainer import train

    path = tmp_path / "metrics.jsonl"
    train(Config(
        model=ModelConfig(
            vocab_size=512, hidden_size=64, intermediate_size=128,
            num_layers=2, num_heads=4, num_kv_heads=2, head_dim=16,
            max_seq_len=64,
        ),
        data=DataConfig(synthetic=True, synthetic_examples=64, batch_size=8,
                        num_epochs=1, **data),
        train=TrainConfig(total_steps=4, warmup_steps=1, log_every=2,
                          metrics_file=str(path)),
    ))
    return [json.loads(ln) for ln in path.read_text().splitlines()]


def test_rows_carry_the_compile_counter_and_a_new_shape_raises_it(tmp_path):
    from ditl_tpu.utils.profiling import compile_counter

    (tmp_path / "a").mkdir()
    rows = _train_rows(tmp_path / "a", seq_len=32)
    assert all({"compile_count_cum", "compile_s_cum"} <= r.keys() for r in rows)
    # cumulative: never falls, and flat once the step program is compiled
    counts = [r["compile_count_cum"] for r in rows]
    assert counts == sorted(counts) and counts[-1] == counts[-2]
    assert rows[-1]["compile_s_cum"] > 0
    before = compile_counter().snapshot()
    # the same job at another sequence length: a forced recompile
    (tmp_path / "b").mkdir()
    again = _train_rows(tmp_path / "b", seq_len=48)
    after = compile_counter().snapshot()
    assert again[0]["compile_count_cum"] > rows[-1]["compile_count_cum"]
    assert after["compile_count"] > before["compile_count"]
    assert after["compile_s"] > before["compile_s"]


def test_compile_events_reach_the_journal_with_the_programs_name(tmp_path):
    import json

    from ditl_tpu.telemetry import EventJournal
    from ditl_tpu.utils.profiling import compile_counter

    journal = EventJournal(str(tmp_path / "events.jsonl"), source="test")
    counter = compile_counter()
    counter.journal = journal
    try:
        @jax.jit
        def a_new_program(x):
            return x * 3 + 1

        a_new_program(jnp.ones((7, 3))).block_until_ready()
    finally:
        counter.journal = None
        journal.close()
    events = [json.loads(ln) for ln in open(tmp_path / "events.jsonl")]
    mine = [e for e in events if e.get("event") == "jit.compile"
            and e["program"] == "jit(a_new_program)"]
    assert len(mine) == 1 and mine[0]["compile_s"] > 0


def test_flush_step_s_is_the_flush_to_flush_wall_on_the_flushing_row(tmp_path):
    import json
    import time

    from ditl_tpu.train.metrics import MetricsLogger

    path = tmp_path / "rows.jsonl"
    logger = MetricsLogger(log_every=2, n_chips=1, metrics_file=str(path))
    t0 = time.perf_counter()
    for s in range(4):
        logger.start_step()
        time.sleep(0.05)  # the wall a step really takes; the enqueue is instant
        logger.end_step(s, {"loss": jnp.ones(()), "n_tokens": jnp.full((), 100.0)})
    wall = time.perf_counter() - t0
    logger.close()
    rows = [json.loads(ln) for ln in path.read_text().splitlines()]
    flushing = [r for r in rows if "flush_step_s" in r]
    assert [r["step"] for r in flushing] == [0, 2, 3]  # where sync_s is, too
    assert all("sync_s" in r for r in flushing)
    assert all("flush_step_s" not in r for r in rows if r not in flushing)
    # step 2's flush covers steps 1 and 2: two sleeps over two steps
    assert 0.045 <= flushing[1]["flush_step_s"] <= 1.0
    covered = flushing[0]["flush_step_s"] + 2 * flushing[1]["flush_step_s"] \
        + flushing[2]["flush_step_s"]
    assert covered == pytest.approx(wall, abs=0.25)
    # the old keys keep their meaning: the enqueue's time
    assert all({"step_time_s", "dispatch_s"} <= r.keys() for r in rows)
