"""Performance observatory (ISSUE 7): step-time anatomy conservation,
roofline cost analysis, HBM accounting degradation, versioned sweep
records, and the perf_compare regression gate — including THE acceptance
smoke: a 2-cell recorded sweep of tiny CPU trainer runs whose record
``perf_compare`` passes against itself and fails against a synthetically
degraded copy."""

from __future__ import annotations

import json
import os
import time

import pytest

from ditl_tpu.telemetry import (
    MemoryWatcher,
    StepAnatomy,
    compiled_cost,
    load_sweep_record,
    new_sweep_record,
    record_sweep_cell,
    roofline,
)
from ditl_tpu.telemetry.perf import SWEEP_SCHEMA, cell_key, git_rev
from ditl_tpu.telemetry.perf_compare import compare_records

pytestmark = pytest.mark.perf

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ---------------------------------------------------------------------------
# Step-time anatomy.
# ---------------------------------------------------------------------------


def test_step_anatomy_report_and_conservation():
    a = StepAnatomy()
    a.add("host_dispatch", 0.08)
    a.add("device_compute", 0.01)
    a.add("data_wait", 0.005)
    a.add("checkpoint_overlap", 0.004)
    a.add_wall(0.1, n_steps=4)
    rep = a.report()
    assert rep["wall_step_s"] == pytest.approx(0.1)
    assert rep["steps"] == 4
    tracked = sum(v for k, v in rep.items()
                  if k.endswith("_s") and k not in ("wall_step_s", "other_s"))
    assert tracked + rep["other_s"] == pytest.approx(rep["wall_step_s"],
                                                    abs=1e-6)
    assert abs(rep["conservation_error"]) < 0.05
    assert rep["per_step_ms"]["wall"] == pytest.approx(25.0)
    # unknown buckets are rejected (typos must not silently vanish)
    with pytest.raises(ValueError):
        a.add("gpu_time", 1.0)


def test_step_anatomy_overshoot_is_visible():
    a = StepAnatomy()
    a.add("host_dispatch", 0.2)
    a.add_wall(0.1, 1)
    rep = a.report()
    assert rep["conservation_error"] == pytest.approx(1.0)  # 100% overshoot
    assert rep["other_s"] == 0.0  # floored, never negative


def test_trainer_step_anatomy_conservation(tmp_path):
    """The acceptance invariant: anatomy buckets sum to within 5% of the
    measured step-path wall on a real (tiny, CPU) training run, and the
    decomposition lands in the summary next to the goodput report."""
    from ditl_tpu.config import Config, DataConfig, ModelConfig, TrainConfig
    from ditl_tpu.train.trainer import train

    cfg = Config(
        model=ModelConfig(
            vocab_size=512, hidden_size=64, intermediate_size=128,
            num_layers=2, num_heads=4, num_kv_heads=2, head_dim=16,
            max_seq_len=64,
        ),
        data=DataConfig(synthetic=True, synthetic_examples=64, batch_size=8,
                        seq_len=32, num_epochs=1),
        train=TrainConfig(total_steps=6, warmup_steps=1, log_every=2,
                          checkpoint_dir=str(tmp_path / "ckpt"),
                          checkpoint_every=3,
                          # Arm a profiler capture window mid-run: its wall
                          # has its own goodput bucket and must be EXCLUDED
                          # from the anatomy's dispatch feed, or a capture
                          # (trace write included) breaks conservation.
                          profile_dir=str(tmp_path / "prof"),
                          profile_start_step=2, profile_num_steps=2),
    )
    out = train(cfg)
    assert out["steps"] == 6
    rep = out["step_anatomy"]
    assert rep["wall_step_s"] > 0
    # warm steps only: the compile window is goodput's, not the anatomy's
    assert rep["steps"] == 5
    tracked = sum(v for k, v in rep.items()
                  if k.endswith("_s") and k not in ("wall_step_s", "other_s"))
    assert tracked == pytest.approx(rep["wall_step_s"],
                                    rel=0.05), rep
    assert abs(rep["conservation_error"]) <= 0.05, rep
    assert rep.get("host_dispatch_s", 0) > 0
    # the in-loop checkpoint save (step 3) shows up as its own bucket
    assert rep.get("checkpoint_overlap_s", 0) > 0, rep


# ---------------------------------------------------------------------------
# Cost analysis + roofline.
# ---------------------------------------------------------------------------


def test_compiled_cost_extracts_flops_and_bytes():
    import jax
    import jax.numpy as jnp

    @jax.jit
    def f(x):
        return (x @ x.T).sum()

    exe = f.lower(jnp.ones((64, 64))).compile()
    cost = compiled_cost(exe, n_steps=2)
    assert cost is not None
    # one 64^3 matmul is ~2*64^3 flops; halved by n_steps=2
    assert cost["flops_per_step"] >= 64 ** 3
    assert cost["bytes_per_step"] > 0
    assert cost["temp_bytes"] >= 0


def test_compiled_cost_degrades_to_none():
    class NoCost:
        def cost_analysis(self):
            raise NotImplementedError("plugin backend")

    class EmptyCost:
        def cost_analysis(self):
            return [{}]

    assert compiled_cost(NoCost()) is None
    assert compiled_cost(EmptyCost()) is None


def test_roofline_memory_vs_compute_bound():
    # memory-bound: 1 flop/byte on a machine with ridge 100 flops/byte
    r = roofline(1e12, 1e12, 1.0, peak_flops=1e14, peak_bw=1e12)
    assert r["bound"] == "memory"
    assert r["roofline_mfu_cap"] == pytest.approx(0.01)
    assert r["ai_flops_per_byte"] == pytest.approx(1.0)
    # compute-bound: high intensity caps at 1.0
    r = roofline(1e14, 1e11, 1.0, peak_flops=1e14, peak_bw=1e12)
    assert r["bound"] == "compute"
    assert r["roofline_mfu_cap"] == 1.0
    assert r["mfu_cost"] == pytest.approx(1.0)
    # no bandwidth peak: intensity numbers only, no cap claimed
    r = roofline(1e12, 1e12, 1.0, peak_flops=1e14, peak_bw=None)
    assert "roofline_mfu_cap" not in r and "bound" not in r


# ---------------------------------------------------------------------------
# Sweep records.
# ---------------------------------------------------------------------------


def test_sweep_record_roundtrip_and_resume(tmp_path):
    path = str(tmp_path / "sweep.json")
    rec = new_sweep_record("unit", meta={"model": "t"})
    assert rec["schema"] == SWEEP_SCHEMA
    assert rec["git_rev"]  # never empty ("unknown" outside a repo)
    key = cell_key({"flash_block_q": 512, "remat": "dots"})
    assert key == "flash_block_q=512,remat=dots"
    assert cell_key({}) == "(base)"
    rec = record_sweep_cell(path, rec, key, {"value": 10.0, "step_ms": 5.0})
    loaded = load_sweep_record(path)
    assert loaded is not None and key in loaded["cells"]
    # resume semantics: existing cells are what callers skip on
    assert loaded["cells"][key]["value"] == 10.0
    # a wrong-schema file refuses to load (rewritten, not appended to)
    with open(path, "w") as f:
        json.dump({"schema": 999, "cells": {}}, f)
    assert load_sweep_record(path) is None
    # garbage refuses to load
    with open(path, "w") as f:
        f.write("{not json")
    assert load_sweep_record(path) is None
    assert load_sweep_record(str(tmp_path / "absent.json")) is None


def test_git_rev_in_this_repo():
    rev = git_rev(REPO)
    assert rev != "unknown" and len(rev.split("-")[0]) >= 7


def test_run_recorded_cells_resume_and_error_retry(tmp_path):
    """The record-as-you-go loop: cells
    recorded without error are skipped on resume, errored cells are
    retried, and runner failures land as error cells perf_compare can
    gate."""
    from ditl_tpu.telemetry.perf import pop_out_arg, run_recorded_cells

    path = str(tmp_path / "legs.json")
    runs: list[str] = []

    def runner(key, payload):
        runs.append(key)
        if payload == "boom":
            return {"error": "Boom"}
        return {"step_ms": float(payload)}

    items = [("base", "10"), ("lever", "boom")]
    cells = run_recorded_cells(path, "unit", {"m": 1}, items, runner)
    assert runs == ["base", "lever"]
    assert cells["base"]["step_ms"] == 10.0
    assert cells["lever"] == {"error": "Boom"}
    # resume: good cell skipped, errored cell retried (now succeeding)
    runs.clear()
    cells = run_recorded_cells(
        path, "unit", {"m": 1}, [("base", "10"), ("lever", "7")], runner)
    assert runs == ["lever"]
    assert cells["base"]["step_ms"] == 10.0
    assert load_sweep_record(path)["cells"]["lever"]["step_ms"] == 7.0
    # the scripts' --out= argv spelling
    args = ["4", "--out=/x/y.json", "2"]
    assert pop_out_arg(args, "d.json") == "/x/y.json"
    assert args == ["4", "2"]
    assert pop_out_arg(["1"], "d.json") == "d.json"


# ---------------------------------------------------------------------------
# perf_compare.
# ---------------------------------------------------------------------------


def _row(value=100.0, step_ms=50.0, mfu=0.5):
    return {"metric": "m", "schema": SWEEP_SCHEMA, "value": value,
            "step_time_p50_ms": step_ms, "mfu": mfu}


def test_perf_compare_bench_rows():
    code, rep = compare_records(_row(), _row(), 0.05)
    assert code == 0, rep
    # throughput fell past threshold
    code, rep = compare_records(_row(), _row(value=90.0), 0.05)
    assert code == 1 and "REGRESSION" in rep
    # step time rose past threshold
    code, rep = compare_records(_row(), _row(step_ms=60.0), 0.05)
    assert code == 1
    # improvement in both directions passes
    code, rep = compare_records(_row(), _row(value=120.0, step_ms=40.0), 0.05)
    assert code == 0
    # within threshold passes
    code, rep = compare_records(_row(), _row(value=97.0), 0.05)
    assert code == 0


def test_perf_compare_sweeps_and_shape_errors():
    sweep_a = {"schema": SWEEP_SCHEMA, "cells": {
        "a=1": {"step_ms": 10.0}, "a=2": {"step_ms": 20.0}}}
    sweep_b = {"schema": SWEEP_SCHEMA, "cells": {
        "a=1": {"step_ms": 10.1}, "a=3": {"step_ms": 5.0}}}
    code, rep = compare_records(sweep_a, sweep_b, 0.05)
    # common cell within threshold; disjoint cells reported, never gated
    assert code == 0, rep
    assert "only in old" in rep and "only in new" in rep
    code, rep = compare_records(
        sweep_a,
        {"schema": SWEEP_SCHEMA, "cells": {"a=1": {"step_ms": 15.0}}},
        0.05,
    )
    assert code == 1
    # mixing a sweep with a bench row is a usage error
    code, rep = compare_records(sweep_a, _row(), 0.05)
    assert code == 2
    # schema mismatch is a usage error, not a silent pass
    code, rep = compare_records({"schema": 999, "cells": {}}, sweep_a, 0.05)
    assert code == 2
    # no shared cells cannot gate anything
    code, rep = compare_records(
        sweep_a, {"schema": SWEEP_SCHEMA, "cells": {"z=1": {}}}, 0.05)
    assert code == 2


def test_perf_compare_errored_cell_is_a_regression():
    """A cell that went from measured to crashing must FAIL the gate, not
    pass because it has no numbers to compare; a cell errored on both
    sides (a standing null) is reported, never gated."""
    old = {"schema": SWEEP_SCHEMA, "cells": {"a=1": {"step_ms": 10.0}}}
    new = {"schema": SWEEP_SCHEMA,
           "cells": {"a=1": {"error": "RESOURCE_EXHAUSTED: oom"}}}
    code, rep = compare_records(old, new, 0.05)
    assert code == 1 and "now fails" in rep
    both = {"schema": SWEEP_SCHEMA, "cells": {"a=1": {"error": "x"}}}
    code, rep = compare_records(both, both, 0.05)
    assert code == 0 and "still failing" in rep
    # recovered: errored -> measured passes (nothing comparable to gate on)
    code, rep = compare_records(both, old, 0.05)
    assert code == 0


def test_perf_compare_hoists_roofline_keys():
    """mfu_cost lives under the row's nested roofline block; the gate must
    still see it (the cost-counted-MFU regression the docstring sells)."""
    old = dict(_row(), roofline={"mfu_cost": 0.6})
    new = dict(_row(), roofline={"mfu_cost": 0.4})
    code, rep = compare_records(old, new, 0.05)
    assert code == 1 and "mfu_cost" in rep


def test_perf_compare_cli_exit_codes(tmp_path):
    from ditl_tpu.telemetry.perf_compare import main

    a = tmp_path / "a.json"
    b = tmp_path / "b.json"
    a.write_text(json.dumps(_row()))
    b.write_text(json.dumps(_row(value=80.0)))
    assert main([str(a), str(a)]) == 0
    assert main([str(a), str(b)]) == 1
    assert main([str(a), str(tmp_path / "missing.json")]) == 2
    assert main([str(a), str(b), "--threshold", "0.5"]) == 0
    assert main([str(a), str(b), "--threshold", "7"]) == 2


# ---------------------------------------------------------------------------
# HBM accounting: degradation contract + OOM dump.
# ---------------------------------------------------------------------------


class _StatsDevice:
    def __init__(self, stats):
        self._stats = stats

    def memory_stats(self):
        return self._stats


def test_memwatch_absent_stats_means_absent_gauges():
    """CPU-backend degradation: no memory_stats -> no ditl_memory_* gauges,
    no crash, empty report — absent, never zero-valued lies."""
    w = MemoryWatcher()

    class NoMethod:
        pass

    assert w.sample([NoMethod(), _StatsDevice(None)]) == {}
    assert w.available is False
    assert w.report() == {}
    assert "ditl_memory" not in w.registry.render()
    # the real local backend in this test process is CPU: same contract
    # end-to-end through the /metrics helper
    from ditl_tpu.telemetry.memwatch import memory_metrics_lines

    assert memory_metrics_lines() == []


def test_memwatch_gauges_and_high_watermark():
    w = MemoryWatcher()
    d = _StatsDevice({"bytes_in_use": 100.0, "peak_bytes_in_use": 150.0,
                      "bytes_limit": 1000.0})
    out = w.sample([d])
    assert out[0]["peak_bytes_in_use"] == 150.0
    # allocator counters reset; OUR watermark must survive
    d._stats = {"bytes_in_use": 50.0, "peak_bytes_in_use": 60.0,
                "bytes_limit": 1000.0}
    out = w.sample([d])
    assert out[0]["peak_bytes_in_use"] == 150.0
    rep = w.report()
    assert rep["device0"]["peak_utilization"] == pytest.approx(0.15)
    body = w.registry.render()
    assert "ditl_memory_device0_bytes_in_use 50" in body
    assert "ditl_memory_device0_peak_bytes_in_use 150" in body


def test_memwatch_oom_dump_journaled(tmp_path):
    """Simulated allocation failure: the guard journals a top-k live-buffer
    dump with shapes and shardings, then re-raises; non-OOM exceptions pass
    through without a dump."""
    import jax.numpy as jnp

    from ditl_tpu.telemetry import EventJournal

    # Dropped-but-uncollected arrays from earlier suites (engine params,
    # bench fleets) can crowd the top-k ranking this test asserts on —
    # collect them first so "our buffer ranks" depends only on what is
    # genuinely still live.
    import gc

    gc.collect()
    big = jnp.ones((128, 128))  # a real live buffer to show up in the dump
    big.block_until_ready()
    jpath = str(tmp_path / "events.jsonl")
    journal = EventJournal(jpath, source="test")
    w = MemoryWatcher(journal=journal, topk=8)
    w.sample([_StatsDevice({"bytes_in_use": 7.0, "bytes_limit": 10.0})])
    with pytest.raises(ValueError, match="RESOURCE_EXHAUSTED"):
        with w.guard():
            raise ValueError(
                "RESOURCE_EXHAUSTED: Out of memory allocating 123 bytes"
            )
    with pytest.raises(KeyError):
        with w.guard():
            raise KeyError("not a memory problem")
    journal.close()
    recs = [json.loads(ln) for ln in open(jpath)]
    dumps = [r for r in recs if r["event"] == "memory.oom_dump"]
    assert len(dumps) == 1  # the KeyError produced none
    dump = dumps[0]
    assert dump["n_live_buffers"] >= 1
    assert dump["top"], dump
    top = dump["top"][0]
    assert {"shape", "dtype", "nbytes", "sharding"} <= top.keys()
    assert any(i["shape"] == [128, 128] for i in dump["top"])
    assert "RESOURCE_EXHAUSTED" in dump["error"]
    assert dump["device_stats"]["device0"]["bytes_in_use"] == 7
    del big


def test_is_oom_error_classification():
    from ditl_tpu.telemetry.memwatch import is_oom_error

    assert is_oom_error(RuntimeError("RESOURCE_EXHAUSTED: out of memory"))
    assert is_oom_error(Exception("Failed to allocate 16GB on device"))
    assert is_oom_error(Exception("OOM when allocating tensor"))
    assert not is_oom_error(ValueError("shape mismatch"))
    assert not is_oom_error(ValueError("zoom level out of range"))


# ---------------------------------------------------------------------------
# THE acceptance smoke: a 2-cell recorded sweep of tiny CPU trainer runs, then
# perf_compare passes on identical records and fails a degraded copy.
# ---------------------------------------------------------------------------


_SWEEP_BASE = dict(
    vocab_size=512, hidden_size=64, intermediate_size=128, num_layers=2,
    num_heads=4, num_kv_heads=2, head_dim=16, max_seq_len=64,
    loss_impl="fused",
)


def _train_cell(base: dict, cell: dict) -> dict:
    """One sweep cell: a tiny fine-tune through the normal Trainer with the
    cell's ModelConfig knobs over ``base``; the row is what a sweep record
    holds for it (stamped, with the run's own step anatomy)."""
    from ditl_tpu.config import (
        Config, DataConfig, ModelConfig, TrainConfig, parse_overrides,
    )
    from ditl_tpu.train.trainer import train

    cfg = parse_overrides(
        Config(
            model=ModelConfig(**base),
            data=DataConfig(synthetic=True, synthetic_examples=64,
                            batch_size=8, seq_len=32, num_epochs=1),
            train=TrainConfig(total_steps=5, warmup_steps=1, log_every=2),
        ),
        [f"model.{k}={v}" for k, v in cell.items()],
    )
    out = train(cfg)
    anatomy = out["step_anatomy"]
    step_s = anatomy["wall_step_s"] / anatomy["steps"]
    return {
        "schema": SWEEP_SCHEMA,
        "git_rev": git_rev(),
        "value": round(8 * 32 / step_s, 1),
        "unit": "tokens/sec",
        "step_time_p50_ms": round(step_s * 1e3, 2),
        "step_anatomy": anatomy,
        "cell": dict(cell),
    }


def test_bench_sweep_smoke_and_regression_gate(tmp_path, capsys):
    from ditl_tpu.telemetry.perf import run_recorded_cells
    from ditl_tpu.telemetry.perf_compare import main as perf_compare

    out = str(tmp_path / "sweep.json")
    grid = [{"loss_block_tokens": v} for v in ("128", "256")]
    items = [(cell_key(c), c) for c in grid]
    ran: list[str] = []

    def sweep(base=_SWEEP_BASE):
        ran.clear()

        def runner(key, cell):
            ran.append(key)
            return _train_cell(base, cell)

        return run_recorded_cells(
            out, "train-tiny", {"base": dict(base)}, items, runner)

    cells = sweep()
    assert len(ran) == 2 and not any("error" in c for c in cells.values())
    rec = load_sweep_record(out)
    assert rec is not None and len(rec["cells"]) == 2
    for key, cell in rec["cells"].items():
        # each cell is a full schema-stamped row
        assert cell["schema"] == SWEEP_SCHEMA
        assert cell["git_rev"]
        assert cell["value"] > 0 and cell["step_time_p50_ms"] > 0
        assert cell["step_anatomy"]["wall_step_s"] > 0
        assert abs(cell["step_anatomy"]["conservation_error"]) <= 0.05
        assert cell["cell"] == dict(
            kv.split("=") for kv in key.split(","))

    # resumable: a second run skips both cells (no recompute)
    assert set(sweep()) == set(rec["cells"]) and ran == []

    # an ERRORED cell is retried on resume (a transient failure must not
    # be permanently skipped)
    rec_edit = json.loads(open(out).read())
    victim = sorted(rec_edit["cells"])[0]
    rec_edit["cells"][victim] = {"error": "Injected: transient host OOM"}
    with open(out, "w") as f:
        json.dump(rec_edit, f)
    sweep()
    assert ran == [victim]
    assert "error" not in load_sweep_record(out)["cells"][victim]

    # resuming under a DIFFERENT base config must refuse, not silently
    # reuse the other config's numbers (cell keys name only swept knobs)
    with pytest.raises(ValueError, match="different base config"):
        sweep({**_SWEEP_BASE, "num_layers": 1})
    assert ran == []

    # the gate: identical records pass ...
    capsys.readouterr()
    assert perf_compare([out, out]) == 0
    assert "PASS" in capsys.readouterr().out
    # ... and a thresholded degradation exits nonzero
    bad = json.loads(open(out).read())
    for cell in bad["cells"].values():
        cell["value"] *= 0.85
        cell["step_time_p50_ms"] *= 1.2
    bad_path = str(tmp_path / "degraded.json")
    with open(bad_path, "w") as f:
        json.dump(bad, f)
    assert perf_compare([out, bad_path]) == 1
    assert "REGRESSION" in capsys.readouterr().out
