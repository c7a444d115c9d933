"""``tick_overlap_share_chat`` (benchmarks/layer_metrics): the share of the
window's scheduler steps that dispatched a decode program and harvested the
tick before it under that program, from the server's ``engine.tick`` spans.
On a journal known by construction it returns the hand-reckoned share; on one
whose spans lack ``overlapped`` (a program from before double-buffered ticks,
the parent a new metric is first read on) it returns 0.0 and not None, so no
traced line lacks it; without a traced run it returns None."""

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

NAME = "tick_overlap_share_chat"
WINDOW = (100.0, 130.0)


@pytest.fixture(scope="module")
def reader():
    return load_module(os.path.join(BENCH, "layer_metrics", f"{NAME}.py"))


def journal(tmp_path, ticks, with_attribute=True):
    """``ticks``: (tick, wall start, dispatched a program?, overlapped)."""
    run_dir = tmp_path / "run"
    (run_dir / "spans").mkdir(parents=True)
    lines = ["not json"]
    for tick, ts, dispatched, overlapped in ticks:
        if dispatched:
            lines.append(json.dumps({"event": "trace.span", "name": "engine.tick.dispatch",
                                     "ts": ts + 0.001, "dur_s": 0.001, "tick": tick}))
        span = {"event": "trace.span", "name": "engine.tick", "ts": ts, "dur_s": 0.05,
                "tick": tick, "first_tokens": 0}
        if with_attribute:
            span.update(overlapped=overlapped, dead_rows=0)
        lines.append(json.dumps(span))
    lines.append(json.dumps({"event": "trace.span", "name": "engine.queue", "ts": 101.0,
                             "dur_s": 0.1, "overlapped": 1}))
    (run_dir / "spans" / "events-server-1.jsonl").write_text("\n".join(lines) + "\n")
    return run_dir


TICKS = [
    (1, 99.0, True, 1),    # before the window
    (2, 100.5, True, 0),   # the first step of a busy stretch
    (3, 100.6, True, 1),
    (4, 100.7, True, 1),
    (5, 100.8, False, 0),  # dispatched nothing (every slot prefilling): not a tick
    (6, 129.9, True, 1),
    (7, 130.0, True, 1),   # after the window
]


def run_of(reader, monkeypatch, run_dir):
    trace = run_dir / "trace" / "plugins" / "profile" / "t" / "host.xplane.pb"
    monkeypatch.setattr(reader._scopes, "trace_file", lambda run: str(trace))
    return {"workload": "w", "trace": {"busy_s": 1.0}, "window_wall": list(WINDOW)}


def test_the_share_of_a_known_journal(reader, monkeypatch, tmp_path):
    run = run_of(reader, monkeypatch, journal(tmp_path, TICKS))
    assert reader.read(run) == pytest.approx(75.0)  # ticks 3, 4, 6 of 2, 3, 4, 6


def test_spans_without_the_attribute_read_zero_not_none(reader, monkeypatch, tmp_path):
    run = run_of(reader, monkeypatch, journal(tmp_path, TICKS, with_attribute=False))
    assert reader.read(run) == 0.0
    empty = tmp_path / "empty"
    (empty / "spans").mkdir(parents=True)
    assert reader.read(run_of(reader, monkeypatch, empty)) == 0.0


def test_no_traced_run_no_metric(reader):
    assert reader.read({"workload": "w", "trace": None, "window_wall": list(WINDOW)}) is None


def test_the_readers_constants_are_the_manifests(reader):
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    (entry,) = [m for m in manifest["per_layer"] if m["name"] == NAME]
    assert (entry["layer"], entry["unit"], entry["moves"], entry["source"]) == (
        reader.LAYER, reader.UNIT, reader.MOVES, reader.SOURCE)
    # every serving cell: three until PR 41 added the fourth, PR 44 the fifth
    # (a closed loop over documents: its traffic is no "chat"), PR 56 the
    # seventh (a closed loop of streams)
    serving = [w["name"] for w in manifest["workloads"]
               if any(kind in w["traffic"] for kind in ("chat", "docs", "streams"))]
    assert entry["better"] == "higher" and entry["workloads"] == serving and len(serving) >= 3
