"""Elastic multi-process training drills (runtime/elastic.py).

The two top-ranked VERDICT gaps in one place: a TRAINING leg where DP
gradients cross a real OS-process boundary, and pod-level elastic recovery —
a worker SIGKILLed mid-training, survivors torn down, the whole pod
relaunched on a fresh coordinator port, and training resumed from the
multi-host Orbax checkpoint with loss continuity.

Every drill is hard-bounded (subprocess timeouts / controller deadlines):
there is no pytest-timeout plugin in this image, so the harness itself is
the per-test timeout that keeps tier-1 inside its budget.
"""

from __future__ import annotations

import json
import os
import re
import signal
import subprocess
import sys
import time

import pytest

from ditl_tpu.runtime.elastic import (
    PodController,
    PodState,
    emit_heartbeat,
    heartbeat_path,
    read_heartbeat,
)
from tests.cluster_harness import ClusterHarness, free_port, hermetic_env

pytestmark = pytest.mark.multiproc

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ELASTIC_DRILL = os.path.join(os.path.dirname(__file__), "elastic_drill.py")

_TINY_MODEL = [
    "model.vocab_size=512", "model.hidden_size=32",
    "model.intermediate_size=64", "model.num_layers=2",
    "model.num_heads=2", "model.num_kv_heads=1", "model.head_dim=16",
    "model.max_seq_len=64",
]


# ---------------------------------------------------------------------------
# Pod-controller state machine: fast drills with trivial (jax-free) workers.
# ---------------------------------------------------------------------------


def _cmd(code: str, *args: str):
    return [sys.executable, "-c", code, *args]


def test_pod_controller_clean_completion():
    ctl = PodController(2, lambda i, n, port, a: _cmd("raise SystemExit(0)"),
                        poll_s=0.05)
    result = ctl.run(timeout_s=30)
    assert result.ok and result.state is PodState.DONE
    assert result.restarts == 0 and result.returncode == 0
    assert len(result.ports) == 1


def test_pod_controller_relaunches_full_pod_on_bumped_port(tmp_path):
    # Generation 0 exits 1 (no flag file yet); generation 1 finds the flag
    # and exits 0 — the controller must restart the FULL pod exactly once,
    # on a different coordinator port.
    # Per-WORKER flag files: a shared flag would race (worker 0 creates it,
    # worker 1 reads it as already present and exits 0 in generation 0).
    # A generation-0 worker leaves only once its sibling's flag is there
    # too: the controller tears the pod down at the FIRST non-zero exit, and
    # on a loaded machine that came before the other interpreter had started
    # (no flag, so it failed generation 1 as well: two restarts, seen twice in
    # whole tier-1 runs of PR 33).
    code = (
        "import os, sys, time\n"
        "p, other = sys.argv[1:3]\n"
        "ok = os.path.exists(p)\n"
        "open(p, 'w').close()\n"
        "t = time.time()\n"
        "while not ok and not os.path.exists(other) and time.time() - t < 20:\n"
        "    time.sleep(0.01)\n"
        "sys.exit(0 if ok else 1)\n"
    )
    seen_ports: list[int] = []

    def build(i, n, port, attempt):
        if i == 0:
            seen_ports.append(port)
        return _cmd(code, str(tmp_path / f"gen-0-ran-{i}"),
                    str(tmp_path / f"gen-0-ran-{1 - i}"))

    ctl = PodController(2, build, max_pod_restarts=2, poll_s=0.05)
    result = ctl.run(timeout_s=60)
    assert result.ok, result.transitions
    assert result.restarts == 1
    assert len(set(seen_ports)) == 2, "coordinator port was not bumped"
    assert any("RESTARTING" in t and "bumping coordinator port" in t
               for t in result.transitions), result.transitions


def test_pod_controller_restart_budget_exhausted():
    ctl = PodController(1, lambda i, n, port, a: _cmd("raise SystemExit(3)"),
                        max_pod_restarts=2, poll_s=0.05)
    result = ctl.run(timeout_s=60)
    assert result.state is PodState.FAILED
    assert result.restarts == 2 and result.returncode == 3
    assert any("restart budget exhausted" in t for t in result.transitions)


def test_pod_controller_tears_down_wedged_survivors():
    # Worker 0 dies at once; worker 1 "hangs in a collective" (sleeps).
    # The controller must SIGTERM the survivor instead of waiting it out.
    def build(i, n, port, attempt):
        return _cmd("raise SystemExit(1)") if i == 0 else _cmd(
            "import time; time.sleep(300)"
        )

    t0 = time.monotonic()
    ctl = PodController(2, build, max_pod_restarts=0, poll_s=0.05, grace_s=2)
    result = ctl.run(timeout_s=60)
    assert result.state is PodState.FAILED
    assert time.monotonic() - t0 < 30, "survivor teardown took too long"
    assert any("worker 0 died (rc=1)" in t for t in result.transitions)
    assert result.returncodes[1] is not None, "survivor still running"


def test_pod_controller_heartbeat_stall_is_a_death(tmp_path):
    # A worker that is alive as a process but makes no training progress
    # (wedged: its peer died some way the exit codes don't show) must be
    # treated as dead once its heartbeat goes stale.
    hb = str(tmp_path)
    ctl = PodController(
        1,
        lambda i, n, port, a: _cmd("import time; time.sleep(300)"),
        max_pod_restarts=0,
        heartbeat_dir=hb,
        heartbeat_timeout_s=1.0,
        poll_s=0.1,
        grace_s=2,
    )
    t0 = time.monotonic()
    result = ctl.run(timeout_s=60)
    assert result.state is PodState.FAILED
    assert time.monotonic() - t0 < 30
    assert any("heartbeat stale" in t for t in result.transitions)


def test_pod_controller_live_heartbeats_do_not_false_trip(tmp_path):
    # A slow-but-alive worker that heartbeats under the timeout must finish.
    hb = str(tmp_path)
    code = (
        "import json, os, sys, time\n"
        "d = sys.argv[1]\n"
        "for step in range(5):\n"
        "    tmp = os.path.join(d, 'worker-0.heartbeat.tmp')\n"
        "    with open(tmp, 'w') as f:\n"
        "        json.dump({'step': step, 'time': time.time()}, f)\n"
        "    os.replace(tmp, os.path.join(d, 'worker-0.heartbeat'))\n"
        "    time.sleep(0.3)\n"
    )
    ctl = PodController(
        1,
        lambda i, n, port, a: _cmd(code, hb),
        heartbeat_dir=hb,
        heartbeat_timeout_s=1.0,
        poll_s=0.1,
    )
    result = ctl.run(timeout_s=60)
    assert result.ok, result.transitions


def test_pod_controller_post_completion_death_is_not_a_failure():
    # SPMD: a worker exits 0 only when training completed pod-wide, so a
    # peer dying AFTER that (XLA shutdown abort) must not retrain the tail
    # (and double-print the summary) — the pod is DONE.
    def build(i, n, port, attempt):
        return _cmd("raise SystemExit(0)") if i == 0 else _cmd(
            "import time; time.sleep(0.5); raise SystemExit(3)"
        )

    ctl = PodController(2, build, max_pod_restarts=5, poll_s=0.05, grace_s=2)
    result = ctl.run(timeout_s=60)
    assert result.ok and result.restarts == 0, result.transitions
    assert any("post-completion" in t for t in result.transitions)


def test_inprocess_rejoin_contract_both_polarities():
    """distributed.py re-init for a changed coordinator address: allowed
    before any computation (client swap to the bumped port, collectives
    work in the new generation), refused with the actionable relaunch
    error once a computation has run."""
    harness = ClusterHarness(2, ELASTIC_DRILL, timeout=240)
    outs = harness.run("rejoin", str(free_port()))
    for rc, out in outs:
        assert rc == 0, out
    for i, (_, out) in enumerate(outs):
        assert f"REJOIN-OK p{i}" in out, out
        assert f"REJOIN-REFUSED p{i}" in out, out
        assert "REJOIN-REFUSAL-MISSED" not in out, out
        assert "REJOIN-WRONG-ERROR" not in out, out


def test_heartbeat_roundtrip(tmp_path):
    emit_heartbeat(str(tmp_path), 3, 17)
    hb = read_heartbeat(heartbeat_path(str(tmp_path), 3))
    assert hb is not None and hb["step"] == 17 and hb["time"] > 0
    assert read_heartbeat(heartbeat_path(str(tmp_path), 9)) is None


# ---------------------------------------------------------------------------
# Multi-host Orbax checkpoint: both processes contribute shards, and a FRESH
# 2-process pod restores params-only (the serving path, checkpoint.py).
# ---------------------------------------------------------------------------


def _fingerprints(outs, n):
    fps = []
    for i, (_, out) in enumerate(outs):
        line = next(
            ln for ln in out.splitlines() if ln.startswith(f"FINGERPRINT p{i}")
        )
        fps.append(float(line.split()[2]))
    assert len(fps) == n
    return fps


def test_multihost_checkpoint_save_and_fresh_pod_params_restore(tmp_path):
    """Satellite drill: 2-process fsdp save (each process writes a PROPER
    shard), then a params-only restore on a FRESH 2-process pod — new
    coordinator port, new processes — matching the saved weights exactly."""
    harness = ClusterHarness(2, ELASTIC_DRILL, timeout=300)
    ckpt = str(tmp_path / "ckpt")

    saved = harness.run("save", ckpt)
    for rc, out in saved:
        assert rc == 0, out
    for i, (_, out) in enumerate(saved):
        assert f"SHARDED p{i}" in out, out  # proper cross-process shard
        assert "UNSHARDED" not in out, out
        assert f"SAVED p{i}" in out and f"SHUTDOWN-OK p{i}" in out, out
    save_fps = _fingerprints(saved, 2)
    assert save_fps[0] == pytest.approx(save_fps[1], rel=1e-6)

    restored = harness.run("restore", ckpt)  # fresh pod, bumped port
    for rc, out in restored:
        assert rc == 0, out
    for i, (_, out) in enumerate(restored):
        assert f"SHARDED p{i}" in out, out
        assert f"RESTORED-PARAMS p{i}" in out, out
    restore_fps = _fingerprints(restored, 2)
    assert restore_fps[0] == pytest.approx(save_fps[0], rel=1e-6)
    assert restore_fps[1] == pytest.approx(save_fps[0], rel=1e-6)


# ---------------------------------------------------------------------------
# THE acceptance drill: kill-and-resume through the full product path
# (launch --supervise --pod 2 -> PodController -> distributed trainer ->
# multi-host Orbax checkpoint -> relaunch on a bumped port -> resume).
# ---------------------------------------------------------------------------


def test_elastic_pod_kill_and_resume(tmp_path):
    ckpt_dir = tmp_path / "ckpt"
    hb_dir = tmp_path / "hb"
    telemetry_dir = tmp_path / "telemetry"
    metrics_file = tmp_path / "metrics.jsonl"
    env = hermetic_env(REPO_ROOT)
    cmd = [
        sys.executable, "-m", "ditl_tpu.launch", "--supervise", "--pod", "2",
        "data.synthetic=true", "data.batch_size=4", "data.seq_len=32",
        "train.total_steps=8", "train.checkpoint_every=2",
        "train.max_restarts=2", "train.log_every=1", "train.warmup_steps=1",
        f"train.checkpoint_dir={ckpt_dir}",
        f"train.heartbeat_dir={hb_dir}",
        f"train.metrics_file={metrics_file}",
        f"train.telemetry_dir={telemetry_dir}",
        "train.fault_kill_step=6", "train.fault_kill_process=1",
        *_TINY_MODEL,
    ]
    # Own session: on timeout the WHOLE process group (launcher + both
    # training workers, across generations) is killed, so a wedged pod can
    # never outlive the test.
    proc = subprocess.Popen(
        cmd, stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
        env=env, cwd=REPO_ROOT, start_new_session=True,
    )
    try:
        stdout, stderr = proc.communicate(timeout=540)
    except subprocess.TimeoutExpired:
        os.killpg(proc.pid, signal.SIGKILL)
        stdout, stderr = proc.communicate(timeout=30)
        raise AssertionError(
            f"elastic pod drill wedged\nSTDOUT:\n{stdout[-2000:]}\n"
            f"STDERR:\n{stderr[-4000:]}"
        )
    assert proc.returncode == 0, stderr[-4000:]

    # Worker 1 really died by SIGKILL mid-training...
    assert "SIGKILLing self at step 6" in stderr
    # ...the controller saw it, tore down the wedged survivor, and
    # relaunched the FULL pod on a bumped coordinator port.
    assert "worker 1 died (signal SIGKILL)" in stderr, stderr[-4000:]
    assert re.search(r"RESTARTING \(.*bumping coordinator port", stderr)
    ports = re.findall(r"coordinator port (\d+)", stderr)
    assert len(set(ports)) == 2, f"expected 2 distinct pod ports, got {ports}"
    assert "pod-controller: RESTARTING -> LAUNCHING" in stderr
    assert "-> DONE (all workers exited 0)" in stderr

    # The relaunched pod resumed from the multi-host Orbax checkpoint —
    # params/opt state restored and the data iterator advanced, NOT a
    # restart from step 0.
    m = re.search(r"restored checkpoint: resuming from step (\d+)", stderr)
    assert m, stderr[-4000:]
    resume_step = int(m.group(1))
    assert resume_step in (2, 4, 6), resume_step  # committed save boundaries
    assert "batch offset" in stderr

    # Training completed to the target step with a finite loss.
    summary = json.loads(stdout.strip().splitlines()[-1])
    assert summary["steps"] == 8
    assert summary["final_loss"] == summary["final_loss"]  # not NaN

    # Loss continuity across the kill: the coordinator's JSONL metrics
    # stream (appended across generations) re-logs the replayed steps with
    # the SAME loss (deterministic resume from the restored state + data
    # position), covers every step to the end, and never goes non-finite.
    rows = [json.loads(ln) for ln in metrics_file.read_text().splitlines()]
    by_step: dict[int, list[float]] = {}
    for r in rows:
        by_step.setdefault(int(r["step"]), []).append(float(r["loss"]))
    assert max(by_step) == 7  # metrics log step is global_step - 1
    assert set(range(resume_step, 8)) <= set(by_step)
    for step, losses in by_step.items():
        for loss in losses:
            assert loss == loss and abs(loss) < 1e6, (step, losses)
        if len(losses) > 1:  # replayed step: gen-0 vs gen-1 must agree
            assert losses[0] == pytest.approx(losses[-1], abs=1e-3), (
                step, losses,
            )

    # Heartbeats were emitted by both workers of the final generation.
    for i in range(2):
        hb = read_heartbeat(heartbeat_path(str(hb_dir), i))
        assert hb is not None and hb["step"] >= 8, hb

    # ISSUE 3 acceptance: the controller merged every participant's journal
    # into one ordered pod timeline containing the SIGKILL, relaunch, and
    # resume events in causal order.
    from ditl_tpu.telemetry import read_journal

    timeline = read_journal(str(telemetry_dir / "pod_timeline.jsonl"))
    assert timeline, "pod timeline missing or empty"
    events = [(r["source"], r["event"]) for r in timeline]
    names = [e for _, e in events]
    i_kill = names.index("worker.sigkill_self")
    i_died = names.index("pod.worker_died")
    i_relaunch = names.index("pod.relaunch")
    i_resume = names.index("worker.resume")
    assert i_kill < i_died < i_relaunch < i_resume, events
    # the dying worker's own marker came from worker 1, the SIGKILL target
    assert timeline[i_kill]["source"] == "worker-1"
    assert timeline[i_kill]["step"] == 6
    assert timeline[i_died]["cause"] == "signal SIGKILL"
    # both generations spawned, resume landed at a committed boundary with
    # the lost-work span attributed
    assert names.count("pod.spawn") == 2
    assert timeline[i_resume]["step"] == resume_step
    assert timeline[i_resume]["lost_work_s"] >= 0
    assert names[-1] == "pod.done"
