"""Chaos-plane drill suite (ISSUE 5, ditl_tpu/chaos/).

Each fault class the plane can inject has a drill that (a) reproduces the
fault deterministically from a seed and (b) asserts the DEFINED survival
behavior — not just "it didn't crash":

- plane semantics: rule parsing, seeded determinism (journal-diff equal
  replay), trigger predicates, crash-survivable fire counts;
- data leg: producer-thread error propagation, hang -> DataStallError,
  silent batch corruption journaled;
- checkpoint leg: a save torn by an injected fault is quarantined on
  restore and training falls back to the newest VERIFIED step;
- serving leg: deadline expiry evicts queued/slotted requests with at most
  one chunk of overrun, HTTP 504s, client-disconnect cancels the in-flight
  generation, injected server errors answer clean 500s;
- elastic leg: slow-not-dead stragglers journaled and (optionally)
  escalated to relaunch;
- client leg: total_timeout_s bounds the retry wall clock; injected
  transport failures ride the real retry path;
- THE acceptance drill: kill -9 mid-checkpoint-save through the full
  product path (launch --supervise -> PodController -> trainer), resuming
  from the newest verified step with the torn dir quarantined and the
  journal showing inject -> death -> relaunch -> fallback-restore in
  causal order.
"""

from __future__ import annotations

import json
import os
import re
import signal
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

from ditl_tpu import chaos
from ditl_tpu.chaos import FaultPlane, FaultRule, InjectedFault, parse_rules
from ditl_tpu.telemetry.journal import EventJournal, read_journal

pytestmark = pytest.mark.chaos

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_TINY_MODEL = [
    "model.vocab_size=512", "model.hidden_size=32",
    "model.intermediate_size=64", "model.num_layers=2",
    "model.num_heads=2", "model.num_kv_heads=1", "model.head_dim=16",
    "model.max_seq_len=64",
]


@pytest.fixture(autouse=True)
def _disarm_after():
    yield
    chaos.disarm()


def _chaos_events(path: str) -> list[dict]:
    """The replay-comparable view of a journal: injection identities only
    (ts/pid/seq legitimately differ across runs)."""
    return [
        {k: r.get(k) for k in ("event", "site", "action", "call", "fired",
                               "step", "request")}
        for r in read_journal(path)
        if r.get("event") == "chaos.inject"
    ]


# ---------------------------------------------------------------------------
# Plane semantics
# ---------------------------------------------------------------------------


def test_parse_rules_roundtrip_and_rejection():
    rules = parse_rules(
        "ckpt.save:kill@step=4,max=1; data.batch:delay@p=0.25,delay=0.01"
    )
    assert rules == (
        FaultRule(site="ckpt.save", action="kill", at_step=4, max_count=1),
        FaultRule(site="data.batch", action="delay", p=0.25, delay_s=0.01),
    )
    with pytest.raises(ValueError, match="unknown chaos site"):
        parse_rules("no.such.site:error")
    with pytest.raises(ValueError, match="unknown chaos action"):
        parse_rules("data.batch:explode")
    with pytest.raises(ValueError, match="unknown chaos rule option"):
        parse_rules("data.batch:error@bogus=1")
    with pytest.raises(ValueError, match="site:action"):
        parse_rules("data.batch")
    # corrupt is seam-applied: a site that never applies it must reject
    # the rule, or the drill would journal injections that never happen.
    with pytest.raises(ValueError, match="not applied at site"):
        parse_rules("server.request:corrupt")
    # step= on a seam consulted without a step would silently never fire.
    with pytest.raises(ValueError, match="not consulted with a step"):
        parse_rules("data.batch:error@step=3")


def test_probability_triggers_replay_identically_per_seed():
    def fired_calls(seed):
        plane = FaultPlane(seed=seed, rules="data.batch:error@p=0.3")
        out = []
        for i in range(200):
            try:
                plane.check("data.batch", request=i)
            except InjectedFault:
                out.append(i)
        return out

    a, b, c = fired_calls(7), fired_calls(7), fired_calls(8)
    assert a == b and a  # identical sequence, and something fired
    assert a != c  # a different seed is a different sequence


def test_trigger_predicates_step_call_max():
    plane = FaultPlane(rules="engine.tick:error@step=3;data.batch:error@call=2,max=1")
    # at_step: only the consultation carrying step=3 fires.
    for s in (1, 2, 4):
        assert plane.check("engine.tick", step=s) is None
    with pytest.raises(InjectedFault):
        plane.check("engine.tick", step=3)
    # at_call + max: the SECOND consultation of the site fires, once ever.
    assert plane.check("data.batch") is None
    with pytest.raises(InjectedFault):
        plane.check("data.batch")
    assert plane.check("data.batch") is None
    # proc targeting: a rule for another process never fires here.
    plane2 = FaultPlane(rules="engine.tick:error@proc=1", process_id=0)
    assert plane2.check("engine.tick", step=1) is None


def test_handled_actions_are_returned_not_executed():
    plane = FaultPlane(rules="ckpt.save:kill@call=1")
    fault = plane.check("ckpt.save", step=2, handles=("kill",))
    assert fault is not None and fault.action == "kill"  # we are still alive
    # corrupt is ALWAYS returned for the site to apply.
    plane3 = FaultPlane(rules="data.batch:corrupt")
    assert plane3.check("data.batch").action == "corrupt"


def test_fire_state_persists_across_plane_restarts(tmp_path):
    """max=1 must hold across a relaunch: the plane persists fire counts
    BEFORE executing, so the kill it injects cannot re-fire after the
    supervisor brings the process back."""
    state = str(tmp_path / "chaos-state.json")
    p1 = FaultPlane(rules="ckpt.save:kill@max=1", state_path=state)
    assert p1.check("ckpt.save", handles=("kill",)).action == "kill"
    # "relaunched process": fresh plane, same state file -> already fired.
    p2 = FaultPlane(rules="ckpt.save:kill@max=1", state_path=state)
    assert p2.check("ckpt.save", handles=("kill",)) is None


def test_journals_diff_equal_across_replayed_runs(tmp_path):
    """The replay contract on a multi-site, multi-action sequence: same
    seed + same per-site call sequence -> identical chaos.inject stream."""
    spec = ("engine.tick:delay@p=0.3,delay=0.001;"
            "data.batch:error@p=0.25;"
            "server.request:delay@p=0.2,delay=0.0")

    def run(tag):
        journal = EventJournal(str(tmp_path / f"events-{tag}.jsonl"),
                               source=tag)
        plane = FaultPlane(seed=11, rules=spec, journal=journal)
        for i in range(1, 60):
            plane.check("engine.tick", step=i)
            try:
                plane.check("data.batch", request=i)
            except InjectedFault:
                pass
            plane.check("server.request")
        journal.close()
        return _chaos_events(str(tmp_path / f"events-{tag}.jsonl"))

    a, b = run("a"), run("b")
    assert a and a == b


_KILL_DRILL = """
import sys
from ditl_tpu.chaos import FaultPlane, InjectedFault
from ditl_tpu.telemetry.journal import EventJournal
j = EventJournal(sys.argv[1], source="drill")
plane = FaultPlane(seed=int(sys.argv[2]), rules=(
    "engine.tick:delay@p=0.4,delay=0.001;"
    "data.batch:error@p=0.3;"
    "server.request:kill@call=7"
), journal=j)
for i in range(1, 40):
    plane.check("engine.tick", step=i)
    try:
        plane.check("data.batch", request=i)
    except InjectedFault:
        pass
    plane.check("server.request")
raise SystemExit(3)  # unreachable: the kill rule must fire first
"""


def test_kill_drill_subprocess_replays_identically(tmp_path):
    """A drill that DIES by its own injected SIGKILL still replays: the
    journal (written line-buffered before the kill) is diff-equal across
    two runs of the same seed, and the death really was SIGKILL."""
    def run(tag):
        path = str(tmp_path / f"events-{tag}.jsonl")
        proc = subprocess.run(
            [sys.executable, "-c", _KILL_DRILL, path, "5"],
            cwd=REPO_ROOT, timeout=60,
            env={**os.environ,
                 "PYTHONPATH": REPO_ROOT + os.pathsep
                 + os.environ.get("PYTHONPATH", "")},
        )
        assert proc.returncode == -signal.SIGKILL
        return _chaos_events(path)

    a, b = run("a"), run("b")
    assert a == b
    assert a[-1]["site"] == "server.request" and a[-1]["action"] == "kill"


# ---------------------------------------------------------------------------
# Data leg
# ---------------------------------------------------------------------------


def _pipeline(**data_kw):
    from ditl_tpu.config import DataConfig, MeshConfig
    from ditl_tpu.data.dataset import load_text_dataset
    from ditl_tpu.data.loader import DataPipeline
    from ditl_tpu.data.tokenizer import get_tokenizer
    from ditl_tpu.runtime.mesh import build_mesh

    dcfg = DataConfig(synthetic=True, synthetic_examples=64, batch_size=8,
                      seq_len=32, prefetch=2, **data_kw)
    return DataPipeline(
        load_text_dataset(dcfg), get_tokenizer("byte"), dcfg,
        build_mesh(MeshConfig()),
    )


def test_data_error_fault_propagates_to_consumer():
    """A producer-thread fault must surface in the training loop, not end
    the epoch silently short (which would skew every step count)."""
    chaos.arm(FaultPlane(rules="data.batch:error@call=2"))
    pipe = _pipeline()
    it = pipe.epoch(0)
    next(it)  # batch 0 fine
    with pytest.raises(InjectedFault):
        for _ in it:
            pass


def test_data_hang_raises_data_stall_error():
    """An alive-but-hung producer raises no exception to propagate — the
    data-wait timeout converts the silence into a diagnosable error."""
    chaos.arm(FaultPlane(rules="data.batch:hang@call=2,hang=20"))
    pipe = _pipeline(data_wait_timeout_s=0.4)
    from ditl_tpu.data.loader import DataStallError

    it = pipe.epoch(0)
    next(it)
    t0 = time.monotonic()
    with pytest.raises(DataStallError, match="data_wait_timeout_s"):
        next(it)
    assert time.monotonic() - t0 < 5.0  # bounded, not the 20s hang
    it.close()


def test_data_corrupt_batch_is_zeroed_and_journaled(tmp_path):
    journal = EventJournal(str(tmp_path / "events-t.jsonl"), source="t")
    chaos.arm(FaultPlane(rules="data.batch:corrupt@call=2,max=1",
                         journal=journal))
    pipe = _pipeline()
    batches = []
    for i, b in enumerate(pipe.epoch(0)):
        batches.append(np.asarray(b["input_ids"]))
        if i >= 2:
            break
    assert batches[0].any()  # untouched batch has real tokens
    assert not batches[1].any()  # the corrupted batch is all zeros
    assert batches[2].any()
    events = _chaos_events(str(tmp_path / "events-t.jsonl"))
    assert [(e["site"], e["action"]) for e in events] == [
        ("data.batch", "corrupt")
    ]


# ---------------------------------------------------------------------------
# Checkpoint leg (in-process; the full product path is the multiproc drill)
# ---------------------------------------------------------------------------


def _tiny_state():
    import jax.numpy as jnp

    return {"params": {"w": jnp.arange(64, dtype=jnp.float32),
                       "b": jnp.ones((8,), jnp.float32)}}


def _ckpt_drill(root, journal) -> list[dict]:
    """save(2), save(4) with a corrupt fault torn into step 4, then a fresh
    manager restoring. Returns the merged event list."""
    from ditl_tpu.train.checkpoint import CheckpointManager, DataIterState

    import jax

    state = _tiny_state()
    mgr = CheckpointManager(str(root), save_every=2, max_to_keep=10,
                            journal=journal)
    mgr.save(2, state, DataIterState(global_step=2))
    mgr.save(4, state, DataIterState(global_step=4))
    mgr.wait()
    mgr.close()
    mgr2 = CheckpointManager(str(root), journal=journal)
    restored = mgr2.restore_latest(jax.eval_shape(lambda: state))
    mgr2.close()
    assert restored is not None
    _state, data_iter = restored
    assert data_iter.global_step == 2  # fell back past the torn step 4
    assert os.path.isdir(os.path.join(str(root), "quarantine", "4"))
    assert not os.path.exists(os.path.join(str(root), "4"))
    return read_journal(journal.path)


def test_ckpt_corrupt_fault_quarantines_and_falls_back(tmp_path):
    journal = EventJournal(str(tmp_path / "events-w.jsonl"), source="w")
    chaos.arm(FaultPlane(seed=1, rules="ckpt.save:corrupt@step=4,max=1",
                         journal=journal))
    events = _ckpt_drill(tmp_path / "ckpt", journal)
    names = [e["event"] for e in events]
    # Causal order: inject -> torn -> quarantine -> fallback restore.
    i_inject = names.index("chaos.inject")
    i_torn = names.index("checkpoint.torn")
    i_quar = names.index("checkpoint.quarantine")
    i_fall = names.index("checkpoint.fallback_restore")
    assert i_inject < i_torn < i_quar < i_fall, names
    assert events[i_fall]["step"] == 2
    assert events[i_quar]["step"] == 4


def test_ckpt_drill_replays_identically(tmp_path):
    """Acceptance: the same ChaosConfig seed reproduces the identical fault
    sequence (journal-diff equal) across two runs of the drill."""
    runs = []
    for tag in ("a", "b"):
        journal = EventJournal(str(tmp_path / f"events-{tag}.jsonl"),
                               source=tag)
        chaos.arm(FaultPlane(seed=9, rules="ckpt.save:corrupt@p=0.5",
                             journal=journal))
        try:
            _ckpt_drill(tmp_path / f"ckpt-{tag}", journal)
        except AssertionError:
            # p=0.5 may tear step 2 instead of 4 — the replay claim is
            # about the FAULT SEQUENCE, not which drill assertions hold.
            pass
        chaos.disarm()
        runs.append(_chaos_events(str(tmp_path / f"events-{tag}.jsonl")))
    assert runs[0] == runs[1] and runs[0]


# ---------------------------------------------------------------------------
# Serving leg: deadlines, cancellation, injected server faults
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def model_setup():
    import jax

    from ditl_tpu.config import ModelConfig
    from ditl_tpu.data.tokenizer import ByteTokenizer
    from ditl_tpu.models import llama

    cfg = ModelConfig(
        vocab_size=512, hidden_size=64, intermediate_size=128, num_layers=2,
        num_heads=4, num_kv_heads=2, head_dim=16, max_seq_len=128,
        dtype="float32", param_dtype="float32",
    )
    params = llama.init_params(jax.random.key(0), cfg)
    return params, cfg, ByteTokenizer()


def _engine(model_setup, **kw):
    from ditl_tpu.infer.continuous import ContinuousEngine
    from ditl_tpu.infer.engine import GenerateConfig

    params, cfg, tok = model_setup
    gen = GenerateConfig(max_new_tokens=kw.pop("max_new_tokens", 8))
    return ContinuousEngine(params, cfg, tok, gen=gen, **kw)


def test_queued_deadline_expires_without_consuming_ticks(model_setup):
    """An expired queued request must cost ZERO device work: the engine
    runs the exact same number of ticks as if it was never submitted."""
    _params, _cfg, tok = model_setup
    prompt = [tok.bos_id] + tok.encode("hello world")

    ref = _engine(model_setup, n_slots=1, decode_chunk=4)
    ref.submit(list(prompt))
    ref.run()
    ref_ticks = ref.tick_count

    eng = _engine(model_setup, n_slots=1, decode_chunk=4)
    a = eng.submit(list(prompt))
    b = eng.submit([tok.bos_id] + tok.encode("doomed"), deadline_s=0.0)
    while eng.pending:
        eng.step()
    req_b = eng._completed[b]
    assert req_b.expired and req_b.finished and req_b.tokens == []
    assert req_b.slot is None  # never admitted
    assert eng._completed[a].tokens  # the live request completed normally
    assert eng.tick_count == ref_ticks  # zero extra ticks for the corpse
    assert eng.metrics.deadline_expired.value == 1
    assert "ditl_serving_deadline_expired_total 1" in eng.metrics.render()


def test_slot_deadline_evicts_within_one_chunk(model_setup):
    """A request whose deadline passes mid-flight is evicted at the next
    tick: at most ONE decode chunk of overrun, then the slot frees."""
    _params, _cfg, tok = model_setup
    eng = _engine(model_setup, n_slots=1, decode_chunk=2, max_new_tokens=40)
    rid = eng.submit([tok.bos_id] + tok.encode("hi"), deadline_s=0.05)
    eng.step()  # admit + first chunk (compile dominates: deadline passes)
    time.sleep(0.06)
    eng.step()  # the sweep evicts BEFORE dispatching another chunk
    req = eng._completed[rid]
    assert req.expired
    assert len(req.tokens) <= eng.decode_chunk  # <= one chunk of overrun
    assert eng._slots == [None] and eng.pending == 0
    assert eng.metrics.deadline_expired.value == 1


@pytest.fixture(scope="module")
def served(model_setup):
    import threading as _threading

    from ditl_tpu.infer.continuous import ContinuousEngine, ThreadedEngine
    from ditl_tpu.infer.engine import GenerateConfig, Generator
    from ditl_tpu.infer.server import make_server

    params, cfg, tok = model_setup
    engine = ContinuousEngine(
        params, cfg, tok, n_slots=4, decode_chunk=2,
        gen=GenerateConfig(max_new_tokens=64),
    )
    threaded = ThreadedEngine(engine)
    server = make_server(
        Generator(params, cfg, tok), host="127.0.0.1", port=0,
        threaded_engine=threaded, default_max_tokens=64,
    )
    _threading.Thread(target=server.serve_forever, daemon=True).start()
    yield server, threaded, engine, server.server_address[1]
    server.shutdown()
    threaded.close()


def _post(port, body, headers=None, timeout=120):
    import urllib.error
    import urllib.request

    req = urllib.request.Request(
        f"http://127.0.0.1:{port}/v1/completions",
        data=json.dumps(body).encode(),
        headers={"Content-Type": "application/json", **(headers or {})},
    )
    try:
        with urllib.request.urlopen(req, timeout=timeout) as resp:
            return resp.status, json.loads(resp.read())
    except urllib.error.HTTPError as e:
        return e.code, json.loads(e.read())


def test_http_deadline_maps_to_504(served):
    # Deadline 5 ms: positive (so it passes the server's instant-expiry
    # check and reaches the ENGINE's eviction sweep) but far below the
    # ~30 ticks the 60-token budget needs at decode_chunk=2 — the old
    # 30 ms deadline sat exactly at a warm host's completion time, so a
    # fast run legitimately finished inside the window and flaked this
    # assert.
    _server, _threaded, engine, port = served
    before = engine.metrics.deadline_expired.value
    status, out = _post(port, {"prompt": "hello", "max_tokens": 60,
                               "deadline_s": 0.005})
    assert status == 504, out
    assert out["error"]["type"] == "timeout_error"
    assert engine.metrics.deadline_expired.value >= before + 1
    # The gateway's header spelling reaches the same eviction path.
    status, out = _post(port, {"prompt": "hello", "max_tokens": 60},
                        headers={"X-Request-Deadline-S": "0.005"})
    assert status == 504, out
    # Garbage deadline is a client error, already-expired is an instant 504.
    status, _ = _post(port, {"prompt": "x", "deadline_s": "soon"})
    assert status == 400
    status, _ = _post(port, {"prompt": "x", "deadline_s": -1})
    assert status == 504


def test_stream_client_disconnect_cancels_generation(served):
    """A client that vanishes mid-stream must free its slot (cancel, not
    decode to the token budget) and move the dedicated counter."""
    import socket

    _server, _threaded, engine, port = served
    before = engine.metrics.client_disconnects.value
    body = json.dumps({"prompt": "hello", "max_tokens": 64,
                       "stream": True}).encode()
    sock = socket.create_connection(("127.0.0.1", port), timeout=30)
    sock.sendall(
        b"POST /v1/completions HTTP/1.1\r\nHost: t\r\n"
        b"Content-Type: application/json\r\n"
        b"Content-Length: " + str(len(body)).encode() + b"\r\n\r\n" + body
    )
    data = b""
    while b"data:" not in data:  # the stream is really flowing
        chunk = sock.recv(512)
        assert chunk, data
        data += chunk
    sock.close()  # vanish mid-stream
    deadline = time.monotonic() + 15
    while time.monotonic() < deadline:
        if (engine.metrics.client_disconnects.value > before
                and all(r is None for r in engine._slots)):
            break
        time.sleep(0.05)
    assert engine.metrics.client_disconnects.value == before + 1
    assert all(r is None for r in engine._slots)  # slot freed by cancel


def test_server_chaos_error_answers_500(served):
    _server, _threaded, _engine, port = served
    chaos.arm(FaultPlane(rules="server.request:error@max=1"))
    status, out = _post(port, {"prompt": "hello", "max_tokens": 4})
    assert status == 500 and "chaos" in out["error"]["message"]
    # The rule is exhausted (max=1): the next request serves normally.
    status, out = _post(port, {"prompt": "hello", "max_tokens": 4})
    assert status == 200 and out["choices"][0]["text"] is not None


# ---------------------------------------------------------------------------
# Elastic leg: straggler escalation
# ---------------------------------------------------------------------------


def _sleeper_cmd(*_args):
    return [sys.executable, "-c", "import time; time.sleep(300)"]


def _beat_later(hb_dir, beats, delay=0.3):
    from ditl_tpu.runtime.elastic import emit_heartbeat

    def run():
        time.sleep(delay)  # after _spawn's stale-heartbeat sweep
        for worker, step in beats:
            emit_heartbeat(hb_dir, worker, step)

    t = threading.Thread(target=run, daemon=True)
    t.start()
    return t


def test_straggler_is_journaled_once_log_only(tmp_path):
    from ditl_tpu.runtime.elastic import PodController, PodState
    from ditl_tpu.telemetry.journal import controller_journal_path

    hb = str(tmp_path / "hb")
    jdir = str(tmp_path / "journal")
    _beat_later(hb, [(0, 10), (1, 2)])
    ctl = PodController(
        2, lambda i, n, port, a: _sleeper_cmd(),
        heartbeat_dir=hb, straggler_lag_steps=3, poll_s=0.05, grace_s=1,
        journal_dir=jdir,
    )
    result = ctl.run(timeout_s=3)  # log-only: the run ends by deadline
    assert result.state is PodState.FAILED
    assert not any("straggling" in t for t in result.transitions)
    stragglers = [e for e in read_journal(controller_journal_path(jdir))
                  if e["event"] == "pod.straggler"]
    assert len(stragglers) == 1  # flagged ONCE, not per poll
    assert stragglers[0]["worker"] == 1
    assert stragglers[0]["lag"] == 4 and stragglers[0]["median"] == 6
    assert stragglers[0]["escalate"] is False


def test_straggler_escalates_to_relaunch(tmp_path):
    from ditl_tpu.runtime.elastic import PodController, PodState
    from ditl_tpu.telemetry.journal import controller_journal_path

    hb = str(tmp_path / "hb")
    jdir = str(tmp_path / "journal")
    _beat_later(hb, [(0, 10), (1, 2)])
    ctl = PodController(
        2, lambda i, n, port, a: _sleeper_cmd(),
        heartbeat_dir=hb, straggler_lag_steps=3, straggler_relaunch=True,
        max_pod_restarts=0, poll_s=0.05, grace_s=1, journal_dir=jdir,
    )
    t0 = time.monotonic()
    result = ctl.run(timeout_s=30)
    assert result.state is PodState.FAILED
    assert time.monotonic() - t0 < 20  # escalated, not deadline-waited
    assert any("worker 1 straggling" in t for t in result.transitions), (
        result.transitions
    )
    events = read_journal(controller_journal_path(jdir))
    names = [e["event"] for e in events]
    assert "pod.straggler" in names and "pod.teardown" in names
    assert names.index("pod.straggler") < names.index("pod.teardown")


# ---------------------------------------------------------------------------
# Client leg
# ---------------------------------------------------------------------------


def test_client_total_timeout_bounds_retry_wall_time():
    from ditl_tpu.client.llm import (
        ERROR_SENTINEL, LLMClient, client_metrics,
    )
    from ditl_tpu.config import APIConfig

    attempts = []

    def transport(url, headers, body, timeout):
        attempts.append(timeout)
        raise OSError("endpoint down")

    cfg = APIConfig(total_timeout_s=0.5, timeout_s=30.0, max_retries=1000,
                    backoff_base_s=0.02, backoff_max_s=0.05)
    before = client_metrics.deadline_exhausted.value
    t0 = time.monotonic()
    out = LLMClient(cfg, transport=transport).complete("hi")
    dt = time.monotonic() - t0
    assert out == ERROR_SENTINEL  # still a total function
    assert dt < 3.0  # bounded — NOT max_retries x (timeout + backoff)
    assert client_metrics.deadline_exhausted.value == before + 1
    assert attempts and all(t <= 0.5 + 1e-6 for t in attempts[1:]), (
        "per-attempt timeouts must clamp to the remaining budget"
    )


def test_client_chaos_transport_error_rides_retry_path():
    from ditl_tpu.client.llm import LLMClient, client_metrics
    from ditl_tpu.config import APIConfig

    chaos.arm(FaultPlane(rules="client.request:error@max=2"))
    ok_body = json.dumps({
        "choices": [{"message": {"content": "recovered"}}]
    }).encode()

    def transport(url, headers, body, timeout):
        return 200, {}, ok_body

    before = client_metrics.retries.value
    cfg = APIConfig(max_retries=5, backoff_base_s=0.01, backoff_max_s=0.02)
    out = LLMClient(cfg, transport=transport).complete("hi")
    assert out == "recovered"  # survived 2 injected transport failures
    assert client_metrics.retries.value == before + 2


# ---------------------------------------------------------------------------
# THE acceptance drill: kill -9 mid-checkpoint-save through the product path
# ---------------------------------------------------------------------------


@pytest.mark.multiproc
def test_chaos_kill_mid_save_resumes_from_verified_step(tmp_path):
    from tests.cluster_harness import hermetic_env

    ckpt_dir = tmp_path / "ckpt"
    telemetry_dir = tmp_path / "telemetry"
    cmd = [
        sys.executable, "-m", "ditl_tpu.launch", "--supervise",
        # No persistent compile cache: XLA:CPU intermittently SIGSEGVs
        # deserializing cached executables in a relaunched process
        # (troubleshooting §20) — that known crash must not alias the
        # fault this drill injects on purpose.
        "runtime.compile_cache=false",
        "data.synthetic=true", "data.batch_size=4", "data.seq_len=32",
        "train.total_steps=8", "train.checkpoint_every=2",
        "train.max_restarts=1", "train.log_every=1", "train.warmup_steps=1",
        f"train.checkpoint_dir={ckpt_dir}",
        f"train.telemetry_dir={telemetry_dir}",
        "chaos.rules=ckpt.save:kill@step=4,max=1", "chaos.seed=0",
        *_TINY_MODEL,
    ]
    proc = subprocess.Popen(
        cmd, stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
        env=hermetic_env(REPO_ROOT), cwd=REPO_ROOT, start_new_session=True,
    )
    try:
        stdout, stderr = proc.communicate(timeout=480)
    except subprocess.TimeoutExpired:
        os.killpg(proc.pid, signal.SIGKILL)
        stdout, stderr = proc.communicate(timeout=30)
        raise AssertionError(
            f"chaos kill-mid-save drill wedged\nSTDOUT:\n{stdout[-2000:]}\n"
            f"STDERR:\n{stderr[-4000:]}"
        )
    assert proc.returncode == 0, stderr[-4000:]

    # The injected SIGKILL really landed mid-save and the supervisor saw it.
    assert "worker 0 died (signal SIGKILL)" in stderr, stderr[-4000:]
    # The relaunched run fell back PAST the torn step 4 to verified step 2
    # (fault_kill at the step-4 save tears that step's files after commit).
    m = re.search(r"restored checkpoint: resuming from step (\d+)", stderr)
    assert m and int(m.group(1)) == 2, stderr[-4000:]
    # Zero manual cleanup: the torn step dir was quarantined, the newest
    # verified step survived, and training completed to the target.
    qdir = ckpt_dir / "quarantine"
    assert qdir.is_dir() and any(
        name == "4" or name.startswith("4.")
        for name in os.listdir(qdir)
    ), list(os.listdir(qdir)) if qdir.is_dir() else "no quarantine dir"
    summary = json.loads(stdout.strip().splitlines()[-1])
    assert summary["steps"] == 8
    # The resumed run re-saved step 4 legitimately (the kill rule's max=1
    # survived the relaunch): the NEW step-4 dir verifies clean.
    if (ckpt_dir / "4").exists():
        from ditl_tpu.train.checkpoint import CheckpointManager

        mgr = CheckpointManager(str(ckpt_dir))
        assert mgr.verify_step(4) == "verified"
        mgr.close()

    # The merged pod timeline shows the whole causal chain:
    # inject -> death -> relaunch -> fallback-restore -> resume.
    timeline = read_journal(str(telemetry_dir / "pod_timeline.jsonl"))
    names = [r["event"] for r in timeline]
    i_inject = names.index("chaos.inject")
    i_died = names.index("pod.worker_died")
    i_relaunch = names.index("pod.relaunch")
    i_fallback = names.index("checkpoint.fallback_restore")
    i_resume = names.index("worker.resume")
    assert i_inject < i_died < i_relaunch < i_fallback < i_resume, names
    assert timeline[i_inject]["site"] == "ckpt.save"
    assert timeline[i_inject]["action"] == "kill"
    assert timeline[i_inject]["step"] == 4
    assert timeline[i_died]["cause"] == "signal SIGKILL"
    assert timeline[i_fallback]["step"] == 2
    assert timeline[i_resume]["step"] == 2
    # The max=1 cap survived the kill (persisted fire state): the resumed
    # generation saved step 4 again WITHOUT re-firing, and completed.
    assert names.count("chaos.inject") == 1
    assert names[-1] == "pod.done"
