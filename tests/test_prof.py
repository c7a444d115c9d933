"""Continuous profiling & stall attribution drills (ISSUE 18,
telemetry/prof.py).

The claims under test, most expensive to get wrong first:

- **THE stall drill** — a chaos-injected ``loop.block`` delay (1.5 s)
  under open SSE streams must produce exactly ONE ``loop.stall``
  incident bundle whose convicting stack names the injected site's
  file:line inside evloop.py, with a visible lag-histogram excursion;
  the chaos-free control run must produce ZERO stalls and ZERO bundles.
- **False-positive pin** — a loop parked idle at the stall threshold is
  HEALTHY: zero stalls, and ``lag_p95()`` is None (absent != 0).
- **Bounded memory** — the sampler's collapsed-stack map is hard-capped
  at ``max_stacks`` with oldest-first eviction; a stack that keeps
  firing is never the one dropped.
- **Conviction unit** — a thread that stamps busy and then blocks in a
  named function gets that function's frame as the stall's fingerprint.
- **Phase attribution** — samples taken while the armed thread has a
  phase set name real frames (the trainer's ``host_dispatch`` story).
- **/profile endpoint** — a live evloop gateway answers
  ``/profile?seconds=N`` with parseable collapsed stacks under load.
- **Exports & CLI** — collapsed text round-trips ``parse_collapsed``,
  renders to a Chrome trace, and the ``python -m ditl_tpu.telemetry.prof``
  post-processor handles the happy path and both error exits.
- **The overhead gate** — ``prof_vs_off_rps_ratio`` is gated by
  perf_compare at its 15% noise floor: a halved ratio regresses, a
  within-floor wobble compares clean.
- **Import layering** — prof.py must import without jax (subprocess
  pin, same discipline as the rest of ditl_tpu/telemetry)."""

from __future__ import annotations

import json
import os
import socket
import subprocess
import sys
import threading
import time

import pytest

from ditl_tpu.telemetry.prof import (
    DEFAULT_HZ, LoopHeartbeat, LoopWatchdog, SamplingProfiler,
    active_profiler, collapsed_to_chrome, main as prof_main,
    parse_collapsed, profile_for, top_frames,
)
from ditl_tpu.telemetry.registry import MetricsRegistry

pytestmark = pytest.mark.prof

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ---------------------------------------------------------------------------
# import layering
# ---------------------------------------------------------------------------


def test_prof_imports_without_jax():
    """prof.py is stdlib-only on import: the watchdog and /profile must
    be available in processes that never load jax (gateway, CLI)."""
    code = (
        "import sys\n"
        "import ditl_tpu.telemetry.prof\n"
        "assert 'jax' not in sys.modules, 'prof import pulled in jax'\n"
    )
    subprocess.run([sys.executable, "-c", code], check=True, cwd=REPO_ROOT,
                   timeout=60)


# ---------------------------------------------------------------------------
# sampler units
# ---------------------------------------------------------------------------


def test_sampler_rejects_bad_config():
    with pytest.raises(ValueError):
        SamplingProfiler(hz=0)
    with pytest.raises(ValueError):
        SamplingProfiler(hz=-5)
    with pytest.raises(ValueError):
        SamplingProfiler(max_stacks=0)


def test_sampler_bounded_memory_oldest_first_eviction():
    """The hard invariant: never more than max_stacks distinct stacks,
    evictions counted, and recency (not insertion) decides the victim."""
    p = SamplingProfiler(hz=10, max_stacks=4)
    keys = [f"main;f{i} (x.py:{i})" for i in range(10)]
    for k in keys:
        p._note(p._stacks, k)
    assert len(p._stacks) == 4
    assert p.evicted == 6
    assert list(p._stacks) == keys[6:]  # oldest-first: the last 4 survive
    # a re-hit increments and refreshes recency without evicting
    p._note(p._stacks, keys[6])
    assert p._stacks[keys[6]] == 2
    assert list(p._stacks)[-1] == keys[6]
    assert p.evicted == 6
    # the refreshed stack survives the next two inserts; the stale ones go
    p._note(p._stacks, "main;new1 (y.py:1)")
    p._note(p._stacks, "main;new2 (y.py:2)")
    assert keys[6] in p._stacks
    assert keys[7] not in p._stacks and keys[8] not in p._stacks


def _spin_here(done: threading.Event) -> None:
    while not done.is_set():
        sum(i * i for i in range(200))


def test_sampler_live_smoke_and_registry_mirror():
    """A busy named thread shows up in collapsed output; the registry
    mirror tracks samples; active_profiler() registers/unregisters."""
    reg = MetricsRegistry()
    done = threading.Event()
    t = threading.Thread(target=_spin_here, args=(done,),
                         name="prof-spin", daemon=True)
    p = SamplingProfiler(hz=500, max_stacks=256, registry=reg)
    assert active_profiler() is not p
    p.start()
    t.start()
    try:
        deadline = time.monotonic() + 5.0
        while time.monotonic() < deadline and p.samples < 20:
            time.sleep(0.02)
        assert active_profiler() is p
    finally:
        done.set()
        t.join(timeout=5.0)
        p.stop()
    assert active_profiler() is not p
    assert p.samples >= 20
    text = p.collapsed()
    parsed = parse_collapsed(text)
    assert parsed == p.snapshot()
    assert any("_spin_here" in stack for stack in parsed)
    # the /metrics mirror saw the same world
    assert reg.counter("ditl_prof_samples").value == p.samples
    assert reg.gauge("ditl_prof_stacks").value == float(len(p.snapshot()))


def test_profile_for_transient_capture():
    text = profile_for(0.2, hz=200)
    stacks = parse_collapsed(text)
    assert stacks
    # the calling thread was parked inside profile_for the whole time
    assert any("profile_for" in s for s in stacks)


def _dispatch_spin(done: threading.Event) -> None:
    while not done.is_set():
        sum(range(500))


def test_phase_attribution_names_real_frames():
    p = SamplingProfiler(hz=500, max_stacks=256)
    done = threading.Event()

    def worker():
        p.arm_phases()
        p.set_phase("host_dispatch")
        try:
            _dispatch_spin(done)
        finally:
            p.set_phase(None)

    t = threading.Thread(target=worker, name="phase-worker", daemon=True)
    p.start()
    t.start()
    try:
        deadline = time.monotonic() + 5.0
        while (time.monotonic() < deadline
               and not p.phase_top("host_dispatch", 1)):
            time.sleep(0.02)
    finally:
        done.set()
        t.join(timeout=5.0)
        p.stop()
    frames = p.phase_top("host_dispatch", 5)
    assert frames, "no samples attributed to the armed phase"
    assert all(row["samples"] > 0 for row in frames)
    assert any("_dispatch_spin" in row["frame"] for row in frames)
    # an unknown phase has no bucket
    assert p.phase_top("nonexistent") == []


# ---------------------------------------------------------------------------
# collapsed-stack exports + CLI
# ---------------------------------------------------------------------------


def test_collapsed_roundtrip_top_frames_and_chrome():
    stacks = {
        "main;run (a.py:1);step (a.py:9)": 7,
        "worker-1;poll (b.py:3)": 3,
        "main;run (a.py:1);flush (a.py:12)": 2,
    }
    text = "\n".join(f"{k} {v}" for k, v in stacks.items())
    assert parse_collapsed(text) == stacks
    assert parse_collapsed("garbage line\n\n" + text) == stacks
    tops = top_frames(stacks, 2)
    assert tops[0] == {"frame": "step (a.py:9)", "samples": 7}
    assert tops[1] == {"frame": "poll (b.py:3)", "samples": 3}
    trace = collapsed_to_chrome(stacks, hz=100.0)
    events = trace["traceEvents"]
    assert events
    spans = [e for e in events if e.get("ph") == "X"]
    assert len(spans) == len(stacks)
    # span duration is the stack's sampled share of the wall: count / hz
    by_name = {e["name"]: e for e in spans}
    assert by_name["step (a.py:9)"]["dur"] == pytest.approx(
        7 / 100.0 * 1e6)


def test_cli_top_chrome_and_error_exits(tmp_path, capsys):
    src = tmp_path / "profile.txt"
    src.write_text("main;f (x.py:1) 5\nmain;g (x.py:2) 3\n")
    assert prof_main(["--collapse", str(src), "--top", "2"]) == 0
    out = capsys.readouterr().out
    assert "8 samples, 2 distinct stacks" in out
    assert "f (x.py:1)" in out
    chrome = tmp_path / "out.json"
    assert prof_main(["--collapse", str(src),
                      "--chrome", str(chrome)]) == 0
    data = json.loads(chrome.read_text())
    assert data["traceEvents"]
    # missing input file and empty input both exit 2
    assert prof_main(["--collapse", str(tmp_path / "missing.txt")]) == 2
    empty = tmp_path / "empty.txt"
    empty.write_text("\n")
    assert prof_main(["--collapse", str(empty)]) == 2


# ---------------------------------------------------------------------------
# heartbeat + watchdog units
# ---------------------------------------------------------------------------


def test_watchdog_idle_loop_is_not_a_stall():
    """THE false-positive pin: a loop parked in select at (or far past)
    the threshold is healthy. Zero stalls, and lag_p95() is None — absent
    means 'never busy-sampled', never 0."""
    reg = MetricsRegistry()
    hb = LoopHeartbeat()
    hb.attach()  # stamps idle
    wd = LoopWatchdog(hb, threshold_s=0.05, registry=reg).start()
    try:
        time.sleep(0.3)  # 6x the threshold, parked the whole time
    finally:
        wd.stop()
    assert wd.stalls == 0
    assert wd.last_stall is None
    assert wd.lag_p95() is None


def test_watchdog_rejects_bad_threshold():
    with pytest.raises(ValueError):
        LoopWatchdog(LoopHeartbeat(), threshold_s=0.0)


def _block_here() -> None:
    time.sleep(0.4)


def test_watchdog_convicts_blocking_frame():
    """A thread that stamps busy and then blocks in a named function is
    convicted with that function's frame — once, with the frame as the
    incident fingerprint."""
    reg = MetricsRegistry()
    hb = LoopHeartbeat()
    journaled: list[dict] = []

    class _Journal:
        def event(self, kind, **detail):
            journaled.append({"kind": kind, **detail})

    finished = threading.Event()

    def fake_loop():
        hb.attach()
        hb.busy()
        _block_here()
        hb.idle()
        finished.set()

    wd = LoopWatchdog(hb, threshold_s=0.05, burst_hz=500, registry=reg,
                      journal=_Journal()).start()
    t = threading.Thread(target=fake_loop, name="fake-loop", daemon=True)
    t.start()
    try:
        assert finished.wait(10.0)
        time.sleep(0.1)  # let the watchdog finish its report
    finally:
        wd.stop()
        t.join(timeout=5.0)
    assert wd.stalls == 1
    detail = wd.last_stall
    assert detail["frame"].startswith("_block_here")
    assert "test_prof.py" in detail["frame"]
    assert "_block_here" in detail["stack"]
    assert detail["fingerprint_key"] == detail["frame"]
    assert detail["burst_samples"] > 0
    assert detail["modal_samples"] > 0
    assert detail["duration_s"] >= 0.05
    assert wd.lag_p95() is not None and wd.lag_p95() > 0
    assert reg.counter("ditl_loop_stalls").value == 1
    assert [j["kind"] for j in journaled] == ["loop.stall"]
    assert journaled[0]["frame"] == detail["frame"]


# ---------------------------------------------------------------------------
# live-gateway drills (THE stall drill + /profile endpoint)
# ---------------------------------------------------------------------------


def _sse_fleet(n=2):
    from ditl_tpu.gateway import Fleet, InProcessReplica
    from tests.gateway_drivers import _SelectorSSEStub

    fleet = Fleet([InProcessReplica(f"s{i}", _SelectorSSEStub)
                   for i in range(n)])
    fleet.start_all()
    for rid in fleet.ids:
        assert fleet.probe(rid, timeout=5.0)
    return fleet


def _http_get(port: int, path: str, timeout: float = 15.0):
    with socket.create_connection(("127.0.0.1", port),
                                  timeout=timeout) as s:
        s.sendall(f"GET {path} HTTP/1.1\r\nHost: gw\r\n"
                  f"Connection: close\r\n\r\n".encode())
        chunks = []
        while True:
            c = s.recv(65536)
            if not c:
                break
            chunks.append(c)
    raw = b"".join(chunks)
    head, _, body = raw.partition(b"\r\n\r\n")
    status = int(head.split(None, 2)[1])
    return status, body


@pytest.mark.gateway
@pytest.mark.chaos
@pytest.mark.incident
def test_loop_stall_drill_convicts_injected_site(tmp_path):
    """THE drill: a 1.5 s chaos block inside the loop's tick callback,
    under open SSE streams -> exactly ONE loop.stall whose convicting
    stack names the injected site inside evloop.py, chaos-attributed in
    the bundle manifest, with the lag excursion on /health. Then the
    control leg: a chaos-free gateway under the same watchdog config
    produces ZERO stalls and ZERO bundles. The threshold is 0.5 s, three
    times under the block and well over what a busy machine's scheduler
    does to an idle loop on its own (at 0.1 s a whole tier-1 run counted
    a second, organic stall: PR 28)."""
    from ditl_tpu.chaos import FaultPlane, arm, disarm
    from ditl_tpu.config import GatewayConfig, TelemetryConfig
    from ditl_tpu.gateway import GatewayMetrics, make_gateway
    from ditl_tpu.telemetry.incident import IncidentManager, list_bundles
    from tests.gateway_drivers import hold_open_sse_streams

    inc_dir = str(tmp_path / "incidents")
    incidents = IncidentManager(inc_dir, source="gateway")
    fleet = _sse_fleet(n=2)
    server = make_gateway(
        fleet, config=GatewayConfig(), metrics=GatewayMetrics(), port=0,
        telemetry=TelemetryConfig(loop_stall_threshold_s=0.5,
                                  loop_stall_burst_hz=500.0),
        incidents=incidents)
    assert server.watchdog is not None
    threading.Thread(target=server.serve_forever, daemon=True,
                     name="gw-loop").start()
    port = server.server_address[1]
    socks: list = []
    try:
        socks, opened = hold_open_sse_streams(port, 20)
        assert opened == 20
        # the block must land UNDER the open streams: arm one delay, then
        # poke the loop so a tick fires with the fault armed
        arm(FaultPlane(seed=1, rules="loop.block:delay@delay=1.5,max=1"))
        try:
            status, body = _http_get(port, "/health")
            assert status == 200
            deadline = time.monotonic() + 10.0
            while (time.monotonic() < deadline
                   and (server.watchdog.stalls < 1
                        or incidents.created < 1)):
                time.sleep(0.05)
        finally:
            disarm()
        assert server.watchdog.stalls == 1
        detail = server.watchdog.last_stall
        # the convicting stack names the injected site's file inside the
        # loop's tick callback — the exact place `loop.block` lives
        assert "_tick (evloop.py:" in detail["stack"]
        assert detail["duration_s"] >= 0.05
        lag = server.watchdog.lag_p95()
        assert lag is not None and lag > 0
        bundles = list_bundles(inc_dir)
        assert len(bundles) == 1
        manifest = bundles[0]
        assert manifest["trigger"] == "loop.stall"
        assert "_tick (evloop.py:" in manifest["detail"]["stack"]
        assert manifest["detail"]["fingerprint_key"] == detail["frame"]
        # chaos attribution: the bundle reads as injected, not organic
        assert manifest.get("injected_fault", {}).get("injected")
        # the lag excursion is visible where the planner looks
        status, body = _http_get(port, "/health")
        assert status == 200
        payload = json.loads(body)
        assert payload.get("loop_lag_p95_s", 0) > 0
    finally:
        disarm()
        for s in socks:
            try:
                s.close()
            except OSError:
                pass
        server.shutdown()
        server.server_close()
        fleet.stop_all(drain=False)

    # -- control leg: same watchdog config, no chaos, zero stalls -------
    ctl_dir = str(tmp_path / "incidents-control")
    ctl_inc = IncidentManager(ctl_dir, source="gateway")
    fleet = _sse_fleet(n=2)
    server = make_gateway(
        fleet, config=GatewayConfig(), metrics=GatewayMetrics(), port=0,
        telemetry=TelemetryConfig(loop_stall_threshold_s=0.5,
                                  loop_stall_burst_hz=500.0),
        incidents=ctl_inc)
    threading.Thread(target=server.serve_forever, daemon=True,
                     name="gw-loop-ctl").start()
    port = server.server_address[1]
    socks = []
    try:
        socks, opened = hold_open_sse_streams(port, 10)
        assert opened == 10
        for _ in range(5):
            status, _body = _http_get(port, "/health")
            assert status == 200
            time.sleep(0.1)
        assert server.watchdog.stalls == 0
        assert ctl_inc.created == 0
        assert list_bundles(ctl_dir) == []
    finally:
        for s in socks:
            try:
                s.close()
            except OSError:
                pass
        server.shutdown()
        server.server_close()
        fleet.stop_all(drain=False)


@pytest.mark.gateway
def test_gateway_profile_endpoint_under_load(tmp_path):
    """/profile?seconds=N on a live evloop gateway returns parseable,
    non-empty collapsed stacks while streams are held; bad seconds is a
    400, not a stack trace."""
    from ditl_tpu.config import GatewayConfig
    from ditl_tpu.gateway import GatewayMetrics, make_gateway
    from tests.gateway_drivers import hold_open_sse_streams

    fleet = _sse_fleet(n=1)
    server = make_gateway(fleet, config=GatewayConfig(),
                          metrics=GatewayMetrics(), port=0)
    threading.Thread(target=server.serve_forever, daemon=True,
                     name="gw-loop").start()
    port = server.server_address[1]
    socks: list = []
    try:
        socks, opened = hold_open_sse_streams(port, 5)
        assert opened == 5
        status, body = _http_get(port, "/profile?seconds=0.5")
        assert status == 200
        stacks = parse_collapsed(body.decode())
        assert stacks, "profile endpoint returned no stacks"
        # the loop thread itself is one of the sampled threads
        assert any("serve_forever" in s or "select" in s for s in stacks)
        status, _body = _http_get(port, "/profile?seconds=nope")
        assert status == 400
    finally:
        for s in socks:
            try:
                s.close()
            except OSError:
                pass
        server.shutdown()
        server.server_close()
        fleet.stop_all(drain=False)


# ---------------------------------------------------------------------------
# trainer attribution (the armed sampler names host_dispatch frames)
# ---------------------------------------------------------------------------


def test_trainer_armed_sampler_attributes_host_dispatch(tmp_path):
    """telemetry.prof_hz > 0 arms a sampler around the step loop: the run
    summary carries the profile block and StepAnatomy's host_dispatch
    gains at least one real sampled frame."""
    from ditl_tpu.config import (
        Config, DataConfig, ModelConfig, TelemetryConfig, TrainConfig,
    )
    from ditl_tpu.train.trainer import train

    cfg = Config(
        model=ModelConfig(vocab_size=512, hidden_size=64,
                          intermediate_size=128, num_layers=2, num_heads=4,
                          num_kv_heads=2, head_dim=16, max_seq_len=64),
        data=DataConfig(synthetic=True, synthetic_examples=64,
                        batch_size=8, seq_len=32, num_epochs=1),
        train=TrainConfig(total_steps=6, warmup_steps=1, log_every=2,
                          checkpoint_dir=str(tmp_path / "ckpt"),
                          checkpoint_every=3),
        telemetry=TelemetryConfig(prof_hz=997.0),
    )
    out = train(cfg)
    prof = out["profile"]
    assert prof["hz"] == 997.0
    assert prof["samples"] > 0
    assert prof["distinct_stacks"] > 0
    frames = out["step_anatomy"].get("host_dispatch_frames")
    assert frames, "armed sampler attributed no host_dispatch frames"
    assert all(f["samples"] > 0 and "(" in f["frame"] for f in frames)


# ---------------------------------------------------------------------------
# the overhead gate (perf_compare wiring)
# ---------------------------------------------------------------------------


@pytest.mark.perf
def test_perf_compare_gates_profiler_overhead_ratio():
    """prof_vs_off_rps_ratio rides the gate at its 15% noise floor: a
    within-floor wobble compares clean, a halved ratio is a regression."""
    from ditl_tpu.telemetry.perf_compare import (
        COMPARE_KEYS, KEY_THRESHOLDS, compare_metrics,
    )

    assert COMPARE_KEYS["prof_vs_off_rps_ratio"] == +1
    assert KEY_THRESHOLDS["prof_vs_off_rps_ratio"] == 0.15
    base = {"profiler_overhead": {"prof_vs_off_rps_ratio": 1.0}}
    wobble = {"profiler_overhead": {"prof_vs_off_rps_ratio": 0.95}}
    halved = {"profiler_overhead": {"prof_vs_off_rps_ratio": 0.5}}
    _lines, regressions = compare_metrics(base, wobble, 0.05, "row: ")
    assert regressions == []
    _lines, regressions = compare_metrics(base, halved, 0.05, "row: ")
    assert any("prof_vs_off_rps_ratio" in r for r in regressions)
