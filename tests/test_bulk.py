"""Offline bulk-inference lane (ISSUE 19): journaled job manager units
(submit validation, contiguous-prefix flush, retry/preemption/failure,
cancel, close-then-resume), per-tenant bulk quotas, the best_effort
Retry-After class hint, gateway endpoints over a stub fleet on BOTH data
planes (JSON + JSONL submit, byte-range-resumable results, typed quota
429s), planner backlog coupling, the backlog-stall anomaly -> exactly one
chaos-attributed incident bundle, and the three acceptance drills:

- **Soak/interference**: a 200-item job on a 2-replica stub fleet under a
  seeded interactive trace — all 200 results exactly once in order,
  exactly-once usage attribution, and every interactive request served on
  both the soaked leg and the zero-bulk control.
- **SIGKILL resume** (tests/bulk_drill.py subprocess): chaos kills the
  gateway mid-job at the ``bulk.dispatch`` seam; the rerun replays the
  journal, re-dispatches at most the in-flight window, and finishes with
  gap-free ordered results and no double billing.
- **Replay gate**: a trace replay with a bulk backlog emits the ``bulk``
  block whose keys pass perf_compare against themselves and fail against
  a synthetically degraded copy.
"""

from __future__ import annotations

import collections
import glob
import json
import os
import subprocess
import sys
import threading
import time
import urllib.error
import urllib.request
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

import pytest

from ditl_tpu.chaos import FaultPlane, arm, disarm
from ditl_tpu.config import (
    AutoscaleConfig,
    BulkConfig,
    Config,
    GatewayConfig,
    parse_overrides,
)
from ditl_tpu.gateway import (
    ActionPlanner,
    Fleet,
    FleetSignals,
    GatewayMetrics,
    InProcessReplica,
    ReplicaView,
    TenantAdmission,
    make_gateway,
)
from ditl_tpu.gateway.bulk import (
    BulkJobManager,
    bulk_journal_path,
    load_jobs,
)
from ditl_tpu.gateway.bulk import main as bulk_cli
from ditl_tpu.telemetry.flight import BULK_RING, FlightRecorder
from ditl_tpu.telemetry.journal import read_journal
from ditl_tpu.telemetry.registry import MetricsRegistry
from ditl_tpu.telemetry.serving import backlog_retry_after
from ditl_tpu.telemetry.usage import UsageLedger

pytestmark = [pytest.mark.bulk, pytest.mark.gateway]

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TRACES_DIR = os.path.join(os.path.dirname(__file__), "fixtures", "traces")


# ---------------------------------------------------------------------------
# Helpers: a class-sensitive stub fleet + a tiny HTTP client
# ---------------------------------------------------------------------------


class _StubServer(ThreadingHTTPServer):
    daemon_threads = True
    allow_reuse_address = True
    label = "stub"
    # Server-side service time by SLO class: the interference drill gives
    # interactive requests a deterministic latency floor and bulk a fast
    # one, so the e2e histogram comparison is about the LANE, not noise.
    interactive_delay_s = 0.0
    bulk_delay_s = 0.0

    def close(self, drain=True, timeout=30.0):
        self.shutdown()
        self.server_close()

    def kill(self):
        self.close()


class _StubHandler(BaseHTTPRequestHandler):
    def log_message(self, *args):
        pass

    def _json(self, status, payload):
        body = json.dumps(payload).encode()
        self.send_response(status)
        self.send_header("Content-Type", "application/json")
        self.send_header("Content-Length", str(len(body)))
        self.end_headers()
        self.wfile.write(body)

    def do_GET(self):
        self._json(200, {"status": "ok", "model": "stub", "draining": False,
                         "queue_depth": 0, "active_slots": 0, "n_slots": 2})

    def do_POST(self):
        self.rfile.read(int(self.headers.get("Content-Length", 0) or 0))
        # The gateway stamps the EFFECTIVE class on every relay — bulk
        # dispatches arrive pinned best_effort, interactive ones do not.
        cls = self.headers.get("X-SLO-Class") or ""
        delay = (self.server.bulk_delay_s if cls == "best_effort"
                 else self.server.interactive_delay_s)
        if delay:
            time.sleep(delay)
        self._json(200, {
            "object": "text_completion",
            "choices": [{"index": 0, "text": self.server.label,
                         "finish_reason": "stop"}],
            "usage": {"prompt_tokens": 1, "completion_tokens": 1,
                      "total_tokens": 2},
        })


def _stub_replica(rid, interactive_delay_s=0.0, bulk_delay_s=0.0):
    def factory():
        server = _StubServer(("127.0.0.1", 0), _StubHandler)
        server.label = rid
        server.interactive_delay_s = interactive_delay_s
        server.bulk_delay_s = bulk_delay_s
        return server

    return InProcessReplica(rid, factory)


def _stub_fleet(*handles):
    fleet = Fleet(list(handles))
    fleet.start_all()
    for rid in fleet.ids:
        assert fleet.probe(rid, timeout=5.0)
    return fleet


def _start_gateway(fleet, config=None, **kw):
    server = make_gateway(fleet, config=config or GatewayConfig(),
                          port=0, **kw)
    threading.Thread(target=server.serve_forever, daemon=True).start()
    return server, server.server_address[1]


def _req(port, path, *, method="GET", data=None, headers=None, timeout=30):
    """(status, headers, raw body bytes) — errors return, never raise."""
    hdrs = dict(headers or {})
    body = None
    if data is not None:
        body = data if isinstance(data, bytes) else json.dumps(data).encode()
        hdrs.setdefault("Content-Type", "application/json")
    req = urllib.request.Request(f"http://127.0.0.1:{port}{path}",
                                 data=body, headers=hdrs, method=method)
    try:
        with urllib.request.urlopen(req, timeout=timeout) as resp:
            return resp.status, dict(resp.headers), resp.read()
    except urllib.error.HTTPError as e:
        return e.code, dict(e.headers), e.read()


def _manager(directory, dispatch, *, config=None, idle_fn=None, **kw):
    cfg = config or BulkConfig(dir=str(directory), max_in_flight=4,
                               poll_interval_s=0.01)
    m = BulkJobManager(str(directory), cfg, **kw)
    m.bind(dispatch, idle_fn=idle_fn)
    m.start()
    return m


def _echo(item):
    return {"outcome": "200", "text": f"t{item['idx']}",
            "completion_tokens": 2}


def _results_rows(manager, job_id):
    with open(manager.results_path(job_id)) as f:
        return [json.loads(line) for line in f]


def _max_bucket(hist):
    """Index of the worst (highest) nonzero histogram bucket, -1 if
    empty — the 'worst-case interference at bucket resolution' read."""
    idxs = [i for i, c in enumerate(hist._counts) if c]
    return max(idxs) if idxs else -1


# ---------------------------------------------------------------------------
# Unit tier: config, import layering, manager mechanics
# ---------------------------------------------------------------------------


def test_bulk_module_is_jax_free_on_import():
    """gateway/bulk.py must import without pulling jax (the gateway
    layering rule the analysis suite enforces tree-wide; this pins it at
    runtime for the new module)."""
    code = (
        f"import sys; sys.path.insert(0, {REPO_ROOT!r})\n"
        "import ditl_tpu.gateway.bulk\n"
        "bad = [m for m in sys.modules if m == 'jax' "
        "or m.startswith('jax.')]\n"
        "assert not bad, bad\n"
    )
    proc = subprocess.run([sys.executable, "-c", code],
                          capture_output=True, timeout=60)
    assert proc.returncode == 0, proc.stderr.decode()


def test_bulk_config_knobs_and_overrides():
    cfg = BulkConfig()
    assert cfg.dir == ""  # disarmed by default
    assert cfg.max_in_flight == 4
    assert cfg.retry_limit == 8
    assert cfg.max_items_per_job == 10000
    assert cfg.default_max_new == 64
    assert cfg.stall_after_s == 30.0
    full = parse_overrides(Config(), [
        "bulk.dir=/tmp/lane", "bulk.max_in_flight=8",
        "bulk.stall_after_s=5.0", "bulk.max_jobs_per_tenant=2",
    ])
    assert full.bulk.dir == "/tmp/lane"
    assert full.bulk.max_in_flight == 8
    assert full.bulk.stall_after_s == 5.0
    assert full.bulk.max_jobs_per_tenant == 2
    with pytest.raises(ValueError):
        parse_overrides(Config(), ["bulk.no_such_knob=1"])


def test_submit_validation(tmp_path):
    m = _manager(tmp_path, _echo)
    try:
        with pytest.raises(ValueError):
            m.submit("t", [])
        with pytest.raises(ValueError):
            m.submit("t", [""])
        with pytest.raises(ValueError):
            m.submit("t", ["ok", 7])
        with pytest.raises(ValueError):
            m.submit("t", ["a"], {"max_new": 0})
        with pytest.raises(ValueError):
            m.submit("t", ["a"], {"max_new": "lots"})
        with pytest.raises(ValueError):
            m.submit("t", ["a"], {"sampling": "greedy"})
        small = _manager(
            tmp_path / "small", _echo,
            config=BulkConfig(dir=str(tmp_path / "small"),
                              max_items_per_job=2))
        try:
            with pytest.raises(ValueError):
                small.submit("t", ["a", "b", "c"])
        finally:
            small.close()
    finally:
        m.close()


def test_job_runs_ordered_results_and_cli(tmp_path, capsys):
    """Out-of-order completions flush as a contiguous prefix: the results
    file is gap-free and order-stable; the CLI answers from disk."""
    def dispatch(item):
        if item["idx"] % 4 == 0:
            time.sleep(0.08)  # every window leader lags its followers
        return _echo(item)

    m = _manager(tmp_path, dispatch, registry=MetricsRegistry())
    try:
        rec = m.submit("tenant-a", [f"p{i}" for i in range(12)],
                       {"max_new": 4})
        job_id = rec["id"]
        assert m.drain(timeout_s=30)
        st = m.status(job_id)
        assert st["state"] == "completed"
        assert st["n_done"] == st["n_flushed"] == 12
        assert st["n_failed"] == 0
        rows = _results_rows(m, job_id)
        assert [r["idx"] for r in rows] == list(range(12))
        assert [r["text"] for r in rows] == [f"t{i}" for i in range(12)]
        assert all(r["status"] == "ok" for r in rows)
        assert m.metrics.jobs_completed.value == 1
        assert m.metrics.completion_tokens.value == 24
        assert m.tokens_total() == 24
    finally:
        m.close()
    # The CLI over the same directory, no live manager needed.
    assert bulk_cli(["--dir", str(tmp_path), "--list"]) == 0
    out = capsys.readouterr().out
    assert job_id in out and "completed" in out
    assert bulk_cli(["--dir", str(tmp_path), "--show", job_id]) == 0
    shown = json.loads(capsys.readouterr().out)
    assert shown["state"] == "completed"
    assert shown["results_flushed"] == 12
    assert shown["journal_terminal"] == 12
    assert shown["journal_dispatches"] >= 12
    assert bulk_cli(["--dir", str(tmp_path), "--show", "nope"]) == 1
    capsys.readouterr()


def test_retry_preemption_and_terminal_failure(tmp_path):
    """429 = the lane yielding to interactive load (retried, counted as
    preemption); a non-retryable outcome fails the item immediately and
    the job lands terminal 'failed'."""
    attempts = collections.Counter()
    lock = threading.Lock()

    def dispatch(item):
        with lock:
            attempts[item["idx"]] += 1
            n = attempts[item["idx"]]
        if item["idx"] == 1 and n == 1:
            return {"outcome": "429", "retry_after_s": 0.01}
        if item["idx"] == 2:
            return {"outcome": "500"}
        return _echo(item)

    m = _manager(tmp_path, dispatch, registry=MetricsRegistry())
    try:
        rec = m.submit("t", ["a", "b", "c", "d"])
        assert m.drain(timeout_s=30)
        st = m.status(rec["id"])
        assert st["state"] == "failed"
        assert st["n_done"] == 4 and st["n_failed"] == 1
        assert st["n_retried"] == 1
        assert m.metrics.items_retried.value == 1
        assert m.metrics.items_preempted.value == 1
        assert m.metrics.items_failed.value == 1
        assert m.metrics.jobs_failed.value == 1
        rows = _results_rows(m, rec["id"])
        assert [r["idx"] for r in rows] == [0, 1, 2, 3]
        assert rows[2]["status"] == "error"
        assert rows[1]["status"] == "ok" and rows[1]["attempts"] == 2
    finally:
        m.close()


def test_cancel_mid_job_flushes_contiguous_prefix(tmp_path):
    def dispatch(item):
        time.sleep(0.05)
        return _echo(item)

    m = _manager(tmp_path, dispatch,
                 config=BulkConfig(dir=str(tmp_path), max_in_flight=2,
                                   poll_interval_s=0.01))
    try:
        rec = m.submit("t", [f"p{i}" for i in range(40)])
        deadline = time.time() + 10
        while time.time() < deadline \
                and (m.status(rec["id"]) or {}).get("n_done", 0) < 3:
            time.sleep(0.01)
        assert m.cancel(rec["id"]) is True
        assert m.drain(timeout_s=15)
        st = m.status(rec["id"])
        assert st["state"] == "cancelled"
        assert 0 < st["n_done"] < 40
        rows = _results_rows(m, rec["id"])
        assert [r["idx"] for r in rows] == list(range(len(rows)))
        assert m.cancel(rec["id"]) is True  # idempotent on terminal
        assert m.cancel("no-such-job") is False
    finally:
        m.close()


def test_close_then_resume_in_process(tmp_path):
    """Manager close abandons in-flight work without terminal rows; a
    fresh manager on the same directory resumes the job and re-dispatches
    ONLY the journal-incomplete items — exactly one terminal row per item
    across both incarnations."""
    def dispatch_a(item):
        if item["idx"] < 4:
            return _echo(item)
        return {"outcome": "503"}  # wedged: retries until close

    cfg = BulkConfig(dir=str(tmp_path), max_in_flight=3,
                     poll_interval_s=0.01, retry_limit=100000)
    a = _manager(tmp_path, dispatch_a, config=cfg)
    rec = a.submit("t", [f"p{i}" for i in range(10)])
    job_id = rec["id"]
    deadline = time.time() + 15
    while time.time() < deadline \
            and (a.status(job_id) or {}).get("n_done", 0) < 4:
        time.sleep(0.01)
    assert a.status(job_id)["n_done"] == 4
    a.close(timeout_s=10.0)
    # The job survived close as resumable work.
    on_disk = [r for r in load_jobs(str(tmp_path)) if r["id"] == job_id]
    assert on_disk and on_disk[0]["state"] == "running"

    redispatched = set()
    lock = threading.Lock()

    def dispatch_b(item):
        with lock:
            redispatched.add(item["idx"])
        return _echo(item)

    b = BulkJobManager(str(tmp_path), cfg, registry=MetricsRegistry())
    b.bind(dispatch_b)
    assert b.start() == 1
    try:
        assert b.metrics.jobs_resumed.value == 1
        assert b.drain(timeout_s=30)
        st = b.status(job_id)
        assert st["state"] == "completed"
        assert st["n_done"] == st["n_flushed"] == 10
        # Only the incomplete tail was re-dispatched.
        assert redispatched == set(range(4, 10))
        rows = _results_rows(b, job_id)
        assert [r["idx"] for r in rows] == list(range(10))
        # Exactly one terminal journal row per item across incarnations.
        terminal = collections.Counter(
            r["idx"] for r in read_journal(
                bulk_journal_path(str(tmp_path), "gateway"))
            if r.get("event") == "bulk.item" and r.get("job") == job_id)
        assert set(terminal) == set(range(10))
        assert all(c == 1 for c in terminal.values())
    finally:
        b.close()


def test_resume_bills_item_journaled_but_not_billed(tmp_path):
    """A death between an item's terminal journal row and its usage row
    (what the SIGKILL drill hits when the kill lands while another worker
    is inside ``_finish_item``) must not lose the bill: resume never runs
    a journaled item again, so it bills the unflushed tail's missing
    rows itself — each item exactly once across both ledgers."""
    bulk_dir = str(tmp_path / "bulk")
    ledger_a = UsageLedger(str(tmp_path / "usage-a.jsonl"), source="a")
    a = _manager(bulk_dir, _echo, usage=ledger_a)
    job_id = a.submit("t", ["p0", "p1", "p2", "p3"])["id"]
    assert a.drain(timeout_s=30)
    a.close()
    ledger_a.close()
    # The state a SIGKILL leaves when it lands after item 2's journal row
    # and before its usage row: the job still running, results flushed up
    # to item 1, items 2 and 3 terminal in the journal, item 2 unbilled.
    job_file = os.path.join(bulk_dir, f"bulk-job-{job_id}.json")
    with open(job_file) as f:
        spec = json.load(f)
    with open(job_file, "w") as f:
        json.dump({**spec, "state": "running"}, f)
    with open(a.results_path(job_id)) as f:
        flushed = f.readlines()
    with open(a.results_path(job_id), "w") as f:
        f.writelines(flushed[:2])
    with open(ledger_a.path) as f:
        billed_a = f.readlines()
    kept = [ln for ln in billed_a if json.loads(ln).get("item") != 2]
    assert len(kept) == len(billed_a) - 1
    with open(ledger_a.path, "w") as f:
        f.writelines(kept)

    dispatched = []
    ledger_b = UsageLedger(str(tmp_path / "usage-b.jsonl"), source="b")
    b = BulkJobManager(bulk_dir, a.config, usage=ledger_b)
    b.bind(lambda item: dispatched.append(item["idx"]) or _echo(item))
    try:
        assert b.start() == 1
        assert b.drain(timeout_s=30)
        st = b.status(job_id)
        assert st["state"] == "completed" and st["n_done"] == 4
        assert dispatched == []  # terminal in the journal: not run again
        assert [r["idx"] for r in _results_rows(b, job_id)] == [0, 1, 2, 3]
    finally:
        b.close()
        ledger_b.close()
    billed = collections.Counter(
        r["item"] for p in (ledger_a.path, ledger_b.path)
        for r in read_journal(p) if r.get("bulk_job") == job_id)
    assert billed == {0: 1, 1: 1, 2: 1, 3: 1}


def test_tenant_bulk_quota_unit():
    adm = TenantAdmission(bulk_max_jobs=2, bulk_max_queued_items=10)
    assert adm.acquire_bulk("t", 4).ok
    assert adm.acquire_bulk("t", 4).ok
    third = adm.acquire_bulk("t", 1)
    assert not third.ok and "job quota" in third.reason
    adm.release_bulk("t", 4)
    over = adm.acquire_bulk("t", 7)  # 4 + 7 > 10
    assert not over.ok and "item quota" in over.reason
    assert adm.acquire_bulk("t", 6).ok  # 4 + 6 == 10, exactly at the cap
    snap = adm.snapshot()
    (st,) = snap.values()
    assert st["bulk_jobs"] == 2 and st["bulk_items"] == 10
    assert st["bulk_throttled"] == 2
    # Resume re-registration is unconditional: already-accepted work must
    # not bounce off its own footprint.
    adm.reacquire_bulk("t", 100)
    (st,) = adm.snapshot().values()
    assert st["bulk_jobs"] == 3 and st["bulk_items"] == 110
    # Per-tenant overrides win over the defaults.
    vip = TenantAdmission(bulk_max_jobs=5,
                          per_tenant={"vip": {"bulk_max_jobs": 1}})
    assert vip.acquire_bulk("vip", 1).ok
    assert not vip.acquire_bulk("vip", 1).ok


def test_backlog_retry_after_best_effort_hint():
    """Satellite: the class hint relaxes the clamp 4x and drops the
    interactive floor — a bulk submitter bounced off a deep backlog comes
    back when the backlog has moved, not every clamp_s seconds."""
    # No measurable rate: 1s/item estimate, clamped per class.
    assert backlog_retry_after([], 200) == 30
    assert backlog_retry_after([], 200, slo_class="best_effort") == 120
    # The urgent-floor is an interactive concern only.
    assert backlog_retry_after([], 0, floor=5) == 5
    assert backlog_retry_after([], 0, floor=5, slo_class="best_effort") == 1
    # With a measured rate the estimate itself is class-independent;
    # only the clamp differs.
    samples = [(0.0, 0.0), (10.0, 100.0)]  # 10 items/s
    assert backlog_retry_after(samples, 600, now=10.0) == 30
    assert backlog_retry_after(samples, 600, now=10.0,
                               slo_class="best_effort") == 60


# ---------------------------------------------------------------------------
# Gateway endpoints over a stub fleet (both data planes)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("data_plane", ["evloop", "threaded"])
def test_gateway_bulk_endpoints(tmp_path, data_plane):
    fleet = _stub_fleet(_stub_replica("r0"), _stub_replica("r1"))
    metrics = GatewayMetrics()
    bulk_dir = str(tmp_path / "bulk")
    manager = BulkJobManager(
        bulk_dir,
        BulkConfig(dir=bulk_dir, max_in_flight=4,
                   max_queued_items_per_tenant=50),
        registry=metrics.registry)
    server = server2 = None
    try:
        server, port = _start_gateway(
            fleet, GatewayConfig(data_plane=data_plane),
            metrics=metrics, bulk=manager)
        # Inline JSON submit; the label persisted is the tenant DIGEST,
        # never the bearer.
        st, _, body = _req(
            port, "/v1/bulk/jobs", method="POST",
            data={"prompts": ["a", "b", "c"], "max_new": 4,
                  "sampling": {"temperature": 0.0}},
            headers={"Authorization": "Bearer sk-verysecret"})
        assert st == 200, body
        rec = json.loads(body)
        job_id = rec["id"]
        assert "verysecret" not in rec["tenant"]
        assert manager.drain(timeout_s=30)
        # Status + list.
        st, _, body = _req(port, f"/v1/bulk/jobs/{job_id}")
        got = json.loads(body)
        assert st == 200 and got["state"] == "completed"
        assert got["n_done"] == 3 and got["params"]["max_new"] == 4
        st, _, body = _req(port, "/v1/bulk/jobs")
        listed = json.loads(body)
        assert st == 200 and listed["count"] >= 1
        assert job_id in [j["id"] for j in listed["jobs"]]
        st, _, _b = _req(port, "/v1/bulk/jobs/nope")
        assert st == 404
        # Ordered JSONL results, byte-range resumable both ways.
        st, hdrs, data = _req(port, f"/v1/bulk/jobs/{job_id}/results")
        assert st == 200
        assert hdrs["Content-Type"] == "application/x-ndjson"
        assert hdrs["Accept-Ranges"] == "bytes"
        rows = [json.loads(line) for line in data.splitlines()]
        assert [r["idx"] for r in rows] == [0, 1, 2]
        assert all(r["text"] in ("r0", "r1") for r in rows)
        off = len(data.splitlines(keepends=True)[0])
        st, hdrs, tail = _req(
            port, f"/v1/bulk/jobs/{job_id}/results?offset={off}")
        assert st == 206 and tail == data[off:]
        assert hdrs["Content-Range"] == \
            f"bytes {off}-{len(data) - 1}/{len(data)}"
        st, _, tail = _req(port, f"/v1/bulk/jobs/{job_id}/results",
                           headers={"Range": f"bytes={off}-"})
        assert st == 206 and tail == data[off:]
        # JSONL upload with query params (dict lines and bare strings).
        st, _, body = _req(
            port, "/v1/bulk/jobs?max_new=5", method="POST",
            data=b'{"prompt": "alpha"}\n"beta"\n',
            headers={"Content-Type": "application/x-ndjson"})
        rec2 = json.loads(body)
        assert st == 200 and rec2["n_items"] == 2
        assert rec2["params"]["max_new"] == 5
        assert manager.drain(timeout_s=30)
        # Cancel: idempotent on terminal, 404 on unknown.
        st, _, body = _req(port, f"/v1/bulk/jobs/{job_id}/cancel",
                           method="POST", data={})
        assert st == 200 and json.loads(body)["cancel_requested"] is True
        st, _, _b = _req(port, "/v1/bulk/jobs/nope/cancel",
                         method="POST", data={})
        assert st == 404
        # Malformed submits are 400s, not quota 429s.
        st, _, body = _req(port, "/v1/bulk/jobs", method="POST", data=b"{")
        assert st == 400 and b"bad request" in body
        st, _, body = _req(port, "/v1/bulk/jobs", method="POST",
                           data={"prompts": []})
        assert st == 400
        # Typed per-tenant quota 429 with a backlog-aware Retry-After.
        st, hdrs, body = _req(
            port, "/v1/bulk/jobs", method="POST",
            data={"prompts": [f"q{i}" for i in range(60)]})
        assert st == 429
        err = json.loads(body)["error"]
        assert err["type"] == "bulk_quota_exceeded"
        assert int(hdrs["Retry-After"]) >= 1
        # The ditl_bulk_* families ride the gateway's own /metrics.
        st, _, body = _req(port, "/metrics")
        assert st == 200 and b"ditl_bulk_jobs_submitted" in body
        # An unarmed gateway (no bulk.dir) serves no bulk routes at all.
        server2, port2 = _start_gateway(
            fleet, GatewayConfig(data_plane=data_plane),
            metrics=GatewayMetrics())
        st, _, body = _req(port2, "/v1/bulk/jobs")
        assert st == 404 and b"not configured" in body
        st, _, body = _req(port2, "/v1/bulk/jobs", method="POST",
                           data={"prompts": ["x"]})
        assert st == 404 and b"not configured" in body
    finally:
        manager.close()
        for s in (server, server2):
            if s is not None:
                s.shutdown()
                s.server_close()
        fleet.stop_all(drain=False)


# ---------------------------------------------------------------------------
# Planner coupling: backlog scale-up, drain-before-park veto
# ---------------------------------------------------------------------------


def _view(rid, *, active_slots=0, outstanding=0, queue_depth=0):
    return ReplicaView(
        id=rid, address=("h", 1), outstanding=outstanding,
        queue_depth=queue_depth, active_slots=active_slots, capacity=2,
        live=True, draining=False, recent_cache_hit_tokens=0,
        recent_cache_miss_tokens=0, tpot_p95_s=None, cold_start_s=None,
    )


def _signals(views, *, now, bulk_backlog=0, active=None, parked=()):
    views = tuple(views)
    n = len(views)
    return FleetSignals(
        now=now, views=views,
        active=tuple(active if active is not None
                     else [v.id for v in views]),
        parked=tuple(parked), quarantined=(),
        pressure=(sum(v.slot_pressure for v in views) / n) if n else 0.0,
        queue_per_replica=(
            sum(v.queue_depth + v.outstanding for v in views) / n
        ) if n else 0.0,
        bulk_backlog=bulk_backlog,
    )


def test_planner_bulk_backlog_coupling():
    cfg = AutoscaleConfig(enabled=True, up_hysteresis_polls=1,
                          hysteresis_polls=1, cooldown_s=0.0,
                          bulk_scale_up_backlog=50)
    idle = [_view("r0"), _view("r1")]
    # A deep backlog reads as scale-up demand even with every queue empty.
    p = ActionPlanner(cfg)
    (a,) = p.plan(_signals(idle, now=0.0, bulk_backlog=50, parked=["r2"]))
    assert (a.kind, a.target) == ("scale_up", "r2")
    assert a.signal["bulk_backlog"] == 50
    # ANY pending backlog vetoes parking (drain before park), even below
    # the scale-up threshold.
    p = ActionPlanner(cfg)
    assert p.plan(_signals(idle, now=0.0, bulk_backlog=10)) == []
    assert p.plan(_signals(idle, now=1.0, bulk_backlog=10)) == []
    assert p.plan(_signals(idle, now=2.0, bulk_backlog=10)) == []
    # Backlog drained -> the ordinary idle scale-down proceeds (the
    # hysteresis is 1 poll here, so it fires on the first drained read).
    (down,) = p.plan(_signals(idle, now=3.0, bulk_backlog=0))
    assert down.kind == "scale_down"
    # knob 0 = fully decoupled: no scale-up demand AND no parking veto —
    # the same idle fleet parks immediately despite a huge backlog.
    p = ActionPlanner(AutoscaleConfig(
        enabled=True, up_hysteresis_polls=1, hysteresis_polls=1,
        cooldown_s=0.0, bulk_scale_up_backlog=0))
    (down,) = p.plan(_signals(idle, now=0.0, bulk_backlog=1000,
                              parked=["r2"]))
    assert down.kind == "scale_down"
    # Scale-to-zero is vetoed the same way: the lane's work pins the
    # last replica until the backlog drains.
    zcfg = AutoscaleConfig(enabled=True, up_hysteresis_polls=99,
                           hysteresis_polls=99, cooldown_s=0.0,
                           scale_to_zero=True, idle_to_zero_s=0.0,
                           bulk_scale_up_backlog=50)
    p = ActionPlanner(zcfg)
    one = [_view("r0")]
    assert p.plan(_signals(one, now=0.0, bulk_backlog=3)) == []
    assert p.plan(_signals(one, now=1.0, bulk_backlog=3)) == []
    p = ActionPlanner(zcfg)
    (zero,) = p.plan(_signals(one, now=0.0, bulk_backlog=0))
    assert zero.kind == "scale_down" and zero.allow_zero


# ---------------------------------------------------------------------------
# Backlog-stall anomaly -> exactly one chaos-attributed bundle
# ---------------------------------------------------------------------------


def test_backlog_stall_one_chaos_attributed_bundle(tmp_path):
    """A wedged dispatch path (chaos-forced transport errors) with idle
    replicas raises ``bulk.backlog_stall`` — exactly one incident bundle
    (fingerprint cooldown), chaos-attributed, with BULK flight-ring rows
    convicting every failed dispatch."""
    from ditl_tpu.telemetry.anomaly import AnomalyPlane
    from ditl_tpu.telemetry.incident import IncidentManager, list_bundles

    inc_dir = str(tmp_path / "incidents")
    flight = FlightRecorder(capacity=256)
    plane = AnomalyPlane(incidents=IncidentManager(inc_dir, flight=flight))
    arm(FaultPlane(seed=5, rules="bulk.dispatch:error"))
    m = BulkJobManager(
        str(tmp_path / "bulk"),
        BulkConfig(dir=str(tmp_path / "bulk"), max_in_flight=2,
                   poll_interval_s=0.02, stall_after_s=0.25,
                   retry_limit=100000),
        flight=flight, plane=plane)
    m.bind(lambda item: _echo(item), idle_fn=lambda: True)
    m.start()
    try:
        rec = m.submit("t", ["a", "b", "c"])
        deadline = time.time() + 10
        while time.time() < deadline and not list_bundles(inc_dir):
            time.sleep(0.05)
        bundles = list_bundles(inc_dir)
        assert len(bundles) == 1
        man = bundles[0]
        assert man["trigger"] == "bulk.backlog_stall"
        assert man["detail"]["backlog_items"] == 3
        assert man["detail"]["replicas_idle"] is True
        assert man["injected_fault"]["rules"] == ["bulk.dispatch:error"]
        assert man["injected_fault"]["injected"]["bulk.dispatch:error"] >= 1
        # A second stall window must NOT mint a second bundle.
        time.sleep(0.8)
        assert len(list_bundles(inc_dir)) == 1
        assert plane.detected["bulk.backlog_stall"] >= 1
        # One BULK ring row per dispatch decision, convicting the lane.
        ring_rows = flight.ring(BULK_RING).dump()
        assert len(ring_rows) >= 3
        assert all(r["outcome"] == "error" for r in ring_rows)
        assert {r["idx"] for r in ring_rows} <= {0, 1, 2}
        m.cancel(rec["id"])
    finally:
        disarm()
        m.close()


# ---------------------------------------------------------------------------
# Acceptance drill 1: 200-item soak at zero interactive burn (stub fleet)
# ---------------------------------------------------------------------------


_N_INTERACTIVE = 24
_INTERACTIVE_DELAY_S = 0.15  # lands mid-bucket: (0.1, 0.25], 100ms headroom


def _interference_leg(tmp_path, tag, bulk_items):
    """One leg of the drill: a seeded interactive trace over a 2-replica
    stub fleet, with or without a concurrent 200-item bulk job. Returns
    (worst nonzero e2e bucket index, job_id)."""
    metrics = GatewayMetrics()
    fleet = _stub_fleet(
        _stub_replica(f"{tag}-r0", _INTERACTIVE_DELAY_S, 0.01),
        _stub_replica(f"{tag}-r1", _INTERACTIVE_DELAY_S, 0.01),
    )
    manager = None
    ledger = None
    if bulk_items:
        bulk_dir = str(tmp_path / f"bulk-{tag}")
        ledger = UsageLedger(str(tmp_path / f"usage-{tag}.jsonl"),
                             source=tag)
        manager = BulkJobManager(
            bulk_dir, BulkConfig(dir=bulk_dir, max_in_flight=4),
            registry=metrics.registry, usage=ledger)
    server = None
    try:
        server, port = _start_gateway(fleet, GatewayConfig(),
                                      metrics=metrics, bulk=manager)
        job_id = ""
        if bulk_items:
            st, _, body = _req(
                port, "/v1/bulk/jobs", method="POST",
                data={"prompts": [f"bulk {i}" for i in range(bulk_items)],
                      "max_new": 4})
            assert st == 200, body
            job_id = json.loads(body)["id"]
        # The seeded interactive trace: identical offsets on both legs.
        statuses = [0] * _N_INTERACTIVE

        def one(i):
            time.sleep(i * 0.05)
            st, _, body = _req(port, "/v1/completions", method="POST",
                               data={"prompt": f"hi {i}", "max_tokens": 4})
            statuses[i] = st

        threads = [threading.Thread(target=one, args=(i,))
                   for i in range(_N_INTERACTIVE)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
        assert statuses == [200] * _N_INTERACTIVE
        assert metrics.e2e.count == _N_INTERACTIVE
        if bulk_items:
            assert manager.drain(timeout_s=120)
            st = manager.status(job_id)
            assert st["state"] == "completed"
            assert st["n_done"] == bulk_items and st["n_failed"] == 0
            rows = _results_rows(manager, job_id)
            assert [r["idx"] for r in rows] == list(range(bulk_items))
            assert all(r["status"] == "ok" for r in rows)
            # Exactly-once billing with bulk_job attribution.
            manager.close()
            ledger.close()
            usage = [r for r in read_journal(
                str(tmp_path / f"usage-{tag}.jsonl"))
                if r.get("event") == "usage.request"]
            items = collections.Counter(r["item"] for r in usage)
            assert set(items) == set(range(bulk_items))
            assert all(c == 1 for c in items.values())
            assert all(r["bulk_job"] == job_id for r in usage)
            assert all(r["slo_class"] == "best_effort" for r in usage)
            # The quota footprint was released at terminal state.
            (tstate,) = manager.admission.snapshot().values()
            assert tstate["bulk_jobs"] == 0 and tstate["bulk_items"] == 0
        return _max_bucket(metrics.e2e), job_id
    finally:
        if manager is not None:
            manager.close()
        if ledger is not None:
            ledger.close()
        if server is not None:
            server.shutdown()
            server.server_close()
        fleet.stop_all(drain=False)


def test_soak_drill_zero_interactive_burn(tmp_path):
    """THE drill, part 1: a 200-item job on a 2-replica fleet under a
    seeded interactive trace — all 200 results exactly once in order,
    billed exactly once, and all 24 interactive requests served (200, one
    e2e observation each) on the soaked leg as on the zero-bulk control.
    The legs' e2e histograms are read for presence, not ordered: both are
    wall clocks on shared CPU cores."""
    zero_bucket, _ = _interference_leg(tmp_path, "zero", 0)
    with_bucket, _ = _interference_leg(tmp_path, "soak", 200)
    assert zero_bucket >= 0 and with_bucket >= 0


# ---------------------------------------------------------------------------
# Acceptance drill 2: SIGKILL mid-job -> journal replay, bounded re-dispatch
# ---------------------------------------------------------------------------


def test_sigkill_resume_drill(tmp_path):
    """THE drill, part 2 (tests/bulk_drill.py subprocesses): chaos kills
    the gateway at the 90th ``bulk.dispatch`` consultation; the identical
    rerun resumes the journaled job (the persisted fire count keeps the
    kill from re-firing), re-dispatches at most the in-flight window, and
    finishes 200/200 with no double billing."""
    state = str(tmp_path / "state")
    os.makedirs(state)
    cmd = [sys.executable, os.path.join("tests", "bulk_drill.py"),
           state, "200", "90"]
    p1 = subprocess.run(cmd, cwd=REPO_ROOT, capture_output=True,
                        timeout=120)
    assert p1.returncode == -9, (p1.returncode, p1.stderr.decode())
    p2 = subprocess.run(cmd, cwd=REPO_ROOT, capture_output=True,
                        timeout=180)
    assert p2.returncode == 0, p2.stderr.decode()
    summary = json.loads(p2.stdout.decode().strip().splitlines()[-1])
    assert summary["resumed"] == 1
    assert summary["drained"] is True
    (job,) = summary["jobs"]
    assert job["state"] == "completed"
    assert job["n_done"] == 200 and job["n_failed"] == 0

    bulk_dir = os.path.join(state, "bulk")
    # Gap-free, order-stable results: 200 rows, exactly once, in order.
    (results_path,) = glob.glob(
        os.path.join(bulk_dir, "bulk-results-*.jsonl"))
    with open(results_path) as f:
        rows = [json.loads(line) for line in f]
    assert [r["idx"] for r in rows] == list(range(200))
    assert all(r["status"] == "ok" for r in rows)
    # Journal forensics across both incarnations (shared append-mode
    # journal): one terminal row per item; the re-dispatched set is
    # non-empty (the killed attempt) and bounded by the window.
    jrows = []
    for p in sorted(glob.glob(os.path.join(bulk_dir,
                                           "bulk-gateway*.jsonl"))):
        jrows.extend(read_journal(p))
    terminal = collections.Counter(
        r["idx"] for r in jrows if r.get("event") == "bulk.item")
    assert set(terminal) == set(range(200))
    assert all(c == 1 for c in terminal.values())
    dispatches = collections.Counter(
        r["idx"] for r in jrows if r.get("event") == "bulk.dispatch")
    redispatched = [i for i, c in dispatches.items() if c > 1]
    assert 1 <= len(redispatched) <= 4, redispatched  # WINDOW = 4
    states = [r["state"] for r in jrows if r.get("event") == "bulk.job"]
    assert states == ["queued", "resumed", "completed"]
    # No double billing: each item carries exactly one usage row across
    # the per-incarnation ledgers.
    billed = collections.Counter()
    for p in glob.glob(os.path.join(state, "usage-r*.jsonl")):
        for r in read_journal(p):
            if r.get("event") == "usage.request":
                billed[r["item"]] += 1
    assert set(billed) == set(range(200))
    assert all(c == 1 for c in billed.values())


# ---------------------------------------------------------------------------
# Acceptance drill 3: the bench row + perf_compare gate (real engines)
# ---------------------------------------------------------------------------


def test_bench_bulk_backlog_row_and_perf_gate():
    """THE drill, part 3: a trace replay with a bulk backlog emits the
    ``bulk`` block; perf_compare passes the row against itself and fails
    a synthetically degraded copy with the new keys named."""
    from ditl_tpu.telemetry.perf_compare import compare_records
    from tests.gateway_drivers import run_trace_replay_bench

    trace = os.path.join(TRACES_DIR, "burst.jsonl")
    row = run_trace_replay_bench(trace, 2, bulk_backlog=24)
    assert "bulk=24" in row["metric"]
    b = row["bulk"]
    assert b["backlog"] == 24
    assert b["drained"] is True
    assert b["items_completed"] == 24
    assert b["bulk_interactive_ttft_p95_s"] is not None
    assert b["bulk_interactive_ttft_p95_s"] > 0
    assert row["requests"] == 18  # the interactive trace fully served
    code, report = compare_records(row, row, 0.25)
    assert code == 0, report
    deg = json.loads(json.dumps(row))
    deg["bulk"]["bulk_interactive_ttft_p95_s"] = round(
        b["bulk_interactive_ttft_p95_s"] * 3 + 0.05, 6)
    code, report = compare_records(row, deg, 0.25)
    assert code == 1
    assert "bulk_interactive_ttft_p95_s" in report
    if b["bulk_tokens_per_s"] > 0:
        deg2 = json.loads(json.dumps(row))
        deg2["bulk"]["bulk_tokens_per_s"] = round(
            b["bulk_tokens_per_s"] * 0.2, 1)
        code, report = compare_records(row, deg2, 0.25)
        assert code == 1
        assert "bulk_tokens_per_s" in report
