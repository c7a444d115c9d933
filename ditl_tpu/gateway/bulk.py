"""Offline bulk-inference lane (ISSUE 19 tentpole): a crash-consistent
bulk job manager soaking spare decode capacity at zero interactive SLO
burn.

"Millions of users" is not only interactive chat — it is overnight
embedding jobs, eval sweeps, and synthetic-data generation. Every
primitive this lane needs already exists and is test-pinned: the
``best_effort`` SLO class (ISSUE 8) guarantees the interactive stall
bound (batch/interactive preempt bulk token-by-token at the engine), the
actuation plane (ISSUE 12) can treat bulk demand as a scale-up signal,
and per-tenant usage ledgers (ISSUE 15) make bulk work billable. This
module adds the missing piece: a journaled job manager behind the
gateway's ``/v1/bulk/jobs`` endpoints that decomposes a job into
per-prompt work items and dispatches them through the existing relay
path pinned to ``best_effort``.

Crash consistency is the design center, the checkpoint-resume story
applied to serving:

- **Spec before ack**: a job's prompts are written to
  ``bulk-items-<id>.jsonl`` and its spec/state to ``bulk-job-<id>.json``
  (atomic tmp+rename) BEFORE the submit response — an acknowledged job
  is always resumable.
- **One ``bulk.item`` journal row per terminal outcome**: line-buffered
  through telemetry/journal.py (segment-rotated like spans/usage), each
  row carries the full result, so it is on disk before the results file
  or any counter moves.
- **Ordered results with a contiguous-prefix flush**:
  ``bulk-results-<id>.jsonl`` only ever holds items ``0..k`` in order;
  out-of-order completions wait in memory (bounded by the in-flight
  window) until the gap fills. The journal row is the durable record for
  the waiters, so a SIGKILL between journal and flush loses nothing.
- **Resume = results prefix ∪ journal rows**: a restarted manager
  re-dispatches only items with NO terminal journal row — at most the
  in-flight window is re-dispatched, and no item is ever billed twice
  (usage rows are written with the terminal journal row, which is
  written exactly once per item). Drilled with a real SIGKILL via the
  ``bulk.dispatch`` chaos site.

Like everything in gateway/, this module is stdlib-only and jax-free on
import. The relay dependency is INJECTED (``bind(dispatch=...)``) so the
manager is unit-testable against a fake fleet.

CLI over the on-disk state (no live gateway needed)::

    python -m ditl_tpu.gateway.bulk --dir D --list
    python -m ditl_tpu.gateway.bulk --dir D --show JOB_ID
"""

from __future__ import annotations

import collections
import glob
import json
import os
import re
import threading
import time
import uuid
from concurrent.futures import FIRST_COMPLETED, ThreadPoolExecutor, wait

from ditl_tpu.chaos import InjectedFault, maybe_inject
from ditl_tpu.config import BulkConfig
from ditl_tpu.gateway.admission import sanitize_label
from ditl_tpu.telemetry.flight import BULK_RING
from ditl_tpu.telemetry.journal import EventJournal, read_journal
from ditl_tpu.telemetry.usage import load_usage
from ditl_tpu.utils.logging import get_logger

logger = get_logger(__name__)

__all__ = [
    "BulkJobManager",
    "JOB_STATES",
    "bulk_journal_path",
    "load_jobs",
    "main",
]

PREFIX = "ditl_bulk"

# Journal schema stamp (the usage-ledger discipline): readers of an old
# journal know which row vocabulary produced it.
BULK_SCHEMA = 1

JOB_STATES = ("queued", "running", "completed", "cancelled", "failed")

# Dispatch outcomes that merit another attempt: fleet saturation and
# replica death/timeout are transient by definition (the idempotent-safe
# relay already retried WITHIN one attempt; this is the slower outer
# loop), and "error" covers transport faults incl. injected chaos.
RETRYABLE_OUTCOMES = ("429", "503", "504", "error")


def bulk_journal_path(directory: str, source: str = "gateway") -> str:
    """``bulk-<source>.jsonl`` — deliberately OUTSIDE the ``events-*``
    glob merge_journals consumes (the usage-ledger naming lesson): item
    rows carry full result payloads and would swamp a merged timeline."""
    return os.path.join(directory, f"bulk-{source}.jsonl")


def _job_path(directory: str, job_id: str) -> str:
    return os.path.join(directory, f"bulk-job-{job_id}.json")


def _items_path(directory: str, job_id: str) -> str:
    return os.path.join(directory, f"bulk-items-{job_id}.jsonl")


def _results_path(directory: str, job_id: str) -> str:
    return os.path.join(directory, f"bulk-results-{job_id}.jsonl")


_JOB_ID_RE = re.compile(r"^[A-Za-z0-9_-]{1,64}$")


class BulkMetrics:
    """The ``ditl_bulk_*`` families (telemetry/catalog.py registers them;
    all optional — they exist only on a bulk-armed gateway). Registered
    lazily on the gateway's own registry so /metrics carries the lane
    next to the interactive families."""

    def __init__(self, registry):
        r = registry
        self.jobs_submitted = r.counter(
            f"{PREFIX}_jobs_submitted", "bulk jobs accepted at submit")
        self.jobs_completed = r.counter(
            f"{PREFIX}_jobs_completed", "bulk jobs that ran to completion")
        self.jobs_cancelled = r.counter(
            f"{PREFIX}_jobs_cancelled", "bulk jobs cancelled by a client")
        self.jobs_failed = r.counter(
            f"{PREFIX}_jobs_failed",
            "bulk jobs terminal with at least one permanently failed item")
        self.jobs_resumed = r.counter(
            f"{PREFIX}_jobs_resumed",
            "incomplete bulk jobs resumed from the journal after a "
            "gateway restart")
        self.items_dispatched = r.counter(
            f"{PREFIX}_items_dispatched",
            "bulk work items dispatched through the relay path "
            "(attempts, so retries count again)")
        self.items_completed = r.counter(
            f"{PREFIX}_items_completed",
            "bulk work items that reached a terminal journal row")
        self.items_retried = r.counter(
            f"{PREFIX}_items_retried",
            "bulk dispatch attempts retried after a transient outcome")
        self.items_preempted = r.counter(
            f"{PREFIX}_items_preempted",
            "bulk dispatch attempts bounced by fleet saturation (429) — "
            "the lane yielding to interactive load, working as designed")
        self.items_failed = r.counter(
            f"{PREFIX}_items_failed",
            "bulk work items terminally failed after exhausting retries")
        self.backlog = r.gauge(
            f"{PREFIX}_backlog_items",
            "bulk work items not yet terminal across non-terminal jobs "
            "(the autoscale planner's scale-up signal)")
        self.jobs_active = r.gauge(
            f"{PREFIX}_jobs_active", "bulk jobs currently queued or running")
        self.completion_tokens = r.counter(
            f"{PREFIX}_completion_tokens",
            "completion tokens generated by the bulk lane")
        self.tokens_per_s = r.gauge(
            f"{PREFIX}_tokens_per_s",
            "recent bulk-lane completion tokens/sec (windowed over the "
            "manager's rate samples; 0 when the lane is idle)")


class _Job:
    """In-memory state of one job; the durable truth lives in the job
    file + journal. All mutable fields are guarded by ``lock``."""

    def __init__(self, job_id: str, tenant: str, params: dict,
                 n_items: int, state: str = "queued",
                 created_ts: float | None = None):
        self.id = job_id
        self.tenant = tenant  # credential-safe label, never the bearer
        self.params = params
        self.n_items = n_items
        self.state = state
        self.created_ts = time.time() if created_ts is None else created_ts
        self.lock = threading.Lock()
        self.cancel_requested = False
        # Contiguous-prefix flush state (guarded-by: lock).
        self.flushed = 0  # items 0..flushed-1 are in the results file
        self.pending: dict[int, dict] = {}  # journaled, awaiting the gap
        self.done: set[int] = set()  # terminal (journaled) item idxs
        self.n_failed = 0
        self.n_retried = 0
        self.n_dispatched = 0
        self.thread: threading.Thread | None = None

    def counters(self) -> dict:
        with self.lock:
            return {
                "n_items": self.n_items,
                "n_done": len(self.done),
                "n_flushed": self.flushed,
                "n_failed": self.n_failed,
                "n_retried": self.n_retried,
                "n_dispatched": self.n_dispatched,
            }


class BulkJobManager:
    """The journaled bulk job manager. Construction wires the durable
    state (directory + journal); :meth:`bind` wires the live gateway
    pieces (the relay dispatch closure, the idle-fleet probe); and
    :meth:`start` resumes incomplete jobs and begins dispatching.

    ``dispatch(item) -> dict`` is the injected relay: it receives one
    work-item dict (``job``, ``idx``, ``rid``, ``prompt``, ``tenant``,
    ``adapter``, ``max_new``, ``sampling``) and returns ``{"outcome":
    "200"|"429"|"503"|"504"|"error", "text": ..., "completion_tokens":
    N, "retry_after_s": S}``. The gateway builds it over
    ``_route_and_relay`` pinned to ``best_effort`` with a STABLE
    per-item request id (``bulk-<job>-<idx>``) so replica-death retries
    ride the existing idempotent-safe relay."""

    def __init__(self, directory: str, config=None, *, journal=None,
                 registry=None, flight=None, plane=None, usage=None,
                 admission=None, source: str = "gateway",
                 max_bytes: int | None = None):
        if not directory:
            raise ValueError("bulk manager needs a directory")
        os.makedirs(directory, exist_ok=True)
        self.directory = directory
        self.config = config if config is not None else BulkConfig()
        self.journal = journal if journal is not None else EventJournal(
            bulk_journal_path(directory, source), source=f"bulk-{source}",
            max_bytes=max_bytes,
        )
        self.metrics = BulkMetrics(registry) if registry is not None else None
        self.flight = flight
        self.plane = plane
        self.usage = usage
        self.admission = admission
        self._dispatch = None
        self._idle_fn = None
        self._jobs: dict[str, _Job] = {}
        self._jobs_lock = threading.Lock()
        self._stop = threading.Event()
        self._started = False
        # (wall_time, cumulative items completed): the best_effort
        # Retry-After derivation (telemetry/serving.backlog_retry_after)
        # reads this exactly like the gateway reads _rate_samples.
        self.rate_samples: collections.deque = collections.deque(maxlen=64)
        # (wall_time, cumulative completion tokens): the lane tokens/sec
        # gauge's window.
        self._token_samples: collections.deque = collections.deque(maxlen=64)
        self._items_completed = 0
        self._tokens_total = 0
        self._progress_lock = threading.Lock()
        self._last_progress = time.time()
        self._stall_fired_at = 0.0

    # -- wiring --------------------------------------------------------------

    def bind(self, dispatch, idle_fn=None) -> "BulkJobManager":
        """Attach the live relay closure (and optionally a zero-arg
        ``idle_fn`` reporting "the fleet has idle decode capacity" — the
        backlog-stall detector's second input)."""
        self._dispatch = dispatch
        if idle_fn is not None:
            self._idle_fn = idle_fn
        return self

    def start(self) -> int:
        """Resume every incomplete job found on disk, then accept new
        submissions. Returns the number of jobs resumed. Idempotent."""
        if self._started:
            return 0
        self._started = True
        resumed = 0
        for rec in load_jobs(self.directory):
            if rec.get("state") not in ("queued", "running"):
                continue
            job = self._rebuild_job(rec)
            if job is None:
                continue
            with self._jobs_lock:
                self._jobs[job.id] = job
            if self.admission is not None:
                # Quota state is in-memory and died with the old gateway:
                # re-register resumed work so NEW submissions see it —
                # resumed jobs themselves are already-accepted work and
                # must not be re-admitted against their own footprint.
                self.admission.reacquire_bulk(
                    job.tenant, job.n_items - len(job.done))
            if self.metrics is not None:
                self.metrics.jobs_resumed.inc()
            self.journal.event("bulk.job", schema=BULK_SCHEMA, job=job.id,
                               state="resumed",
                               tenant=sanitize_label(job.tenant),
                               n_items=job.n_items, n_done=len(job.done))
            self._launch(job)
            resumed += 1
        self._refresh_gauges()
        return resumed

    def _rebuild_job(self, rec: dict) -> _Job | None:
        """Resume state = results-file contiguous prefix ∪ journal
        ``bulk.item`` rows. The results file persists everything already
        flushed (rotation-proof); the journal covers the tail that was
        journaled but not yet flushed when the process died — bounded by
        the in-flight window, so segment rotation cannot out-age it."""
        job_id = rec.get("id") or ""
        if not _JOB_ID_RE.match(job_id):
            return None
        job = _Job(job_id, str(rec.get("tenant") or "anonymous"),
                   dict(rec.get("params") or {}),
                   int(rec.get("n_items") or 0), state="running",
                   created_ts=rec.get("created_ts"))
        job.n_failed = int(rec.get("n_failed") or 0)
        # 1) the flushed prefix (count whole lines; a torn tail line is
        #    simply re-flushed from its journal row).
        flushed_rows = _read_jsonl(_results_path(self.directory, job_id))
        job.flushed = 0
        for row in flushed_rows:
            if row.get("idx") == job.flushed:
                job.done.add(job.flushed)
                job.flushed += 1
            else:
                break
        # 2) journaled terminal rows beyond the prefix (this journal plus
        #    its rotated segments — EventJournal resumes the segment
        #    counter, so globbing the stem finds them all).
        for jrec in self._journal_rows():
            if jrec.get("event") != "bulk.item" or jrec.get("job") != job_id:
                continue
            idx = jrec.get("idx")
            if not isinstance(idx, int) or idx in job.done:
                continue
            job.done.add(idx)
            job.pending[idx] = {
                k: jrec[k] for k in
                ("idx", "status", "text", "completion_tokens", "attempts")
                if k in jrec
            }
            if jrec.get("status") != "ok":
                job.n_failed += 1
        if self.usage is not None and job.pending:
            # A death between _finish_item's journal row and its usage row
            # leaves a terminal item unbilled, and resume never runs it
            # again: bill it now. Only this tail can be affected (an item
            # is flushed after it is billed), so the ledgers beside this
            # incarnation's are read only when there is one.
            billed = {
                r.get("item")
                for r in load_usage(os.path.dirname(self.usage.path))
                if r.get("bulk_job") == job_id
            }
            for idx, row in sorted(job.pending.items()):
                if idx not in billed:
                    self._bill(job, idx, row)
        self._flush_locked_job(job)
        return job

    def _journal_rows(self) -> list[dict]:
        stem, ext = os.path.splitext(self.journal.path)
        paths = sorted(glob.glob(f"{stem}.r[0-9][0-9][0-9][0-9]{ext}"))
        paths.append(self.journal.path)
        rows: list[dict] = []
        for p in paths:
            rows.extend(read_journal(p))
        return rows

    # -- submission ----------------------------------------------------------

    def submit(self, tenant: str, prompts: list[str],
               params: dict | None = None) -> dict:
        """Accept one job: persist spec+items (durable BEFORE the ack),
        journal it, and start dispatching. ``tenant`` is the
        credential-safe label. Raises ValueError on a bad spec — the
        handler maps that to a 400."""
        cfg = self.config
        if not prompts:
            raise ValueError("bulk job needs at least one prompt")
        if len(prompts) > cfg.max_items_per_job:
            raise ValueError(
                f"bulk job holds {len(prompts)} items; cap is "
                f"{cfg.max_items_per_job} (bulk.max_items_per_job)")
        if not all(isinstance(p, str) and p for p in prompts):
            raise ValueError("every bulk item needs a non-empty prompt")
        params = dict(params or {})
        sampling = params.get("sampling")
        if sampling is not None and not isinstance(sampling, dict):
            raise ValueError("sampling must be a JSON object")
        max_new = params.get("max_new", cfg.default_max_new)
        if not isinstance(max_new, int) or max_new <= 0:
            raise ValueError("max_new must be a positive integer")
        job_id = f"bj-{uuid.uuid4().hex[:12]}"
        job = _Job(job_id, tenant, {
            "adapter": str(params.get("adapter") or ""),
            "max_new": int(max_new),
            "sampling": dict(sampling or {}),
        }, len(prompts))
        # Items first, then the job file: a job file without its items
        # would resume as an empty job; items without a job file are an
        # orphan sweep-up, never a wrong answer.
        with open(_items_path(self.directory, job_id), "w") as f:
            for idx, prompt in enumerate(prompts):
                f.write(json.dumps({"idx": idx, "prompt": prompt}) + "\n")
        self._save_job(job)
        self.journal.event("bulk.job", schema=BULK_SCHEMA, job=job_id,
                           state="queued", tenant=sanitize_label(tenant),
                           n_items=job.n_items)
        with self._jobs_lock:
            self._jobs[job_id] = job
        if self.metrics is not None:
            self.metrics.jobs_submitted.inc()
        if self._started:
            self._launch(job)
        self._refresh_gauges()
        return self.status(job_id)

    def _save_job(self, job: _Job) -> None:
        """Atomic spec+state snapshot (the checkpoint-commit idiom):
        readers (resume, the CLI) never observe a torn job file."""
        path = _job_path(self.directory, job.id)
        tmp = f"{path}.tmp.{os.getpid()}"
        with job.lock:
            rec = {
                "schema": BULK_SCHEMA,
                "id": job.id,
                "tenant": job.tenant,
                "state": job.state,
                "params": job.params,
                "n_items": job.n_items,
                "n_done": len(job.done),
                "n_failed": job.n_failed,
                "created_ts": job.created_ts,
            }
        with open(tmp, "w") as f:
            json.dump(rec, f, sort_keys=True)
        os.replace(tmp, path)

    # -- queries -------------------------------------------------------------

    def get(self, job_id: str) -> _Job | None:
        with self._jobs_lock:
            return self._jobs.get(job_id)

    def status(self, job_id: str) -> dict | None:
        job = self.get(job_id)
        if job is None:
            # Terminal jobs of past incarnations still answer from disk.
            for rec in load_jobs(self.directory):
                if rec.get("id") == job_id:
                    return {**rec, "results":
                            _results_path(self.directory, job_id)}
            return None
        with job.lock:
            state = job.state
        return {
            "id": job.id,
            "tenant": job.tenant,
            "state": state,
            "params": job.params,
            "created_ts": job.created_ts,
            **job.counters(),
            "results": _results_path(self.directory, job.id),
        }

    def jobs(self) -> list[dict]:
        with self._jobs_lock:
            ids = list(self._jobs)
        out = [self.status(i) for i in ids]
        seen = {o["id"] for o in out if o}
        for rec in load_jobs(self.directory):
            if rec.get("id") not in seen:
                out.append(rec)
        return sorted([o for o in out if o],
                      key=lambda r: r.get("created_ts") or 0.0)

    def results_path(self, job_id: str) -> str:
        return _results_path(self.directory, job_id)

    def backlog(self) -> int:
        """Work items not yet terminal across non-terminal jobs — the
        autoscale scale-up signal and the best_effort Retry-After input."""
        total = 0
        with self._jobs_lock:
            jobs = list(self._jobs.values())
        for job in jobs:
            with job.lock:
                if job.state in ("queued", "running"):
                    total += job.n_items - len(job.done)
        return total

    def active_jobs(self) -> int:
        with self._jobs_lock:
            jobs = list(self._jobs.values())
        n = 0
        for job in jobs:
            with job.lock:
                n += job.state in ("queued", "running")
        return n

    def tokens_per_s(self) -> float:
        """Windowed lane token rate over the recent samples (the
        backlog_retry_after estimator shape)."""
        now = time.time()
        recent = [(t, c) for t, c in tuple(self._token_samples)
                  if now - t <= 60.0]
        if len(recent) >= 2:
            (t0, c0), (t1, c1) = recent[0], recent[-1]
            if t1 - t0 >= 0.5 and c1 > c0:
                return (c1 - c0) / (t1 - t0)
        return 0.0

    def tokens_total(self) -> int:
        """Cumulative lane completion tokens this incarnation — bench
        snapshots it around the timed region to grade the soak rate."""
        with self._progress_lock:
            return self._tokens_total

    def cancel(self, job_id: str) -> bool:
        job = self.get(job_id)
        if job is None:
            return False
        with job.lock:
            if job.state not in ("queued", "running"):
                return True  # idempotent: cancelling a terminal job is a no-op
            job.cancel_requested = True
        return True

    def drain(self, timeout_s: float = 60.0) -> bool:
        """Block until no job is queued/running and every job thread has
        finished finalizing (tests, drills). Returns False on timeout."""
        deadline = time.monotonic() + timeout_s
        while self.active_jobs() and time.monotonic() < deadline:
            time.sleep(0.02)
        # A job's terminal state is visible before _finalize_job has saved,
        # journaled and counted it: join the threads so the caller reads
        # all of it, not just the state.
        with self._jobs_lock:
            threads = [job.thread for job in self._jobs.values()]
        threads = [t for t in threads if t is not None and t.ident]
        for t in threads:
            t.join(max(0.0, deadline - time.monotonic()))
        return (self.active_jobs() == 0
                and not any(t.is_alive() for t in threads))

    # -- the dispatch loop ---------------------------------------------------

    def _launch(self, job: _Job) -> None:
        if self._dispatch is None:
            raise RuntimeError(
                "bulk manager is not bound to a dispatch path; call "
                "bind(dispatch=...) before start()/submit()")
        t = threading.Thread(target=self._run_job, args=(job,),
                             name=f"bulk-job-{job.id}", daemon=True)
        job.thread = t
        with job.lock:
            job.state = "running"
        self._save_job(job)
        t.start()

    def _load_prompts(self, job: _Job) -> dict[int, str]:
        prompts: dict[int, str] = {}
        for row in _read_jsonl(_items_path(self.directory, job.id)):
            idx = row.get("idx")
            if isinstance(idx, int) and isinstance(row.get("prompt"), str):
                prompts[idx] = row["prompt"]
        return prompts

    def _run_job(self, job: _Job) -> None:
        """One job's dispatch loop: a bounded in-flight window of relay
        workers, a contiguous-prefix results flush, and the stall
        detector riding the wait loop."""
        window = max(1, self.config.max_in_flight)
        prompts = self._load_prompts(job)
        with job.lock:
            todo = [i for i in range(job.n_items)
                    if i not in job.done and i in prompts]
            missing = [i for i in range(job.n_items)
                       if i not in job.done and i not in prompts]
        # Items whose spec line tore (death mid-submit cannot reach here —
        # submit acks only after the items file is fully written — but a
        # hand-edited or truncated file must fail loudly, not hang).
        for idx in missing:
            self._finish_item(job, idx, {"idx": idx, "status": "error",
                                         "text": "", "completion_tokens": 0,
                                         "attempts": 0})
        try:
            with ThreadPoolExecutor(
                    max_workers=window,
                    thread_name_prefix=f"bulk-{job.id}") as pool:
                futures = set()
                it = iter(todo)
                while True:
                    if self._stop.is_set():
                        return  # manager closing; the job resumes next start
                    cancelled = False
                    with job.lock:
                        cancelled = job.cancel_requested
                    if not cancelled:
                        for idx in it:
                            futures.add(pool.submit(
                                self._run_item, job, idx, prompts[idx]))
                            if len(futures) >= window:
                                break
                    if not futures:
                        break
                    done, futures = wait(futures,
                                         timeout=self.config.poll_interval_s,
                                         return_when=FIRST_COMPLETED)
                    for f in done:
                        exc = f.exception()
                        if exc is not None:
                            logger.exception("bulk: item worker died",
                                             exc_info=exc)
                    self._maybe_stall()
                    if cancelled:
                        # Stop issuing; in-flight items finish (their
                        # journal rows keep resume exact), queued todo is
                        # abandoned.
                        if not futures:
                            break
        finally:
            self._finalize_job(job)

    def _run_item(self, job: _Job, idx: int, prompt: str) -> None:
        """Dispatch one work item to a terminal outcome, retrying
        transient failures. The chaos seam sits BEFORE each attempt —
        ``bulk.dispatch:kill`` is the mid-job gateway death the resume
        drill injects; ``error`` rides the ordinary retry path."""
        cfg = self.config
        m = self.metrics
        attempts = 0
        result = {"outcome": "error", "text": "", "completion_tokens": 0}
        while True:
            attempts += 1
            # Journaled pre-attempt (line-buffered: on disk before the
            # dispatch, so a kill mid-attempt leaves the re-dispatch
            # countable — the resume drill's evidence).
            self.journal.event("bulk.dispatch", schema=BULK_SCHEMA,
                               job=job.id, idx=idx, attempt=attempts)
            try:
                maybe_inject("bulk.dispatch", request=idx + 1)
                result = self._dispatch({
                    "job": job.id,
                    "idx": idx,
                    "rid": f"bulk-{job.id}-{idx}",
                    "prompt": prompt,
                    "tenant": job.tenant,
                    "adapter": job.params.get("adapter") or "",
                    "max_new": int(job.params.get("max_new") or
                                   cfg.default_max_new),
                    "sampling": dict(job.params.get("sampling") or {}),
                }) or {"outcome": "error"}
            except InjectedFault:
                result = {"outcome": "error", "text": "",
                          "completion_tokens": 0}
            except Exception:  # noqa: BLE001 - a dispatch bug fails the item
                logger.exception("bulk: dispatch raised (job %s item %d)",
                                 job.id, idx)
                result = {"outcome": "error", "text": "",
                          "completion_tokens": 0}
            outcome = str(result.get("outcome") or "error")
            if m is not None:
                m.items_dispatched.inc()
            if self.flight is not None:
                # One ROUTING-style ring row per dispatch decision: the
                # black box shows which items the lane pushed, and what
                # the fleet said.
                self.flight.ring(BULK_RING).record(
                    job=job.id, idx=idx, attempt=attempts, outcome=outcome,
                    tenant=job.tenant,
                )
            if outcome == "200":
                self._finish_item(job, idx, {
                    "idx": idx, "status": "ok",
                    "text": str(result.get("text") or ""),
                    "completion_tokens":
                        int(result.get("completion_tokens") or 0),
                    "attempts": attempts,
                })
                return
            stopping = self._stop.is_set()
            with job.lock:
                stopping = stopping or job.cancel_requested
            if (outcome not in RETRYABLE_OUTCOMES
                    or attempts > max(1, cfg.retry_limit) or stopping):
                if stopping and outcome in RETRYABLE_OUTCOMES:
                    # Mid-shutdown/cancel: leave the item incomplete (no
                    # terminal row) rather than branding it failed — a
                    # resume re-dispatches it.
                    return
                self._finish_item(job, idx, {
                    "idx": idx, "status": "error", "text": "",
                    "completion_tokens": 0, "attempts": attempts,
                })
                return
            if m is not None:
                m.items_retried.inc()
                if outcome == "429":
                    m.items_preempted.inc()
            retry_after = result.get("retry_after_s")
            backoff = (float(retry_after) if isinstance(
                retry_after, (int, float)) and retry_after > 0
                else min(2.0, 0.05 * attempts))
            # Interruptible sleep: cancel/close must not wait out a backoff.
            if self._stop.wait(min(backoff, 5.0)):
                return

    def _finish_item(self, job: _Job, idx: int, row: dict) -> None:
        """One item's terminal path, in durability order: journal row
        first (the crash-consistent record), then the usage row, then the
        in-memory flush + counters. Exactly once per (job, idx) per
        process — and the resume scan skips journaled idxs, so exactly
        once across incarnations too."""
        self.journal.event("bulk.item", schema=BULK_SCHEMA, job=job.id,
                           **row)
        if self.usage is not None:
            self._bill(job, idx, row)
        failed = row["status"] != "ok"
        with job.lock:
            if idx in job.done:
                return
            job.done.add(idx)
            job.pending[idx] = row
            job.n_dispatched += 1
            job.n_retried += max(0, int(row.get("attempts") or 1) - 1)
            if failed:
                job.n_failed += 1
        self._flush_locked_job(job)
        m = self.metrics
        if m is not None:
            m.items_completed.inc()
            if failed:
                m.items_failed.inc()
            m.completion_tokens.inc(int(row.get("completion_tokens") or 0))
        with self._progress_lock:
            self._last_progress = time.time()
            self._items_completed += 1
            self._tokens_total += int(row.get("completion_tokens") or 0)
            self.rate_samples.append((time.time(), self._items_completed))
            self._token_samples.append((time.time(), self._tokens_total))
        self._refresh_gauges()

    def _bill(self, job: _Job, idx: int, row: dict) -> None:
        """One item's usage row. bulk_job attribution (ISSUE 15
        coupling): the aggregator bills bulk separately from interactive
        — rollups preserve unknown fields, so the row stays filterable
        downstream."""
        self.usage.record(
            tenant=job.tenant,
            outcome="200" if row.get("status") == "ok" else "503",
            slo_class="best_effort",
            bulk_job=job.id,
            item=idx,
            completion_tokens=int(row.get("completion_tokens") or 0),
        )

    def _flush_locked_job(self, job: _Job) -> None:
        """Contiguous-prefix flush: append every pending row whose idx
        extends the flushed prefix — the results file is gap-free and
        order-stable BY CONSTRUCTION, resumable by byte range."""
        with job.lock:
            if job.flushed in job.pending:
                # Line-buffered append, the journal's durability posture.
                with open(_results_path(self.directory, job.id), "a",
                          buffering=1) as f:
                    while job.flushed in job.pending:
                        row = job.pending.pop(job.flushed)
                        f.write(json.dumps(row, sort_keys=True) + "\n")
                        job.flushed += 1

    def _finalize_job(self, job: _Job) -> None:
        if self._stop.is_set():
            return  # manager close: job stays "running" on disk -> resumes
        with job.lock:
            if job.cancel_requested and len(job.done) < job.n_items:
                job.state = "cancelled"
            elif job.n_failed:
                job.state = "failed"
            else:
                job.state = "completed"
            state = job.state
        self._save_job(job)
        self.journal.event("bulk.job", schema=BULK_SCHEMA, job=job.id,
                           state=state, tenant=sanitize_label(job.tenant),
                           n_items=job.n_items, n_done=len(job.done),
                           n_failed=job.n_failed)
        if self.admission is not None:
            self.admission.release_bulk(job.tenant, job.n_items)
        m = self.metrics
        if m is not None:
            {"completed": m.jobs_completed, "cancelled": m.jobs_cancelled,
             "failed": m.jobs_failed}[state].inc()
        self._refresh_gauges()

    def _refresh_gauges(self) -> None:
        m = self.metrics
        if m is not None:
            m.backlog.set(self.backlog())
            m.jobs_active.set(self.active_jobs())
            m.tokens_per_s.set(round(self.tokens_per_s(), 3))

    # -- the backlog-stall detector ------------------------------------------

    def _maybe_stall(self) -> None:
        """backlog deep AND not draining AND replicas idle = the lane is
        wedged (dead dispatch path, mis-pinned class, quota livelock) —
        exactly one incident bundle via the anomaly plane's fingerprint
        cooldown, chaos-attributed like every bundle."""
        if self.plane is None or self._idle_fn is None:
            return
        cfg = self.config
        now = time.time()
        with self._progress_lock:
            stalled_s = now - self._last_progress
        if stalled_s < cfg.stall_after_s:
            return
        if now - self._stall_fired_at < cfg.stall_after_s:
            return  # local rate-limit under the plane's own cooldown
        backlog = self.backlog()
        if backlog <= 0:
            return
        try:
            idle = bool(self._idle_fn())
        except Exception:  # noqa: BLE001 - a broken probe reads busy
            idle = False
        if not idle:
            return  # busy replicas = the lane is yielding, not stuck
        self._stall_fired_at = now
        from ditl_tpu.telemetry.anomaly import Anomaly

        self.plane.trigger(Anomaly(
            kind="bulk.backlog_stall",
            severity="critical",
            detail={
                "fingerprint_key": "bulk",
                "backlog_items": backlog,
                "stalled_s": round(stalled_s, 3),
                "jobs_active": self.active_jobs(),
                "replicas_idle": True,
            },
        ))

    # -- lifecycle -----------------------------------------------------------

    def close(self, timeout_s: float = 5.0) -> None:
        """Stop dispatching and persist. In-flight items are abandoned
        without terminal rows (resume re-dispatches them); jobs stay
        ``running`` on disk, which is what makes them resumable."""
        self._stop.set()
        with self._jobs_lock:
            jobs = list(self._jobs.values())
        deadline = time.monotonic() + timeout_s
        for job in jobs:
            t = job.thread
            if t is not None and t.is_alive():
                t.join(timeout=max(0.1, deadline - time.monotonic()))
            self._save_job(job)
        self.journal.close()


# -- on-disk readers (shared by resume, status, and the CLI) -----------------


def _read_jsonl(path: str) -> list[dict]:
    out: list[dict] = []
    try:
        with open(path) as f:
            for line in f:
                try:
                    rec = json.loads(line)
                except ValueError:
                    continue  # torn tail line: skipped, never fatal
                if isinstance(rec, dict):
                    out.append(rec)
    except OSError:
        pass
    return out


def load_jobs(directory: str) -> list[dict]:
    """Every readable job file in ``directory`` (torn/partial files are
    skipped — the atomic save means those cannot exist short of disk
    corruption), sorted by creation time."""
    out: list[dict] = []
    for path in glob.glob(os.path.join(directory, "bulk-job-*.json")):
        try:
            with open(path) as f:
                rec = json.load(f)
        except (OSError, ValueError):
            continue
        if isinstance(rec, dict) and rec.get("id"):
            out.append(rec)
    return sorted(out, key=lambda r: r.get("created_ts") or 0.0)


def main(argv: list[str] | None = None) -> int:
    """``python -m ditl_tpu.gateway.bulk --dir D [--list|--show ID]`` —
    the journal/job-file reader for operators (no live gateway needed;
    troubleshooting §37 walks the stuck-job signatures)."""
    import argparse

    parser = argparse.ArgumentParser(prog="python -m ditl_tpu.gateway.bulk")
    parser.add_argument("--dir", required=True,
                        help="the bulk lane's state directory (bulk.dir)")
    parser.add_argument("--list", action="store_true",
                        help="one line per job: id, state, progress")
    parser.add_argument("--show", default="", metavar="ID",
                        help="full detail for one job: spec, counters, "
                        "last dispatch/terminal journal rows")
    args = parser.parse_args(argv)
    jobs = load_jobs(args.dir)
    if args.show:
        rec = next((j for j in jobs if j["id"] == args.show), None)
        if rec is None:
            print(f"no job {args.show!r} in {args.dir}")
            return 1
        results = _read_jsonl(_results_path(args.dir, args.show))
        rows: list[dict] = []
        stem, ext = os.path.splitext(
            bulk_journal_path(args.dir, "gateway"))
        for p in sorted(glob.glob(f"{stem}*{ext}")):
            rows.extend(r for r in read_journal(p)
                        if r.get("job") == args.show)
        print(json.dumps({
            **rec,
            "results_flushed": len(results),
            "journal_dispatches": sum(
                1 for r in rows if r["event"] == "bulk.dispatch"),
            "journal_terminal": sum(
                1 for r in rows if r["event"] == "bulk.item"),
            "journal_tail": rows[-10:],
        }, indent=2, sort_keys=True))
        return 0
    # --list (the default)
    if not jobs:
        print(f"no bulk jobs in {args.dir}")
        return 0
    for rec in jobs:
        n = rec.get("n_items") or 0
        done = rec.get("n_done") or 0
        print(f"{rec['id']}  {rec.get('state', '?'):9s}  "
              f"{done}/{n} items  tenant={rec.get('tenant', '?')}  "
              f"failed={rec.get('n_failed', 0)}")
    return 0


if __name__ == "__main__":
    import sys

    from ditl_tpu.utils.logging import setup_logging

    setup_logging()
    sys.exit(main())
