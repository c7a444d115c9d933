"""OpenAI-compatible HTTP server over the local TPU model (L4/L6).

The reference points LiteLLM at an external OpenAI-compatible endpoint
(``CONFIG['API_BASE']``, ref ``src/distributed_inference.py:53-54``) — the
serving side is someone else's. This module supplies it: a ``/v1/chat/
completions`` + ``/v1/completions`` server backed by the KV-cache Generator,
so the framework's own L4 client (client/llm.py) — or litellm, or the openai
SDK — can evaluate against a model running on *this* TPU.

Threading model: stdlib ``ThreadingHTTPServer`` accepts concurrently. Two
engines (``--engine``):

- ``lockstep`` (default): a lock serializes device work; each request runs
  the batch Generator exclusively.
- ``continuous``: requests from all connections share slot-based decode
  ticks (infer/continuous.py) — concurrent requests batch on the device
  automatically, and a long generation no longer blocks short ones.

CLI (any host of a pod; serving is process-0-gated):

    python -m ditl_tpu.infer.server --preset tiny-llama --port 8300
"""

from __future__ import annotations

import argparse
import collections
import dataclasses
import json
import socket
import threading
import time
import uuid
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

from ditl_tpu.chaos import InjectedFault, maybe_inject
from ditl_tpu.config import ModelConfig
from ditl_tpu.data.tokenizer import Tokenizer
from ditl_tpu.infer.continuous import (
    BadRequestError,
    DeadlineExceededError,
    QueueFullError,
)
from ditl_tpu.infer.engine import GenerateConfig, Generator
from ditl_tpu.telemetry.serving import ServingMetrics
from ditl_tpu.telemetry.slo import BurnRateMonitor, serving_slo
from ditl_tpu.telemetry.usage import sanitize_label, tenant_label
from ditl_tpu.telemetry.tracing import (
    NULL_TRACER,
    StartupRecorder,
    Tracer,
    parse_traceparent,
    resolve_request_id,
)
from ditl_tpu.utils.http11 import KeepAliveHandlerMixin
from ditl_tpu.utils.logging import get_logger
from ditl_tpu.utils.profiling import compile_counter, start_profiler_server

logger = get_logger(__name__)

__all__ = ["DrainableHTTPServer", "serve", "make_server"]


class DrainableHTTPServer(ThreadingHTTPServer):
    """ThreadingHTTPServer with a graceful-drain lifecycle — the primitive
    the gateway's rolling restart (ditl_tpu/gateway/) builds on:

    - ``drain()`` flips ``/health`` to ``{"status": "draining"}`` and makes
      new completion/embedding work answer 503; in-flight requests finish.
    - ``close(drain=True)`` drains, waits for in-flight work to complete
      (bounded), then stops the serve loop and closes the socket — the
      ``SIGTERM`` disposition ``serve()`` installs.
    - ``kill()`` is the abrupt path (the in-process stand-in for kill -9):
      stop accepting, close the listening socket, and sever every open
      client connection mid-flight — clients observe connection reset /
      refused exactly as they would for a SIGKILLed process, which is what
      the gateway's retry-on-replica-death drills exercise.

    In-flight accounting covers the *completion-shaped* POST work (the
    device-occupying routes); metadata GETs are never blocked by a drain so
    health polling keeps working while draining.
    """

    def __init__(self, *args, **kwargs):
        self.draining = False
        self._inflight = 0
        self._idle = threading.Condition()
        self._conns: set = set()
        # Keep-alive connections currently parked between requests (the
        # handler thread blocked waiting for the next request line) —
        # maintained by KeepAliveHandlerMixin via note_parked. drain()
        # severs exactly these: without it a draining replica wedges on
        # the gateway pool's idle sockets (ISSUE 14).
        self._parked: set = set()  # guarded-by: _conn_lock
        self._conn_lock = threading.Lock()
        # (timestamp, completed-counter) samples for the backlog-aware
        # Retry-After derivation (_Handler._retry_after_s).
        self._rate_samples: collections.deque = collections.deque(maxlen=64)
        super().__init__(*args, **kwargs)

    # -- connection tracking (for kill() and drain()) ------------------------

    def process_request(self, request, client_address):
        with self._conn_lock:
            self._conns.add(request)
        super().process_request(request, client_address)

    def shutdown_request(self, request):
        with self._conn_lock:
            self._conns.discard(request)
            self._parked.discard(request)
        super().shutdown_request(request)

    def note_parked(self, request, parked: bool) -> None:
        """KeepAliveHandlerMixin callback: ``request``'s handler thread is
        (or stopped being) blocked between keep-alive requests."""
        with self._conn_lock:
            if parked:
                self._parked.add(request)
            else:
                self._parked.discard(request)

    def sever_parked(self) -> None:
        """Close every idle kept-alive connection. In-flight requests are
        untouched (a connection mid-request is not parked)."""
        with self._conn_lock:
            parked = list(self._parked)
        for s in parked:
            try:
                s.shutdown(socket.SHUT_RDWR)
            except OSError:
                pass
            try:
                s.close()
            except OSError:
                pass

    def handle_error(self, request, client_address):
        import sys

        # Severed connections (client gone, or kill() cut the socket) are
        # expected during drills — log, don't stack-trace to stderr.
        exc = sys.exc_info()[1]
        if isinstance(exc, (ConnectionError, BrokenPipeError, OSError)):
            logger.debug("connection error from %s: %s", client_address, exc)
            return
        super().handle_error(request, client_address)

    # -- in-flight accounting ----------------------------------------------

    def _enter_request(self) -> int:
        """Register one in-flight completion; returns the new count (the
        lockstep admission cap compares it against ``max_pending``)."""
        with self._idle:
            self._inflight += 1
            return self._inflight

    def _exit_request(self) -> None:
        with self._idle:
            self._inflight -= 1
            self._idle.notify_all()

    @property
    def inflight(self) -> int:
        return self._inflight

    # -- lifecycle ----------------------------------------------------------

    def drain(self) -> None:
        """Stop accepting new work (503) while in-flight requests finish;
        /health reports ``draining`` so a router stops sending traffic.
        Idle kept-alive connections are severed — parked peers (the
        gateway's connection pool, lingering pollers) would otherwise pin
        handler threads through the drain and could relay one more
        request onto a replica the fleet believes is gone. New
        connections are still accepted (metadata routes keep working);
        they just stop being kept alive while draining."""
        self.draining = True
        self.sever_parked()

    def wait_idle(self, timeout: float | None = None) -> bool:
        """Block until no completion work is in flight. Returns False on
        timeout (callers may proceed to a hard stop)."""
        with self._idle:
            return self._idle.wait_for(
                lambda: self._inflight == 0, timeout=timeout
            )

    def close(self, drain: bool = True, timeout: float = 30.0) -> None:
        """Graceful shutdown: drain, wait for in-flight work (bounded by
        ``timeout``), stop the serve loop, close the socket. Must be called
        from a thread other than the one running ``serve_forever``."""
        if drain:
            self.drain()
            if not self.wait_idle(timeout):
                logger.warning(
                    "drain timed out after %.1fs with %d request(s) in "
                    "flight; closing anyway", timeout, self._inflight,
                )
        self.shutdown()
        self.server_close()

    def kill(self) -> None:
        """Abrupt death: close the listening socket and sever every open
        client connection. From the network's perspective this is
        indistinguishable from the process being SIGKILLed — new connects
        are refused, in-flight requests see a reset."""
        self.shutdown()
        self.server_close()
        with self._conn_lock:
            conns = list(self._conns)
        for s in conns:
            try:
                s.shutdown(socket.SHUT_RDWR)
            except OSError:
                pass
            try:
                s.close()
            except OSError:
                pass


def _stop_list(stop) -> list[str]:
    """Normalize OpenAI's `stop` (str | list | None) to <= 4 sequences.
    Raises ValueError on non-string entries (callers answer 400)."""
    if not stop:
        return []
    if isinstance(stop, str):
        stop = [stop]
    if not isinstance(stop, list) or any(not isinstance(s, str) for s in stop):
        raise ValueError("stop must be a string or an array of strings")
    return [s for s in stop if s][:4]


def _apply_stop(text: str, stops: list[str]) -> tuple[str, bool]:
    """Truncate at the earliest stop sequence (excluded, per OpenAI)."""
    cut = None
    for s in stops:
        i = text.find(s)
        if i >= 0 and (cut is None or i < cut):
            cut = i
    return (text, False) if cut is None else (text[:cut], True)


class _StopTracker:
    """Streaming stop handling: emits increments, holding back any trailing
    text that could be the start of a stop sequence spanning a chunk
    boundary."""

    def __init__(self, stops: list[str]):
        self.stops = stops
        self.acc = ""
        self.sent = 0
        self.hit = False

    def push(self, piece: str) -> str:
        """Add decoded text; return what is safe to emit now."""
        if self.hit:
            return ""
        self.acc += piece
        cut, self.hit = _apply_stop(self.acc, self.stops)
        if self.hit:
            out = cut[self.sent:]
            self.sent = len(cut)
            return out
        hold = 0
        for s in self.stops:
            for k in range(1, len(s)):
                if self.acc.endswith(s[:k]):
                    hold = max(hold, k)
        safe = len(self.acc) - hold
        out = self.acc[self.sent: safe] if safe > self.sent else ""
        self.sent = max(self.sent, safe)
        return out

    def flush(self) -> str:
        """End of stream: release any held-back stop-prefix text."""
        if self.hit:
            return ""
        out = self.acc[self.sent:]
        self.sent = len(self.acc)
        return out


def _tokenizer_loader(tokenizer) -> str:
    """What built the tokenizer: ``tokenizers`` or ``transformers`` for a
    Hugging Face one (``HFTokenizer.loader``), ``byte`` for the built-in."""
    return getattr(tokenizer, "loader", "byte")


def _chat_prompt(messages: list[dict], tokenizer=None) -> str:
    """Render chat messages to a prompt string. HF tokenizers that carry a
    chat template (Llama-3.1 etc.) use it — real special-token turns, the
    same rendering the model was trained with; the byte/debug tokenizer
    falls back to plain-text role turns."""
    if getattr(tokenizer, "chat_template", None):
        try:
            return tokenizer.apply_chat_template(messages)
        except Exception:
            logger.exception("chat template failed; using plain-text turns")
    parts = [f"{m.get('role', 'user')}: {m.get('content', '')}" for m in messages]
    return "\n".join(parts) + "\nassistant:"


class _Handler(KeepAliveHandlerMixin, BaseHTTPRequestHandler):
    generator: Generator = None  # injected by make_server
    threaded_engine = None  # ContinuousEngine driver; None => lockstep path
    spec_generator = None  # speculative path for greedy lock-step requests
    model_name: str = "ditl-tpu"
    device_lock: threading.Lock = None
    default_max_tokens: int = 64
    # Admission cap for the handler-thread-per-request paths: completions
    # beyond this many in flight answer 429 instead of piling up on the
    # device lock (None = unbounded, the historical behavior). The
    # continuous engine has its own queue cap (--max-queue); this one is
    # the LOCKSTEP overload control.
    max_pending: int = None
    adapter_names: dict = {}  # multi-LoRA: request "model" name -> adapter id
    grammar_cache = None  # guided decoding: spec-key -> CompiledGrammar LRU
    grammar_lock: threading.Lock = None
    embed_cache = None  # /v1/embeddings: (batch, plen) -> jitted program LRU
    # Telemetry bundle (telemetry/serving.py): the continuous engine's own
    # when one is serving (it records queue-wait/TTFT/TPOT on its scheduler
    # ticks), else a server-owned bundle the lock-step path records into.
    serving_metrics: ServingMetrics = None
    # Request tracing (ISSUE 6, telemetry/tracing.py): unarmed by default;
    # make_server derives it from the engine's tracer so one knob arms the
    # replica end-to-end (server span -> engine lifecycle spans).
    tracer: Tracer = NULL_TRACER
    # SLO burn-rate monitor (telemetry/slo.py), rendered at /slo and as
    # gauges on /metrics.
    slo: BurnRateMonitor = None
    # Disaggregated-fleet role tag (ISSUE 9): echoed on /health so the
    # gateway's role-aware routing reads the replica's OWN claim.
    role: str = "hybrid"
    # Incident manager (ISSUE 10, telemetry/incident.py): arms the
    # /incidents listing endpoint; None => 404 (unarmed is distinguishable
    # from "no incidents").
    incidents = None
    # KV handoff (ISSUE 13): arms the /internal/prefill + /internal/
    # kv_handoff endpoints (paged continuous engines only) and the
    # kv_handoff flag on /health the gateway's orchestration keys on.
    kv_handoff_enabled: bool = False
    # Per-tenant usage metering (ISSUE 15, telemetry/usage.py): ``usage``
    # (UsageMeter) serves /usage and the ditl_usage_* families; the
    # continuous engine feeds it on its own terminal paths, the LOCKSTEP
    # paths feed it here (the engine never sees those requests).
    # ``usage_ledger`` (UsageLedger) is the lockstep paths' ledger sink
    # (the continuous engine writes its own rows). Both unarmed by
    # default — /usage then 404s (absent != zero usage).
    usage = None
    usage_ledger = None
    # Adapter plane (ISSUE 16, infer/adapters.py): the LIVE registry —
    # /v1/adapters lifecycle endpoints, live /v1/models, name->row
    # resolution under the registry lock (an evicted name 404s with a
    # reason, never a silent fall-through to base — the launch-frozen
    # adapter_names dict this replaces could not say "gone"), and the
    # owner-billing flush on /usage. None => legacy static routing.
    adapter_registry = None
    # /v1/stats' "device" (runtime/distributed.device_summary) and
    # "param_placement" (parallel/sharding.placement) blocks, read once.
    placement_info: dict = {}

    def log_message(self, *args):  # route through our logger, not stderr
        logger.debug("http: " + args[0], *args[1:])

    def send_response(self, code, message=None):
        self._status = code  # the ``server.request`` span's ``status``
        super().send_response(code, message)

    def _request_id(self) -> str:
        """Stable per-request id: the client's sanitized ``X-Request-Id``
        or a generated one — echoed on EVERY response (success, 429, 504,
        SSE) so client-side logs join to traces (ISSUE 6 satellite). Reset
        per request in do_GET/do_POST: one handler instance serves many
        requests on a keep-alive connection."""
        rid = getattr(self, "_rid", None)
        if rid is None:
            rid = resolve_request_id(self.headers.get("X-Request-Id"))
            self._rid = rid
        return rid

    def _send_json(self, status: int, payload: dict) -> None:
        body = json.dumps(payload).encode("utf-8")
        self.send_response(status)
        self.send_header("Content-Type", "application/json")
        self.send_header("X-Request-Id", self._request_id())
        self.send_header("Content-Length", str(len(body)))
        self.end_headers()
        self.wfile.write(body)

    def _load_snapshot(self) -> dict:
        """The load signal routers consume (gateway/router.py
        least-outstanding): queue depth + active slots, from the engine's
        stats when a continuous engine serves, else from the server's own
        in-flight accounting (the device lock serializes, so the lockstep
        server is a 1-slot engine with ``inflight - 1`` waiting)."""
        eng = self._engine_for_stats()
        if eng is not None:
            st = eng.stats()
            out = {
                "queue_depth": int(st.get("queue_depth", 0)),
                "active_slots": int(st.get("slots_busy", 0)),
                "n_slots": int(st.get("n_slots", 1)),
            }
            # Prefix-cache accounting rides the health payload (ISSUE 8):
            # the gateway's Fleet folds each poll into its ReplicaView, so
            # per-replica hit ratios aggregate on the gateway /metrics
            # without an extra scrape fan-out.
            pc = st.get("prefix_cache")
            if isinstance(pc, dict):
                out["cache_hit_tokens"] = int(pc.get("hit_tokens", 0))
                out["cache_miss_tokens"] = int(pc.get("miss_tokens", 0))
            # KV-handoff cost-model inputs (ISSUE 13): the gateway's
            # transfer-vs-re-prefill decision reads these from ordinary
            # health polls. Measured values only — absent until the engine
            # has prefilled/imported something (absent != 0; the model
            # falls back to its configured floors).
            for key in ("prefill_tok_per_s", "kv_bytes_per_token"):
                if key in st:
                    out[key] = st[key]
            kvt = st.get("kv_transfer")
            if isinstance(kvt, dict) and "put_mbps" in kvt:
                out["kv_put_mbps"] = kvt["put_mbps"]
            if self.kv_handoff_enabled and st.get("cache_mode") == "paged":
                out["kv_handoff"] = True
            return out
        inflight = int(getattr(self.server, "inflight", 0))
        return {
            "queue_depth": max(0, inflight - 1),
            "active_slots": min(1, inflight),
            "n_slots": 1,
        }

    def _sample_service_rate(self) -> None:
        """Append a (now, completed) sample for the Retry-After derivation;
        called after every completion-shaped request (cheap host reads)."""
        samples = getattr(self.server, "_rate_samples", None)
        if samples is not None and self.serving_metrics is not None:
            samples.append((time.time(), self.serving_metrics.completed.value))

    def _retry_after_s(self) -> int:
        """Backlog-aware Retry-After: how long until the CURRENT backlog
        (queued + active requests) clears at the recently measured service
        rate — the shared telemetry.serving.backlog_retry_after derivation
        (clamped [1, 30] s, stale samples aged out), replacing the old
        hardcoded 1 s that synchronized the whole herd's retries onto the
        same instant."""
        from ditl_tpu.telemetry.serving import backlog_retry_after

        self._sample_service_rate()
        load = self._load_snapshot()
        backlog = load["queue_depth"] + load["active_slots"]
        samples = getattr(self.server, "_rate_samples", None)
        return backlog_retry_after(samples or (), backlog)

    def _send_429(self, message: str) -> None:
        """OpenAI rate-limit shape: clients back off and retry, spaced by
        the backlog-aware Retry-After (was a hardcoded 1 s, which
        synchronized the whole herd's retries onto the same instant)."""
        body = json.dumps({"error": {
            "message": message, "type": "rate_limit_error",
        }}).encode()
        self.send_response(429)
        self.send_header("Content-Type", "application/json")
        self.send_header("X-Request-Id", self._request_id())
        self.send_header("Retry-After", str(self._retry_after_s()))
        self.send_header("Content-Length", str(len(body)))
        self.end_headers()
        self.wfile.write(body)

    def _tenant_label(self) -> str:
        """This request's credential-safe tenant label (ISSUE 15). The
        gateway's ``X-Tenant-Label`` relay header wins — it carries the
        admission-layer identity (configured public name or sha digest;
        the gateway NEVER forwards the raw bearer spelling of a tenant it
        admitted). Direct clients fall back to their own Authorization
        bearer, reduced through ``tenant_label`` (digest — the raw key
        must never reach the ledger, /usage, or /metrics). TRUST MODEL:
        the header is honored from whoever can reach this port — the same
        private-network trust the replica's unauthenticated /metrics,
        /stats, and /internal endpoints already assume. On a replica
        exposed beyond the gateway, a client that learns another tenant's
        label can mis-attribute its OWN traffic onto that bill (billing
        pollution, not privilege: admission/quota enforcement stays at
        the gateway) — serve replicas behind the gateway, as everything
        since ISSUE 4 assumes (docs/design.md)."""
        hdr = self.headers.get("X-Tenant-Label")
        if hdr:
            return sanitize_label(hdr)
        auth = self.headers.get("Authorization", "")
        if auth.lower().startswith("bearer "):
            bearer = auth[7:].strip()
            if bearer:
                return tenant_label(bearer)
        return "anonymous"

    def _note_usage_lockstep(self, tenant: str, n_prompt: int, n_gen: int,
                             t0: float, outcome: str = "200",
                             slo_class: str | None = None) -> None:
        """Terminal usage row for a request the LOCKSTEP path served (the
        continuous engine ledgers its own). The device lock serializes
        whole requests, so the request wall doubles as the device-time
        estimate — exclusive occupancy, not a share."""
        if self.usage is None and self.usage_ledger is None:
            return
        dt = round(time.time() - t0, 6)
        row = {
            "tenant": sanitize_label(tenant), "outcome": outcome,
            "slo_class": slo_class or "interactive",
            "prompt_tokens": int(n_prompt), "generated_tokens": int(n_gen),
            "device_time_est_s": dt, "e2e_s": dt,
        }
        try:
            if self.usage is not None:
                self.usage.note_terminal(row)
            if self.usage_ledger is not None:
                self.usage_ledger.record(**row)
        except Exception:  # noqa: BLE001 - metering must not crash serving
            logger.exception("lockstep usage metering failed (row dropped)")

    def _gate_slo_class(self, slo_class, from_header) -> tuple:
        """This serving path cannot honor a scheduling class (lockstep,
        pod FIFO staging, adapter/logprobs fallbacks): drop a header-
        derived hint (the gateway stamps every relay best-effort), 400 an
        explicit non-default payload value (reject-don't-drop — the PR 5
        deadline split). Returns (ok, slo_class)."""
        if slo_class in (None, "interactive"):
            return True, slo_class
        if from_header:
            return True, None
        self._send_json(400, {"error": {"message":
            "slo_class requires the continuous-engine serving path (no "
            "lockstep or pod engine, adapter fallback, or logprobs beyond "
            "--logprobs-k)"}})
        return False, None

    # -- adapter plane (ISSUE 16, infer/adapters.py) -------------------------

    def _resolve_adapter(self, payload: dict):
        """This request's adapter ids (``[row]``, or None = base).

        The gateway's ``X-Adapter-Name`` pin (tenant->adapter pinning,
        gateway/admission ``per_tenant``) wins over the payload's model
        field — the X-SLO-Class precedence. With the registry armed the
        name resolves against LIVE state and an unknown/evicted name
        raises :class:`AdapterNotFound` (404 with a reason — never a
        silent fall-through to base); without it the legacy launch-frozen
        ``adapter_names`` dict routes and unknown names keep serving base
        (OpenAI compat: the model field stays advisory on adapters-less
        servers). Stamps ``self._adapter_fp`` (``adapter:<name>@g<gen>``)
        for the response's ``system_fingerprint`` — a client diffing two
        responses can SEE the publication boundary."""
        self._adapter_fp = None
        pin = self.headers.get("X-Adapter-Name")
        name = str(pin or payload.get("model") or "")
        reg = self.adapter_registry
        if reg is None:
            aid = self.adapter_names.get(name)
            return [aid] if aid is not None else None
        if not name or name == self.model_name:
            return None
        row, generation = reg.resolve(name)  # raises AdapterNotFound
        self._adapter_fp = f"adapter:{name}@g{generation}"
        return [row]

    def _adapter_admin(self, payload: dict, op: str) -> None:
        """POST /v1/adapters/{load,evict,publish}: the hot-lifecycle
        endpoints. Every refusal maps an :class:`AdapterError` status
        (404 unknown/evicted, 409 pool-full/busy, 422 failed
        verification) — reject-don't-drop, with the reason in the body."""
        from ditl_tpu.infer.adapters import AdapterError

        reg = self.adapter_registry
        if reg is None:
            self._send_json(404, {"error": {"message":
                "adapter plane not armed on this replica (serve a "
                "multi-LoRA continuous engine: --adapter and/or "
                "--adapter-pool)"}})
            return
        name = str(payload.get("name") or "")
        if not name:
            self._send_json(400, {"error": {"message":
                f"adapter {op} wants a non-empty 'name'"}})
            return
        if name == self.model_name:
            self._send_json(400, {"error": {"message":
                f"{name!r} is the base model name; an adapter cannot "
                f"shadow it"}})
            return
        try:
            if op == "evict":
                out = reg.evict(name)
            else:
                directory = str(payload.get("dir")
                                or payload.get("directory") or "")
                if not directory:
                    self._send_json(400, {"error": {"message":
                        f"adapter {op} wants 'dir' (a manifest-carrying "
                        f"adapter checkpoint directory or its parent "
                        f"with a LATEST pointer)"}})
                    return
                # The OWNER the row bills to: an explicit payload owner
                # (the gateway's publication fan-out forwards the
                # publisher's label) else the caller's own tenant label.
                owner = str(payload.get("owner") or "") or self._tenant_label()
                fn = reg.publish if op == "publish" else reg.load
                out = fn(name, directory, owner=owner)
            self._send_json(200, out)
        except AdapterError as e:
            self._send_json(e.status, {"error": {"message": str(e)}})
        except Exception as e:  # noqa: BLE001 - admin errors become JSON
            logger.exception("adapter %s %r failed", op, name)
            self._send_json(500, {"error": {"message": str(e)}})

    def do_GET(self):
        self._rid = None  # fresh id per request on keep-alive connections
        if self.path in ("/health", "/v1/health"):
            draining = bool(getattr(self.server, "draining", False))
            payload = {
                "status": "draining" if draining else "ok",
                "model": self.model_name,
                "draining": draining,
                # Disaggregated-fleet role (ISSUE 9): the gateway's Fleet
                # prefers this over the handle's configured role so a
                # relaunch with different args cannot route under a stale
                # tag.
                "role": self.role,
            }
            # Measured cold start (ISSUE 12): time-to-first-ready stamped
            # by serve() (process start -> port bound, compile cache
            # included). The gateway's autoscale planner derives its
            # scale-to-zero wake budget from this MEASURED value, never a
            # constant; absent on embedded servers that never stamped one.
            cold = getattr(self.server, "cold_start_s", None)
            if isinstance(cold, (int, float)):
                payload["cold_start_s"] = round(float(cold), 3)
            payload.update(self._load_snapshot())
            # Latency snapshot for the gateway's per-role TTFT/TPOT
            # aggregation (ISSUE 9): lifetime histogram p95s, present only
            # once something has been served (absent != zero).
            m = self.serving_metrics
            if m is not None:
                for key, hist in (("ttft_p95_s", m.ttft),
                                  ("tpot_p95_s", m.decode_token)):
                    q = hist.quantile(0.95) if hist.count else None
                    if q is not None:
                        payload[key] = round(q, 6)
            self._send_json(200, payload)
        elif self.path in ("/v1/stats", "/stats"):
            stats = {"model": self.model_name, "engine": "lockstep",
                     "draining": bool(getattr(self.server, "draining", False)),
                     "inflight": int(getattr(self.server, "inflight", 0)),
                     # The device as jax reports it in THIS process, and
                     # where the params live by their own shardings (both
                     # read once, at make_server) — a caller checks what
                     # the server runs on from the server's own answer.
                     **self.placement_info}
            stats.update(self._load_snapshot())
            # Programs built by this process and their seconds, cumulative:
            # read at both ends of a window like the other counters.
            compiles = compile_counter().snapshot()
            stats["compile_count_cum"] = compiles["compile_count"]
            stats["compile_s_cum"] = compiles["compile_s"]
            stats["compile_miss_count_cum"] = compiles["cache_miss_count"]
            # Where this process's start went (serve()'s StartupRecorder):
            # the legs sum to /health's cold_start_s.
            startup = getattr(self.server, "startup", None)
            if startup is not None:
                stats["startup"] = {
                    **startup.block(),
                    "tokenizer_loader": _tokenizer_loader(self.generator.tokenizer),
                }
            eng = self._engine_for_stats()
            if eng is not None:
                stats.update(eng.stats())
            spec = self.spec_generator
            if spec is not None:
                stats["speculative"] = True
                acc = getattr(spec, "acceptance_ema", None)
                inner = getattr(spec, "spec", spec)
                if acc is None:
                    acc = getattr(inner, "last_acceptance", None)
                if acc is not None:
                    stats["speculative_acceptance"] = round(acc, 3)
            self._send_json(200, stats)
        elif self.path in ("/v1/models", "/models"):
            # With the adapter plane armed, the list is the REGISTRY's
            # live state (one locked snapshot) — a hot-loaded adapter
            # appears, an evicted one disappears; the launch-frozen
            # adapter_names dict routes only on adapters-less servers.
            if self.adapter_registry is not None:
                names = sorted(self.adapter_registry.names())
            else:
                names = list(self.adapter_names)
            models = [{"id": self.model_name, "object": "model"}] + [
                {"id": name, "object": "model", "parent": self.model_name}
                for name in names
            ]
            self._send_json(200, {"object": "list", "data": models})
        elif self.path in ("/v1/adapters", "/adapters"):
            # Adapter-plane listing (ISSUE 16): pool occupancy + every
            # live binding (name/row/generation/step/owner) + evicted
            # tombstones. 404 when unarmed — distinguishable from an
            # armed, empty pool.
            if self.adapter_registry is None:
                self._send_json(404, {"error": {"message":
                    "adapter plane not armed on this replica (serve a "
                    "multi-LoRA continuous engine: --adapter and/or "
                    "--adapter-pool)"}})
            else:
                self._send_json(200, self.adapter_registry.list())
        elif self.path == "/metrics":
            self._metrics()
        elif self.path in ("/slo", "/v1/slo"):
            # SLO burn-rate evaluation (telemetry/slo.py): the scrape IS
            # the sampling cadence — each hit appends one cumulative
            # snapshot and grades the windows against it.
            if self.slo is None:
                self._send_json(404, {"error": {"message":
                    "no SLO monitor configured"}})
            else:
                self._send_json(200, self.slo.report())
        elif self.path in ("/usage", "/v1/usage"):
            # Per-tenant usage rollups (ISSUE 15): the meter's live
            # in-memory view — what the gateway's /usage fan-out
            # aggregates fleet-wide. 404 when metering is unarmed so an
            # aggregator can tell "no usage" from "not metering".
            if self.adapter_registry is not None and self.usage is not None:
                # Flush accrued adapter owner bills (HBM residency +
                # gather attribution, ISSUE 16) so the rollup below
                # carries them; the same rows land in the ledger sink.
                for row in self.adapter_registry.flush_billing():
                    self.usage.note_terminal(row)
            if self.usage is None:
                self._send_json(404, {"error": {"message":
                    "usage metering is not armed on this replica"}})
            else:
                self._send_json(200, {
                    "requests": self.usage.total_requests,
                    "tenants": self.usage.snapshot(),
                })
        elif self.path in ("/incidents", "/v1/incidents"):
            # Incident bundles (ISSUE 10): list this replica's assembled
            # bundle manifests. Torn/tmp dirs are skipped by the reader,
            # never an error; 404 when the incident plane is unarmed so a
            # fleet aggregator can tell "no incidents" from "not watching".
            if self.incidents is None:
                self._send_json(404, {"error": {"message":
                    "no incident manager configured"}})
            else:
                from ditl_tpu.telemetry.incident import list_bundles

                bundles = list_bundles(self.incidents.directory)
                self._send_json(200, {
                    "count": len(bundles),
                    "suppressed": self.incidents.suppressed_total,
                    "incidents": bundles,
                })
        elif self.path.partition("?")[0].rstrip("/") in ("/profile",
                                                         "/v1/profile"):
            self._profile(self.path.partition("?")[2])
        else:
            self._send_json(404, {"error": {"message": f"no route {self.path}"}})

    def _profile(self, query: str) -> None:
        """On-demand wall-clock profile (ISSUE 18): sample every thread
        for ``?seconds=N`` (clamped) and return flamegraph-ready
        collapsed stacks as text/plain — "what code is this replica
        running right now" without attaching a debugger. Stdlib sampler,
        no lock on the sample path: safe under live decode."""
        from ditl_tpu.telemetry.prof import profile_for

        seconds = 2.0
        for part in query.split("&"):
            if part.startswith("seconds="):
                try:
                    seconds = float(part.split("=", 1)[1])
                except ValueError:
                    self._send_json(400, {"error": {
                        "message": "seconds must be a number"}})
                    return
        seconds = min(max(seconds, 0.1), 60.0)
        body = profile_for(seconds).encode()
        self.send_response(200)
        self.send_header("Content-Type", "text/plain")
        self.send_header("Content-Length", str(len(body)))
        self.end_headers()
        self.wfile.write(body)

    def _metrics(self) -> None:
        """Prometheus text exposition (no device sync), two sections:

        1. The telemetry registry (telemetry/serving.py): REAL cumulative
           series — latency histograms (queue-wait, TTFT, per-token decode,
           e2e) as ``_bucket``/``_sum``/``_count`` triples and monotonic
           ``_total`` counters (admissions, 429s, preemptions, degrade
           windows, grammar-masked tokens, speculative accept/reject).
        2. The /v1/stats snapshot flattened to ``ditl_serving_<path>``
           gauges (slot occupancy, queue depth, page pool, acceptance EMA)
           — point-in-time state, kept as gauges on purpose."""
        stats: dict = {}
        eng = self._engine_for_stats()
        if eng is not None:
            stats.update(eng.stats())
        spec = self.spec_generator
        if spec is not None:
            # Lock-step speculative serving (no continuous engine): surface
            # the same acceptance /v1/stats reports.
            stats["lockstep_speculative"] = True
            acc = getattr(spec, "acceptance_ema", None)
            if acc is None:
                acc = getattr(getattr(spec, "spec", spec),
                              "last_acceptance", None)
            if acc is not None:
                stats["lockstep_speculative_acceptance"] = round(acc, 3)

        lines: list[str] = []
        reserved: set[str] = set()
        if self.slo is not None:
            # Refresh the ditl_slo_* burn-rate gauges (they live in the
            # serving registry) so /metrics carries the same numbers /slo
            # renders; the scrape doubles as the monitor's sample tick.
            self.slo.report()
        if self.serving_metrics is not None:
            lines.extend(self.serving_metrics.render().splitlines())
            # A flattened stats gauge must not shadow a registry metric
            # (e.g. the lifetime "preemptions" count, now a real _total
            # counter) — exposing both a `x` gauge and an `x_total` counter
            # for the same fact invites dashboards built on the wrong one.
            reserved = set(self.serving_metrics.registry._metrics)

        from ditl_tpu.telemetry.serving import flattened_stats_lines

        lines.extend(flattened_stats_lines(stats, reserved))
        # HBM accounting (telemetry/memwatch.py, ISSUE 7): per-device
        # allocator gauges (bytes in use, high-watermark, limit) sampled at
        # scrape time — absent (not zero) on backends without memory stats.
        from ditl_tpu.telemetry.memwatch import memory_metrics_lines

        lines.extend(memory_metrics_lines())
        lines.append("# TYPE ditl_serving_up gauge")
        lines.append("ditl_serving_up 1")
        body = ("\n".join(lines) + "\n").encode()
        self.send_response(200)
        self.send_header("Content-Type", "text/plain; version=0.0.4")
        self.send_header("X-Request-Id", self._request_id())
        self.send_header("Content-Length", str(len(body)))
        self.end_headers()
        self.wfile.write(body)

    def _engine_for_stats(self):
        """The serving driver, if any (both drivers expose ``stats()``)."""
        return self.threaded_engine

    def do_POST(self):
        self._rid = None  # fresh id per request on keep-alive connections
        self._adapter_fp = None  # set by _resolve_adapter per request
        # An armed tracer's ``server.request`` span starts HERE, before the
        # body is read, and ends with the status the response went out with.
        self._t_entry = time.time() if self.tracer.armed else None
        self._status = None
        try:
            length = int(self.headers.get("Content-Length", 0))
            raw = self.rfile.read(length)
        except (ValueError, OSError) as e:
            # The request body was never (fully) consumed: leftover bytes
            # would be parsed as the NEXT request line on this kept-alive
            # connection (desync) — close it after the error response.
            self.close_connection = True
            self._send_json(400, {"error": {"message": f"bad request: {e}"}})
            return
        path = self.path.rstrip("/")
        if path.endswith("/internal/kv_handoff"):
            # Binary paged-KV blob (infer/kv_transfer.py) — never decoded
            # as JSON; its own header/crc framing rejects torn payloads.
            self._kv_handoff(raw or b"")
            return
        try:
            payload = json.loads(raw or b"{}")
        except (ValueError, json.JSONDecodeError) as e:
            self._send_json(400, {"error": {"message": f"bad request: {e}"}})
            return
        if path.endswith("/internal/prefill"):
            self._internal_prefill(payload)
        elif path.endswith(("/adapters/load", "/adapters/evict",
                            "/adapters/publish")):
            self._adapter_admin(payload, path.rsplit("/", 1)[1])
        elif path.endswith(("/chat/completions", "/completions", "/embeddings")):
            self._device_work(payload, path)
        elif path.endswith("/tokenize"):
            tok = self.generator.tokenizer
            text = payload.get("prompt")
            if not isinstance(text, str):
                self._send_json(400, {"error": {"message":
                    "tokenize wants a string 'prompt'"}})
                return
            ids = tok.encode(text)
            if payload.get("add_special_tokens", True):
                ids = [tok.bos_id] + ids
            self._send_json(200, {"tokens": ids, "count": len(ids),
                                  "max_model_len": self.generator.cfg.max_seq_len
                                  if hasattr(self.generator, "cfg") else None})
        elif path.endswith("/detokenize"):
            tok = self.generator.tokenizer
            ids = payload.get("tokens")
            if not isinstance(ids, list) or any(
                not isinstance(i, int) for i in ids
            ):
                self._send_json(400, {"error": {"message":
                    "detokenize wants an integer array 'tokens'"}})
                return
            self._send_json(200, {"prompt": tok.decode(ids)})
        else:
            self._send_json(404, {"error": {"message": f"no route {self.path}"}})

    # -- prefill->decode KV handoff (ISSUE 13) -------------------------------

    def _kv_gate(self):
        """Common gate for the /internal KV endpoints: 404 unless handoff
        is armed on a paged continuous engine (unarmed is distinguishable
        from broken); 503 while draining (the rolling-restart protocol —
        the gateway falls back to plain relay)."""
        eng = self.threaded_engine
        if (not self.kv_handoff_enabled or eng is None
                or getattr(eng, "_engine", None) is None
                or eng._engine.cache_mode != "paged"):
            self._send_json(404, {"error": {"message":
                "KV handoff not armed on this replica "
                "(--kv-handoff with a paged continuous engine)"}})
            return None
        if getattr(self.server, "draining", False):
            self._send_json(503, {"error": {"message":
                "server is draining; retry on another replica",
                "type": "unavailable_error"}})
            return None
        return eng

    def _internal_prefill(self, payload: dict) -> None:
        """Prefill-export half of the handoff: tokenize exactly like
        /v1/completions does (the shipped pages must match the relayed
        request's block keys bit-for-bit), prefill whatever isn't cached,
        and answer the serialized page blob. Runs on the engine driver
        thread via ThreadedEngine.call — handler threads never touch
        device state mid-tick."""
        eng = self._kv_gate()
        if eng is None:
            return
        prompt = payload.get("prompt")
        if not isinstance(prompt, str) or not prompt:
            self._send_json(400, {"error": {"message":
                "internal/prefill wants a non-empty string 'prompt'"}})
            return
        from ditl_tpu.infer.continuous import BadRequestError

        tok = self.generator.tokenizer
        ids = [tok.bos_id] + tok.encode(prompt)
        try:
            blob, shipped = eng.call(lambda: eng._engine.export_kv(ids))
        except BadRequestError as e:
            self._send_json(400, {"error": {"message": str(e)}})
            return
        except MemoryError as e:
            self._send_json(503, {"error": {"message": str(e)}})
            return
        except RuntimeError as e:
            self._send_json(500, {"error": {"message": str(e)}})
            return
        self.send_response(200)
        self.send_header("Content-Type", "application/octet-stream")
        self.send_header("X-Request-Id", self._request_id())
        self.send_header("X-KV-Tokens", str(shipped))
        self.send_header("Content-Length", str(len(blob)))
        self.end_headers()
        self.wfile.write(blob)

    def _kv_handoff(self, raw: bytes) -> None:
        """Import half of the handoff: install + publish a shipped
        prefill's pages so the relayed request's admission prefix-matches
        them. Torn/crc-failing/mismatched blobs answer 400 (counted on
        ``kv_handoff_rejected``) — reject-don't-install; the gateway's
        fallback relay re-prefills."""
        eng = self._kv_gate()
        if eng is None:
            return
        from ditl_tpu.infer.continuous import BadRequestError
        from ditl_tpu.infer.kv_transfer import KVTransferError

        try:
            res = eng.call(lambda: eng._engine.import_kv(raw))
        except (KVTransferError, BadRequestError, ValueError) as e:
            if self.serving_metrics is not None:
                self.serving_metrics.kv_handoff_rejected.inc()
            self._send_json(400, {"error": {"message": str(e)}})
            return
        except RuntimeError as e:
            self._send_json(500, {"error": {"message": str(e)}})
            return
        self._send_json(200, res)

    def _device_work(self, payload: dict, path: str) -> None:
        """Admission wrapper for the device-occupying POST routes
        (completions / chat completions / embeddings): reject while
        draining (503 — the rolling-restart protocol; a router retries on a
        peer replica), count in-flight work (the drain wait and the
        lockstep load signal), and apply the lockstep overload cap
        (``max_pending``) with a real 429 instead of an unbounded pile-up
        on the device lock."""
        srv = self.server
        if getattr(srv, "draining", False):
            self._send_json(503, {"error": {
                "message": "server is draining; retry on another replica",
                "type": "unavailable_error",
            }})
            return
        # Chaos seam: `error` answers a clean 500 (the gateway's retry
        # fodder), `delay`/`hang` make this replica slow-not-dead (hedging
        # and health-poll drills), `kill` is a real replica death.
        try:
            maybe_inject("server.request")
        except InjectedFault as e:
            self._send_json(500, {"error": {"message": str(e)}})
            return
        tracked = hasattr(srv, "_enter_request")
        n = srv._enter_request() if tracked else 0
        try:
            if self.max_pending is not None and n > self.max_pending:
                if self.serving_metrics is not None:
                    self.serving_metrics.queue_full.inc()
                self._send_429(
                    f"server at capacity ({self.max_pending} requests in "
                    "flight)"
                )
                return
            if path.endswith("/chat/completions"):
                self._complete(payload, chat=True)
            elif path.endswith("/completions"):
                self._complete(payload, chat=False)
            else:
                try:
                    self._embeddings(payload)
                except Exception as e:
                    logger.exception("embeddings failed")
                    self._send_json(500, {"error": {"message": str(e)}})
        finally:
            if tracked:
                srv._exit_request()
            self._sample_service_rate()

    def _observe_lockstep(self, t0: float, n_gen: int) -> None:
        """Telemetry for requests the LOCK-STEP path served (the continuous
        engine records its own on scheduler ticks): end-to-end latency plus
        the request/completion/token counters. Queue-wait/TTFT/TPOT have no
        lock-step analog — the device lock serializes whole requests."""
        m = self.serving_metrics
        if m is None:
            return
        dt = time.time() - t0
        m.requests.inc()
        m.completed.inc()
        m.tokens_generated.inc(n_gen)
        m.e2e.observe(dt)

    def _lockstep_generate(self, prompt_ids, gen, adapter_ids) -> list:
        """One lock-step generation, speculatively when eligible: greedy,
        no adapter, and the spec program's k+1 KV slack fits (ValueError
        falls back to the plain Generator). Outputs are token-identical by
        the speculation exactness contract. Used by both the streaming and
        non-streaming paths."""
        if (
            self.spec_generator is not None
            and gen.temperature == 0.0
            and adapter_ids is None
        ):
            try:
                with self.device_lock:
                    return self.spec_generator.generate_tokens(
                        [prompt_ids], gen.max_new_tokens
                    )[0]
            except ValueError:
                pass
        with self.device_lock:
            return self.generator.generate_tokens(
                [prompt_ids], gen, adapter_ids
            )[0]

    def _send_sse(self, events, span=None) -> None:
        """Stream pre-serialized JSON events as Server-Sent Events.
        ``span`` (an armed tracer's ``server.request``): gets ``events``,
        the events flushed to the socket.

        A client that vanishes mid-stream (broken pipe / reset on write)
        CANCELS the in-flight generation deterministically: closing the
        events generator unwinds its ``finally`` chain into
        ``ThreadedEngine.stream_one``'s cancel, freeing the slot instead of
        decoding the abandoned token budget — and the eviction is counted
        (``client_disconnects``) so vanishing clients are visible on
        /metrics, not just a GC side effect."""
        self.send_response(200)
        self.send_header("Content-Type", "text/event-stream")
        self.send_header("X-Request-Id", self._request_id())
        self.send_header("Cache-Control", "no-cache")
        # SSE opts out of HTTP/1.1 keep-alive by design: the stream has
        # no Content-Length, so close-delimited framing is the only
        # correct end-of-body signal — send_header("Connection", "close")
        # also flips the stdlib's close_connection for us (ISSUE 14).
        self.send_header("Connection", "close")
        self.end_headers()
        n = 0
        try:
            for event in events:
                self.wfile.write(f"data: {json.dumps(event)}\n\n".encode())
                self.wfile.flush()
                n += 1
            self.wfile.write(b"data: [DONE]\n\n")
            self.wfile.flush()
        except OSError:  # BrokenPipeError/ConnectionError are subclasses
            if self.serving_metrics is not None:
                self.serving_metrics.client_disconnects.inc()
            logger.info(
                "client disconnected mid-stream; cancelling in-flight "
                "generation"
            )
        finally:
            events.close()
            if span is not None:
                span.annotate(events=n)

    def _multi_complete(
        self, payload: dict, prompt: str, gen, *, chat: bool, n: int,
        best_of: int, adapter_ids=None, stops=None, grammar=None,
        slo_class=None, slo_from_header=False, trace=None, tenant=None,
    ) -> None:
        """OpenAI ``n``/``best_of``: generate ``best_of`` candidates (the
        continuous engine batches them into shared decode ticks; the
        lock-step path replicates the prompt into one batch) and return the
        top ``n`` ranked by mean token logprob (OpenAI's best_of rule).
        Ranking needs per-token logprobs: the continuous engine must be
        armed (``--logprobs-k``) when ``best_of > n``; the lock-step
        generator computes them natively."""
        t0 = time.time()
        rank = best_of > n
        eng = self.threaded_engine
        use_cont = eng is not None and (
            adapter_ids is None or getattr(eng, "multi_lora", False)
        ) and (not rank or getattr(eng, "logprobs_k", 0) > 0)
        if use_cont:
            tok = eng.tokenizer
            prompt_ids = [tok.bos_id] + tok.encode(prompt)
            reqs = eng.generate_many(
                prompt_ids, best_of,
                max_new_tokens=gen.max_new_tokens,
                temperature=gen.temperature, top_p=gen.top_p,
                seed=gen.seed,
                adapter_id=adapter_ids[0] if adapter_ids else None,
                grammar=grammar,
                slo_class=slo_class,
                logprobs=0 if rank else None,
                trace=trace,
                tenant=tenant,
            )
            cands = [(r.tokens, r.lp_token) for r in reqs]
        else:
            # Lock-step batch fallback: no class-ordered scheduler here —
            # drop/400 a non-default class (reject-don't-drop).
            ok, slo_class = self._gate_slo_class(slo_class, slo_from_header)
            if not ok:
                return
            if grammar is not None:
                # Name the ACTUAL missing piece: a guided request can land
                # here despite a guided-armed continuous engine when
                # best_of ranking needs logprobs the engine wasn't built
                # with.
                msg = (
                    "best_of ranking with guided decoding requires the "
                    "continuous engine armed with --logprobs-k >= 1"
                    if eng is not None and rank
                    and getattr(eng, "logprobs_k", 0) == 0
                    else "guided decoding requires the continuous engine"
                )
                self._send_json(400, {"error": {"message": msg}})
                return
            if rank and not hasattr(
                self.generator, "generate_tokens_with_logprobs"
            ):
                self._send_json(400, {"error": {"message":
                    "best_of ranking is not supported with --pod serving"}})
                return
            tok = self.generator.tokenizer
            prompt_ids = [tok.bos_id] + tok.encode(prompt)
            batch = [list(prompt_ids) for _ in range(best_of)]
            if rank:
                lp_gen = dataclasses.replace(gen, logprobs=1)
                with self.device_lock:
                    outs, lps = self.generator.generate_tokens_with_logprobs(
                        batch, lp_gen, adapter_ids * best_of if adapter_ids else None
                    )
                cands = [
                    (outs[i], lps[i]["token_logprobs"]) for i in range(best_of)
                ]
            else:
                with self.device_lock:
                    outs = self.generator.generate_tokens(
                        batch, gen, adapter_ids * best_of if adapter_ids else None
                    )
                cands = [(o, None) for o in outs]
        if rank:
            def score(c):
                toks, lp = c
                return (sum(lp[: len(toks)]) / max(1, len(toks))) if lp else 0.0

            cands.sort(key=score, reverse=True)
        # Bill the tokens actually GENERATED — all best_of candidates, not
        # just the n returned (OpenAI best_of billing); and count ids, not
        # a re-encode: decode->encode is not idempotent for every tokenizer
        # (byte tokenizers strip non-printables), so re-encoding
        # under-counts (ADVICE r3, r4).
        total_out = sum(len(out) for out, _ in cands)
        cands = cands[:n]
        choices = []
        for i, (out, _) in enumerate(cands):
            text, hit_stop = _apply_stop(tok.decode(out), stops or [])
            finish = (
                "stop" if hit_stop or len(out) < gen.max_new_tokens
                else "length"
            )
            choices.append(
                {"index": i, "message": {"role": "assistant", "content": text},
                 "finish_reason": finish}
                if chat
                else {"index": i, "text": text, "finish_reason": finish}
            )
        n_prompt = len(prompt_ids)
        if not use_cont:
            # Before the response write — see _complete's lockstep note.
            self._observe_lockstep(t0, total_out)
            # Usage billing is DEVICE accounting, per candidate: the
            # lockstep batch genuinely prefills all best_of prompt copies
            # (no prefix cache on this path), matching the continuous
            # engine's one-row-per-candidate rows. The API response's
            # OpenAI `usage` field still reports the prompt once.
            self._note_usage_lockstep(tenant or "anonymous",
                                      n_prompt * best_of,
                                      total_out, t0, slo_class=slo_class)
        self._send_json(200, {
            "id": f"cmpl-{uuid.uuid4().hex[:24]}",
            "object": "chat.completion" if chat else "text_completion",
            "created": int(t0),
            "model": payload.get("model") or self.model_name,
            # Which adapter GENERATION served (adapter plane, ISSUE 16):
            # a publication's flip is visible as this value changing.
            **({"system_fingerprint": self._adapter_fp}
               if getattr(self, "_adapter_fp", None) else {}),
            "choices": choices,
            "usage": {
                "prompt_tokens": n_prompt,
                "completion_tokens": total_out,
                "total_tokens": n_prompt + total_out,
            },
        })

    def _embeddings(self, payload: dict) -> None:
        """OpenAI ``/v1/embeddings``: mean-pooled, L2-normalized final
        hidden states (the standard causal-LM embedding recipe). One jitted
        program per (batch, length) bucket, LRU-bounded like every other
        client-shaped compile cache; runs under the device lock (embedding
        batches are one forward — lock-step is the right shape)."""
        import jax
        import jax.numpy as jnp
        import numpy as np

        from ditl_tpu.infer.engine import _next_pow2, lru_program
        from ditl_tpu.models import llama

        if not hasattr(self.generator, "cfg"):
            # --pod wraps the generator in PodGenerator (tokenizer-only
            # surface): a direct forward here would run device work outside
            # the pod broadcast protocol and hang the other processes.
            self._send_json(400, {"error": {"message":
                "embeddings are not supported with --pod serving"}})
            return
        raw = payload.get("input")
        if isinstance(raw, str):
            inputs = [raw]
        elif isinstance(raw, list) and raw and all(
            isinstance(x, int) for x in raw
        ):
            inputs = [raw]  # one pre-tokenized prompt
        elif isinstance(raw, list):
            inputs = raw
        else:
            self._send_json(400, {"error": {"message":
                "input must be a string, array of strings, or token array"}})
            return
        if not inputs or len(inputs) > 64:
            self._send_json(400, {"error": {"message":
                "input must contain 1..64 entries"}})
            return
        gen = self.generator
        tok = gen.tokenizer
        token_lists = []
        for item in inputs:
            if isinstance(item, str):
                ids = [tok.bos_id] + tok.encode(item)
            elif isinstance(item, list) and all(isinstance(x, int) for x in item):
                ids = item or [tok.bos_id]
            else:
                self._send_json(400, {"error": {"message":
                    "each input must be a string or a token-id array"}})
                return
            if len(ids) > gen.cfg.max_seq_len:
                ids = ids[: gen.cfg.max_seq_len]
            token_lists.append(ids)
        batch = _next_pow2(len(token_lists), floor=1)
        plen = _next_pow2(max(len(t) for t in token_lists))
        ids = np.full((batch, plen), tok.pad_id, np.int32)
        lengths = np.ones((batch,), np.int32)
        for i, t in enumerate(token_lists):
            ids[i, : len(t)] = t
            lengths[i] = len(t)
        cfg, mesh, rules = gen.cfg, gen.mesh, gen.rules

        def build():
            def embed_pooled(params, ids, lengths):
                q_pos = jnp.arange(plen, dtype=jnp.int32)
                seg = (q_pos[None, :] < lengths[:, None]).astype(jnp.int32)
                hidden = llama.forward(
                    params, ids, cfg,
                    positions=jnp.broadcast_to(q_pos, (batch, plen)),
                    segment_ids=seg, mesh=mesh, rules=rules,
                    return_hidden=True,
                )
                mask = seg.astype(jnp.float32)[..., None]
                pooled = (hidden.astype(jnp.float32) * mask).sum(1) / mask.sum(1)
                norm = jnp.linalg.norm(pooled, axis=-1, keepdims=True)
                return pooled / jnp.maximum(norm, 1e-9)

            return jax.jit(embed_pooled)

        with self.device_lock:
            program = lru_program(
                self.embed_cache, (batch, plen), build, bound=16
            )
            vecs = np.asarray(
                jax.device_get(program(gen.params, ids, lengths))
            )
        self._send_json(200, {
            "object": "list",
            "model": payload.get("model") or self.model_name,
            "data": [
                {"object": "embedding", "index": i,
                 "embedding": vecs[i].tolist()}
                for i in range(len(token_lists))
            ],
            "usage": {
                "prompt_tokens": int(sum(len(t) for t in token_lists)),
                "total_tokens": int(sum(len(t) for t in token_lists)),
            },
        })

    def _resolve_grammar(self, payload: dict):
        """Parse the request's guided-decoding spec (``guided_regex``,
        ``guided_json``, or OpenAI ``response_format`` json_object /
        json_schema) into a CompiledGrammar, LRU-cached by spec — grammar
        compilation is host work (regex -> DFA -> token table) that repeat
        clients shouldn't pay twice; the engine additionally dedups
        registration by table content. Returns None when the request is
        unconstrained; raises ValueError (caller answers 400) on a bad spec
        or a server not armed for guided decoding."""
        rf = payload.get("response_format")
        rf = rf if isinstance(rf, dict) else {}
        specs = [
            payload.get("guided_regex") is not None,
            payload.get("guided_json") is not None,
            rf.get("type") in ("json_object", "json_schema"),
        ]
        if not any(specs):
            return None
        if sum(specs) > 1:
            raise ValueError(
                "at most one of guided_regex, guided_json, response_format "
                "may constrain a request"
            )
        eng = self.threaded_engine
        if eng is None or not getattr(eng, "guided", False):
            raise ValueError(
                "guided decoding requires --engine continuous with "
                "--fsm-capacity > 0"
            )
        tok = eng.tokenizer
        from ditl_tpu.infer import grammar as G

        if payload.get("guided_regex") is not None:
            pattern = payload["guided_regex"]
            if not isinstance(pattern, str):
                raise ValueError("guided_regex must be a string")
            key, build = ("regex", pattern), (
                lambda: G.compile_regex(pattern, tok)
            )
        elif payload.get("guided_json") is not None:
            schema = payload["guided_json"]
            if isinstance(schema, str):
                schema = json.loads(schema)
            if not isinstance(schema, dict):
                raise ValueError("guided_json must be a JSON-schema object")
            key = ("schema", json.dumps(schema, sort_keys=True))
            build = lambda: G.compile_json_schema(schema, tok)  # noqa: E731
        elif rf.get("type") == "json_schema":
            schema = (rf.get("json_schema") or {}).get("schema")
            if not isinstance(schema, dict):
                raise ValueError(
                    "response_format.json_schema.schema must be an object"
                )
            key = ("schema", json.dumps(schema, sort_keys=True))
            build = lambda: G.compile_json_schema(schema, tok)  # noqa: E731
        else:  # json_object
            key, build = ("json_object",), (lambda: G.compile_json(tok))
        with self.grammar_lock:
            if key in self.grammar_cache:
                self.grammar_cache.move_to_end(key)
                return self.grammar_cache[key]
        g = build()  # compile OUTSIDE the lock: can cost ~seconds
        with self.grammar_lock:
            self.grammar_cache[key] = g
            while len(self.grammar_cache) > 64:
                self.grammar_cache.popitem(last=False)
        return g

    def _stream_complete(
        self, payload: dict, prompt: str, gen, *, chat: bool, adapter_ids=None,
        stops=None, lp_n=None, grammar=None, deadline_s=None, slo_class=None,
        trace=None, tenant=None,
    ) -> None:
        """OpenAI streaming: real incremental chunks from the continuous
        engine; the lockstep engine generates fully, then emits one chunk.
        ``lp_n`` (continuous engine only, validated by the caller): attach
        per-chunk logprobs with ``lp_n`` alternatives."""
        cmpl_id = f"cmpl-{uuid.uuid4().hex[:24]}"
        t_stream0 = time.time()
        created = int(t_stream0)
        model = payload.get("model") or self.model_name
        kind = "chat.completion.chunk" if chat else "text_completion"

        def event(text, finish=None, role=None, logprobs=None):
            if chat:
                delta = {}
                if role is not None:
                    delta["role"] = role
                    delta["content"] = ""
                elif text:
                    delta = {"content": text}
                choice = {"index": 0, "delta": delta, "finish_reason": finish}
            else:
                choice = {"index": 0, "text": text, "finish_reason": finish}
            if logprobs is not None:
                choice["logprobs"] = logprobs
            out = {"id": cmpl_id, "object": kind, "created": created,
                   "model": model, "choices": [choice]}
            if getattr(self, "_adapter_fp", None):
                out["system_fingerprint"] = self._adapter_fp
            return out

        # Submit eagerly, BEFORE the SSE headers go out: stream_one reserves
        # the queue slot here, so QueueFullError still becomes an HTTP 429
        # instead of a silently truncated stream (ADVICE r2).
        stream_iter = None
        if self.threaded_engine is not None and (
            adapter_ids is None
            or getattr(self.threaded_engine, "multi_lora", False)
        ):
            etok = self.threaded_engine.tokenizer
            if lp_n is not None:
                stream_iter = self.threaded_engine.stream_one_with_logprobs(
                    [etok.bos_id] + etok.encode(prompt), lp_n,
                    max_new_tokens=gen.max_new_tokens,
                    temperature=gen.temperature,
                    top_p=gen.top_p,
                    seed=gen.seed,
                    grammar=grammar,
                    deadline_s=deadline_s,
                    slo_class=slo_class,
                    trace=trace,
                    tenant=tenant,
                )
            else:
                stream_iter = self.threaded_engine.stream_one(
                    [etok.bos_id] + etok.encode(prompt),
                    max_new_tokens=gen.max_new_tokens,
                    temperature=gen.temperature,
                    top_p=gen.top_p,
                    seed=gen.seed,
                    adapter_id=adapter_ids[0] if adapter_ids else None,
                    grammar=grammar,
                    deadline_s=deadline_s,
                    slo_class=slo_class,
                    trace=trace,
                    tenant=tenant,
                )

        # An armed tracer's span gets ``first_write_s``: the SSE writer
        # resumes the generator below once the event it yielded is flushed,
        # so the instant after the first yield that carried a token is when
        # that token was on the socket.
        span = trace if self.tracer.armed else None
        pending = span is not None

        def flushed():
            nonlocal pending
            if pending:
                pending = False
                span.annotate(first_write_s=round(time.time() - span.t0, 6))

        def events():
            if chat:
                yield event("", role="assistant")  # role-announcement chunk
            tracker = _StopTracker(stops or [])
            n_gen = 0
            if stream_iter is not None and lp_n is not None:
                # Streaming logprobs (stops excluded by the caller): each
                # chunk carries its tokens' stats; text offsets advance
                # through the decoded stream.
                tok = self.threaded_engine.tokenizer
                pos = len(prompt)
                for toks, lp in stream_iter:
                    n_gen += len(toks)
                    tok_strs = [tok.decode([t]) for t in toks]
                    if chat:
                        lpj = {"content": [
                            {"token": s,
                             "logprob": lp["token_logprobs"][i],
                             "top_logprobs": [
                                 {"token": tok.decode([tid]), "logprob": tlp}
                                 for tid, tlp in zip(lp["top_ids"][i],
                                                     lp["top_logprobs"][i])
                             ]}
                            for i, s in enumerate(tok_strs)
                        ]}
                    else:
                        offsets = []
                        for s in tok_strs:
                            offsets.append(pos)
                            pos += len(s)
                        lpj = {
                            "tokens": tok_strs,
                            "token_logprobs": lp["token_logprobs"],
                            "top_logprobs": [
                                {tok.decode([tid]): tlp
                                 for tid, tlp in zip(lp["top_ids"][i],
                                                     lp["top_logprobs"][i])}
                                for i in range(len(tok_strs))
                            ],
                            "text_offset": offsets,
                        }
                    yield event("".join(tok_strs), logprobs=lpj)
                    flushed()
            elif stream_iter is not None:
                tok = self.threaded_engine.tokenizer
                for chunk in stream_iter:
                    n_gen += len(chunk)
                    text = tracker.push(tok.decode(chunk))
                    if text:
                        yield event(text)
                        flushed()
                    if tracker.hit:
                        break  # stream_one cancels the abandoned request
                tail = tracker.flush()
                if tail:
                    yield event(tail)
                    flushed()
            else:
                # The lock-step stream generates fully before emitting, so
                # greedy streamed requests benefit from speculation the same
                # way non-streaming ones do.
                tok = self.generator.tokenizer
                prompt_ids = [tok.bos_id] + tok.encode(prompt)
                out = self._lockstep_generate(prompt_ids, gen, adapter_ids)
                n_gen = len(out)
                self._observe_lockstep(t_stream0, n_gen)
                self._note_usage_lockstep(tenant or "anonymous",
                                          len(prompt_ids), n_gen, t_stream0,
                                          slo_class=slo_class)
                text, hit = _apply_stop(tok.decode(out), tracker.stops)
                if hit:
                    # Fold into the tracker so the finish computation reports
                    # "stop" even when the completion also used its full
                    # token budget.
                    tracker.hit = True
                if text:
                    yield event(text)
                    flushed()
            finish = (
                "stop"
                if tracker.hit or n_gen < gen.max_new_tokens
                else "length"
            )
            yield event("", finish=finish)

        self._send_sse(events(), span)

    def _complete(self, payload: dict, *, chat: bool) -> None:
        # Request tracing (ISSUE 6): continue the client's/gateway's trace
        # (W3C traceparent) or root a fresh one; the engine's lifecycle
        # spans chain under this span via submit(trace=...), so the merged
        # timeline nests gateway -> server -> engine across processes. The
        # span also covers the stream-write leg (SSE chunks relay inside
        # _stream_complete before this method returns). An armed tracer's
        # span starts at the handler's entry (do_POST) and carries the
        # flush of a streamed request's first token on this clock
        # (``first_write_s``, _stream_complete; the submit is the start of
        # the ``engine.queue`` span under it), with ``events`` (_send_sse)
        # and ``status`` at its end.
        span = self.tracer.start_span(
            "server.request",
            parent=parse_traceparent(self.headers.get("traceparent")),
            t0=self._t_entry,
            request_id=self._request_id(),
            route="chat" if chat else "completions",
        )
        try:
            if chat:
                messages = payload.get("messages") or []
                prompt = _chat_prompt(messages, self.generator.tokenizer)
            else:
                prompt = payload.get("prompt") or ""
                if isinstance(prompt, list):
                    prompt = prompt[0] if prompt else ""
            # Usage attribution (ISSUE 15): the credential-safe tenant
            # label every engine/ledger path below bills to.
            tenant = self._tenant_label()
            # Fresh seed per request unless the client pins one — otherwise
            # every temperature>0 request would replay jax.random.key(0).
            seed = payload.get("seed")
            if seed is None:
                import random as _random

                seed = _random.getrandbits(31)
            gen = GenerateConfig(
                max_new_tokens=int(
                    payload.get("max_tokens") or self.default_max_tokens
                ),
                temperature=float(payload.get("temperature") or 0.0),
                top_p=float(payload.get("top_p") or 1.0),
                seed=int(seed),
            )
            # Per-request deadline (ISSUE 5): the client's `deadline_s`
            # payload field, or the `X-Request-Deadline-S` header the
            # gateway stamps with the remaining fleet budget. Enforced by
            # the continuous engine (queue/slot eviction); an
            # already-expired arrival answers 504 before any device work
            # on either engine.
            deadline_s = payload.get("deadline_s")
            from_header = False
            if deadline_s is None:
                deadline_s = self.headers.get("X-Request-Deadline-S")
                from_header = deadline_s is not None
            if deadline_s is not None:
                try:
                    deadline_s = float(deadline_s)
                except (TypeError, ValueError):
                    self._send_json(400, {"error": {"message":
                        "deadline_s must be a number (seconds)"}})
                    return
                if deadline_s != deadline_s:  # NaN: poisons deadline sweeps
                    self._send_json(400, {"error": {"message":
                        "deadline_s must be a number (seconds)"}})
                    return
                if deadline_s <= 0:
                    if self.serving_metrics is not None:
                        self.serving_metrics.deadline_expired.inc()
                    self._send_json(504, {"error": {
                        "message": "deadline expired before any work began",
                        "type": "timeout_error",
                    }})
                    return
            # SLO class (ISSUE 8): scheduling priority for the continuous
            # engine's class-ordered admission/preemption. The X-SLO-Class
            # HEADER wins over the payload field — the gateway stamps it
            # when per-tenant admission pins a tenant to a class, and the
            # pin must override whatever the tenant's payload claims. On
            # paths whose scheduler cannot honor classes (lockstep, the pod
            # driver's replicated FIFO staging) an explicit non-default
            # payload value is rejected (reject-don't-drop) while a
            # header-derived hint is dropped, so gateway-routed traffic
            # still serves (the PR 5 deadline lesson).
            from ditl_tpu.infer.continuous import SLO_CLASSES

            slo_class = self.headers.get("X-SLO-Class")
            slo_from_header = slo_class is not None
            if slo_class is None:
                slo_class = payload.get("slo_class")
            if slo_class is not None:
                if slo_class not in SLO_CLASSES:
                    self._send_json(400, {"error": {"message":
                        f"unknown slo_class {slo_class!r} (one of "
                        f"{sorted(SLO_CLASSES)})"}})
                    return
                classful = (
                    self.threaded_engine is not None
                    and getattr(self.threaded_engine,
                                "supports_slo_classes", True)
                )
                if not classful:
                    ok, slo_class = self._gate_slo_class(
                        slo_class, slo_from_header)
                    if not ok:
                        return
            try:
                stops = _stop_list(payload.get("stop"))
            except ValueError as e:
                self._send_json(400, {"error": {"message": str(e)}})
                return
            # Multi-LoRA routing: the OpenAI "model" field (or the
            # gateway's X-Adapter-Name tenant pin) selects an adapter by
            # name. Registry-armed servers resolve LIVE (unknown/evicted
            # names 404 with a reason); legacy static servers keep serving
            # base for unknown names.
            from ditl_tpu.infer.adapters import AdapterNotFound

            try:
                adapter_ids = self._resolve_adapter(payload)
            except AdapterNotFound as e:
                self._send_json(e.status, {"error": {
                    "message": str(e), "type": "invalid_request_error",
                    "param": "model", "code": "model_not_found"}})
                return
            try:
                grammar = self._resolve_grammar(payload)
            except ValueError as e:
                self._send_json(400, {"error": {"message": str(e)}})
                return
            if (grammar is not None and adapter_ids is not None
                    and not getattr(self.threaded_engine, "multi_lora", False)):
                self._send_json(400, {"error": {"message":
                    "guided decoding with adapter routing requires a "
                    "multi-LoRA continuous engine"}})
                return
            try:
                n_choices = int(payload.get("n") or 1)
                best_of = int(payload.get("best_of") or n_choices)
            except (TypeError, ValueError):
                self._send_json(400, {"error": {"message":
                    "n and best_of must be integers"}})
                return
            if (slo_class is not None and adapter_ids is not None
                    and not getattr(self.threaded_engine, "multi_lora",
                                    False)):
                # Adapter requests on a non-multi-LoRA engine serve via the
                # lock-step generator — no class-ordered scheduler there.
                ok, slo_class = self._gate_slo_class(
                    slo_class, slo_from_header)
                if not ok:
                    return
            if deadline_s is not None:
                # Deadline ENFORCEMENT (queue/slot eviction) lives in the
                # continuous engine's single-choice path only. Everywhere
                # else — lockstep, the pod driver (per-process clocks would
                # desync its replicated scheduler), the adapter fallback,
                # n/best_of batching — an explicit client `deadline_s` is
                # rejected rather than silently decoded to the full budget
                # (reject-don't-drop), while the gateway's header (stamped
                # on every relay) is a best-effort hint and is dropped.
                enforceable = (
                    self.threaded_engine is not None
                    and getattr(self.threaded_engine, "supports_deadlines",
                                True)
                    and (adapter_ids is None
                         or getattr(self.threaded_engine, "multi_lora",
                                    False))
                    and n_choices == 1 and best_of == 1
                )
                if not enforceable:
                    if from_header:
                        deadline_s = None
                    else:
                        self._send_json(400, {"error": {"message":
                            "deadline_s requires the continuous-engine "
                            "single-choice serving path (no lockstep/pod "
                            "engine, adapter fallback, or n/best_of)"}})
                        return
            if n_choices > 1 or best_of > 1:
                if not (1 <= n_choices <= best_of <= 8):
                    self._send_json(400, {"error": {"message":
                        "need 1 <= n <= best_of <= 8"}})
                    return
                if payload.get("stream"):
                    self._send_json(400, {"error": {"message":
                        "n/best_of do not compose with stream"}})
                    return
                if payload.get("logprobs") not in (None, False):
                    self._send_json(400, {"error": {"message":
                        "logprobs with n > 1 is not supported"}})
                    return
                self._multi_complete(
                    payload, prompt, gen, chat=chat, n=n_choices,
                    best_of=best_of, adapter_ids=adapter_ids, stops=stops,
                    grammar=grammar, slo_class=slo_class,
                    slo_from_header=slo_from_header, trace=span,
                    tenant=tenant,
                )
                return
            # OpenAI semantics: completions' `logprobs: 0` is a real request
            # (chosen-token logprob, zero alternatives) — 0 is falsy, so test
            # presence, not truthiness. Chat's `logprobs: false` means off.
            lp_req = payload.get("logprobs")
            has_lp = lp_req is not None and lp_req is not False
            if payload.get("stream"):
                lp_n = None
                if has_lp:
                    # Streaming logprobs: served through the continuous
                    # engine's per-chunk stats; anything it can't carry
                    # (lock-step-only serving, stop sequences, adapter
                    # routing, N beyond the compiled logprobs_k) fails
                    # loudly instead of silently dropping the field.
                    if chat:
                        tl = payload.get("top_logprobs")
                        lp_n = int(tl) if tl is not None else 1
                    else:
                        lp_n = int(lp_req)
                    lp_n = max(0, min(lp_n, 20))
                    engine_k = getattr(self.threaded_engine, "logprobs_k", 0)
                    if not (self.threaded_engine is not None and engine_k > 0
                            and lp_n <= engine_k and not stops
                            and adapter_ids is None):
                        self._send_json(400, {"error": {"message":
                            "streaming logprobs requires --engine continuous "
                            "with --logprobs-k >= N, no stop sequences, and "
                            "no adapter routing"}})
                        return
                try:
                    self._stream_complete(
                        payload, prompt, gen, chat=chat,
                        adapter_ids=adapter_ids, stops=stops, lp_n=lp_n,
                        grammar=grammar, deadline_s=deadline_s,
                        slo_class=slo_class, trace=span, tenant=tenant,
                    )
                except QueueFullError as e:
                    # The stream's submit is eager (before SSE headers), so
                    # a full queue still becomes a real 429 (ADVICE r2).
                    self._send_429(str(e))
                except ValueError as e:
                    # Eager-submit validation (e.g. fsm_capacity exhausted)
                    # also precedes the SSE headers.
                    status = 503 if "fsm_capacity" in str(e) else 400
                    self._send_json(status, {"error": {"message": str(e)}})
                except (BrokenPipeError, ConnectionError):
                    logger.info("client disconnected mid-stream")
                except Exception:
                    # Headers (200/text-event-stream) may already be out —
                    # a JSON 500 would corrupt the stream; just log and close.
                    logger.exception("streaming completion failed")
                return
            t0 = time.time()
            logprobs_json = None
            lockstep_served = False
            if has_lp:
                # OpenAI logprobs: completions' `logprobs: N` = top-N; chat's
                # `logprobs: true` + `top_logprobs: N`. N is clamped (OpenAI
                # caps at 5/20).
                if chat:
                    # top_logprobs: 0 is a valid explicit request (chosen
                    # token only) — presence, not truthiness, again.
                    tl = payload.get("top_logprobs")
                    n_top = int(tl) if tl is not None else 1
                else:
                    n_top = int(lp_req)
                n_top = max(0, min(n_top, 20))
                engine_k = getattr(self.threaded_engine, "logprobs_k", 0)
                if (
                    self.threaded_engine is not None
                    and adapter_ids is None
                    and engine_k > 0
                    and n_top <= engine_k
                ):
                    # Continuous engine with logprobs armed: the request
                    # rides ordinary decode ticks (sharing the batch with
                    # everyone else) — no lock-step fallback stalling the
                    # engine's throughput for a standard capability.
                    tok = self.threaded_engine.tokenizer
                    prompt_ids = [tok.bos_id] + tok.encode(prompt)
                    gen_ids, lp = self.threaded_engine.generate_one_with_logprobs(
                        prompt_ids, n_top,
                        max_new_tokens=gen.max_new_tokens,
                        temperature=gen.temperature, top_p=gen.top_p,
                        seed=gen.seed,
                        grammar=grammar,
                        deadline_s=deadline_s,
                        slo_class=slo_class,
                        trace=span,
                        tenant=tenant,
                    )
                elif grammar is not None:
                    # Guided requests never fall back to the lock-step
                    # generator (no FSM path there) — the conditions above
                    # (logprobs_k >= N) must hold for guided + logprobs.
                    self._send_json(400, {"error": {"message":
                        "guided decoding with logprobs requires the "
                        "continuous engine armed with --logprobs-k >= N"}})
                    return
                elif not hasattr(self.generator, "generate_tokens_with_logprobs"):
                    # --pod wraps the generator in PodGenerator; its broadcast
                    # protocol doesn't carry logprobs (and device work must
                    # not bypass it).
                    self._send_json(
                        400,
                        {"error": {"message": "logprobs is not supported "
                                   "with --pod serving"}},
                    )
                    return
                else:
                    # Falling back to lock-step loses the class-ordered
                    # scheduler: drop/400 a non-default class first.
                    ok, slo_class = self._gate_slo_class(
                        slo_class, slo_from_header)
                    if not ok:
                        return
                    # Lock-step generator (exact per-step logits): the
                    # no-continuous-engine server, adapter requests, and
                    # n_top beyond the engine's compiled logprobs_k. The
                    # Generator's LRU program cache bounds what other
                    # client-controlled compile-key fields (temperature,
                    # top_p, max_tokens) can pin in memory.
                    tok = self.generator.tokenizer
                    prompt_ids = [tok.bos_id] + tok.encode(prompt)
                    # The engine's top-k needs k >= 1; n_top == 0 is served
                    # by computing one alternative and emitting none.
                    lp_gen = dataclasses.replace(gen, logprobs=max(1, n_top))
                    with self.device_lock:
                        outs, lps = self.generator.generate_tokens_with_logprobs(
                            [prompt_ids], lp_gen, adapter_ids
                        )
                    gen_ids = outs[0]
                    lp = lps[0]
                    lockstep_served = True
                # Apply stop truncation at TOKEN granularity before building
                # the logprobs JSON: the entries must stay aligned with the
                # returned text (keep whole tokens up to the stop cut).
                full_text = tok.decode(gen_ids)
                cut_text, hit_stop = _apply_stop(full_text, stops)
                n_gen_full = len(gen_ids)
                if hit_stop:
                    keep, acc = 0, ""
                    for t in gen_ids:
                        piece = tok.decode([t])
                        if len(acc) + len(piece) > len(cut_text):
                            break
                        acc += piece
                        keep += 1
                    gen_ids = gen_ids[:keep]
                    lp = {k: v[:keep] for k, v in lp.items()}
                    text = acc
                else:
                    text = full_text
                tok_strs = [tok.decode([t]) for t in gen_ids]
                if chat:
                    logprobs_json = {
                        "content": [
                            {
                                "token": s,
                                "logprob": lp["token_logprobs"][i],
                                "top_logprobs": [
                                    {"token": tok.decode([tid]), "logprob": tlp}
                                    for tid, tlp in zip(
                                        lp["top_ids"][i][:n_top],
                                        lp["top_logprobs"][i][:n_top],
                                    )
                                ],
                            }
                            for i, s in enumerate(tok_strs)
                        ]
                    }
                else:
                    offsets, pos = [], len(prompt)
                    for s in tok_strs:
                        offsets.append(pos)
                        pos += len(s)
                    logprobs_json = {
                        "tokens": tok_strs,
                        "token_logprobs": lp["token_logprobs"],
                        "top_logprobs": [
                            {
                                tok.decode([tid]): tlp
                                for tid, tlp in zip(
                                    lp["top_ids"][i][:n_top],
                                    lp["top_logprobs"][i][:n_top],
                                )
                            }
                            for i in range(len(tok_strs))
                        ],
                        "text_offset": offsets,
                    }
                n_prompt = len(prompt_ids)
                n_gen = n_gen_full
            elif self.threaded_engine is not None and (
                adapter_ids is None
                or getattr(self.threaded_engine, "multi_lora", False)
            ):
                tok = self.threaded_engine.tokenizer
                prompt_ids = [tok.bos_id] + tok.encode(prompt)
                out = self.threaded_engine.generate_one(
                    prompt_ids,
                    max_new_tokens=gen.max_new_tokens,
                    temperature=gen.temperature,
                    top_p=gen.top_p,
                    seed=gen.seed,
                    adapter_id=adapter_ids[0] if adapter_ids else None,
                    grammar=grammar,
                    deadline_s=deadline_s,
                    slo_class=slo_class,
                    trace=span,
                    tenant=tenant,
                )
                n_gen = len(out)
                text, hit_stop = _apply_stop(tok.decode(out), stops)
                n_prompt = len(prompt_ids)
            else:
                if grammar is not None:  # unreachable guard: no FSM path
                    self._send_json(400, {"error": {"message":
                        "guided decoding requires the continuous engine"}})
                    return
                tok = self.generator.tokenizer
                prompt_ids = [tok.bos_id] + tok.encode(prompt)
                out = self._lockstep_generate(prompt_ids, gen, adapter_ids)
                n_gen = len(out)
                text, hit_stop = _apply_stop(tok.decode(out), stops)
                n_prompt = len(prompt_ids)
                lockstep_served = True
            # "length" = the GENERATED token count hit the budget (decoded
            # text round-trips are not token-count-preserving, so never
            # re-encode to decide this).
            finish = (
                "stop" if hit_stop or n_gen < gen.max_new_tokens else "length"
            )
            # Bill the tokens actually GENERATED (n_gen), not a re-encode
            # of the decoded/stop-trimmed text — decode->encode is not
            # idempotent for every tokenizer (ADVICE r3; a byte tokenizer
            # stripping non-printables billed 0 for 8 generated tokens).
            n_out = n_gen
            kind = "chat.completion" if chat else "text_completion"
            choice = (
                {"index": 0, "message": {"role": "assistant", "content": text},
                 "finish_reason": finish}
                if chat
                else {"index": 0, "text": text, "finish_reason": finish}
            )
            if logprobs_json is not None:
                choice["logprobs"] = logprobs_json
            if lockstep_served:
                # BEFORE the response write: a client that scrapes /metrics
                # the instant its completion returns must see the counters
                # moved (the response write itself is not service time —
                # and recording after it raced exactly that scrape).
                self._observe_lockstep(t0, n_out)
                self._note_usage_lockstep(tenant, n_prompt, n_out, t0,
                                          slo_class=slo_class)
            self._send_json(
                200,
                {
                    "id": f"cmpl-{uuid.uuid4().hex[:24]}",
                    "object": kind,
                    "created": int(t0),
                    "model": payload.get("model") or self.model_name,
                    # Which adapter GENERATION served (ISSUE 16): a
                    # publication's flip is visible as this changing.
                    **({"system_fingerprint": self._adapter_fp}
                       if getattr(self, "_adapter_fp", None) else {}),
                    "choices": [choice],
                    "usage": {
                        "prompt_tokens": n_prompt,
                        "completion_tokens": n_out,
                        "total_tokens": n_prompt + n_out,
                    },
                },
            )
            logger.info(
                "served %s: %d prompt + %d completion tokens in %.2fs",
                kind, n_prompt, n_out, time.time() - t0,
            )
        except Exception as e:  # total-server: errors become JSON, not crashes
            from ditl_tpu.infer.continuous import BadRequestError, QueueFullError

            span.annotate(error=type(e).__name__)
            if isinstance(e, QueueFullError):
                self._send_429(str(e))
                return
            if isinstance(e, DeadlineExceededError):
                # The engine already evicted the request and counted it
                # (deadline_expired); 504 tells the client (and gateway)
                # the deadline — not the server — ended this request.
                self._send_json(504, {"error": {
                    "message": str(e), "type": "timeout_error",
                }})
                return
            if isinstance(e, ValueError) and "fsm_capacity exhausted" in str(e):
                # Guided table full: a server-capacity condition, not a
                # client error. Rows are never evicted (active slots may
                # point anywhere in the table), so NEW grammars keep
                # failing until the operator restarts with a larger
                # --fsm-capacity; already-registered grammars still serve.
                self._send_json(503, {"error": {"message":
                    str(e) + " (new grammars need a restart with a larger "
                    "--fsm-capacity; already-registered grammars still "
                    "serve)"}})
                return
            if isinstance(e, BadRequestError):
                # Engine request validation (seed/max_tokens bounds, prompt
                # too long, bad adapter, guided-in-pod): the client's fault
                # — 400. Only this dedicated class maps here; any other
                # ValueError is a server bug and stays on the logged 500
                # path below.
                self._send_json(400, {"error": {"message": str(e)}})
                return
            logger.exception("completion failed")
            self._send_json(500, {"error": {"message": str(e)}})
        finally:
            if self.tracer.armed:
                span.annotate(status=self._status)
            span.end()


def make_server(
    generator: Generator,
    *,
    host: str = "127.0.0.1",
    port: int = 8300,
    model_name: str = "ditl-tpu",
    default_max_tokens: int = 64,
    threaded_engine=None,
    adapter_names: dict | None = None,
    spec_generator=None,
    max_pending: int | None = None,
    tracer: Tracer | None = None,
    slo: BurnRateMonitor | None = None,
    telemetry=None,
    role: str = "hybrid",
    incidents=None,
    serving_metrics: ServingMetrics | None = None,
    cold_start_s: float | None = None,
    kv_handoff: bool = False,
    usage=None,
    usage_ledger=None,
    adapter_registry=None,
    adapter_drain_timeout_s: float = 30.0,
) -> DrainableHTTPServer:
    """Build (not start) the HTTP server — tests drive it on a thread.
    Pass ``threaded_engine`` (infer/continuous.ThreadedEngine) to serve with
    continuous batching instead of the lock-step Generator;
    ``adapter_names`` maps OpenAI "model" names to multi-LoRA adapter ids
    (the generator's params must be a stacked-adapter tree);
    ``spec_generator`` (Speculative/AutoSpeculativeGenerator) serves greedy
    lock-step requests — streaming and non-streaming — speculatively;
    ``max_pending`` caps concurrent in-flight completion work (429 beyond
    it) — the lockstep overload control; ``role`` tags the replica's
    disaggregated-fleet serving shape (gateway/roles.py), echoed on
    /health for the gateway's role-aware routing.

    The returned :class:`DrainableHTTPServer` supports ``drain()`` /
    ``close(drain=True)`` (graceful: /health flips to draining, new work
    gets 503, in-flight finishes) and ``kill()`` (abrupt, for failover
    drills)."""

    # One telemetry bundle per server: an explicit ``serving_metrics``
    # (the incident-armed serve() path shares one bundle between the
    # engine, the incident manager, and this server), else the continuous
    # engine's own (its scheduler records into it), else a fresh bundle
    # the lock-step handler path records into. Either way /metrics
    # renders it. ``incidents`` (telemetry/incident.IncidentManager) arms
    # the /incidents listing endpoint.
    if serving_metrics is None:
        serving_metrics = getattr(threaded_engine, "metrics", None)
    if serving_metrics is None:
        serving_metrics = ServingMetrics()
    # Tracing (ISSUE 6): default to the engine's tracer so one knob
    # (constructing the engine with a journal-backed tracer) arms the whole
    # replica — server.request spans and engine lifecycle spans land in the
    # same per-process journal and nest under one trace.
    if tracer is None:
        tracer = getattr(threaded_engine, "tracer", None) or NULL_TRACER
    # Usage metering (ISSUE 15): default to the engine's own meter (the
    # tracer rule — constructing the engine with one arms the replica
    # end-to-end); the families render on whatever registry this server's
    # /metrics serves. bind is idempotent, so an engine-bound meter keeps
    # its binding.
    if usage is None:
        usage = getattr(threaded_engine, "usage", None)
    if usage is not None:
        usage.bind(serving_metrics.registry)
    if slo is None:
        # SLO burn-rate monitor over this server's bundle; ``telemetry``
        # (config.TelemetryConfig) overrides the objectives, defaults
        # otherwise. Always on: sampling happens only on /slo//metrics
        # scrapes, so an unscraped server pays nothing.
        kw = telemetry.serving_slo_kwargs() if telemetry is not None else {}
        slo = serving_slo(serving_metrics, **kw)
    # Adapter plane (ISSUE 16): auto-arm the registry whenever a
    # multi-LoRA THREADED continuous engine serves (hasattr call = the
    # driver-thread seam exists; the pod driver is excluded on purpose —
    # a hot install on process 0 alone would desync the replicated
    # schedulers, so pod fleets keep the rolling-restart path). Launch
    # adapters seed the registry so /v1/adapters and eviction cover them.
    if (adapter_registry is None and threaded_engine is not None
            and getattr(threaded_engine, "multi_lora", False)
            and hasattr(threaded_engine, "call")):
        from ditl_tpu.infer.adapters import AdapterRegistry

        inner = getattr(threaded_engine, "_engine", threaded_engine)
        adapter_registry = AdapterRegistry(
            threaded_engine,
            journal=getattr(tracer, "journal", None),
            usage_ledger=usage_ledger
            or getattr(inner, "usage_ledger", None),
            drain_timeout_s=adapter_drain_timeout_s,
        )
        for name, row in (adapter_names or {}).items():
            adapter_registry.seed(name, row)
    from ditl_tpu.parallel.sharding import placement
    from ditl_tpu.runtime.distributed import device_summary

    placement_info = {"device": device_summary()}
    if getattr(generator, "params", None) is not None:
        placement_info["param_placement"] = placement(generator.params)
    handler = type(
        "BoundHandler",
        (_Handler,),
        {
            "generator": generator,
            "threaded_engine": threaded_engine,
            "model_name": model_name,
            "device_lock": threading.Lock(),
            "default_max_tokens": default_max_tokens,
            "adapter_names": adapter_names or {},
            "spec_generator": spec_generator,
            "grammar_cache": collections.OrderedDict(),
            "grammar_lock": threading.Lock(),
            "embed_cache": collections.OrderedDict(),
            "serving_metrics": serving_metrics,
            "max_pending": max_pending,
            "tracer": tracer,
            "slo": slo,
            "role": role,
            "incidents": incidents,
            "kv_handoff_enabled": kv_handoff,
            "usage": usage,
            "usage_ledger": usage_ledger,
            "adapter_registry": adapter_registry,
            "placement_info": placement_info,
        },
    )
    server = DrainableHTTPServer((host, port), handler)
    if cold_start_s is not None:
        # Measured time-to-first-ready (ISSUE 12): echoed on /health so
        # the gateway's scale-to-zero wake budget uses a measured number.
        server.cold_start_s = float(cold_start_s)
    return server


def _tree_bytes(tree) -> int:
    """Bytes of a tree's arrays, by their shapes (no device access)."""
    import jax

    return sum(int(x.nbytes) for x in jax.tree.leaves(tree)
               if hasattr(x, "nbytes"))


def _place_params(params, cfg: ModelConfig, mesh):
    """Put the serving params on the mesh by the model's own rule table.
    Eager init leaves the whole tree on the first device; un-placed, every
    program call would copy it out from there and the first chip would
    carry the full model. A tree the rule table does not describe (int8
    weights, stacked adapters) is left to GSPMD, loudly."""
    import jax

    from ditl_tpu.models import llama
    from ditl_tpu.parallel.sharding import is_axes_leaf, named_sharding_tree

    axes = llama.param_logical_axes(cfg)
    if (jax.tree.structure(axes, is_leaf=is_axes_leaf)
            != jax.tree.structure(params)):
        logger.warning(
            "--mesh: the params tree (quantized weights / stacked adapters) "
            "does not match the model's logical axes; it stays on the first "
            "device and is copied out per program call"
        )
        return params
    return jax.device_put(params, named_sharding_tree(mesh, axes))


def serve(argv: list[str] | None = None) -> int:
    # The start's one clock (ISSUE 12, leg by leg since ISSUE 54): from here
    # (before the jax import below — that import and the engine build ARE
    # the cold start; the persistent compile cache is what shrinks it on a
    # warm start) to the moment the listening server is built, as six
    # contiguous legs whose sum is /health's cold_start_s.
    startup = StartupRecorder()
    import jax

    from ditl_tpu.data.tokenizer import get_tokenizer
    from ditl_tpu.models import llama
    from ditl_tpu.models.presets import get_preset

    parser = argparse.ArgumentParser(prog="ditl_tpu.infer.server")
    parser.add_argument("--preset", default=None)
    parser.add_argument("--host", default="0.0.0.0")
    parser.add_argument("--port", type=int, default=8300)
    parser.add_argument("--tokenizer", default="byte")
    parser.add_argument("--checkpoint-dir", default="")
    parser.add_argument("--max-tokens", type=int, default=64)
    parser.add_argument(
        "--engine", choices=("lockstep", "continuous"), default="lockstep"
    )
    parser.add_argument("--slots", type=int, default=8,
                        help="decode slots for --engine continuous")
    parser.add_argument(
        "--prefill-chunk", type=int, default=0,
        help="chunked prefill for --engine continuous: prompts longer than "
        "this prefill one chunk per tick, interleaved with in-flight "
        "decodes (0 = whole-prompt prefill)",
    )
    parser.add_argument(
        "--token-budget", type=int, default=0,
        help="per-tick token budget for --engine continuous (ISSUE 8): "
        "each scheduler tick spends at most budget - decode_ready x "
        "decode_chunk tokens on prefill chunks, so co-scheduled long "
        "prompts cannot stall decode-ready streams (stall-free batching; "
        "pair with --prefill-chunk). Must cover one full decode tick "
        "(slots x decode-chunk); 0 = unbudgeted",
    )
    parser.add_argument(
        "--host-tier-mb", type=float, default=0.0,
        help="host-RAM prefix-cache tier capacity in MiB (ISSUE 13): "
        "LRU-evicted published KV pages spill to host memory and swap "
        "back in on admission miss, so the shared-prefix working set "
        "stops being bounded by HBM pages. Requires --cache-mode paged; "
        "0 = off",
    )
    parser.add_argument(
        "--spill-max-pages-per-tick", type=int, default=32,
        help="per-tick cap on pages the host tier's spill batch moves "
        "device->host (bounds the one batched device_get a tick pays; "
        "the remainder carries over)",
    )
    parser.add_argument(
        "--kv-handoff", action="store_true",
        help="serve the /internal/prefill + /internal/kv_handoff "
        "endpoints (ISSUE 13): the gateway ships a prefill_heavy "
        "replica's finished prefill here instead of re-prefilling. "
        "Requires a paged continuous engine",
    )
    parser.add_argument(
        "--speculative", choices=("off", "on", "auto"), default="off",
        help="prompt-lookup speculative decoding: 'on' always speculates, "
        "'auto' decides from measured acceptance. Continuous engine: "
        "speculative decode ticks for greedy AND sampled requests (greedy "
        "outputs token-identical; sampled exact in distribution via "
        "rejection sampling). Lock-step engine: greedy requests via "
        "infer/speculative.py",
    )
    parser.add_argument(
        "--logprobs-k", type=int, default=0,
        help="arm the continuous engine to serve per-token logprobs with up "
        "to K alternatives natively (requests ride ordinary decode ticks); "
        "0 = logprob requests fall back to the lock-step generator",
    )
    parser.add_argument(
        "--max-queue", type=int, default=0,
        help="admission-queue depth cap for --engine continuous; beyond it "
        "requests get HTTP 429 (0 = unbounded)",
    )
    parser.add_argument(
        "--max-pending", type=int, default=0,
        help="cap on concurrent in-flight completion requests (the lockstep "
        "overload control — beyond it requests get HTTP 429 instead of "
        "piling up on the device lock); 0 = unbounded",
    )
    parser.add_argument(
        "--admission", choices=("reserve", "optimistic"), default="reserve",
        help="paged admission policy: 'reserve' books worst-case pages "
        "(prompt+max_tokens) up front; 'optimistic' books prompt + one "
        "tick, feeds pages per tick, and preempts the youngest request on "
        "pool exhaustion (exact resume) — more concurrency when clients "
        "set pessimistic max_tokens",
    )
    parser.add_argument(
        "--fsm-capacity", type=int, default=0,
        help="arm guided (grammar-constrained) decoding on --engine "
        "continuous: total DFA states servable at once (device table rows; "
        "a JSON grammar is ~1.1k states at depth 5, a typical regex tens). "
        "Requests then accept guided_regex / guided_json / response_format "
        "json_object. 0 = off",
    )
    parser.add_argument(
        "--draft-preset", default="",
        help="model-based speculation (--speculative --engine continuous): "
        "preset name of a small DRAFT model whose greedy predictions draft "
        "for the target's verify forwards (same tokenizer/vocab); every "
        "tick speculates. Pair with --draft-checkpoint for trained weights",
    )
    parser.add_argument(
        "--draft-checkpoint", default="",
        help="Orbax checkpoint dir for --draft-preset's weights "
        "(random-init without it — only useful for smoke tests)",
    )
    parser.add_argument(
        "--cache-mode", choices=("contiguous", "paged"), default="contiguous",
        help="KV cache layout for --engine continuous: 'paged' pools KV in "
        "content-hashed pages with automatic prefix reuse "
        "(infer/paged_cache.py, ops/paged_attention.py)",
    )
    parser.add_argument(
        "--page-size", type=int, default=256,
        help="tokens per KV page for --cache-mode paged (256 decodes "
        "~1.5x faster than contiguous on v5e; smaller = finer sharing)",
    )
    parser.add_argument(
        "--pages", type=int, default=0,
        help="page-pool size for --cache-mode paged; 0 = the contiguous "
        "equivalent (slots x max context)",
    )
    parser.add_argument(
        "--window-pages", type=int, default=0,
        help="size of the WINDOW layers' page pool where the model has window "
        "attention layers (two pools, page ids of their own: "
        "infer/page_format.py WindowKVPages); 0 = slots x (pages a window + 6)",
    )
    parser.add_argument(
        "--quantize", choices=("none", "int8"), default="none",
        help="weight-only int8 (halves decode HBM reads; ops/quant.py)",
    )
    parser.add_argument(
        "--kv-quant", choices=("none", "int8"), default="none",
        help="int8 KV cache (halves cache reads/footprint; composes with "
        "both cache modes)",
    )
    parser.add_argument(
        "--override", action="append", default=[], metavar="FIELD=VALUE",
        help="ModelConfig override (repeatable), e.g. lora_rank=16 or "
        "hidden_size=64 — same dotted-override machinery as the launcher",
    )
    parser.add_argument(
        "--adapter", action="append", default=[], metavar="NAME=ORBAX_DIR",
        help="multi-LoRA serving (repeatable): load the LoRA adapters from "
        "an Orbax checkpoint dir; requests with \"model\": NAME use that "
        "adapter, any other model name serves the base weights",
    )
    parser.add_argument(
        "--adapter-pool", type=int, default=0,
        help="adapter plane (ISSUE 16, --engine continuous): reserve this "
        "many EXTRA zeroed rows in the stacked adapter pool for hot "
        "loads — POST /v1/adapters/load installs manifest-verified "
        "adapter checkpoints into free rows at runtime (no restart), "
        "/v1/adapters/evict drains and frees them. Composes with "
        "--adapter (launch adapters seed the registry); without it, "
        "needs a LoRA-capable config (model.lora_rank > 0)",
    )
    parser.add_argument(
        "--mesh", default="",
        help='shard the model over a device mesh, e.g. "tensor=4" or '
        '"fsdp=2,tensor=4" (axes as in MeshConfig); spans all pod devices',
    )
    parser.add_argument(
        "--pod", action="store_true",
        help="multi-host serving: every process joins the broadcast-driven "
        "SPMD decode loop (infer/podserve.py); process 0 serves HTTP",
    )
    parser.add_argument(
        "--max-cache-len", type=int, default=0,
        help="per-slot KV cache cap for --engine continuous; 0 = model "
        "max_seq_len (set this for long-context presets like llama31-8b, "
        "whose 131072-token cache would be ~17 GB per slot)",
    )
    parser.add_argument(
        "--role", choices=("hybrid", "prefill_heavy", "decode_heavy"),
        default="hybrid",
        help="disaggregated-fleet role tag (ISSUE 9): echoed on /health so "
        "a gateway steering by class+role reads the replica's own claim. "
        "Purely a label — pair it with the matching --slots/--token-budget/"
        "--prefill-chunk knobs (the launcher's gateway.replica_roles does "
        "both)",
    )
    parser.add_argument(
        "--trace-dir", default="",
        help="arm end-to-end request tracing (ISSUE 6): span records "
        "(server.request + the engine's queue/prefill/decode lifecycle, "
        "tick and tick-phase spans) append to {dir}/events-server-<pid>.jsonl; "
        "export with python -m ditl_tpu.telemetry.trace_export --dir DIR",
    )
    parser.add_argument(
        "--profiler-port", type=int, default=0,
        help="> 0: start jax.profiler's server on this port (the trainer's "
        "runtime.profiler_port), so a device trace of the serving process "
        "can be captured from TensorBoard/XProf",
    )
    parser.add_argument(
        "--telemetry-override", action="append", default=[],
        metavar="FIELD=VALUE",
        help="TelemetryConfig override (repeatable), e.g. slo_ttft_s=0.5 "
        "or journal_max_mb=64 — tunes the /slo objectives and the trace "
        "journal's rotation cap",
    )
    parser.add_argument(
        "--incident-dir", default="",
        help="arm the flight-recorder/anomaly/incident plane (ISSUE 10): "
        "the continuous engine's detectors (deadline/429 storms, "
        "preemption thrash, TTFT/TPOT jumps, hit-ratio collapse) and SLO "
        "burn-alert transitions assemble fingerprint-deduped incident "
        "bundles into this directory, listed at /incidents and via "
        "python -m ditl_tpu.telemetry.incident --dir DIR; detector "
        "thresholds ride --telemetry-override (anomaly_*/incident_*)",
    )
    parser.add_argument(
        "--usage-dir", default="",
        help="arm the crash-consistent per-tenant usage ledger (ISSUE 15): "
        "one JSONL row per terminal request (outcome 200/429/504/cancel, "
        "prompt/generated tokens, cached-token tiers, queue wait, "
        "device-time estimate, interference, preemptions) appended to "
        "{dir}/usage-server-<pid>.jsonl; aggregate with "
        "python -m ditl_tpu.telemetry.usage --dir DIR",
    )
    parser.add_argument(
        "--no-usage-metering", action="store_true",
        help="disable the in-memory per-tenant usage meter (/usage, "
        "ditl_usage_* families, noisy-neighbor conviction windows) — "
        "the metering-off A/B leg; on by default",
    )
    parser.add_argument(
        "--usage-override", action="append", default=[],
        metavar="FIELD=VALUE",
        help="UsageConfig override (repeatable), e.g. "
        "max_tenant_families=64 or conviction_share=0.5",
    )
    args = parser.parse_args(argv)
    startup.mark("imports")

    # Persistent compile cache, before the first program compiles: a
    # restarted server, and a gateway's next replica, skip the compile.
    from ditl_tpu.runtime.distributed import enable_compile_cache

    enable_compile_cache()
    compile_counter()  # counts every program this process builds from here on
    start_profiler_server(args.profiler_port)

    from ditl_tpu.config import Config, parse_overrides

    _cfg = parse_overrides(
        Config(), [f"telemetry.{o}" for o in args.telemetry_override]
        + [f"usage.{o}" for o in args.usage_override]
    )
    telemetry_cfg = _cfg.telemetry
    usage_cfg = _cfg.usage
    tracer = None
    if args.trace_dir and jax.process_index() == 0:
        # Process-0-gated like serving itself: pod WORKER replicas replay
        # the coordinator's scheduler ticks with no upstream trace context
        # — an armed worker tracer would journal a rootless phantom span
        # tree per request (N traces for one client request in the export).
        import os

        from ditl_tpu.telemetry.journal import EventJournal

        tag = os.getpid()  # unique per replica subprocess behind a gateway
        tracer = Tracer(EventJournal(
            os.path.join(args.trace_dir, f"events-server-{tag}.jsonl"),
            source=f"server-{tag}",
            max_bytes=telemetry_cfg.journal_max_bytes(),
        ))
        compile_counter().journal = tracer.journal  # jit.compile events
        startup.attach(tracer)  # startup.* spans, the closed leg backdated

    # Per-tenant usage metering (ISSUE 15): the meter is on by default on
    # process 0 (bounded per-tenant state, terminal-path-only updates);
    # --usage-dir additionally arms the crash-consistent ledger. Both are
    # handed to the engine (its terminal paths write the rows) and to
    # make_server (the lockstep paths + /usage).
    usage_meter = usage_ledger = None
    if not args.no_usage_metering and jax.process_index() == 0:
        from ditl_tpu.telemetry.usage import UsageMeter

        usage_meter = UsageMeter(
            max_tenant_families=usage_cfg.max_tenant_families)
    if args.usage_dir and jax.process_index() == 0:
        import os

        from ditl_tpu.telemetry.usage import UsageLedger, usage_ledger_path

        usage_ledger = UsageLedger(
            usage_ledger_path(args.usage_dir, f"server-{os.getpid()}"),
            source=f"server-{os.getpid()}",
            max_bytes=telemetry_cfg.journal_max_bytes(),
        )

    # Flight recorder + anomaly plane (ISSUE 10): the engine's tick ring is
    # always on; --incident-dir additionally arms the serving detectors +
    # the incident manager, all sharing ONE metrics bundle so the bundle's
    # metrics.prom snapshot is exactly what /metrics would have answered.
    serving_metrics = incidents = anomaly_monitor = slo = None
    if args.incident_dir and jax.process_index() == 0:
        import os

        from ditl_tpu.telemetry import (  # noqa: F401 (grouped arm imports)
            AnomalyPlane, FlightRecorder, IncidentManager,
            ServingAnomalyMonitor, ServingDetector, ServingMetrics,
        )
        from ditl_tpu.telemetry.slo import serving_slo

        serving_metrics = ServingMetrics()
        flight = FlightRecorder(telemetry_cfg.flight_ring_size)
        journal = tracer.journal if tracer is not None else None
        incidents = IncidentManager(
            args.incident_dir,
            flight=flight,
            metrics_render=serving_metrics.render,
            journal_dir=args.trace_dir,
            registry=serving_metrics.registry,
            source=f"server-{os.getpid()}",
            **telemetry_cfg.incident_kwargs(),
        )
        plane = AnomalyPlane(incidents=incidents, journal=journal)
        slo = serving_slo(
            serving_metrics, **telemetry_cfg.serving_slo_kwargs(),
            journal=journal, on_alert=plane.on_slo_alert,
        )
        anomaly_monitor = ServingAnomalyMonitor(
            plane,
            ServingDetector(**telemetry_cfg.serving_detector_kwargs()),
            slo=slo,
            check_every=telemetry_cfg.anomaly_check_every_ticks,
            # Noisy-neighbor forensics (ISSUE 15): when a latency storm
            # fires, the monitor convicts the tenant dominating the
            # meter's windowed prefill/device share and names it (plus
            # its usage snapshot) in the incident bundle.
            usage=usage_meter,
            conviction_share=usage_cfg.conviction_share,
            conviction_min_tokens=usage_cfg.conviction_min_tokens,
        )
    else:
        flight = None

    if args.mesh and not args.pod and jax.process_count() > 1:
        parser.error("--mesh on a multi-host pod requires --pod: the mesh "
                     "spans all hosts' devices, so every process must join "
                     "the collective decode loop")
    # --adapter composes with BOTH engines: the continuous engine carries
    # a per-slot adapter id (requests with different adapters share ticks).
    if args.adapter and args.pod and args.engine != "continuous":
        parser.error("--adapter with --pod requires --engine continuous "
                     "(only the continuous tick broadcast carries adapter "
                     "ids)")
    if args.speculative != "off" and args.engine != "continuous":
        # Lock-step speculation rides its own generator (below); the extra
        # compositions (pod, adapters) exist on the continuous engine only.
        if args.pod:
            parser.error("--speculative with --pod requires --engine "
                         "continuous (spec ticks ride the tick broadcast; "
                         "the lock-step pod protocol has no verify path)")
        if args.adapter:
            parser.error("--speculative with --adapter requires --engine "
                         "continuous (spec ticks carry per-slot adapter "
                         "ids; the lock-step spec generator does not)")
    if args.fsm_capacity and args.engine != "continuous":
        parser.error("--fsm-capacity (guided decoding) requires --engine "
                     "continuous: grammar masks ride the slot scheduler's "
                     "decode ticks")
    if args.draft_preset and (
        args.engine != "continuous" or args.speculative == "off"
    ):
        parser.error("--draft-preset requires --engine continuous with "
                     "--speculative on|auto (the draft model drafts for "
                     "speculative ticks)")
    if args.draft_checkpoint and not args.draft_preset:
        parser.error("--draft-checkpoint needs --draft-preset")
    if args.fsm_capacity and args.pod:
        parser.error("--fsm-capacity does not compose with --pod yet (the "
                     "tick broadcast does not carry grammar registrations)")
    if args.host_tier_mb and (
        args.engine != "continuous" or args.cache_mode != "paged"
    ):
        parser.error("--host-tier-mb requires --engine continuous with "
                     "--cache-mode paged (the tier spills and swaps KV "
                     "pages)")
    if args.host_tier_mb and args.pod:
        parser.error("--host-tier-mb does not compose with --pod yet "
                     "(every process would pay the spill fetch, and "
                     "handoff imports would desync the replicated "
                     "scheduler)")
    if args.kv_handoff and (
        args.engine != "continuous" or args.cache_mode != "paged"
        or args.pod
    ):
        parser.error("--kv-handoff requires a solo paged continuous "
                     "engine (--engine continuous --cache-mode paged, "
                     "no --pod)")
    # Double-buffered ticks (every continuous engine runs them) and
    # --admission optimistic both compose with --pod:
    # the lagged harvest and the preemption decisions (_topup_pages /
    # _pick_victim) are deterministic functions of the replicated scheduler
    # state, so every replica double-buffers, preempts, and resumes
    # identically. Pinned single-process in tests/test_podserve.py and at
    # real process_count=2 by the "paged" drill leg (tests/multiproc_drill.py).
    if args.admission == "optimistic":
        if args.engine != "continuous" or args.cache_mode != "paged":
            parser.error("--admission optimistic requires --engine "
                         "continuous --cache-mode paged (only the page pool "
                         "can be reclaimed mid-flight)")
    if jax.process_index() != 0 and not args.pod:
        # Without --pod, one process binds the port and the others exit; with
        # --pod every process joins the collective decode loop below.
        logger.info("process %d: serving is process-0 only, exiting", jax.process_index())
        return 0

    mesh = None
    if args.mesh:
        import dataclasses as _dc

        from ditl_tpu.config import MeshConfig
        from ditl_tpu.runtime.mesh import build_mesh

        axes = dict(kv.split("=", 1) for kv in args.mesh.split(","))
        mesh = build_mesh(
            _dc.replace(MeshConfig(), **{k: int(v) for k, v in axes.items()})
        )

    cfg = get_preset(args.preset) if args.preset else ModelConfig()
    if args.override:
        from ditl_tpu.config import Config, parse_overrides

        cfg = parse_overrides(
            Config(model=cfg), [f"model.{o}" for o in args.override]
        ).model
    if args.kv_quant == "int8":
        cfg = dataclasses.replace(cfg, kv_cache_dtype="int8")
    startup.mark("runtime")
    tokenizer = get_tokenizer(args.tokenizer)
    loader = _tokenizer_loader(tokenizer)
    logger.info("tokenizer %s loaded through %s", args.tokenizer, loader)
    startup.mark("tokenizer", loader=loader)
    params = llama.init_params(jax.random.key(0), cfg)
    params_restored = False
    if args.checkpoint_dir:
        from ditl_tpu.train.checkpoint import CheckpointManager

        ckpt = CheckpointManager(args.checkpoint_dir)
        restored = ckpt.restore_latest_params(jax.eval_shape(lambda: params))
        if restored is not None:
            params = restored
            params_restored = True
            logger.info("restored params from %s", args.checkpoint_dir)
        ckpt.close()
    adapter_names: dict[str, int] = {}
    if args.adapter_pool < 0:
        parser.error("--adapter-pool must be >= 0")
    if args.adapter_pool and (args.engine != "continuous" or args.pod):
        # Hot loads ride the ThreadedEngine.call driver seam; the lockstep
        # path has no driver thread and a pod install on process 0 alone
        # would desync the replicated schedulers.
        parser.error("--adapter-pool requires --engine continuous without "
                     "--pod (hot loads ride the driver-thread seam)")
    if args.adapter or args.adapter_pool:
        if cfg.lora_rank <= 0:
            parser.error("--adapter/--adapter-pool need a LoRA-capable "
                         "config (a preset/checkpoint with "
                         "model.lora_rank > 0)")
        if args.quantize == "int8":
            parser.error("--adapter does not compose with --quantize "
                         "(adapters stay float; merge instead to quantize)")
        from ditl_tpu.models import lora as lora_mod
        from ditl_tpu.train.checkpoint import CheckpointManager

        # Adapter id 0 serves the "base" model name. If the restored base
        # checkpoint was itself LoRA-fine-tuned (its own lora tree is
        # non-zero), that tree IS the base behavior — replacing it with a
        # zeros adapter would silently serve un-adapted weights for the base
        # model name.
        base_lora = params["layers"].get("lora")
        if base_lora is not None and any(
            bool(jax.numpy.any(leaf != 0)) for leaf in jax.tree.leaves(base_lora)
        ):
            stacks = [base_lora]
            logger.info(
                "--adapter: base checkpoint carries a non-zero LoRA tree; "
                "keeping it as adapter slot 0"
            )
        else:
            stacks = [lora_mod.zeros_adapter(cfg)]  # id 0 = base model
        for item in args.adapter:
            if "=" not in item:
                parser.error(f"--adapter wants NAME=ORBAX_DIR, got {item!r}")
            name, path = item.split("=", 1)
            ckpt = CheckpointManager(path)
            restored = ckpt.restore_latest_params(jax.eval_shape(lambda: params))
            ckpt.close()
            if restored is None:
                parser.error(f"--adapter {name}: no checkpoint in {path}")
            adapter = restored["layers"].get("lora")
            if adapter is None:
                parser.error(f"--adapter {name}: checkpoint has no LoRA tree")
            stacks.append(adapter)
            adapter_names[name] = len(stacks) - 1
        # Hot-load pool (ISSUE 16): extra zeroed rows the adapter
        # registry fills at runtime — a zeros row serves exactly base
        # until /v1/adapters/load installs something into it.
        for _ in range(args.adapter_pool):
            stacks.append(lora_mod.zeros_adapter(cfg))
        params = {
            **params,
            "layers": {
                **params["layers"],
                "lora": lora_mod.stack_adapters(stacks),
            },
        }
        logger.info(
            "multi-LoRA serving: base + %d adapters (%s)%s",
            len(adapter_names), ", ".join(adapter_names) or "-",
            f" + {args.adapter_pool} free pool rows"
            if args.adapter_pool else "",
        )
    if args.quantize == "int8":
        from ditl_tpu.ops.quant import quantize_weights

        params = quantize_weights(params)
        logger.info("quantized weights to int8 (weight-only)")
    if mesh is not None and not args.pod:
        params = _place_params(params, cfg, mesh)
    if startup.armed:
        # A leg is host wall time; only a journaled start pays the wait that
        # puts the device's share of the draw in THIS leg (synced=1).
        jax.block_until_ready(params)
    startup.mark("params", synced=int(startup.armed),
                 param_bytes=lambda: _tree_bytes(params),
                 restored=params_restored)
    generator = Generator(params, cfg, tokenizer, mesh=mesh)
    draft_params = draft_cfg = None
    if args.draft_preset:
        draft_cfg = get_preset(args.draft_preset)
        if draft_cfg.vocab_size != cfg.vocab_size:
            parser.error(
                f"--draft-preset vocab {draft_cfg.vocab_size} must match "
                f"the target's {cfg.vocab_size} (same token space)"
            )
        draft_params = llama.init_params(jax.random.key(1), draft_cfg)
        if args.draft_checkpoint:
            from ditl_tpu.train.checkpoint import CheckpointManager

            ckpt = CheckpointManager(args.draft_checkpoint)
            restored = ckpt.restore_latest_params(
                jax.eval_shape(lambda: draft_params)
            )
            ckpt.close()
            if restored is None:
                parser.error(
                    f"--draft-checkpoint: no checkpoint in "
                    f"{args.draft_checkpoint}"
                )
            draft_params = restored
            logger.info("restored draft params from %s", args.draft_checkpoint)

    def build_engine():
        from ditl_tpu.infer.continuous import ContinuousEngine

        return ContinuousEngine(
            params, cfg, tokenizer, n_slots=args.slots,
            max_cache_len=args.max_cache_len or None,
            prefill_chunk=args.prefill_chunk,
            cache_mode=args.cache_mode,
            page_size=args.page_size,
            n_pages=args.pages or None,
            max_queue=args.max_queue or None,
            mesh=mesh,
            speculative=args.speculative != "off",
            # 'on' forces every greedy tick speculative; 'auto' keeps the
            # measured-acceptance decision (engine default threshold).
            spec_threshold=0.0 if args.speculative == "on" else None,
            logprobs_k=args.logprobs_k,
            fsm_capacity=args.fsm_capacity,
            draft_params=draft_params, draft_cfg=draft_cfg,
            admission=args.admission,
            token_budget=args.token_budget,
            host_tier_mb=args.host_tier_mb,
            spill_max_pages_per_tick=args.spill_max_pages_per_tick,
            window_pages=args.window_pages,
            tracer=tracer,
            # Incident plane (ISSUE 10): shared metrics bundle + flight
            # recorder + detector monitor when --incident-dir armed them.
            metrics=serving_metrics,
            flight=flight,
            anomaly=anomaly_monitor,
            usage=usage_meter,
            usage_ledger=usage_ledger,
        )

    if args.pod and jax.process_index() != 0:
        if args.engine == "continuous":
            # Pod-wide continuous batching: every process replays the
            # coordinator's scheduler ticks on an identical engine replica.
            from ditl_tpu.infer.podserve import continuous_worker_loop

            continuous_worker_loop(build_engine())
        else:
            from ditl_tpu.infer.podserve import worker_loop

            worker_loop(generator)  # returns on the shutdown opcode
        return 0
    pod = None
    threaded = None
    engine = None
    if args.engine == "continuous":
        engine = build_engine()
        if args.pod:
            from ditl_tpu.infer.podserve import PodContinuousDriver

            threaded = pod = PodContinuousDriver(engine)

            class _TokenizerOnly:
                """All device work must ride the tick broadcast: direct
                Generator fallbacks (logprobs) would run a pod-wide SPMD
                program on process 0 alone and hang the pod — absent
                methods turn those requests into clean 400s."""

                def __init__(self, tok):
                    self.tokenizer = tok

            generator = _TokenizerOnly(tokenizer)
        else:
            from ditl_tpu.infer.continuous import ThreadedEngine

            threaded = ThreadedEngine(engine)
    elif args.pod:
        from ditl_tpu.infer.podserve import PodGenerator

        generator = pod = PodGenerator(generator)
    spec = None
    if args.speculative != "off" and args.engine == "lockstep":
        # The continuous engine speculates inside its own decode ticks
        # (build_engine above); the lock-step path uses the dedicated
        # speculative generator.
        from ditl_tpu.infer.speculative import (
            AutoSpeculativeGenerator, SpeculativeGenerator,
        )

        if args.speculative == "auto":
            spec = AutoSpeculativeGenerator(
                params, cfg, tokenizer, mesh=mesh, plain=generator
            )
        else:
            spec = SpeculativeGenerator(params, cfg, tokenizer, mesh=mesh)
    # the engine's cache tree: the page pools (0 without an engine)
    startup.mark("engine",
                 pool_bytes=lambda: _tree_bytes(getattr(engine, "cache", ())))
    server = make_server(
        generator, host=args.host, port=args.port, model_name=cfg.name,
        default_max_tokens=args.max_tokens, threaded_engine=threaded,
        adapter_names=adapter_names, spec_generator=spec,
        max_pending=args.max_pending or None,
        tracer=tracer, telemetry=telemetry_cfg, role=args.role,
        slo=slo, incidents=incidents, serving_metrics=serving_metrics,
        kv_handoff=args.kv_handoff and threaded is not None and pod is None,
        usage=usage_meter,
        usage_ledger=usage_ledger,
    )
    startup.mark("listen", port=server.server_address[1])
    startup.close()
    # Measured time-to-first-ready (ISSUE 12): /health echoes the legs' sum,
    # /v1/stats the legs themselves.
    server.cold_start_s = startup.total()
    server.startup = startup

    # SIGTERM = graceful drain (the gateway/orchestrator rolling-restart
    # protocol): /health flips to draining so routers stop sending traffic,
    # new work answers 503, in-flight requests finish, then the serve loop
    # exits. close() must not run on the serve_forever thread, so the
    # handler hands it to a helper thread.
    import signal as _signal

    def _on_sigterm(signum, frame):
        logger.info("SIGTERM: draining (in-flight requests will finish)")
        threading.Thread(
            target=server.close, kwargs={"drain": True}, daemon=True
        ).start()

    try:
        _signal.signal(_signal.SIGTERM, _on_sigterm)
    except ValueError:
        pass  # not the main thread (embedded serve()); drain via close()
    logger.info("serving %s (%s) on %s:%d", cfg.name, args.engine, args.host, args.port)
    try:
        server.serve_forever()
    except KeyboardInterrupt:
        pass
    finally:
        if pod is not None:
            pod.close()  # broadcast shutdown so workers exit their loop
        server.shutdown()
        if threaded is not None:
            threaded.close()
        if usage_ledger is not None:
            usage_ledger.close()
    return 0


if __name__ == "__main__":
    import sys

    from ditl_tpu.utils.logging import setup_logging

    setup_logging()
    sys.exit(serve())
