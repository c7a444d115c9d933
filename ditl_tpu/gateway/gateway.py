"""The serving gateway front door (ISSUE 4 tentpole): one OpenAI-compatible
HTTP endpoint over N engine replicas.

``infer/server.py`` is one listener over one engine; this module is the
layer above it that production serving actually needs — horizontal
scale-out (a fleet of replicas behind one URL), failover (idempotent
requests retry on surviving replicas when one dies mid-request),
cache-aware routing (router.py's consistent-hash affinity policy feeds
same-prefix/same-session traffic to the replica that already holds the
prefix KV), and tenant isolation (admission.py's per-tenant token buckets
and concurrency caps, applied before any routing).

Surface:

- ``POST /v1/completions``, ``/v1/chat/completions`` — routed + proxied,
  including SSE streaming pass-through (chunks relay as they arrive).
- ``POST /v1/embeddings``, ``/tokenize``, ``/detokenize`` — routed+proxied.
- ``GET /v1/models`` — proxied from a live replica.
- ``GET /health``, ``/stats`` — fleet state; ``GET /metrics`` — the
  gateway's own Prometheus exposition (per-replica routed/retried/hedged
  counts, affinity hit-rate, per-tenant throttles, fleet gauges).
- ``429`` with a backlog-aware ``Retry-After`` when the WHOLE fleet is
  saturated (every replica answered 429) or a tenant is over budget.

The gateway is stdlib-only (no jax import anywhere in ditl_tpu/gateway):
it must be runnable as a thin front process and unit-testable against stub
replicas. Wire-up lives in ``launch.py gateway`` (subprocess replicas);
the drills build in-process fleets (``tests/gateway_drivers.py``).
"""

from __future__ import annotations

import collections
import http.client
import json
import math
import re
import threading
import time
import urllib.error
import urllib.request
from concurrent.futures import (
    FIRST_COMPLETED,
    ThreadPoolExecutor,
    TimeoutError as FutureTimeoutError,
    wait,
)
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

from ditl_tpu.chaos import InjectedFault, maybe_inject
from ditl_tpu.config import GatewayConfig
from ditl_tpu.gateway.admission import (
    SLO_CLASS_NAMES, TenantAdmission, sanitize_label, tenant_label,
)
from ditl_tpu.gateway.replica import Fleet, FleetSupervisor
from ditl_tpu.gateway.roles import handoff_sources, role_candidates
from ditl_tpu.gateway.router import (
    affinity_key, make_policy, prompt_token_estimate,
)
from ditl_tpu.telemetry.flight import ROUTING_RING
from ditl_tpu.telemetry.registry import LATENCY_BUCKETS_S, MetricsRegistry
from ditl_tpu.telemetry.serving import backlog_retry_after
from ditl_tpu.telemetry.slo import BurnRateMonitor, gateway_slo
from ditl_tpu.telemetry.tracing import (
    NULL_TRACER,
    Tracer,
    format_traceparent,
    parse_traceparent,
    resolve_request_id,
)
from ditl_tpu.utils.http11 import KeepAliveHandlerMixin
from ditl_tpu.utils.logging import get_logger

logger = get_logger(__name__)

__all__ = ["GatewayMetrics", "make_gateway", "main"]

PREFIX = "ditl_gateway"

# Loop ticks are sub-millisecond when healthy; the serving-latency
# buckets (5ms floor) would put every healthy tick in the first bucket
# and hide a 10x regression. A tick in the right tail means something
# blocked the loop (troubleshooting §35).
LOOP_TICK_BUCKETS_S = (
    0.0005, 0.001, 0.0025, 0.005, 0.01, 0.025, 0.05, 0.1, 0.25, 0.5, 1.0,
)


class _HedgeQueueTimeout(OSError):
    """A relay attempt expired in the hedge executor's queue before its
    upstream open could start — a GATEWAY-local backlog, not a replica
    failure. _relay_one retries it like any connection error but must NOT
    note_failure the replica: a request storm saturating the executor
    would otherwise bump healthy replicas past the supervisor's
    fail_threshold and restart them, amplifying the overload exactly when
    the gateway is the bottleneck."""


class GatewayMetrics:
    """Gateway-side telemetry bundle (telemetry/registry.py instruments;
    rendered by the gateway's /metrics). Per-replica and per-tenant
    counters are created lazily with the id sanitized into the metric NAME
    (the registry has no label support; each replica/tenant becomes its own
    family, which the classic text format is fine with)."""

    def __init__(self, registry: MetricsRegistry | None = None):
        self.registry = registry if registry is not None else MetricsRegistry()
        self._tenant_labels: set[str] = set()
        r = self.registry
        self.requests = r.counter(
            f"{PREFIX}_requests", "requests received by the gateway")
        self.completed = r.counter(
            f"{PREFIX}_requests_completed", "requests relayed to completion")
        self.retries = r.counter(
            f"{PREFIX}_retries",
            "proxy attempts retried on another replica (replica death/busy)")
        self.hedges = r.counter(
            f"{PREFIX}_hedges", "hedged duplicate requests fired")
        self.throttled = r.counter(
            f"{PREFIX}_throttled", "requests rejected by tenant admission")
        self.saturated = r.counter(
            f"{PREFIX}_fleet_saturated",
            "requests 429'd because every replica was saturated")
        self.no_replica = r.counter(
            f"{PREFIX}_no_replica", "requests failed with no live replica")
        self.stream_aborts = r.counter(
            f"{PREFIX}_stream_aborts",
            "streams cut mid-flight by a dying replica (not retryable)")
        self.replica_deaths = r.counter(
            f"{PREFIX}_replica_deaths",
            "replica died->drain->relaunch cycles the supervisor ran "
            "(the anomaly plane's death-rate input, ISSUE 10)")
        # Crash recovery (ISSUE 20): the --recover path's outcome
        # accounting. adopted + relaunched partition the non-parked,
        # non-quarantined roster of each recovery pass; a nonzero
        # relaunched count on a drill that expected pure adoption is the
        # stale-manifest signature (troubleshooting §38).
        self.recovery_runs = r.counter(
            f"{PREFIX}_recovery_runs",
            "gateway crash-recovery passes run (--recover startups that "
            "found a fleet manifest)")
        self.recovery_adopted = r.counter(
            f"{PREFIX}_recovery_adopted",
            "still-alive replica processes adopted by a recovering "
            "gateway (pid liveness + /health cross-check both passed; "
            "zero restarts paid)")
        self.recovery_relaunched = r.counter(
            f"{PREFIX}_recovery_relaunched",
            "manifest replicas a recovering gateway had to relaunch "
            "fresh (dead pid, recycled pid, or no /health answer on the "
            "recorded port)")
        # Restart amnesty accounting (ISSUE 20 satellite): tenants whose
        # token bucket restarted FULL because no persisted level covered
        # them — the pre-recovery behavior, now visible instead of a
        # silent rate-limit reset on every gateway bounce.
        self.admission_amnesty = r.counter(
            f"{PREFIX}_admission_amnesty",
            "rate-limited tenants whose token bucket restarted full "
            "after --recover because the manifest held no admission "
            "snapshot for them")
        self.affinity_hits = r.counter(
            f"{PREFIX}_affinity_hits",
            "requests routed to the same replica as the previous request "
            "with the same affinity key")
        self.affinity_misses = r.counter(
            f"{PREFIX}_affinity_misses",
            "requests whose affinity key landed on a different replica "
            "than last time")
        self.e2e = r.histogram(
            f"{PREFIX}_request_e2e_seconds",
            "gateway receive -> response relayed", LATENCY_BUCKETS_S)
        self.replicas_live = r.gauge(
            f"{PREFIX}_replicas_live", "replicas currently routable")
        self.replicas_draining = r.gauge(
            f"{PREFIX}_replicas_draining", "replicas currently draining")
        # Actuation plane (ISSUE 12): pool accounting next to liveness —
        # active = launched minus parked/quarantined (a crashed-but-
        # recovering replica is still active), so "why are only 2 of my 4
        # replicas serving" is answerable from one scrape.
        self.replicas_active = r.gauge(
            f"{PREFIX}_replicas_active",
            "replicas participating in serving (not parked by a "
            "scale-down, not quarantined)")
        self.replicas_quarantined = r.gauge(
            f"{PREFIX}_replicas_quarantined",
            "replicas quarantined by death-storm remediation")
        # KV handoff orchestration (ISSUE 13): one counter per cost-model
        # outcome so the "handoff-fallback storm" signature is scrapable
        # (troubleshooting §30). attempted = eligible requests the model
        # evaluated; shipped / declined are its two branches; fallback =
        # an accepted handoff whose leg failed (the request still serves
        # via plain relay + re-prefill — zero client-visible failures).
        self.handoff_attempted = r.counter(
            f"{PREFIX}_handoff_attempted",
            "requests evaluated by the KV-handoff transfer-cost model")
        self.handoff_shipped = r.counter(
            f"{PREFIX}_handoff_shipped",
            "prefill->decode KV handoffs shipped to the decode replica")
        self.handoff_declined = r.counter(
            f"{PREFIX}_handoff_declined",
            "handoffs the cost model declined (re-prefill estimated "
            "cheaper than the transfer)")
        self.handoff_fallback = r.counter(
            f"{PREFIX}_handoff_fallback",
            "accepted handoffs that failed mid-leg and fell back to plain "
            "relay (the decode replica re-prefills)")
        # Upstream connection pool (ISSUE 14): lifetime pool accounting as
        # stats-mirror gauges (the pool's counters are plain host ints;
        # render() mirrors them each scrape — the host_tier_spilled
        # idiom). hits/misses grade reuse, discards flag stale-socket
        # churn (troubleshooting §32), idle is the parked-socket gauge.
        self.pool_hits = r.gauge(
            f"{PREFIX}_pool_hits",
            "pooled upstream connections reused across relays/polls/"
            "probes (lifetime, stats mirror)")
        self.pool_misses = r.gauge(
            f"{PREFIX}_pool_misses",
            "upstream hops that had to open a fresh connection "
            "(lifetime, stats mirror)")
        self.pool_discards = r.gauge(
            f"{PREFIX}_pool_discards",
            "pooled upstream connections discarded (stale socket, age/"
            "idle cap, mid-request error, or fleet-mutation invalidation; "
            "lifetime, stats mirror)")
        self.pool_idle = r.gauge(
            f"{PREFIX}_pool_idle",
            "idle kept-alive upstream connections currently parked in "
            "the pool")
        # Event-loop data plane (ISSUE 17): the loop's own health family.
        # All zero on the threaded fallback. Tick time is PROCESSING time
        # per loop iteration (select return -> work drained), not the
        # select wait; the p95 gauge is maintained by the loop itself over
        # its recent tick window so a scrape never reads the histogram's
        # buckets cross-thread mid-update.
        self.loop_open_connections = r.gauge(
            f"{PREFIX}_loop_open_connections",
            "client connections currently held by the event-loop data "
            "plane (0 on the threaded fallback)")
        self.loop_open_sse_streams = r.gauge(
            f"{PREFIX}_loop_open_sse_streams",
            "SSE relays currently fanned through the event loop without "
            "a parked thread")
        self.loop_tick = r.histogram(
            f"{PREFIX}_loop_tick_seconds",
            "event-loop tick processing time (select return -> work "
            "drained; a stalled loop shows here first)",
            LOOP_TICK_BUCKETS_S)
        self.loop_tick_p95 = r.gauge(
            f"{PREFIX}_loop_tick_p95_s",
            "p95 loop-tick processing time over the loop's recent tick "
            "window (loop-maintained mirror; troubleshooting §35)")
        self.loop_ready_queue_depth = r.gauge(
            f"{PREFIX}_loop_ready_queue_depth",
            "file descriptors the last selector poll returned ready "
            "(sustained high depth = the loop is the bottleneck)")
        self.loop_accept_backlog_drops = r.counter(
            f"{PREFIX}_loop_accept_backlog_drops",
            "accepted client connections dropped at the "
            "gateway.evloop_max_connections cap")
        # Offload-pool saturation accounting (ISSUE 18): queue-wait plus
        # worker occupancy so "loop is fine, pool is starved" is
        # distinguishable from a blocked loop (troubleshooting §36).
        self.loop_offload_queue = r.histogram(
            f"{PREFIX}_loop_offload_queue_seconds",
            "handler offload queue wait (loop submit -> worker pickup; "
            "grows when the pool, not the loop, is the bottleneck)",
            LOOP_TICK_BUCKETS_S)
        self.loop_offload_busy = r.gauge(
            f"{PREFIX}_loop_offload_busy_workers",
            "offload-pool workers currently running a handler (pinned at "
            "pool size + queue wait growing = pool starvation)")
        self.loop_offload_workers = r.gauge(
            f"{PREFIX}_loop_offload_workers",
            "configured offload-pool size (gateway.evloop_offload_workers"
            "; denominator for occupancy)")

    # Each distinct tenant label becomes its own metric family; tenants
    # arrive as arbitrary unauthenticated bearer tokens, so beyond this
    # many distinct labels the long tail aggregates into one
    # `..._tenant_other_*` family instead of growing the registry (and
    # the /metrics exposition) without bound.
    MAX_TENANT_FAMILIES = 256

    def replica_counter(self, replica_id: str, kind: str):
        return self.registry.counter(
            f"{PREFIX}_replica_{sanitize_label(replica_id)}_{kind}",
            f"requests {kind} for replica {sanitize_label(replica_id)}")

    def class_counter(self, kind: str, slo_class: str | None):
        """Per-SLO-class routed/relayed/429 counters (ISSUE 9 satellite):
        ``ditl_gateway_<kind>_by_class_<class>`` — class steering is
        observable from /metrics without reading journals. Attribution is
        the class the request is SCHEDULED under: a tenant pin wins, else
        the client's ask; requests with neither land under ``default``
        (the engine schedules those as interactive). Bounded: 3 known
        classes + default."""
        label = sanitize_label(slo_class or "default")
        return self.registry.counter(
            f"{PREFIX}_{kind}_by_class_{label}",
            f"requests {kind} carrying SLO class {label}")

    def role_counter(self, role: str, kind: str):
        """Per-replica-role routed/spilled counters (ISSUE 9): the
        disaggregated fleet's steering decisions, aggregated by role
        rather than replica id. Bounded: 3 roles."""
        label = sanitize_label(role or "hybrid")
        return self.registry.counter(
            f"{PREFIX}_role_{label}_{kind}",
            f"requests {kind} on {label}-role replicas")

    def action_counter(self, kind: str, outcome: str):
        """Per action-kind/outcome counters (ISSUE 12):
        ``ditl_gateway_action_<kind>_<outcome>`` — how often the autoscale
        planner acted, refused, or failed, scrapeable without reading
        journals. Bounded: 4 kinds x 4 outcomes."""
        return self.registry.counter(
            f"{PREFIX}_action_{sanitize_label(kind)}_{sanitize_label(outcome)}",
            f"autoscale/remediation actions of kind {sanitize_label(kind)} "
            f"with outcome {sanitize_label(outcome)}")

    def tenant_counter(self, tenant: str, kind: str):
        label = sanitize_label(tenant)
        if label not in self._tenant_labels:
            if len(self._tenant_labels) >= self.MAX_TENANT_FAMILIES:
                label = "other"
            else:
                self._tenant_labels.add(label)
        return self.registry.counter(
            f"{PREFIX}_tenant_{label}_{kind}",
            f"requests {kind} for tenant {label}")

    def affinity_ratio(self) -> float | None:
        """Measured affinity hit-rate (hits / (hits + misses)); None before
        any repeated key. Policy-independent: computed from where requests
        actually LANDED, so round-robin and affinity are comparable on the
        same trace."""
        total = self.affinity_hits.value + self.affinity_misses.value
        if total == 0:
            return None
        return self.affinity_hits.value / total

    def render(self, fleet: Fleet | None = None) -> str:
        if fleet is not None:
            self.replicas_live.set(fleet.live_count())
            self.replicas_draining.set(fleet.draining_count())
            self.replicas_active.set(len(fleet.active_ids()))
            self.replicas_quarantined.set(len(fleet.quarantined_ids()))
            pool = fleet.pool.stats()
            self.pool_hits.set(pool["hits"])
            self.pool_misses.set(pool["misses"])
            self.pool_discards.set(pool["discards"])
            self.pool_idle.set(pool["idle"])
            views = fleet.views()
            self._set_cache_gauges(views)
            self._set_role_gauges(views)
            self._set_cold_start_gauges(views)
        return self.registry.render()

    def _set_cold_start_gauges(self, views) -> None:
        """Measured per-replica time-to-first-ready (ISSUE 12), from each
        replica's /health stamp: the number the scale-to-zero wake budget
        is derived from, exposed so an operator can see what Retry-After a
        cold fleet will promise. Absent until a replica reports one."""
        for v in views:
            if isinstance(v.cold_start_s, (int, float)):
                self.registry.gauge(
                    f"{PREFIX}_replica_{sanitize_label(v.id)}"
                    "_cold_start_seconds",
                    "measured time-to-first-ready the replica stamped on "
                    "/health (process start -> port bound) - the "
                    "scale-to-zero wake-budget input",
                ).set(round(v.cold_start_s, 3))

    def _set_cache_gauges(self, views) -> None:
        """Per-replica + token-weighted fleet prefix-cache hit ratios
        (ISSUE 8), sourced from each replica's last /health poll (no scrape
        fan-out) and rendered NEXT TO the routing-side affinity hit-rate so
        the router's claim (routed hit => KV reuse) is checkable from one
        exposition: affinity_ratio high while fleet_prefix_cache_hit_ratio
        is ~0 means the router is keying on something the engines cannot
        reuse (docs/troubleshooting.md §26). The lifetime ratio and the
        windowed recent ratio (ISSUE 9 — per-poll deltas, what the spill
        walk actually steers on) render side by side so a stale-sticky
        lifetime number is visible as such."""
        hit = miss = 0
        r_hit = r_miss = 0
        for v in views:
            rid = sanitize_label(v.id)
            ratio = v.cache_hit_ratio
            if ratio is not None:
                hit += v.cache_hit_tokens
                miss += v.cache_miss_tokens
                self.registry.gauge(
                    f"{PREFIX}_replica_{rid}_prefix_cache_hit_ratio",
                    f"measured engine prefix-cache hit ratio of replica "
                    f"{rid} (lifetime, from its last health poll)",
                ).set(round(ratio, 4))
            recent = v.recent_cache_hit_ratio
            if recent is not None:
                r_hit += v.recent_cache_hit_tokens
                r_miss += v.recent_cache_miss_tokens
                self.registry.gauge(
                    f"{PREFIX}_replica_{rid}_recent_prefix_cache_hit_ratio",
                    f"windowed (last few health polls) prefix-cache hit "
                    f"ratio of replica {rid} - the spill-steering input",
                ).set(round(recent, 4))
        if hit + miss:
            self.registry.gauge(
                f"{PREFIX}_fleet_prefix_cache_hit_ratio",
                "token-weighted fleet prefix-cache hit ratio - compare "
                "against the affinity hit-rate counters",
            ).set(round(hit / (hit + miss), 4))
        if r_hit + r_miss:
            self.registry.gauge(
                f"{PREFIX}_fleet_recent_prefix_cache_hit_ratio",
                "token-weighted fleet prefix-cache hit ratio over the "
                "recent health-poll window",
            ).set(round(r_hit / (r_hit + r_miss), 4))

    def _set_role_gauges(self, views) -> None:
        """Per-role fleet aggregation (ISSUE 9): live replica counts and
        worst-case (max) TTFT/TPOT p95 across each role's replicas, plus
        the role's peak slot pressure — the per-role latency view that
        makes 'which half of the disaggregated fleet is hurting' a single
        scrape (docs/troubleshooting.md §27)."""
        by_role: dict[str, list] = {}
        for v in views:
            by_role.setdefault(v.role or "hybrid", []).append(v)
        for role, vs in sorted(by_role.items()):
            label = sanitize_label(role)
            self.registry.gauge(
                f"{PREFIX}_role_{label}_replicas_live",
                f"live {label}-role replicas",
            ).set(sum(1 for v in vs if v.live))
            self.registry.gauge(
                f"{PREFIX}_role_{label}_slot_pressure",
                f"max active_slots/capacity across {label}-role replicas",
            ).set(round(max((v.slot_pressure for v in vs), default=0.0), 4))
            for key, name in (("ttft_p95_s", "ttft"),
                              ("tpot_p95_s", "tpot")):
                vals = [getattr(v, key) for v in vs
                        if isinstance(getattr(v, key), (int, float))]
                if vals:
                    self.registry.gauge(
                        f"{PREFIX}_role_{label}_{name}_p95_s",
                        f"worst per-replica {name} p95 across {label}-role "
                        "replicas (lifetime histograms, health-polled)",
                    ).set(round(max(vals), 6))

    def summary(self) -> dict:
        out = self.registry.summary()
        ratio = self.affinity_ratio()
        if ratio is not None:
            out[f"{PREFIX}_affinity_ratio"] = round(ratio, 4)
        return out


class GatewayHTTPServer(ThreadingHTTPServer):
    daemon_threads = True
    allow_reuse_address = True

    def __init__(self, *args, **kwargs):
        # (timestamp, completed) samples for the fleet-level backlog-aware
        # Retry-After (same derivation the single server satellite uses).
        self._rate_samples: collections.deque = collections.deque(maxlen=64)
        # Persistent executors (ISSUE 14 satellite): hedged relays used to
        # build a fresh 2-worker ThreadPoolExecutor PER HEDGED REQUEST and
        # every /metrics//incidents fan-out built its own pool — thread
        # construction on the data plane's hot path. One hedge executor
        # and one fan-out executor per gateway, created here, shut down in
        # server_close's finally (the PR 11 thread-hygiene contract).
        # Hedge opens are short (connect + headers) but a primary must
        # never queue behind other requests' slow opens, so the hedge pool
        # is sized generously; fan-out probes are probe_timeout-bounded.
        self._hedge_pool = ThreadPoolExecutor(
            max_workers=64, thread_name_prefix="gw-hedge")
        self._fanout_pool = ThreadPoolExecutor(
            max_workers=32, thread_name_prefix="gw-fanout")
        super().__init__(*args, **kwargs)

    def server_close(self):
        try:
            super().server_close()
        finally:
            self._hedge_pool.shutdown(wait=False, cancel_futures=True)
            self._fanout_pool.shutdown(wait=False, cancel_futures=True)


class _GatewayHandler(KeepAliveHandlerMixin, BaseHTTPRequestHandler):
    # Injected by make_gateway:
    fleet: Fleet = None
    router = None
    admission: TenantAdmission = None
    gw: GatewayMetrics = None
    gwcfg: GatewayConfig = None
    # key -> replica id that last served it (affinity hit-rate measurement)
    affinity_last: collections.OrderedDict = None  # guarded-by: affinity_lock
    affinity_lock: threading.Lock = None
    # Request tracing (ISSUE 6): the gateway roots (or continues) each
    # request's trace and stamps every relay attempt's span context on the
    # upstream request (W3C traceparent), so replica/engine spans nest
    # under the relay that carried them. Unarmed by default.
    tracer: Tracer = NULL_TRACER
    # Fleet-level SLO burn-rate monitor (telemetry/slo.py), served at /slo.
    slo: BurnRateMonitor = None
    # Incident plane (ISSUE 10): the gateway's own bundle manager (served
    # and aggregated with the replicas' at /incidents) and the routing-
    # decision flight ring (telemetry/flight.py). Both unarmed by default.
    incidents = None
    flight = None
    # Actuation plane (ISSUE 12): the autoscale actuator (serves /actions,
    # answers scale-to-zero demand with a measured wake budget) and the
    # traffic recorder (--save-trace). Both unarmed by default.
    actuator = None
    recorder = None
    # KV movement plane (ISSUE 13): kvtier (config.KVTierConfig) arms the
    # prefill->decode handoff orchestration on the relay leg; journal
    # (telemetry/journal.EventJournal) records the per-request cost-model
    # decision + both estimates (`kv.handoff.*` events). Unarmed by
    # default.
    kvtier = None
    journal = None
    # Usage metering (ISSUE 15): a telemetry/usage.UsageLedger recording
    # one gateway-edge row per admission-controlled request (tenant
    # digest, class, terminal outcome, e2e) — the edge half of the
    # attribution story (tenant throttles and fleet-level 429/503/504s
    # never reach an engine ledger). Unarmed by default.
    usage = None
    # Adapter publication coordinator (ISSUE 16): a
    # gateway/publish.AdapterPublisher driving fleet-wide
    # verify -> per-replica swap walks for /v1/adapters/{load,evict,
    # publish}. make_gateway always arms one (it needs only the fleet).
    publisher = None
    # Offline bulk-inference lane (ISSUE 19): a gateway/bulk.BulkJobManager
    # serving /v1/bulk/jobs — journaled crash-consistent jobs dispatching
    # per-prompt items through _route_and_relay pinned best_effort.
    # Unarmed by default (bulk.dir empty -> the routes 404).
    bulk = None

    def log_message(self, *args):
        logger.debug("gateway http: " + args[0], *args[1:])

    # -- plumbing -----------------------------------------------------------

    def _request_id(self) -> str:
        """Stable per-request id echoed on EVERY response — including
        429/503/504 and SSE relays — and forwarded upstream, so one id
        joins the client's logs, the gateway's spans, and the replica's
        (ISSUE 6 satellite). Reset per request in do_GET/do_POST (handler
        instances persist across keep-alive requests)."""
        rid = getattr(self, "_rid", None)
        if rid is None:
            rid = resolve_request_id(self.headers.get("X-Request-Id"))
            self._rid = rid
        return rid

    def _send_json(self, status: int, payload: dict,
                   retry_after: int | None = None) -> None:
        body = json.dumps(payload).encode()
        self.send_response(status)
        self.send_header("Content-Type", "application/json")
        self.send_header("X-Request-Id", self._request_id())
        if retry_after is not None:
            self.send_header("Retry-After", str(retry_after))
        self.send_header("Content-Length", str(len(body)))
        self.end_headers()
        self.wfile.write(body)

    def _tenant(self) -> str:
        auth = self.headers.get("Authorization", "")
        if auth.lower().startswith("bearer "):
            return auth[7:].strip() or "anonymous"
        return "anonymous"

    def _sample_rate(self) -> None:
        self.server._rate_samples.append(
            (time.time(), self.gw.completed.value)
        )

    def _fleet_retry_after(self, floor: int = 1,
                           slo_class: str = "") -> int:
        """Backlog-aware Retry-After for fleet-level 429s: total backlog
        (queue + active across live replicas) over the gateway's recent
        completion rate — the same telemetry.serving.backlog_retry_after
        derivation the single server uses per replica.

        For ``best_effort`` callers on a bulk-armed gateway (ISSUE 19)
        the derivation switches inputs entirely: backlog = the bulk
        lane's pending work items, rate = the lane's own item-completion
        samples. A bulk submitter bounced off a deep offline backlog
        must come back when the BACKLOG has moved, not on the
        interactive service-rate clamp — the class hint also relaxes
        the clamp inside backlog_retry_after."""
        if slo_class == "best_effort" and self.bulk is not None:
            return backlog_retry_after(
                self.bulk.rate_samples, self.bulk.backlog(), floor=floor,
                slo_class=slo_class,
            )
        backlog = sum(
            v.queue_depth + v.active_slots + v.outstanding
            for v in self.fleet.views() if v.live
        )
        return backlog_retry_after(
            self.server._rate_samples, backlog, floor=floor,
            slo_class=slo_class,
        )

    # -- GET ----------------------------------------------------------------

    def do_GET(self):
        self._rid = None  # fresh id per request on keep-alive connections
        path, _, query = self.path.partition("?")
        path = path.rstrip("/") or "/"
        if path in ("/health", "/v1/health"):
            live = self.fleet.live_count()
            payload = {
                "status": "ok" if live else "no_live_replicas",
                "replicas_live": live,
                "replicas_draining": self.fleet.draining_count(),
                "replicas_total": len(self.fleet.ids),
            }
            # Loop-lag p95 from the evloop watchdog, absent != 0: only
            # reported when the watchdog is armed AND has observations
            # (same discipline as the replica role p95s).
            wd = getattr(self.server, "watchdog", None)
            lag = wd.lag_p95() if wd is not None else None
            if lag is not None:
                payload["loop_lag_p95_s"] = round(lag, 6)
            self._send_json(200 if live else 503, payload)
        elif path in ("/stats", "/v1/stats"):
            payload = {
                "router": getattr(self.router, "name", "unknown"),
                "replicas": {
                    v.id: {
                        "address": list(v.address),
                        "live": v.live,
                        "draining": v.draining,
                        "role": v.role,
                        "outstanding": v.outstanding,
                        "queue_depth": v.queue_depth,
                        "active_slots": v.active_slots,
                        "capacity": v.capacity,
                        "slot_pressure": round(v.slot_pressure, 4),
                        "prefix_cache_hit_ratio": v.cache_hit_ratio,
                        "recent_prefix_cache_hit_ratio":
                            v.recent_cache_hit_ratio,
                        "ttft_p95_s": v.ttft_p95_s,
                        "tpot_p95_s": v.tpot_p95_s,
                        "loop_lag_p95_s": v.loop_lag_p95_s,
                    }
                    for v in self.fleet.views()
                },
            }
            ratio = self.gw.affinity_ratio()
            if ratio is not None:
                payload["affinity_ratio"] = round(ratio, 4)
            if self.admission is not None:
                payload["tenants"] = self.admission.snapshot()
            self._send_json(200, payload)
        elif path == "/metrics":
            if self.slo is not None:
                # Refresh the ditl_slo_* gauges (same registry) so /metrics
                # carries the burn rates /slo renders; the scrape doubles
                # as the monitor's sample tick.
                self.slo.report()
            body = (self.gw.render(self.fleet)
                    + self._replica_memory_section()
                    + f"\n# TYPE {PREFIX}_up gauge\n{PREFIX}_up 1\n").encode()
            self.send_response(200)
            self.send_header("Content-Type", "text/plain; version=0.0.4")
            self.send_header("X-Request-Id", self._request_id())
            self.send_header("Content-Length", str(len(body)))
            self.end_headers()
            self.wfile.write(body)
        elif path in ("/slo", "/v1/slo"):
            if self.slo is None:
                self._send_json(404, {"error": {"message":
                    "no SLO monitor configured"}})
            else:
                self._send_json(200, self.slo.report())
        elif path in ("/usage", "/v1/usage"):
            self._usage()
        elif path in ("/incidents", "/v1/incidents"):
            self._incidents()
        elif path in ("/actions", "/v1/actions"):
            # Actuation log (ISSUE 12): every planned/executed/refused/
            # failed action with its triggering signal snapshot and the
            # incident bundle it produced (the /actions-to-incident
            # cross-link, troubleshooting §30). 404 when the actuation
            # plane is unarmed — absent != "no actions taken".
            if self.actuator is None:
                self._send_json(404, {"error": {"message":
                    "no autoscale actuator configured"}})
            else:
                actions = self.actuator.recent()
                self._send_json(200, {
                    "count": len(actions),
                    "dry_run": bool(self.actuator.config.dry_run),
                    "wake_budget_s": round(
                        self.actuator.wake_budget_s(), 3),
                    "actions": actions,
                })
        elif path in ("/v1/models", "/models"):
            self._proxy_get("/v1/models")
        elif path in ("/v1/adapters", "/adapters"):
            self._adapters_get()
        elif path in ("/profile", "/v1/profile"):
            self._profile(query)
        elif path.startswith("/v1/bulk/jobs") or path.startswith("/bulk/jobs"):
            self._bulk_get(path, query)
        else:
            self._send_json(404, {"error": {"message": f"no route {self.path}"}})

    def _profile(self, query: str) -> None:
        """On-demand wall-clock profile (ISSUE 18): sample every thread
        for ``?seconds=N`` (clamped) and return flamegraph-ready
        collapsed stacks as text/plain. Stdlib sampler, no lock on the
        sample path — safe to hit on a loaded gateway."""
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
        self.send_header("X-Request-Id", self._request_id())
        self.send_header("Content-Length", str(len(body)))
        self.end_headers()
        self.wfile.write(body)

    def _incidents(self) -> None:
        """Fleet incident view (ISSUE 10): the gateway's own bundles plus
        every routable replica's /incidents listing, aggregated under one
        endpoint — "did anything fire anywhere" is one GET. Replicas
        without an armed incident plane answer 404 and are simply absent
        (absent != zero bundles); a slow/dead replica costs one skipped
        entry, never a wedged response."""
        from ditl_tpu.telemetry.incident import list_bundles

        own = (list_bundles(self.incidents.directory)
               if self.incidents is not None else [])
        replicas: dict[str, list] = {}

        def fetch(view):
            # Pooled probe (ISSUE 14): non-200 (404 = unarmed) raises
            # ValueError, read by the caller as "absent", exactly like the
            # old urlopen HTTPError.
            return self.fleet.pool.get_json(
                view.id, view.address, "/incidents",
                timeout=self.gwcfg.probe_timeout_s,
            )

        # /incidents is hit exactly when replicas are misbehaving, so N
        # slow replicas must cost ~probe_timeout_s total, not N x that.
        for view, data in self._fan_out_replicas(self.fleet.routable(),
                                                 fetch):
            if isinstance(data, dict) and data.get("incidents"):
                replicas[view.id] = data["incidents"]
        self._send_json(200, {
            "count": len(own) + sum(len(v) for v in replicas.values()),
            "gateway": own,
            "replicas": replicas,
        })

    def _adapters_get(self) -> None:
        """Fleet adapter view (ISSUE 16): every routable replica's
        /v1/adapters listing, fanned out concurrently with one shared
        deadline (the /incidents pattern). Replicas without an armed
        adapter plane answer 404 and are simply absent (absent != "zero
        adapters") — on a converged fleet every replica shows the same
        name->generation map; a mid-publication snapshot shows exactly
        which replicas have flipped."""
        def fetch(view):
            return self.fleet.pool.get_json(
                view.id, view.address, "/v1/adapters",
                timeout=self.gwcfg.probe_timeout_s,
            )

        replicas: dict[str, dict] = {}
        for view, data in self._fan_out_replicas(self.fleet.routable(),
                                                 fetch):
            if isinstance(data, dict) and "adapters" in data:
                replicas[view.id] = data
        self._send_json(200, {"replicas": replicas})

    def _usage(self) -> None:
        """Fleet usage view (ISSUE 15): every routable replica's /usage
        rollups fanned out concurrently (one shared deadline, the
        /incidents pattern) and merged into one per-tenant fleet rollup,
        plus the gateway's own admission counters — "what did tenant X
        consume, fleet-wide" is one GET. Replicas without an armed meter
        answer 404 and are simply absent (absent != zero usage)."""
        from ditl_tpu.telemetry.usage import merge_rollups

        def fetch(view):
            return self.fleet.pool.get_json(
                view.id, view.address, "/usage",
                timeout=self.gwcfg.probe_timeout_s,
            )

        replicas: dict[str, dict] = {}
        for view, data in self._fan_out_replicas(self.fleet.routable(),
                                                 fetch):
            if isinstance(data, dict) and isinstance(
                    data.get("tenants"), dict):
                replicas[view.id] = data["tenants"]
        payload = {
            "fleet": merge_rollups(list(replicas.values())),
            "replicas": replicas,
        }
        if self.admission is not None:
            # The gateway-edge view: admissions/throttles per tenant —
            # requests a throttle rejected never reach any replica meter.
            payload["gateway_tenants"] = self.admission.snapshot()
        self._send_json(200, payload)

    def _fan_out_replicas(self, views, fetch) -> list:
        """Concurrent per-replica ``fetch`` with ONE shared deadline
        (~probe_timeout_s for the whole fan-out): returns ``(view,
        result)`` pairs for the replicas that answered in time. A slow or
        dead replica costs one skipped entry, never a wedged response —
        stragglers are abandoned (queued-not-started futures cancelled,
        running ones die at their own socket timeouts). Runs on the
        gateway's persistent fan-out executor (ISSUE 14 satellite — no
        more per-scrape pool construction); shared by the /metrics memory
        section and /incidents."""
        out: list = []
        if not views:
            return out
        pool = self.server._fanout_pool
        futures = {pool.submit(fetch, v): v for v in views}
        done, not_done = wait(futures, timeout=self.gwcfg.probe_timeout_s)
        for f in not_done:
            f.cancel()
        for f in done:
            try:
                out.append((futures[f], f.result()))
            except (urllib.error.URLError, OSError,
                    http.client.HTTPException, ValueError):
                continue
        return out

    def _replica_memory_section(self) -> str:
        """Fleet HBM view (ISSUE 7): each routable replica's
        ``ditl_memory_*`` gauges, re-namespaced per replica
        (``ditl_memory_<rid>_device0_bytes_in_use``) so the fleet's memory
        headroom is scrapable from ONE endpoint. Replicas are fetched
        CONCURRENTLY with one shared deadline (~probe_timeout_s for the
        whole section, not per replica — N slow replicas must not push the
        gateway scrape past Prometheus's own timeout); a slow or dead
        replica costs one skipped section, never a wedged scrape. CPU
        replicas contribute nothing (no ditl_memory_* lines to filter)."""
        def fetch(view):
            return self.fleet.pool.get_text(
                view.id, view.address, "/metrics",
                timeout=self.gwcfg.probe_timeout_s,
            )

        out: list[str] = []
        for view, text in self._fan_out_replicas(self.fleet.routable(),
                                                 fetch):
            rid = sanitize_label(view.id)
            for line in text.splitlines():
                # Matches both samples and their # TYPE/# HELP metadata
                # (the family name follows the directive keyword).
                if "ditl_memory_" in line.split("{", 1)[0]:
                    out.append(line.replace(
                        "ditl_memory_", f"ditl_memory_{rid}_"
                    ))
        return ("\n" + "\n".join(out)) if out else ""

    def _proxy_get(self, path: str) -> None:
        for view in self.fleet.routable():
            try:
                self._send_json(200, self.fleet.pool.get_json(
                    view.id, view.address, path,
                    timeout=self.gwcfg.probe_timeout_s,
                ))
                return
            except (urllib.error.URLError, OSError,
                    http.client.HTTPException, ValueError):
                self.fleet.note_failure(view.id)
                continue
        self._send_json(503, {"error": {"message": "no live replica"}})

    # -- POST ---------------------------------------------------------------

    def do_POST(self):
        self._rid = None  # fresh id per request on keep-alive connections
        self._adapter_pin = None  # set per-request by _admit_and_route
        bulk_path = self.path.partition("?")[0].rstrip("/")
        if (bulk_path.startswith("/v1/bulk/jobs")
                or bulk_path.startswith("/bulk/jobs")):
            # Bulk routes parse their own body (a submit may be a JSONL
            # prompt upload, which the JSON-object gate below would 400).
            self._bulk_post(bulk_path)
            return
        try:
            length = int(self.headers.get("Content-Length", 0))
            raw = self.rfile.read(length) or b"{}"
            payload = json.loads(raw)
            if not isinstance(payload, dict):
                raise ValueError("body must be a JSON object")
        except (ValueError, json.JSONDecodeError) as e:
            # A malformed Content-Length leaves the body unread; on a
            # kept-alive connection those bytes would desync the next
            # request — close after the error response. (Malformed JSON
            # reached here with the body fully read; closing anyway is
            # one wasted reconnect, not a correctness cost.)
            self.close_connection = True
            self._send_json(400, {"error": {"message": f"bad request: {e}"}})
            return
        path = self.path.rstrip("/")
        if path.endswith(("/chat/completions", "/completions", "/embeddings")):
            self.gw.requests.inc()
            # Root (or continue, if the client sent traceparent) this
            # request's trace: every relay attempt below becomes a child
            # span, and the replica continues the chain across the process
            # boundary.
            span = self.tracer.start_span(
                "gateway.request",
                parent=parse_traceparent(self.headers.get("traceparent")),
                request_id=self._request_id(),
                route=path,
            )
            try:
                self._admit_and_route(path, payload, raw, span=span)
            finally:
                det = getattr(self, "_evloop_detached", None)
                if det is None:
                    span.end()
                else:
                    # Evloop SSE detach (ISSUE 17): the stream outlives
                    # this handler invocation — the loop ends the root
                    # span at stream end, after the relay span.
                    det["root"] = span
        elif path.endswith(("/tokenize", "/detokenize")):
            # Metadata routes: cheap, not admission-controlled, and kept
            # OUT of the serving instruments (record=False) — a stream of
            # millisecond tokenize calls would otherwise inflate the
            # measured completion rate behind Retry-After and corrupt the
            # affinity hit-rate the router A/B records.
            self.gw.requests.inc()
            self._route_and_relay(path, payload, raw, record=False)
        elif path.endswith(("/adapters/load", "/adapters/evict",
                            "/adapters/publish")):
            # Adapter control plane (ISSUE 16): fleet-wide publication —
            # verify-at-edge, then a journaled per-replica walk. Not
            # admission-controlled (operator/trainer traffic, like the
            # actuation plane), and kept out of the serving instruments.
            self._adapter_admin(payload, path.rsplit("/", 1)[1])
        else:
            self._send_json(404, {"error": {"message": f"no route {self.path}"}})

    def _adapter_admin(self, payload: dict, op: str) -> None:
        if self.publisher is None:
            self._send_json(404, {"error": {"message":
                "no adapter publisher configured"}})
            return
        owner = str(payload.get("owner") or "")
        if not owner:
            # Default attribution: the caller's credential-safe label —
            # same identity the replicas' per-tenant ledgers bill under.
            owner = tenant_label(
                self._tenant(),
                self.admission.per_tenant
                if self.admission is not None else ())
        status, answer = self.publisher.run(
            op,
            str(payload.get("name") or ""),
            directory=str(payload.get("dir")
                          or payload.get("directory") or ""),
            owner=owner,
        )
        self._send_json(status, answer)

    # -- bulk lane (ISSUE 19) ------------------------------------------------

    def _bulk_label(self) -> str:
        """Credential-safe tenant label — the only identity the bulk lane
        ever persists (job files, journal rows, usage rows). Raw bearers
        stay in admission state, exactly the ISSUE 15 discipline."""
        return tenant_label(
            self._tenant(),
            self.admission.per_tenant if self.admission is not None else ())

    @staticmethod
    def _bulk_parts(path: str) -> list[str]:
        parts = [p for p in path.split("/") if p]
        if parts and parts[0] == "v1":
            parts = parts[1:]
        return parts

    def _bulk_get(self, path: str, query: str) -> None:
        if self.bulk is None:
            self._send_json(404, {"error": {"message":
                "bulk lane not configured (set bulk.dir)"}})
            return
        parts = self._bulk_parts(path)
        if parts == ["bulk", "jobs"]:
            jobs = self.bulk.jobs()
            self._send_json(200, {"count": len(jobs), "jobs": jobs})
        elif len(parts) == 3 and parts[:2] == ["bulk", "jobs"]:
            st = self.bulk.status(parts[2])
            if st is None:
                self._send_json(404, {"error": {"message":
                    f"no bulk job {parts[2]!r}"}})
            else:
                self._send_json(200, st)
        elif (len(parts) == 4 and parts[:2] == ["bulk", "jobs"]
                and parts[3] == "results"):
            self._bulk_results(parts[2], query)
        else:
            self._send_json(404, {"error": {"message":
                f"no route {self.path}"}})

    def _bulk_results(self, job_id: str, query: str) -> None:
        """Ordered results JSONL. Range-resumable: ``Range: bytes=N-``
        (or ``?offset=N``) answers 206 with the suffix — a client that
        died mid-download (or is polling a running job) resumes from its
        last byte, and the contiguous-prefix flush guarantees every byte
        it already holds is final."""
        if self.bulk.status(job_id) is None:
            self._send_json(404, {"error": {"message":
                f"no bulk job {job_id!r}"}})
            return
        try:
            with open(self.bulk.results_path(job_id), "rb") as f:
                data = f.read()
        except OSError:
            data = b""
        start = 0
        m = re.match(r"^bytes=(\d+)-$", self.headers.get("Range") or "")
        if m:
            start = int(m.group(1))
        else:
            m = re.search(r"(?:^|&)offset=(\d+)", query or "")
            if m:
                start = int(m.group(1))
        start = min(start, len(data))
        body = data[start:]
        self.send_response(206 if start else 200)
        self.send_header("Content-Type", "application/x-ndjson")
        self.send_header("X-Request-Id", self._request_id())
        self.send_header("Accept-Ranges", "bytes")
        if start:
            self.send_header(
                "Content-Range",
                f"bytes {start}-{max(start, len(data) - 1)}/{len(data)}")
        self.send_header("Content-Length", str(len(body)))
        self.end_headers()
        self.wfile.write(body)

    def _bulk_post(self, path: str) -> None:
        length = int(self.headers.get("Content-Length", 0) or 0)
        raw = self.rfile.read(length) if length > 0 else b""
        if self.bulk is None:
            self._send_json(404, {"error": {"message":
                "bulk lane not configured (set bulk.dir)"}})
            return
        parts = self._bulk_parts(path)
        if parts == ["bulk", "jobs"]:
            self._bulk_submit(raw)
        elif (len(parts) == 4 and parts[:2] == ["bulk", "jobs"]
                and parts[3] == "cancel"):
            if self.bulk.cancel(parts[2]):
                self._send_json(200, {"id": parts[2],
                                      "cancel_requested": True})
            else:
                self._send_json(404, {"error": {"message":
                    f"no bulk job {parts[2]!r}"}})
        else:
            self._send_json(404, {"error": {"message":
                f"no route {self.path}"}})

    def _bulk_submit(self, raw: bytes) -> None:
        """POST /v1/bulk/jobs: inline JSON (``{"prompts": [...], adapter,
        max_new, sampling}``) or an uploaded JSONL body (one
        ``{"prompt": ...}`` — or bare string — per line; params via
        ``?adapter=&max_new=`` query). Quota-gated per tenant with typed
        429s; the accepted job is durable before this returns 200."""
        query = self.path.partition("?")[2]
        try:
            prompts, params = self._bulk_parse_submit(raw, query)
        except ValueError as e:
            self.close_connection = True
            self._send_json(400, {"error": {"message": f"bad request: {e}"}})
            return
        label = self._bulk_label()
        if self.admission is not None:
            decision = self.admission.acquire_bulk(label, len(prompts))
            if not decision.ok:
                self.gw.class_counter("429", "best_effort").inc()
                self._send_json(
                    429,
                    {"error": {"message": decision.reason,
                               "type": "bulk_quota_exceeded"}},
                    retry_after=max(
                        1, int(decision.retry_after_s + 0.999),
                        self._fleet_retry_after(slo_class="best_effort")),
                )
                return
        try:
            st = self.bulk.submit(label, prompts, params)
        except ValueError as e:
            if self.admission is not None:
                self.admission.release_bulk(label, len(prompts))
            self._send_json(400, {"error": {"message": f"bad request: {e}"}})
            return
        self._send_json(200, st)

    @staticmethod
    def _bulk_parse_submit(raw: bytes, query: str) -> tuple[list, dict]:
        text = (raw or b"").decode("utf-8", "replace").strip()
        if not text:
            raise ValueError("empty bulk submit body")
        params: dict = {}
        if text.startswith("{"):
            try:
                payload = json.loads(text)
                if not isinstance(payload, dict):
                    raise ValueError
            except ValueError:
                payload = None
            if payload is not None and "prompts" in payload:
                prompts = payload.get("prompts")
                if not isinstance(prompts, list):
                    raise ValueError("prompts must be a list")
                for k in ("adapter", "max_new", "sampling"):
                    if k in payload:
                        params[k] = payload[k]
                return prompts, params
        # JSONL upload: one prompt per line ({"prompt": ...} or a bare
        # JSON string); per-job params ride the query string.
        prompts = []
        for n, line in enumerate(text.splitlines(), start=1):
            line = line.strip()
            if not line:
                continue
            try:
                rec = json.loads(line)
            except ValueError as e:
                raise ValueError(f"bad JSONL at line {n}: {e}") from None
            if isinstance(rec, str):
                prompts.append(rec)
            elif isinstance(rec, dict) and isinstance(
                    rec.get("prompt"), str):
                prompts.append(rec["prompt"])
            else:
                raise ValueError(
                    f"JSONL line {n} must be a string or hold a "
                    "string 'prompt'")
        for m in re.finditer(r"(?:^|&)(adapter|max_new)=([^&]*)",
                             query or ""):
            k, v = m.group(1), m.group(2)
            params[k] = int(v) if k == "max_new" else v
        return prompts, params

    def _admit_and_route(self, path: str, payload: dict, raw: bytes,
                         span=None) -> None:
        m = self.gw
        tenant = self._tenant()
        # Credential-safe label (ISSUE 15): computed ONCE here and used
        # everywhere downstream — metrics, the traffic recorder, the
        # routing flight ring, the X-Tenant-Label relay header, and the
        # gateway usage ledger. The raw bearer keys admission state only.
        label = tenant_label(
            tenant,
            self.admission.per_tenant if self.admission is not None else ())
        # Reject-don't-drop for explicit client classes: a malformed
        # X-SLO-Class must 400 HERE, exactly as the replica would — the
        # relay layer only forwards KNOWN names (header-injection guard),
        # so silently stripping a typo'd class would serve the request at
        # the default priority with no error signal.
        cls_hdr = self.headers.get("X-SLO-Class")
        if cls_hdr is not None and cls_hdr not in SLO_CLASS_NAMES:
            self._send_json(400, {"error": {"message":
                f"unknown X-SLO-Class (one of {list(SLO_CLASS_NAMES)})"}})
            return
        pinned_class = None
        if self.admission is not None:
            # Raw Bearer token keys the admission state (per_tenant
            # overrides match on it); metrics get the credential-safe
            # label only (/metrics is unauthenticated).
            decision = self.admission.acquire(tenant)
            if not decision.ok:
                m.throttled.inc()
                m.tenant_counter(label, "throttled").inc()
                # Same attribution as routed/relayed/saturated: the class
                # the request would have been scheduled under (pin wins).
                m.class_counter(
                    "429",
                    decision.slo_class or self._client_class(payload),
                ).inc()
                if span is not None:
                    span.annotate(throttled=True)
                self._send_json(
                    429,
                    {"error": {"message": decision.reason,
                               "type": "rate_limit_error"}},
                    retry_after=max(1, min(30, math.ceil(
                        decision.retry_after_s))),
                )
                if self.usage is not None:
                    # A throttle is a terminal outcome only the gateway
                    # can bill — the request never reaches a replica.
                    self.usage.record(
                        tenant=label, outcome="429",
                        slo_class=(decision.slo_class
                                   or self._client_class(payload)
                                   or "default"),
                        prompt_tokens=prompt_token_estimate(payload),
                        throttled=True,
                    )
                return
            m.tenant_counter(label, "admitted").inc()
            pinned_class = decision.slo_class or None
            # Adapter pin (ISSUE 16): rides X-Adapter-Name on every relay
            # attempt of THIS request (stashed on the handler instance,
            # which serves one request at a time — the _rid pattern), and
            # OVERRIDES the payload's model field at the replica.
            self._adapter_pin = decision.adapter or None
        if self.recorder is not None:
            # Traffic recorder (ISSUE 12 satellite): one row per ADMITTED
            # request — throttled requests never reach here, so the saved
            # shape is the demand the fleet actually served, replayable
            # (autoscale.load_trace) with preserved inter-arrival times. Tenant rides as the credential-safe
            # digest, never the bearer token.
            self.recorder.note(
                tenant=label,
                slo_class=pinned_class or self._client_class(payload),
                prompt_tokens=prompt_token_estimate(payload),
                max_new=int(payload.get("max_tokens") or 0)
                if isinstance(payload.get("max_tokens"), (int, float))
                else 0,
                stream=bool(payload.get("stream")),
            )
        t0 = time.time()
        outcome = "error"
        try:
            outcome = self._route_and_relay(path, payload, raw, span=span,
                                            slo_class=pinned_class,
                                            tenant=label)
        finally:
            det = (getattr(self, "_evloop_detached", None)
                   if outcome == "detached" else None)
            if det is not None:
                # Evloop SSE detach (ISSUE 17): the request is still in
                # flight — it holds its admission slot and its e2e clock
                # until the loop sees the stream end. Everything below
                # runs then, via this closure, with the outcome the
                # CLIENT actually saw.
                def _finish(final_outcome: str) -> None:
                    if self.admission is not None:
                        self.admission.release(tenant)
                    m.e2e.observe(time.time() - t0)
                    if self.usage is not None:
                        self.usage.record(
                            tenant=label, outcome=final_outcome,
                            slo_class=(pinned_class
                                       or self._client_class(payload)
                                       or "default"),
                            prompt_tokens=prompt_token_estimate(payload),
                            stream=True,
                            e2e_s=round(time.time() - t0, 6),
                        )
                det["finish"] = _finish
            else:
                if self.admission is not None:
                    self.admission.release(tenant)
                m.e2e.observe(time.time() - t0)
                if self.usage is not None:
                    # One gateway-edge usage row per admitted request —
                    # the outcome the CLIENT saw (fleet 429/503/504s
                    # included), next to the engine-side rows the
                    # replicas ledger.
                    self.usage.record(
                        tenant=label, outcome=outcome,
                        slo_class=(pinned_class
                                   or self._client_class(payload)
                                   or "default"),
                        prompt_tokens=prompt_token_estimate(payload),
                        stream=bool(payload.get("stream")),
                        e2e_s=round(time.time() - t0, 6),
                    )

    def _client_class(self, payload: dict) -> str | None:
        """The SLO class the CLIENT asked for (validated header, else
        payload) — the metrics/steering view before any tenant pin."""
        cls = self.headers.get("X-SLO-Class")
        if cls in SLO_CLASS_NAMES:
            return cls
        cls = payload.get("slo_class")
        return cls if cls in SLO_CLASS_NAMES else None

    def _route_and_relay(self, path: str, payload: dict, raw: bytes,
                         record: bool = True, span=None,
                         slo_class: str | None = None,
                         tenant: str | None = None) -> str:
        """Route + relay one request; returns the terminal outcome the
        client saw (``200``/``429``/``503``/``504``/``cancel`` — the
        usage-ledger vocabulary; ``cancel`` = a stream aborted after
        bytes moved). ``tenant`` is the CREDENTIAL-SAFE label (never the
        bearer) — it rides the routing flight ring and the
        X-Tenant-Label header every relay stamps, which is how the
        replica's engine attributes its accounting (ISSUE 15)."""
        m, cfg = self.gw, self.gwcfg
        stream = bool(payload.get("stream"))
        key = affinity_key(payload, cfg.affinity_prefix_tokens)
        # The class the REPLICA will schedule under: the tenant pin wins
        # (it rides X-SLO-Class on every relay, overriding the payload),
        # else whatever the client asked for. This is also the routing
        # input for role steering on disaggregated fleets (ISSUE 9).
        eff_class = slo_class or self._client_class(payload)
        prompt_toks = prompt_token_estimate(payload) if cfg.role_routing \
            else 0
        # Deadline propagation (ISSUE 5): the effective budget is the
        # smaller of the client's `deadline_s` and the gateway's own
        # request_timeout_s; each relay attempt forwards the REMAINING
        # budget as X-Request-Deadline-S so the replica's engine evicts
        # work the gateway will have abandoned anyway (otherwise a retry
        # storm leaves dead generations burning slots fleet-wide).
        budget = cfg.request_timeout_s
        client_deadline = payload.get("deadline_s")
        has_client_deadline = (
            isinstance(client_deadline, (int, float)) and client_deadline > 0
        )
        if has_client_deadline:
            budget = min(budget, float(client_deadline))
        # Streams are the exception to "work the gateway will have
        # abandoned anyway": the gateway's socket timeout is per-read, so a
        # healthy stream longer than request_timeout_s is never abandoned
        # here — stamping the header would make the replica's engine evict
        # it and silently truncate the generation. Only an explicit client
        # deadline propagates into a stream; `budget` still bounds the
        # pre-first-byte attempt loop either way.
        propagate_deadline = has_client_deadline or not stream
        t_deadline0 = time.monotonic()
        timed_out = False
        tried: list[str] = []
        saw_busy = False
        busy_hint = 0
        for attempt in range(max(1, cfg.max_attempts)):
            remaining = budget - (time.monotonic() - t_deadline0)
            if remaining <= 0:
                timed_out = True
                break
            candidates = self.fleet.routable(exclude=tried)
            if not candidates:
                break
            # Role/class steering (ISSUE 9): restrict the candidate set by
            # the request's class before the policy picks. A no-op on
            # homogeneous fleets; on heterogeneous ones an empty preferred
            # set falls back to everything — no class is ever unroutable.
            if cfg.role_routing:
                candidates = role_candidates(
                    candidates, eff_class, prompt_toks,
                    cfg.long_prompt_tokens,
                )
            # route_info["spill"]: the affinity policy reports whether the
            # pick landed away from the key's (role-filtered) home — a
            # saturation spill, counted per role so the "all prefill-heavy
            # replicas saturated" signature is scrapable (troubleshooting
            # §27). Policies without homes never set it.
            route_info: dict = {}
            view = self.router.pick(key, candidates, slo_class=eff_class,
                                    prompt_tokens=prompt_toks,
                                    info=route_info)
            spilled = attempt == 0 and bool(route_info.get("spill"))
            if self.flight is not None:
                # Flight recorder (ISSUE 10): one routing-decision row per
                # relay attempt — which replica/role a request landed on,
                # under what class, and whether affinity spilled. Host
                # state only; dumped only into incident bundles.
                self.flight.ring(ROUTING_RING).record(
                    request=self._request_id(), attempt=attempt,
                    replica=view.id, role=view.role,
                    slo_class=eff_class or "default", spill=spilled,
                    stream=stream, candidates=len(candidates),
                    # Attribution (ISSUE 15): ring dumps inside incident
                    # bundles carry WHOSE requests landed where.
                    tenant=tenant or "anonymous",
                )
            if record:
                if attempt > 0:
                    m.retries.inc()
                    m.replica_counter(view.id, "retried").inc()
                m.replica_counter(view.id, "routed").inc()
                m.role_counter(view.role, "routed").inc()
                if spilled:
                    m.role_counter(view.role, "spilled").inc()
                if attempt == 0:
                    m.class_counter("routed", eff_class).inc()
            elif attempt > 0:
                m.retries.inc()
            if attempt == 0 and record and path.endswith("/completions"):
                # KV handoff (ISSUE 13): before relaying to the decode
                # replica the router just chose, maybe prefill the prompt
                # on a prefill_heavy replica and ship the paged KV over —
                # the decode replica's admission then prefix-matches the
                # shipped pages instead of re-prefilling. Best-effort by
                # construction: every failure path falls back to the plain
                # relay below (the replica re-prefills; the client never
                # sees a handoff failure).
                self._maybe_handoff(
                    view, payload, span=span,
                    deadline_left=remaining if propagate_deadline else None,
                )
            hedge_peers = (
                [v for v in candidates if v.id != view.id]
                if cfg.hedge_after_s > 0 and not stream else []
            )
            # The gateway's own in-flight count is the live half of the
            # load signal (least-outstanding, affinity spill, hedge-peer
            # choice, rolling_restart's drain-wait all read it); health-poll
            # queue depth alone is a full interval stale.
            # One relay span per attempt (retries are tagged, hedged
            # secondaries become SIBLING spans inside _hedged_open); the
            # attempt's span context rides the upstream request as
            # traceparent so the replica's spans nest under it.
            rspan = (
                self.tracer.start_span(
                    "gateway.relay", parent=span, replica=view.id,
                    attempt=attempt, retry=attempt > 0,
                    # Role-routing decision evidence (ISSUE 9): the trace
                    # shows WHERE each class landed and whether it spilled.
                    role=view.role, slo_class=eff_class or "default",
                    spill=spilled,
                )
                if span is not None else None
            )
            self.fleet.inc_outstanding(view.id)
            outcome, info = "error", None
            try:
                outcome, info = self._relay_one(
                    view, path, raw, stream, hedge_peers,
                    deadline_left=remaining if propagate_deadline else None,
                    span=rspan, root=span, slo_class=slo_class,
                    tenant=tenant,
                )
            finally:
                if outcome == "detached":
                    # Evloop SSE detach (ISSUE 17): the stream is still
                    # live — it stays outstanding (it IS load on the
                    # replica) and its relay span stays open; the loop
                    # runs both at stream end via the closure below.
                    pass
                else:
                    self.fleet.dec_outstanding(view.id)
                    if rspan is not None:
                        if outcome == "done" and info and info != view.id:
                            # A hedged peer served: THIS attempt lost —
                            # its span must not read as the one that
                            # answered (the winner's hedge span carries
                            # outcome="won").
                            rspan.end(outcome="lost", served_by=info)
                        else:
                            rspan.end(outcome=outcome)
            if outcome == "done":
                if record:
                    self._note_affinity(key, info or view.id)
                    m.completed.inc()
                    m.class_counter("relayed", eff_class).inc()
                    self._sample_rate()
                return "200"
            if outcome == "detached":
                # The loop owns both sockets now; the deferred half of
                # the "done"/"aborted" bookkeeping above runs when it
                # sees the stream end.
                det = self._evloop_detached
                served_id = info or view.id

                def _complete(ok: bool) -> None:
                    if ok:
                        if record:
                            self._note_affinity(key, served_id)
                            m.completed.inc()
                            m.class_counter("relayed", eff_class).inc()
                            self._sample_rate()
                    else:
                        # Bytes already relayed; nothing more the
                        # gateway can do (same terminal as "aborted").
                        m.stream_aborts.inc()
                    self.fleet.dec_outstanding(view.id)
                det["complete"] = _complete
                return "detached"
            if outcome == "aborted":
                # Bytes already relayed; nothing more the gateway can do.
                m.stream_aborts.inc()
                return "cancel"
            if outcome == "busy":
                saw_busy = True
                hint, busy_id = info
                busy_hint = max(busy_hint, hint)
                # Exclude the replica that actually SAID busy — under
                # hedging that can be the peer, not the primary (a merely
                # slow primary stays eligible for the next attempt).
                tried.append(busy_id)
            else:
                tried.append(view.id)
        if self.flight is not None:
            # Terminal failure row: the ring shows not just where requests
            # went but which ones the FLEET failed, and how.
            self.flight.ring(ROUTING_RING).record(
                request=self._request_id(),
                outcome=("timeout" if timed_out
                         else "saturated" if saw_busy else "no_replica"),
                slo_class=eff_class or "default",
                tenant=tenant or "anonymous",
            )
        if timed_out:
            self._send_json(504, {"error": {
                "message": "request deadline exhausted before any replica "
                           "answered",
                "type": "timeout_error"}})
            return "504"
        elif saw_busy:
            m.saturated.inc()
            if record:
                m.class_counter("429", eff_class).inc()
            self._send_json(
                429,
                {"error": {"message": "fleet saturated; retry later",
                           "type": "rate_limit_error"}},
                retry_after=self._fleet_retry_after(
                    floor=busy_hint, slo_class=eff_class or ""),
            )
            return "429"
        else:
            if self.actuator is not None:
                # Cold-start-aware admission (ISSUE 12): nothing routable
                # but a scale-down parked capacity we can wake — answer
                # 429 with the MEASURED wake budget as Retry-After (the
                # client's backoff lands after the replica is up) and let
                # the planner's wake action bring it back. A plain 503
                # would teach clients the fleet is broken when it is
                # merely asleep.
                retry = self.actuator.note_demand()
                if retry is not None:
                    self.gw.registry.counter(
                        f"{PREFIX}_cold_start_429",
                        "requests answered 429 with a wake-up Retry-After "
                        "while serving capacity was parked (scale-to-zero "
                        "admission)",
                    ).inc()
                    self._send_json(
                        429,
                        {"error": {"message":
                                   "fleet scaled to zero; waking a replica",
                                   "type": "rate_limit_error"}},
                        retry_after=retry,
                    )
                    return "429"
            m.no_replica.inc()
            self._send_json(503, {"error": {
                "message": "no live replica available"}})
            return "503"

    # -- KV handoff orchestration (ISSUE 13) ---------------------------------

    def _handoff_post(self, view, path: str, body: bytes, ctype: str,
                      timeout: float) -> bytes:
        """One bounded intra-host handoff hop over the upstream pool;
        non-200 raises (the caller falls back to plain relay)."""
        status, _, data = self.fleet.pool.request(
            view.id, view.address, "POST", path, body=body, headers={
                "Content-Type": ctype,
                "X-Request-Id": self._request_id(),
            }, timeout=timeout,
        )
        if status != 200:
            raise ValueError(f"{path} on {view.id} answered {status}")
        return data

    def _maybe_handoff(self, view, payload: dict, span=None,
                       deadline_left: float | None = None) -> None:
        """Prefill->decode KV handoff on the relay leg: when the chosen
        decode replica would have to prefill a long prompt, have a
        ``prefill_heavy`` replica prefill it instead, serialize the paged
        KV (infer/kv_transfer.py), and import it into the decode replica
        BEFORE the relay — DistServe/Splitwise disaggregation made real
        rather than routed-around.

        Gated by a measured transfer-cost model: estimated ship time
        (bytes / the decode replica's measured device_put bandwidth +
        fixed overhead) against estimated re-prefill time (tokens / its
        measured prefill tok/s), with configured floors before anything
        is measured. Re-prefill wins for short prompts and the model must
        say so — the decision AND both estimates are journaled per
        request (``kv.handoff.decision``). Chaos site ``kv.handoff``
        (error/delay) and any transport/HTTP failure — including a
        SIGKILL'd prefill replica mid-handoff — land in the fallback
        branch: counted, journaled, and the caller's plain relay proceeds
        with zero client-visible failures."""
        kt = self.kvtier
        if kt is None or not kt.handoff:
            return
        if not getattr(view, "kv_handoff", False) \
                or view.role == "prefill_heavy":
            return  # a prefill_heavy target prefills locally by design
        # The request's deadline budget BOUNDS the handoff, it is never
        # spent past it: with under a second left there is no room for
        # two hops plus a prefill — relay immediately (the deadline
        # contract promised a 504 in seconds, not a 120 s stall behind a
        # wedged prefill replica), and below each leg's socket timeout is
        # capped at the remaining budget.
        if deadline_left is not None and deadline_left < 1.0:
            return
        prompt = payload.get("prompt")
        if not isinstance(prompt, str) or not prompt:
            return  # chat/messages tokenization is replica-side; skip
        sources = handoff_sources(self.fleet.routable(), view.id)
        if not sources:
            return
        m = self.gw
        # Model-token estimate, not a raw word count: the floors and the
        # cost formulas are denominated in model tokens, and a whitespace
        # count undercounts subword/byte tokenizers several-fold (a long
        # code prompt would never clear the min-tokens floor). chars /
        # est_chars_per_token is the tokenizer-free approximation; the
        # word count stays as a lower bound.
        tokens = max(prompt_token_estimate(payload),
                     int(len(prompt) / kt.est_chars_per_token))
        m.handoff_attempted.inc()
        bpt = view.kv_bytes_per_token
        if not bpt:
            bpt = next(
                (v.kv_bytes_per_token for v in sources
                 if v.kv_bytes_per_token), 0.0,
            )
        bw = (view.kv_put_mbps or kt.put_bw_floor_mbps) * 1e6
        tps = view.prefill_tok_per_s or kt.prefill_tps_floor
        est_transfer_s = kt.handoff_overhead_s + tokens * (bpt or 0.0) / bw
        est_prefill_s = tokens / tps
        ship = (tokens >= kt.handoff_min_prompt_tokens
                and est_transfer_s < est_prefill_s)
        source = min(sources, key=lambda v: v.outstanding + v.queue_depth)
        if self.journal is not None:
            self.journal.event(
                "kv.handoff.decision",
                request=self._request_id(),
                decision="ship" if ship else "decline",
                prompt_tokens=tokens,
                est_transfer_s=round(est_transfer_s, 6),
                est_prefill_s=round(est_prefill_s, 6),
                decode_replica=view.id, prefill_replica=source.id,
            )
        if not ship:
            m.handoff_declined.inc()
            return
        t_start = time.monotonic()

        def leg_timeout() -> float:
            t = kt.handoff_timeout_s
            if deadline_left is not None:
                t = min(t, max(
                    0.001, deadline_left - (time.monotonic() - t_start)
                ))
            return t

        try:
            # Chaos seam: `error` = a lost handoff leg, `delay` = a slow
            # one; both end in the fallback branch below, exactly like a
            # replica dying mid-handoff does.
            maybe_inject("kv.handoff")
            blob = self._handoff_post(
                source, "/internal/prefill",
                json.dumps({"prompt": prompt}).encode(),
                "application/json", leg_timeout(),
            )
            self._handoff_post(
                view, "/internal/kv_handoff", blob,
                "application/octet-stream", leg_timeout(),
            )
        except (InjectedFault, OSError, http.client.HTTPException,
                ValueError) as e:
            m.handoff_fallback.inc()
            if self.journal is not None:
                self.journal.event(
                    "kv.handoff.fallback", request=self._request_id(),
                    error=str(e)[:200],
                    decode_replica=view.id, prefill_replica=source.id,
                )
            if span is not None:
                span.annotate(handoff="fallback")
            return
        m.handoff_shipped.inc()
        if self.journal is not None:
            self.journal.event(
                "kv.handoff.shipped", request=self._request_id(),
                bytes=len(blob), prompt_tokens=tokens,
                decode_replica=view.id, prefill_replica=source.id,
            )
        if span is not None:
            span.annotate(handoff="shipped")

    # -- relaying -----------------------------------------------------------

    def _open(self, view, path: str, raw: bytes,
              deadline_left: float | None = None, trace=None,
              slo_class: str | None = None, tenant: str | None = None):
        """One upstream request; returns (conn, resp) or raises OSError/
        HTTPException on connection-level failure (retryable — no bytes
        have been relayed to the client yet). ``deadline_left`` (seconds)
        bounds the socket AND is forwarded as X-Request-Deadline-S so the
        replica's engine gives up when the gateway will. ``trace`` (this
        attempt's relay span) is forwarded as the W3C traceparent, and the
        request id always rides X-Request-Id — the replica's logs/spans
        join the client's on either."""
        timeout = self.gwcfg.request_timeout_s
        headers = {
            "Content-Type": "application/json",
            "Authorization": self.headers.get("Authorization", ""),
            "X-Request-Id": self._request_id(),
        }
        # SLO class (ISSUE 8): a tenant pin from admission wins; otherwise
        # the client's own header is relayed. The header OVERRIDES the
        # payload at the replica, which is exactly what makes the pin
        # enforceable. Forwarded only when it names a known class — the
        # header-injection guard; malformed client values were already
        # 400'd in _admit_and_route before any relay.
        cls = slo_class or self.headers.get("X-SLO-Class")
        if cls in SLO_CLASS_NAMES:
            headers["X-SLO-Class"] = cls
        # Adapter pin (ISSUE 16): same precedence shape as the SLO class —
        # a tenant pin from admission wins, else the client's own header
        # is relayed. The header OVERRIDES the payload's model field at
        # the replica; an evicted/unknown name 404s there with a reason
        # (reject-don't-drop), so no validation is needed at this hop.
        adapter = getattr(self, "_adapter_pin", None) \
            or self.headers.get("X-Adapter-Name")
        if adapter:
            headers["X-Adapter-Name"] = adapter
        if tenant:
            # Tenant relay header (ISSUE 15): the admission-layer label
            # (digest or configured name — NEVER the raw bearer), so the
            # replica's engine attributes tokens/pages/device time to the
            # same identity the gateway throttles and meters under.
            headers["X-Tenant-Label"] = sanitize_label(tenant)
        if trace is not None:
            headers["traceparent"] = format_traceparent(trace.context)
        if deadline_left is not None:
            timeout = min(timeout, max(0.001, deadline_left))
            headers["X-Request-Deadline-S"] = f"{max(0.001, deadline_left):.3f}"
        # Pooled upstream hop (ISSUE 14): a kept-alive connection when one
        # is parked for this replica, else a fresh connect — exactly the
        # pre-pool behavior. A mid-request failure discards the connection
        # (closed + counted) and raises into the caller's existing retry
        # path; full-read-before-relay keeps that idempotent-safe.
        conn = self.fleet.pool.checkout(view.id, view.address, timeout)
        try:
            conn.request("POST", path, body=raw, headers=headers)
            return conn, conn.getresponse()
        except BaseException:
            self.fleet.pool.discard(conn)
            raise

    def _relay_one(self, view, path, raw, stream, hedge_peers,
                   deadline_left: float | None = None, span=None, root=None,
                   slo_class: str | None = None, tenant: str | None = None):
        """Proxy one attempt. Returns (outcome, info):
        ``("done", served_replica_id)`` — response relayed;
        ``("retry", None)`` — connection-level failure, safe to fail over;
        ``("busy", (retry_after, busy_replica_id))`` — a replica said
        429/503 (spill; under hedging the busy answer can come from the
        peer rather than the primary);
        ``("aborted", None)`` — died mid-stream after bytes were relayed.
        ``span`` is this attempt's relay span (its context rides upstream);
        ``root`` is the request span hedged secondaries chain under as
        SIBLINGS of this attempt."""
        # Chaos seam: `error` = an upstream connection failure before any
        # byte moved (exercises idempotent-safe failover), `delay` = a slow
        # relay (hedging drills), `kill` = losing the gateway process.
        fault = maybe_inject("gateway.relay", handles=("error",))
        if fault is not None and fault.action == "error":
            if span is not None:
                span.annotate(injected_fault=True)
            self.fleet.note_failure(view.id)
            return ("retry", None)
        served = view.id
        try:
            if hedge_peers:
                conn, resp, served = self._hedged_open(
                    view, hedge_peers, path, raw, deadline_left,
                    span=span, root=root, slo_class=slo_class,
                    tenant=tenant,
                )
            else:
                conn, resp = self._open(view, path, raw, deadline_left,
                                        trace=span, slo_class=slo_class,
                                        tenant=tenant)
        except (OSError, http.client.HTTPException) as e:
            if not isinstance(e, _HedgeQueueTimeout):
                # A queue timeout is gateway-local backlog; blaming the
                # replica would feed the supervisor's fail_threshold.
                self.fleet.note_failure(view.id)
            return ("retry", None)
        # The winning connection belongs to whichever replica SERVED (under
        # hedging that can be the peer); check it back into the pool only
        # when its response was fully drained and the upstream didn't ask
        # to close — everything else (SSE relays, torn reads) is a counted
        # discard (ISSUE 14).
        reusable = False
        try:
            if resp.status in (429, 503):
                try:
                    hint = int(resp.getheader("Retry-After") or 1)
                except ValueError:
                    hint = 1
                resp.read()
                reusable = True
                return ("busy", (hint, served))
            ctype = resp.getheader("Content-Type", "application/json")
            if stream and ctype.startswith("text/event-stream"):
                # SSE responses are close-delimited (the replica sends
                # Connection: close by design); never pooled.
                out = self._relay_stream(view, resp, ctype)
                if out == "detached":
                    # Evloop data plane (ISSUE 17): the loop takes the
                    # upstream socket — the finally below must NOT
                    # discard the live connection; the loop discards it
                    # (counted, as on the threaded path) at stream end.
                    self._evloop_detached.update(
                        conn=conn, served=served, rspan=span, handler=self,
                    )
                    conn = None
                return (out, served)
            try:
                data = resp.read()
            except (OSError, http.client.HTTPException):
                # Full response never arrived: nothing relayed, retryable.
                self.fleet.note_failure(view.id)
                return ("retry", None)
            reusable = True
            self.send_response(resp.status)
            self.send_header("Content-Type", ctype)
            self.send_header("X-Request-Id", self._request_id())
            self.send_header("Content-Length", str(len(data)))
            self.end_headers()
            self.wfile.write(data)
            return ("done", served)
        finally:
            if conn is None:
                pass  # detached: the event loop owns the socket now
            elif reusable:
                self.fleet.pool.checkin(served, conn, response=resp)
            else:
                self.fleet.pool.discard(conn)

    def _relay_stream(self, view, resp, ctype) -> str:
        """SSE pass-through: relay chunks as they arrive (read1 returns
        whatever the socket holds, preserving incremental delivery). The
        FIRST upstream chunk is read before any header goes to the client,
        so a replica dying at stream start is still retryable — once our
        200 is out, a death can only abort."""
        try:
            first = resp.read1(65536)
        except (OSError, http.client.HTTPException):
            self.fleet.note_failure(view.id)
            return "retry"
        self.send_response(resp.status)
        self.send_header("Content-Type", ctype)
        self.send_header("X-Request-Id", self._request_id())
        self.send_header("Cache-Control", "no-cache")
        # The relayed SSE body is close-delimited (no Content-Length), so
        # the client connection cannot be kept alive — same opt-out the
        # replica's own SSE responses make (ISSUE 14).
        self.send_header("Connection", "close")
        self.end_headers()
        try:
            chunk = first
            while chunk:
                self.wfile.write(chunk)
                self.wfile.flush()
                chunk = resp.read1(65536)
            return "done"
        except (OSError, http.client.HTTPException):
            self.fleet.note_failure(view.id)
            logger.warning("replica %s died mid-stream", view.id)
            return "aborted"

    def _hedged_open(self, view, peers, path, raw, deadline_left=None,
                     span=None, root=None, slo_class=None, tenant=None):
        """Tail-latency hedging (non-streaming only): if the primary has
        not answered within ``hedge_after_s``, fire the same request at the
        least-loaded peer and take whichever responds first. The loser's
        connection is abandoned (its replica finishes the wasted work —
        the standard hedging trade; a propagated deadline caps even that
        waste). Completions are idempotent from the client's perspective,
        so duplicates are safe. A fired hedge gets its OWN relay span as a
        SIBLING of the primary attempt's (both children of ``root``) — the
        trace shows two overlapping relays and which one won. Runs on the
        gateway's persistent hedge executor (ISSUE 14 satellite): no more
        2-worker pool construction per hedged relay."""
        pool = self.server._hedge_pool
        hspan = None
        try:
            t0 = time.monotonic()
            primary = pool.submit(self._open, view, path, raw, deadline_left,
                                  span, slo_class, tenant)
            done, _ = wait([primary], timeout=self.gwcfg.hedge_after_s)
            if done:
                conn, resp = primary.result()  # may raise: caller retries
                return conn, resp, view.id
            if not primary.running() and not primary.done():
                # Executor saturated: the primary never STARTED, so the
                # elapsed hedge_after_s measured queue depth, not a slow
                # replica — firing a secondary would queue behind the same
                # backlog and double the load exactly when workers are
                # short (and count a hedge that never was). Wait the
                # primary out instead, BOUNDED by the request's remaining
                # deadline (else the gateway's own timeout): a queued
                # future has no socket timeout protecting it yet, and a
                # deadline_s=5 request must not sit tens of seconds in an
                # executor queue before its first connect.
                left = (
                    deadline_left - (time.monotonic() - t0)
                    if deadline_left is not None
                    else self.gwcfg.request_timeout_s
                )
                try:
                    conn, resp = primary.result(
                        timeout=max(0.001, left))
                except FutureTimeoutError:
                    # Give up on this attempt; if the open starts later
                    # anyway, its connection is abandoned through the
                    # pool's accounting. Raise the caller's retryable
                    # error class.
                    primary.cancel()
                    primary.add_done_callback(
                        self._abandoned_conn_closer())
                    raise _HedgeQueueTimeout(
                        "hedge executor saturated; relay attempt timed "
                        "out before its upstream open could start"
                    ) from None
                return conn, resp, view.id
            peer = min(peers, key=lambda v: v.outstanding + v.queue_depth)
            self.gw.hedges.inc()
            self.gw.replica_counter(peer.id, "hedged").inc()
            if root is not None:
                hspan = self.tracer.start_span(
                    "gateway.relay", parent=root, replica=peer.id,
                    hedge=True,
                )
            # The secondary starts hedge_after_s (at least) into the budget:
            # re-derive its remaining deadline, or its replica keeps the
            # hedged generation alive past the moment the gateway gives up.
            secondary_left = (
                deadline_left - (time.monotonic() - t0)
                if deadline_left is not None else None
            )
            secondary = pool.submit(self._open, peer, path, raw,
                                    secondary_left, hspan, slo_class, tenant)
            futures = {primary: view.id, secondary: peer.id}
            last_exc: BaseException | None = None
            pending = set(futures)
            while pending:
                done, pending = wait(pending, return_when=FIRST_COMPLETED)
                for f in done:
                    try:
                        conn, resp = f.result()
                    except BaseException as e:
                        last_exc = e
                        continue
                    # Abandon every loser: the still-pending future AND any
                    # that completed in the same wake-up (both can land in
                    # `done` at once — its connection must close too, not
                    # leak an FD per hedge). Losers go through the pool's
                    # discard so the churn counter stays honest (a loser
                    # was counted at checkout; its close must be counted
                    # too, troubleshooting §32 reads the ratio).
                    abandon = self._abandoned_conn_closer()
                    for other in done | pending:
                        if other is not f:
                            other.add_done_callback(abandon)
                    if hspan is not None:
                        hspan.end(outcome=(
                            "won" if futures[f] == peer.id else "lost"
                        ))
                    return conn, resp, futures[f]
            if hspan is not None:
                hspan.end(outcome="error")
            raise last_exc  # both failed
        finally:
            if hspan is not None:
                hspan.end()  # no-op when already ended with an outcome

    def _abandoned_conn_closer(self):
        """Done-callback that discards a hedge loser's connection through
        the pool (mid-flight — never reusable, always counted)."""
        pool = self.fleet.pool

        def _closer(future) -> None:
            try:
                conn, _resp = future.result()
            except BaseException:
                return  # the losing open failed; _open already discarded
            pool.discard(conn)

        return _closer

    def _note_affinity(self, key, replica_id: str) -> None:
        if key is None:
            return
        with self.affinity_lock:
            prev = self.affinity_last.get(key)
            if prev is not None:
                if prev == replica_id:
                    self.gw.affinity_hits.inc()
                else:
                    self.gw.affinity_misses.inc()
            self.affinity_last[key] = replica_id
            self.affinity_last.move_to_end(key)
            while len(self.affinity_last) > 4096:
                self.affinity_last.popitem(last=False)


class _EvloopGatewayHandler(_GatewayHandler):
    """The handler the event-loop data plane (gateway/evloop.py, ISSUE 17)
    runs on its offload workers: identical control plane, one override —
    an SSE relay reads its FIRST upstream chunk here (preserving the
    retry-on-dead-start contract), then DETACHES instead of looping: the
    event loop takes both raw sockets and fans chunks through without
    this worker parked for the stream's lifetime. ``_evloop_detached``
    carries the deferred terminal state (span ends, admission release,
    usage row, pool discard) the loop runs at stream end."""

    # Per-request detach state. The loop builds one handler instance per
    # request (gateway/evloop.py _run_handler), so instance state here is
    # exactly as private as _rid/_adapter_pin on the threaded path.
    _evloop_detached: dict | None = None

    def _relay_stream(self, view, resp, ctype) -> str:
        # First chunk on the worker, blocking — a replica dying at stream
        # start stays retryable, exactly like the threaded path. This
        # read also drains http.client's internal BufferedReader (8 KiB,
        # < the 64 KiB ask), so after detach the raw socket is the only
        # byte source left (evloop.py re-checks for residue anyway).
        try:
            first = resp.read1(65536)
        except (OSError, http.client.HTTPException):
            self.fleet.note_failure(view.id)
            return "retry"
        self.send_response(resp.status)
        self.send_header("Content-Type", ctype)
        self.send_header("X-Request-Id", self._request_id())
        self.send_header("Cache-Control", "no-cache")
        # Close-delimited, as on the threaded path (ISSUE 14).
        self.send_header("Connection", "close")
        self.end_headers()
        if not first:
            # Upstream closed with an empty body: headers-only relay,
            # terminal here (threaded parity) — nothing to detach.
            return "done"
        self.wfile.write(first)
        self.close_connection = True
        self._evloop_detached = {"view": view, "resp": resp}
        return "detached"


def make_gateway(
    fleet: Fleet,
    *,
    config: GatewayConfig | None = None,
    router=None,
    admission: TenantAdmission | None = None,
    metrics: GatewayMetrics | None = None,
    host: str | None = None,
    port: int | None = None,
    tracer: Tracer | None = None,
    slo: BurnRateMonitor | None = None,
    telemetry=None,
    incidents=None,
    flight=None,
    actuator=None,
    recorder=None,
    kvtier=None,
    journal=None,
    usage=None,
    bulk=None,
    recover_manifest=None,
):
    """Build (not start) the gateway server over ``fleet`` — tests drive it
    on a thread, ``main`` drives it with ``serve_forever``. ``router``
    defaults to the config's policy; ``admission`` defaults to the config's
    tenant budgets (None when the config sets no limits — requests are then
    admitted unconditionally). ``tracer`` (telemetry/tracing.py) arms
    request tracing; ``slo`` defaults to a fleet-level burn-rate monitor
    built from ``telemetry`` (config.TelemetryConfig) or its defaults;
    ``incidents`` (telemetry/incident.IncidentManager) arms the
    /incidents aggregation endpoint and ``flight``
    (telemetry/flight.FlightRecorder) the per-request routing ring
    (ISSUE 10) — both unarmed by default. ``actuator``
    (gateway.autoscale.Actuator) arms the /actions endpoint and the
    scale-to-zero wake admission; ``recorder``
    (gateway.autoscale.TrafficRecorder) appends one JSONL row per
    admitted request (ISSUE 12) — both unarmed by default. ``kvtier``
    (config.KVTierConfig with ``handoff=True``) arms the prefill->decode
    KV handoff orchestration (ISSUE 13); ``journal``
    (telemetry/journal.EventJournal) records its per-request cost-model
    decisions. ``usage`` (telemetry/usage.UsageLedger) arms the
    gateway-edge usage ledger: one row per admission-controlled request
    with the tenant digest, class, and terminal outcome (ISSUE 15) —
    unarmed by default. ``bulk`` (gateway.bulk.BulkJobManager) arms the
    /v1/bulk/jobs endpoints (ISSUE 19): make_gateway binds the manager's
    dispatch path to this gateway's relay (pinned ``best_effort``, stable
    per-item request ids so retries ride the idempotent-safe relay) plus
    an idle-fleet probe for the backlog-stall detector, and calls
    ``start()`` so incomplete jobs resume before the first request
    lands. ``config.data_plane`` picks the transport
    (ISSUE 17): the selectors event loop (gateway/evloop.py, the
    default) or the legacy thread-per-connection ``GatewayHTTPServer`` —
    both expose the same serve_forever/shutdown/server_close/
    server_address surface, so callers never branch.
    ``recover_manifest`` (a dict from recovery.load_manifest, ISSUE 20)
    marks this gateway a --recover incarnation: admission token buckets
    re-warm from the manifest's persisted levels (amnesty counted when
    absent) and adapter generations reconcile against each replica's
    live GET /v1/adapters — both BEFORE the bulk manager resumes, so
    resumed jobs meet re-warmed budgets."""
    config = config or GatewayConfig()
    # Upstream keep-alive pool caps (ISSUE 14): the fleet owns the pool
    # (health polls and fleet-mutation invalidation need it gateway or
    # not); the gateway applies its config's knobs here.
    # pool_max_idle_per_replica=0 disables pooling — every upstream hop
    # connects fresh, the microbench's A/B leg.
    fleet.pool.configure(
        max_idle_per_replica=config.pool_max_idle_per_replica,
        max_age_s=config.pool_max_age_s,
    )
    if router is None:
        router = make_policy(config.router)
    # Bulk quotas (ISSUE 19) live in the SAME admission object as the
    # interactive budgets — one fairness layer, one per-tenant state map,
    # one snapshot at /stats. A bulk-armed gateway therefore always has
    # admission, even when the config sets no interactive limits.
    bulk_cfg = bulk.config if bulk is not None else None
    if admission is None and (
        config.tenant_rate > 0 or config.tenant_max_concurrent > 0
        or config.tenant_slo_class or bulk_cfg is not None
    ):
        admission = TenantAdmission(
            rate=config.tenant_rate, burst=config.tenant_burst,
            max_concurrent=config.tenant_max_concurrent,
            slo_class=config.tenant_slo_class,
            bulk_max_jobs=(bulk_cfg.max_jobs_per_tenant
                           if bulk_cfg is not None else 0),
            bulk_max_queued_items=(bulk_cfg.max_queued_items_per_tenant
                                   if bulk_cfg is not None else 0),
        )
    if bulk is not None and bulk.admission is None:
        # The manager releases a job's quota footprint at terminal state
        # and re-registers resumed jobs — it needs the live object.
        bulk.admission = admission
    if fleet.manifest is not None and admission is not None:
        # Crash-recovery manifest (ISSUE 20): admission bucket levels
        # ride every manifest record from here on (keyed on tenant
        # labels inside admission.bucket_snapshot — raw bearers never
        # reach the file). Re-record immediately: a crash between here
        # and the next fleet mutation / 2s supervisor refresh must find
        # an admission section (empty != absent), not the pre-wiring
        # snapshot.
        fleet.manifest.admission = admission
        fleet.manifest.record()
    gw_metrics = metrics if metrics is not None else GatewayMetrics()
    if slo is None:
        kw = telemetry.gateway_slo_kwargs() if telemetry is not None else {}
        slo = gateway_slo(gw_metrics, **kw)
    # Adapter publication coordinator (ISSUE 16): always armed — it needs
    # only the fleet; replicas without an adapter plane answer its hops
    # with 404s, which the walk reports per-replica instead of hiding.
    from ditl_tpu.gateway.publish import AdapterPublisher
    publisher = AdapterPublisher(
        fleet, journal=journal, registry=gw_metrics.registry,
        timeout_s=config.request_timeout_s, manifest=fleet.manifest,
    )
    if recover_manifest is not None:
        from ditl_tpu.gateway.recovery import reconcile_adapters

        if admission is not None:
            # Restart amnesty fix (ISSUE 20 satellite): armed before the
            # bulk manager resumes below, so even the first tenants back
            # (resumed bulk jobs re-registering quota) re-warm instead
            # of silently restarting full.
            admission.rewarm(
                recover_manifest.get("admission") or {},
                on_amnesty=gw_metrics.admission_amnesty.inc,
            )
        reconcile_adapters(fleet, recover_manifest, publisher,
                           journal=journal,
                           timeout_s=config.recovery_adopt_timeout_s)
    base = (_EvloopGatewayHandler if config.data_plane == "evloop"
            else _GatewayHandler)
    handler = type(
        "BoundGatewayHandler",
        (base,),
        {
            "fleet": fleet,
            "router": router,
            "admission": admission,
            "gw": gw_metrics,
            "gwcfg": config,
            "affinity_last": collections.OrderedDict(),
            "affinity_lock": threading.Lock(),
            "tracer": tracer if tracer is not None else NULL_TRACER,
            "slo": slo,
            "incidents": incidents,
            "flight": flight,
            "actuator": actuator,
            "recorder": recorder,
            "kvtier": kvtier,
            "journal": journal,
            "usage": usage,
            "publisher": publisher,
            "bulk": bulk,
        },
    )
    address = (host if host is not None else config.host,
               port if port is not None else config.port)
    if config.data_plane == "evloop":
        # Event-loop data plane (ISSUE 17): same bound handler (run on
        # offload workers), same 4-method server surface
        # (serve_forever/shutdown/server_close/server_address).
        from ditl_tpu.gateway.evloop import EventLoopGateway
        server = _bind_with_retry(
            lambda: EventLoopGateway(address, handler, config=config,
                                     metrics=gw_metrics),
            config)
        # Stall-attribution plane (ISSUE 18): when armed, the watchdog
        # converts heartbeat age into ditl_loop_lag_seconds and, on a
        # stall, burst-samples the loop thread into a convicting stack
        # fed to the anomaly->incident path. Disarmed by default
        # (loop_stall_threshold_s == 0): zero extra threads.
        if telemetry is not None and telemetry.loop_stall_threshold_s > 0:
            from ditl_tpu.telemetry.anomaly import AnomalyPlane
            from ditl_tpu.telemetry.prof import LoopWatchdog
            server.watchdog = LoopWatchdog(
                server.heartbeat,
                registry=gw_metrics.registry,
                plane=AnomalyPlane(incidents=incidents, journal=journal),
                journal=journal,
                source="gateway",
                **telemetry.watchdog_kwargs(),
            )
        if telemetry is not None and telemetry.prof_hz > 0:
            from ditl_tpu.telemetry.prof import SamplingProfiler
            server.profiler = SamplingProfiler(
                hz=telemetry.prof_hz,
                max_stacks=telemetry.prof_max_stacks,
                registry=gw_metrics.registry,
            )
            server.profiler.start()
    else:
        server = _bind_with_retry(
            lambda: GatewayHTTPServer(address, handler), config)
    if bulk is not None:
        _bind_bulk(bulk, server, handler, fleet)
    return server


def _bind_with_retry(build, config):
    """Construct a data-plane server, retrying a bounded number of
    EADDRINUSE bind failures (ISSUE 20 fast-restart satellite): a
    recovering gateway reclaims its predecessor's FIXED port while
    kernel TIME_WAIT entries from severed connections linger. Both
    planes set SO_REUSEADDR on their listeners (which clears ordinary
    TIME_WAIT) and tear down cleanly on a failed construction, so
    re-invoking ``build`` is always safe. Any other OSError — and
    EADDRINUSE past the budget — propagates unchanged."""
    import errno

    attempts = max(0, int(config.recovery_bind_retries))
    for remaining in range(attempts, -1, -1):
        try:
            return build()
        except OSError as e:
            if e.errno != errno.EADDRINUSE or remaining == 0:
                raise
            logger.warning(
                "gateway bind EADDRINUSE; retrying in %.1fs "
                "(%d attempts left)",
                config.recovery_bind_wait_s, remaining)
            time.sleep(config.recovery_bind_wait_s)
    raise AssertionError("unreachable")


def _bind_bulk(bulk, server, handler_cls, fleet) -> None:
    """Wire a BulkJobManager (ISSUE 19) to THIS gateway: its dispatch
    path becomes a pseudo-handler run of ``_route_and_relay`` — the
    evloop offload idiom, so bulk items traverse the IDENTICAL routing/
    retry/hedging/KV-handoff/usage machinery a socket request would,
    pinned ``best_effort`` with a stable per-item request id (replica-
    death retries ride the idempotent-safe relay). Also binds the
    idle-fleet probe the backlog-stall detector needs, then starts the
    manager (resuming any incomplete journaled jobs)."""
    import io

    def dispatch(item: dict) -> dict:
        h = handler_cls.__new__(handler_cls)
        h.server = server
        h.client_address = ("bulk", 0)
        h.connection = None
        h.request = None
        h.rfile = io.BytesIO(b"")
        h.wfile = io.BytesIO()
        h.close_connection = True
        h.requestline = "POST /v1/completions HTTP/1.1"
        h.request_version = "HTTP/1.1"
        h.command = "POST"
        h.path = "/v1/completions"
        h.headers = {}
        # Stable id: the SAME item re-dispatched (outer retry, or resume
        # after a kill) carries the same X-Request-Id — the join key
        # across gateway spans, replica logs, and the bulk journal.
        h._rid = str(item.get("rid") or "")
        h._adapter_pin = item.get("adapter") or None
        payload = {
            "prompt": item.get("prompt") or "",
            "max_tokens": int(item.get("max_new") or 0),
            "stream": False,
            **dict(item.get("sampling") or {}),
        }
        if not payload["max_tokens"]:
            del payload["max_tokens"]
        raw = json.dumps(payload).encode()
        try:
            outcome = h._route_and_relay(
                "/v1/completions", payload, raw, record=True,
                slo_class="best_effort",
                tenant=item.get("tenant") or "anonymous",
            )
        except Exception:  # noqa: BLE001 - a relay bug reads as transient
            logger.exception("bulk: pseudo-handler relay failed")
            return {"outcome": "error"}
        resp = h.wfile.getvalue()
        head, _, body = resp.partition(b"\r\n\r\n")
        out: dict = {"outcome": str(outcome), "text": "",
                     "completion_tokens": 0}
        if outcome == "200":
            try:
                ans = json.loads(body)
                choice = (ans.get("choices") or [{}])[0]
                out["text"] = str(choice.get("text") or "")
                out["completion_tokens"] = int(
                    (ans.get("usage") or {}).get("completion_tokens") or 0)
            except (ValueError, AttributeError, IndexError, TypeError):
                out["outcome"] = "error"
        elif outcome == "429":
            m = re.search(rb"(?im)^Retry-After:\s*(\d+)", head)
            if m:
                out["retry_after_s"] = float(m.group(1))
        return out

    def idle_fn() -> bool:
        views = [v for v in fleet.views() if v.live]
        return bool(views) and all(
            v.active_slots == 0 and v.queue_depth == 0
            and v.outstanding == 0 for v in views)

    bulk.bind(dispatch, idle_fn=idle_fn)
    bulk.start()


def main(argv: list[str] | None = None) -> int:
    """``python -m ditl_tpu.launch gateway``: spawn N subprocess replicas
    of ``infer/server.py`` and front them with one gateway endpoint."""
    import argparse
    import signal
    import sys

    from ditl_tpu.config import Config, parse_overrides
    from ditl_tpu.gateway.replica import (
        SubprocessReplica, gateway_journal_path,
    )
    from ditl_tpu.telemetry.journal import EventJournal

    parser = argparse.ArgumentParser(prog="ditl_tpu.launch gateway")
    parser.add_argument("--preset", default=None,
                        help="model preset for every replica")
    parser.add_argument("--tokenizer", default="byte")
    parser.add_argument("--checkpoint-dir", default="")
    parser.add_argument("--engine", choices=("lockstep", "continuous"),
                        default="continuous")
    parser.add_argument("--slots", type=int, default=8,
                        help="decode slots per replica (continuous engine); "
                        "the BASE value role knobs scale (gateway/roles.py)")
    parser.add_argument("--max-queue", type=int, default=32,
                        help="per-replica admission queue cap (replica "
                        "429s beyond it; the gateway spills/429s in turn)")
    parser.add_argument("--prefill-chunk", type=int, default=0,
                        help="base chunked-prefill size per replica "
                        "(continuous engine; 0 = whole-prompt) — "
                        "role-scaled for heterogeneous fleets")
    parser.add_argument("--token-budget", type=int, default=0,
                        help="base per-tick token budget per replica "
                        "(continuous engine; 0 = unbudgeted) — role-scaled "
                        "for heterogeneous fleets")
    parser.add_argument("--pages", type=int, default=0,
                        help="base KV page-pool size per replica (paged "
                        "cache mode; 0 = engine default) — role-scaled "
                        "for heterogeneous fleets")
    parser.add_argument("--replica-arg", action="append", default=[],
                        metavar="ARG",
                        help="extra argument passed through to every "
                        "ditl_tpu.infer.server replica (repeatable), e.g. "
                        "--replica-arg=--cache-mode --replica-arg=paged")
    parser.add_argument("--trace-dir", default="",
                        help="arm end-to-end request tracing (ISSUE 6): "
                        "the gateway AND every replica journal their spans "
                        "into this directory; merge + export with "
                        "python -m ditl_tpu.telemetry.trace_export --dir "
                        "DIR")
    parser.add_argument("--incident-dir", default="",
                        help="arm the anomaly/incident plane fleet-wide "
                        "(ISSUE 10): the gateway watches replica deaths "
                        "and spill/relay-error storms, each replica "
                        "watches its own engine (deadline/429 storms, "
                        "latency jumps), and all bundles aggregate at the "
                        "gateway's /incidents (each process writes its own "
                        "subdirectory)")
    parser.add_argument("--save-trace", default="", metavar="PATH",
                        help="traffic recorder (ISSUE 12): append one "
                        "JSONL row per admitted request (arrival offset, "
                        "tenant digest, class, prompt/max_new token "
                        "estimates) — the shape autoscale.load_trace "
                        "reads back for a replay")
    parser.add_argument("--recover", default="", metavar="DIR",
                        help="crash recovery (ISSUE 20): adopt the fleet a "
                        "SIGKILLed gateway left behind from DIR's "
                        "gateway-manifest.json — still-alive replicas are "
                        "adopted (zero restarts), parked/quarantined state "
                        "is restored, planner cooldowns replay from the "
                        "journal tail, admission buckets re-warm, adapter "
                        "generations reconcile, and journaled bulk jobs "
                        "resume. DIR doubles as gateway.journal_dir when "
                        "that is unset. A missing manifest cold-starts "
                        "with a warning")
    parser.add_argument("overrides", nargs="*",
                        help="config overrides like gateway.router=affinity "
                        "gateway.replicas=4 telemetry.slo_ttft_s=0.5 "
                        "autoscale.enabled=true")
    args = parser.parse_args(argv)

    full_config = parse_overrides(
        Config(),
        [o for o in args.overrides
         if o.startswith(("gateway.", "telemetry.", "autoscale.",
                          "kvtier.", "usage.", "bulk."))],
    )
    config = full_config.gateway
    telemetry_cfg = full_config.telemetry
    autoscale_cfg = full_config.autoscale
    kvtier_cfg = full_config.kvtier
    usage_cfg = full_config.usage
    bulk_cfg = full_config.bulk

    from ditl_tpu.gateway.roles import parse_roles, role_knobs

    roles = parse_roles(config.replica_roles, config.replicas)

    def make_build_argv(replica_id: str, role: str):
        # One closure per replica: the role's engine knobs (roles.py) are
        # derived from the BASE --slots/--prefill-chunk/--token-budget so a
        # heterogeneous fleet launches from one command line.
        knobs = role_knobs(role, n_slots=args.slots,
                           prefill_chunk=args.prefill_chunk,
                           token_budget=args.token_budget)

        def build_argv(port: int):
            cmd = [sys.executable, "-m", "ditl_tpu.infer.server",
                   "--host", "127.0.0.1", "--port", str(port),
                   "--tokenizer", args.tokenizer,
                   "--engine", args.engine,
                   "--role", role]
            if args.engine == "continuous":
                cmd += ["--slots", str(knobs["n_slots"]),
                        "--max-queue", str(args.max_queue)]
                if knobs["prefill_chunk"]:
                    cmd += ["--prefill-chunk", str(knobs["prefill_chunk"])]
                if knobs["token_budget"]:
                    cmd += ["--token-budget", str(knobs["token_budget"])]
                if args.pages:
                    # --pages is sized for the BASE slot count: scale it by
                    # the role's slot ratio first (a decode_heavy replica
                    # running 2x the slots needs 2x the pool just to keep
                    # per-slot headroom), THEN by the role's extra depth
                    # (pages_scale).
                    scaled = (args.pages * knobs["n_slots"]
                              / max(1, args.slots) * knobs["pages_scale"])
                    cmd += ["--pages", str(max(2, int(scaled)))]
            if args.engine == "continuous" and kvtier_cfg.host_tier_mb:
                # Requires paged replicas (--replica-arg=--cache-mode
                # --replica-arg=paged); a mismatch fails the replica
                # launch loudly rather than silently serving tierless.
                cmd += ["--host-tier-mb", str(kvtier_cfg.host_tier_mb),
                        "--spill-max-pages-per-tick",
                        str(kvtier_cfg.spill_max_pages_per_tick)]
            if args.engine == "continuous" and kvtier_cfg.handoff:
                cmd += ["--kv-handoff"]
            if args.preset:
                cmd += ["--preset", args.preset]
            if args.checkpoint_dir:
                cmd += ["--checkpoint-dir", args.checkpoint_dir]
            if args.trace_dir:
                # Each replica journals its own spans (events-server-<pid>)
                # into the shared directory; trace_export merges by
                # trace_id.
                cmd += ["--trace-dir", args.trace_dir]
            if args.incident_dir:
                # Per-replica bundle subdirectory: managers never contend
                # on bundle names, and the gateway's /incidents aggregation
                # reads each replica's listing over HTTP anyway.
                import os as _os

                cmd += ["--incident-dir",
                        _os.path.join(args.incident_dir, replica_id)]
            if usage_cfg.ledger_dir:
                # Per-replica ledger subdirectory (ISSUE 15): each process
                # appends its own usage-*.jsonl; the aggregator CLI reads
                # any of them, the gateway's /usage fan-out reads the live
                # meters over HTTP.
                import os as _os

                cmd += ["--usage-dir",
                        _os.path.join(usage_cfg.ledger_dir, replica_id)]
            if not usage_cfg.metering:
                cmd += ["--no-usage-metering"]
            for field_name in ("max_tenant_families", "conviction_share",
                               "conviction_min_tokens"):
                cmd += ["--usage-override",
                        f"{field_name}={getattr(usage_cfg, field_name)}"]
            return cmd + list(args.replica_arg)

        return build_argv

    # The recovery state directory doubles as the journal directory: the
    # manifest, the action journal tail, and the crash/recovery events
    # must all live where the NEXT incarnation's --recover will look.
    journal_dir = config.journal_dir or args.recover
    journal = None
    if journal_dir:
        journal = EventJournal(
            gateway_journal_path(journal_dir), source="gateway",
            max_bytes=telemetry_cfg.journal_max_bytes(),
        )
    tracer = None
    if args.trace_dir:
        import os as _os

        tracer = Tracer(EventJournal(
            _os.path.join(args.trace_dir, "events-gateway-trace.jsonl"),
            source="gateway",
            max_bytes=telemetry_cfg.journal_max_bytes(),
        ))
    def replica_env(i: int) -> dict | None:
        """One replica per chip. A process that initialises JAX claims
        every chip it can see, so N replicas sharing one environment would
        all want the same chips and all but the first would die at
        start-up. Replica ``i`` is shown chip ``i`` alone, by the variables
        libtpu honours (probed on a four-chip v5e host, PR 21); on a host
        without TPUs they are inert. A lone replica keeps every chip (and
        may shard over them with --replica-arg=--mesh)."""
        if config.replicas <= 1:
            return None
        import os as _os

        return {
            **_os.environ,
            "TPU_VISIBLE_CHIPS": str(i),
            "TPU_CHIPS_PER_PROCESS_BOUNDS": "1,1,1",
            "TPU_PROCESS_BOUNDS": "1,1,1",
            "ALLOW_MULTIPLE_LIBTPU_LOAD": "1",
        }

    handles = [
        SubprocessReplica(f"r{i}", make_build_argv(f"r{i}", roles[i]),
                          role=roles[i], env=replica_env(i))
        for i in range(config.replicas)
    ]
    fleet = Fleet(handles)
    # Crash-recovery manifest (ISSUE 20): armed whenever a journal
    # directory exists — crash consistency costs one small atomic JSON
    # write per fleet mutation. The PRIOR incarnation's manifest (if
    # --recover) is loaded before this incarnation's first record can
    # replace it.
    prior_manifest = None
    if journal_dir:
        from ditl_tpu.gateway.recovery import FleetManifest, load_manifest
        from ditl_tpu.gateway.recovery import manifest_path as _mpath

        if args.recover:
            prior_manifest = load_manifest(args.recover)
            if prior_manifest is None:
                logger.warning(
                    "--recover %s: no fleet manifest found; cold-starting",
                    args.recover)
        fleet.manifest = FleetManifest(_mpath(journal_dir))
    # Gateway-side anomaly/incident plane (ISSUE 10): replica death-rate +
    # spill/relay-error storms + fleet SLO burn alerts, bundling the
    # routing flight ring, gateway metrics, and the journal tail. The
    # metrics bundle exists regardless (the supervisor's replica_deaths
    # counter must be honest on unarmed gateways too); only the
    # detectors/bundles gate on --incident-dir.
    gw_metrics = GatewayMetrics()
    flight = incidents = slo = gw_anomaly = plane = None
    if args.incident_dir:
        import os as _os

        from ditl_tpu.telemetry import (
            AnomalyPlane, FlightRecorder, GatewayDetector,
            GatewayAnomalyMonitor, IncidentManager,
        )

        flight = FlightRecorder(telemetry_cfg.flight_ring_size)
        plane_journal = journal if journal is not None else (
            tracer.journal if tracer is not None else None
        )
        incidents = IncidentManager(
            _os.path.join(args.incident_dir, "gateway"),
            flight=flight,
            metrics_render=gw_metrics.registry.render,
            journal_dir=journal_dir or args.trace_dir,
            registry=gw_metrics.registry,
            source="gateway",
            **telemetry_cfg.incident_kwargs(),
        )
        plane = AnomalyPlane(incidents=incidents, journal=plane_journal)
        slo = gateway_slo(
            gw_metrics, **telemetry_cfg.gateway_slo_kwargs(),
            journal=plane_journal, on_alert=plane.on_slo_alert,
        )
        gw_anomaly = GatewayAnomalyMonitor(
            plane, gw_metrics,
            GatewayDetector(
                storm_threshold=telemetry_cfg.anomaly_storm_threshold),
            slo=slo, flight=flight,
        )
    recorder = None
    if args.save_trace:
        from ditl_tpu.gateway.autoscale import TrafficRecorder

        recorder = TrafficRecorder(args.save_trace)
    usage_ledger = None
    if usage_cfg.ledger_dir:
        from ditl_tpu.telemetry.usage import UsageLedger, usage_ledger_path

        usage_ledger = UsageLedger(
            usage_ledger_path(usage_cfg.ledger_dir, "gateway"),
            source="gateway",
            max_bytes=telemetry_cfg.journal_max_bytes(),
        )
    bulk_manager = None
    if bulk_cfg.dir:
        # Offline bulk lane (ISSUE 19): the manager is built here (durable
        # state + journal) and wired to the relay inside make_gateway,
        # which also resumes any jobs a previous incarnation left
        # incomplete.
        from ditl_tpu.gateway.bulk import BulkJobManager

        bulk_manager = BulkJobManager(
            bulk_cfg.dir, bulk_cfg,
            registry=gw_metrics.registry,
            flight=flight, plane=plane, usage=usage_ledger,
            source="gateway",
            max_bytes=telemetry_cfg.journal_max_bytes(),
        )
    supervisor = None
    server = None
    # One finally covers startup too: a replica that never turns healthy
    # (bad --preset, broken checkpoint) raises out of start_all, and the
    # other N-1 subprocess replicas must not be left orphaned holding
    # ports and devices.
    try:
        if prior_manifest is not None:
            # Adopt-or-relaunch BEFORE start_all: adopted replicas are
            # already alive and parked/quarantined replicas are restored
            # down-on-purpose, so start_all only launches what genuinely
            # needs launching.
            from ditl_tpu.gateway.recovery import recover_fleet

            recover_fleet(
                fleet, prior_manifest, journal=journal,
                metrics=gw_metrics,
                probe_timeout_s=config.recovery_adopt_timeout_s,
            )
            fleet.manifest.seed_adapters(prior_manifest.get("adapters"))
        logger.info("starting %d replica(s)...", config.replicas)
        fleet.start_all(wait_healthy_s=config.restart_timeout_s)
        supervisor = FleetSupervisor(
            fleet,
            interval_s=config.health_interval_s,
            fail_threshold=config.fail_threshold,
            probe_timeout_s=config.probe_timeout_s,
            restart_timeout_s=config.restart_timeout_s,
            journal=journal,
            anomaly=gw_anomaly,
            metrics=gw_metrics,
        )
        actuator = None
        if autoscale_cfg.enabled:
            # Actuation plane (ISSUE 12): planner + actuator riding the
            # supervisor's poll loop, sharing its fleet-mutation lock,
            # journal, and — when --incident-dir armed one — the SAME
            # anomaly plane the detectors feed, so action bundles and
            # organic bundles land in one tally and one directory.
            from ditl_tpu.gateway.autoscale import Actuator

            actuator = Actuator(
                fleet, supervisor, autoscale_cfg,
                journal=journal, tracer=tracer, metrics=gw_metrics,
                flight=flight, plane=plane, slo=slo, bulk=bulk_manager,
            )
            supervisor.autoscaler = actuator
            if prior_manifest is not None and journal_dir:
                # Cooldown replay (ISSUE 20): re-stamp the planner's
                # scale/remediation recency from the action.executed
                # tail so the recovered gateway does not immediately
                # re-plan inside a window the old incarnation opened.
                from ditl_tpu.gateway.recovery import replay_action_tail

                replay_action_tail(journal_dir, actuator.planner,
                                   journal=journal)
        supervisor.start()
        server = make_gateway(fleet, config=config, tracer=tracer,
                              telemetry=telemetry_cfg, metrics=gw_metrics,
                              slo=slo, incidents=incidents, flight=flight,
                              actuator=actuator, recorder=recorder,
                              kvtier=kvtier_cfg if kvtier_cfg.handoff
                              else None,
                              journal=journal, usage=usage_ledger,
                              bulk=bulk_manager,
                              recover_manifest=prior_manifest)
        stopping = threading.Event()

        def _shutdown(signum, frame):
            if not stopping.is_set():
                stopping.set()
                threading.Thread(target=server.shutdown, daemon=True).start()

        for sig in (signal.SIGTERM, signal.SIGINT):
            try:
                signal.signal(sig, _shutdown)
            except ValueError:
                pass
        logger.info(
            "gateway serving %d replica(s) on %s:%d (router=%s)",
            config.replicas, *server.server_address[:2], config.router,
        )
        try:
            server.serve_forever()
        except KeyboardInterrupt:
            pass
    finally:
        if supervisor is not None:
            supervisor.stop()
        if server is not None:
            server.server_close()
        fleet.stop_all(drain=True, timeout=config.drain_timeout_s)
        if bulk_manager is not None:
            # In-flight items are abandoned WITHOUT terminal rows; jobs
            # stay "running" on disk — the next gateway resumes them.
            bulk_manager.close()
        if recorder is not None:
            recorder.close()
        if usage_ledger is not None:
            usage_ledger.close()
        if journal is not None:
            journal.close()
        if tracer is not None and tracer.journal is not None:
            tracer.journal.close()
    return 0


if __name__ == "__main__":
    import sys

    from ditl_tpu.utils.logging import setup_logging

    setup_logging()
    sys.exit(main())
