"""Generated metrics catalog (ISSUE 10 satellite).

The repo exposes 130+ ``ditl_*`` metric families, and until this module
they lived only in code — scattered across ServingMetrics, GatewayMetrics,
the flattened /v1/stats gauges, the SLO burn gauges, memwatch, and the
incident counters. :data:`CATALOG` is the single source of truth: every
family's exposed name (with ``<placeholder>`` segments for unbounded
labels like replica ids), its Prometheus type, and a one-line meaning.

Two artifacts hang off it:

- ``docs/metrics.md`` is GENERATED from this table
  (``python -m ditl_tpu.telemetry.catalog --write docs/metrics.md``); the
  drift-guard test asserts the doc matches the table byte-for-byte, so a
  stale doc fails CI instead of rotting.
- the drift-guard test (tests/test_metrics_catalog.py) registers the
  families a live server/gateway/training surface actually creates,
  normalizes dynamic label segments with :func:`normalize_family`, and
  asserts live ⊆ catalog AND required-catalog ⊆ live — a new instrument
  without a catalog row (or a catalog row whose instrument was deleted)
  fails the build.

Entries marked ``optional`` are absent on some backends/configurations by
design (memwatch on statless CPU, multi-LoRA gauges without adapters,
overflow tenant labels) — the absent-not-zero rule; they still must
normalize onto a catalog row when they DO appear.
"""

from __future__ import annotations

import re
from dataclasses import dataclass

__all__ = [
    "CATALOG",
    "CatalogEntry",
    "catalog_families",
    "main",
    "normalize_family",
    "render_markdown",
    "required_families",
]


@dataclass(frozen=True)
class CatalogEntry:
    family: str  # exposed name (classic text format; counters carry _total)
    type: str  # counter | gauge | histogram
    labels: str  # meaning of <placeholder> segments ("" = none)
    meaning: str
    optional: bool = False  # absent on some backends/configs (absent != 0)


# Dynamic-label normalization: a live family name -> its catalog pattern.
# Rules are applied first-match; anything untouched must match a catalog
# row verbatim.
_NORMALIZE_RULES: tuple[tuple[re.Pattern, str], ...] = (
    (re.compile(r"^(ditl_gateway_replica_)(?!deaths_total$)(.+?)_"
                r"(routed_total|retried_total|"
                r"recent_prefix_cache_hit_ratio|prefix_cache_hit_ratio|"
                r"cold_start_seconds)$"),
     r"\1<id>_\3"),
    (re.compile(r"^(ditl_gateway_action_)(.+?)_"
                r"(planned|executed|refused|failed|dry_run)(_total)$"),
     r"\1<kind>_\3\4"),
    (re.compile(r"^(ditl_gateway_tenant_)(.+?)_"
                r"(admitted_total|throttled_total)$"),
     r"\1<tenant>_\3"),
    (re.compile(r"^(ditl_memory_device)\d+_(.+)$"), r"\1<i>_\2"),
    (re.compile(r"^(ditl_memory_)(.+?)_device\d+_(.+)$"),
     r"\1<replica>_device<i>_\3"),
    (re.compile(r"^(ditl_incidents_trigger_).+(_total)$"), r"\1<kind>\2"),
    (re.compile(r"^(ditl_usage_tenant_)(.+?)_(prompt_tokens_total|"
                r"generated_tokens_total|cached_tokens_saved_total|"
                r"device_seconds_total)$"),
     r"\1<tenant>_\3"),
    (re.compile(r"^(ditl_slo_\w+_burn_rate_w)\d+$"), r"\1<window>"),
)


def normalize_family(name: str) -> str:
    """Map a live family name onto its catalog pattern (identity for
    families without dynamic labels)."""
    for rx, rep in _NORMALIZE_RULES:
        if rx.match(name):
            return rx.sub(rep, name)
    return name


# (family, type, labels, meaning[, optional]) — keep sorted by family.
_ROWS: tuple = (
    # Client-side counters live in the remote-LLM client's own process
    # (client_metrics singleton, client/llm.py), never on a server or
    # gateway scrape surface — optional by construction. Found by the
    # static metric-catalog pass (ISSUE 11): the live drift guard only
    # sees scrapeable surfaces, so these had silently escaped the catalog.
    # Adapter plane (ISSUE 16): registry families live on multi-LoRA
    # serving replicas (infer/adapters.py), publish families on the
    # gateway (gateway/publish.py) — optional on every other surface.
    ("ditl_adapter_evictions_total", "counter", "", "adapter rows evicted, drained, and freed back to the pool", True),
    ("ditl_adapter_load_failures_total", "counter", "", "adapter loads refused (verification/geometry/pool exhaustion) or lost to injected faults", True),
    ("ditl_adapter_loads_total", "counter", "", "adapter hot loads committed into stacked pool rows (publications included)", True),
    ("ditl_adapter_publish_fallbacks_total", "counter", "", "fleet publications aborted mid-walk (chaos/crash) - straggler replicas keep the old adapter until a re-publish converges them", True),
    ("ditl_adapter_publish_hops_failed_total", "counter", "", "per-replica publication hops that failed (the replica kept its previous adapter)", True),
    ("ditl_adapter_publishes_total", "counter", "", "fleet-wide adapter publications the gateway coordinated (any outcome)", True),
    ("ditl_adapter_rows", "gauge", "", "stacked pool rows the registry manages (excluding base row 0)", True),
    ("ditl_adapter_rows_live", "gauge", "", "stacked pool rows currently serving a named adapter", True),
    ("ditl_adapter_swap_seconds", "histogram", "", "hot load/publish swap latency (verify -> install -> row live)", True),
    # Bulk lane (ISSUE 19): families live on a bulk-armed gateway only
    # (gateway/bulk.py registers them on the gateway registry when
    # bulk.dir is set) — optional on every other surface.
    ("ditl_bulk_backlog_items", "gauge", "", "bulk work items not yet terminal across non-terminal jobs (the autoscale planner's scale-up signal)", True),
    ("ditl_bulk_completion_tokens_total", "counter", "", "completion tokens generated by the bulk lane", True),
    ("ditl_bulk_items_completed_total", "counter", "", "bulk work items that reached a terminal journal row", True),
    ("ditl_bulk_items_dispatched_total", "counter", "", "bulk work items dispatched through the relay path (attempts, so retries count again)", True),
    ("ditl_bulk_items_failed_total", "counter", "", "bulk work items terminally failed after exhausting retries", True),
    ("ditl_bulk_items_preempted_total", "counter", "", "bulk dispatch attempts bounced by fleet saturation (429) - the lane yielding to interactive load, working as designed", True),
    ("ditl_bulk_items_retried_total", "counter", "", "bulk dispatch attempts retried after a transient outcome", True),
    ("ditl_bulk_jobs_active", "gauge", "", "bulk jobs currently queued or running", True),
    ("ditl_bulk_jobs_cancelled_total", "counter", "", "bulk jobs cancelled by a client", True),
    ("ditl_bulk_jobs_completed_total", "counter", "", "bulk jobs that ran to completion", True),
    ("ditl_bulk_jobs_failed_total", "counter", "", "bulk jobs terminal with at least one permanently failed item", True),
    ("ditl_bulk_jobs_resumed_total", "counter", "", "incomplete bulk jobs resumed from the journal after a gateway restart", True),
    ("ditl_bulk_jobs_submitted_total", "counter", "", "bulk jobs accepted at submit", True),
    ("ditl_bulk_tokens_per_s", "gauge", "", "recent bulk-lane completion tokens/sec (windowed; 0 when the lane is idle)", True),
    ("ditl_client_deadline_exhausted_total", "counter", "", "remote-LLM calls aborted by the total_timeout_s wall-clock bound", True),
    ("ditl_client_requests_total", "counter", "", "remote-LLM logical calls started", True),
    ("ditl_client_retries_total", "counter", "", "remote-LLM HTTP attempts retried (429/5xx/connection errors)", True),
    ("ditl_client_retry_exhausted_total", "counter", "", "remote-LLM calls that failed after exhausting max_retries", True),
    ("ditl_gateway_429_by_class_batch_total", "counter", "", "requests 429 carrying SLO class batch"),
    ("ditl_gateway_429_by_class_best_effort_total", "counter", "", "requests 429 carrying SLO class best_effort"),
    ("ditl_gateway_429_by_class_default_total", "counter", "", "requests 429 carrying SLO class default"),
    ("ditl_gateway_429_by_class_interactive_total", "counter", "", "requests 429 carrying SLO class interactive"),
    ("ditl_gateway_action_<kind>_dry_run_total", "counter", "action kind (scale_up/scale_down/drain/quarantine)", "autoscale/remediation actions planned-but-logged under autoscale.dry_run", True),
    ("ditl_gateway_action_<kind>_executed_total", "counter", "action kind (scale_up/scale_down/drain/quarantine)", "autoscale/remediation actions executed against the fleet", True),
    ("ditl_gateway_action_<kind>_failed_total", "counter", "action kind (scale_up/scale_down/drain/quarantine)", "autoscale/remediation actions that failed mid-execution (also incident-bundled)", True),
    ("ditl_gateway_action_<kind>_planned_total", "counter", "action kind (scale_up/scale_down/drain/quarantine)", "autoscale/remediation actions the planner produced", True),
    ("ditl_gateway_action_<kind>_refused_total", "counter", "action kind (scale_up/scale_down/drain/quarantine)", "autoscale/remediation actions refused at execute time (bounds/state re-check under the fleet-mutation lock)", True),
    ("ditl_gateway_admission_amnesty_total", "counter", "", "tenants admitted with a fresh (full) token bucket after a gateway restart because the recovery manifest had no snapshot for them (ISSUE 20: the counted restart-amnesty fallback)"),
    ("ditl_gateway_affinity_hits_total", "counter", "", "requests routed to the same replica as the previous request with the same affinity key"),
    ("ditl_gateway_affinity_misses_total", "counter", "", "requests whose affinity key landed on a different replica than last time"),
    ("ditl_gateway_cold_start_429_total", "counter", "", "requests answered 429 with a wake-up Retry-After while serving capacity was parked (scale-to-zero admission)", True),
    ("ditl_gateway_fleet_prefix_cache_hit_ratio", "gauge", "", "token-weighted fleet prefix-cache hit ratio - compare against the affinity hit-rate counters"),
    ("ditl_gateway_fleet_recent_prefix_cache_hit_ratio", "gauge", "", "token-weighted fleet prefix-cache hit ratio over the recent health-poll window"),
    ("ditl_gateway_fleet_saturated_total", "counter", "", "requests 429'd because every replica was saturated"),
    ("ditl_gateway_handoff_attempted_total", "counter", "", "requests evaluated by the KV-handoff transfer-cost model"),
    ("ditl_gateway_handoff_declined_total", "counter", "", "handoffs the cost model declined (re-prefill estimated cheaper than the transfer)"),
    ("ditl_gateway_handoff_fallback_total", "counter", "", "accepted handoffs that failed mid-leg and fell back to plain relay (the decode replica re-prefills)"),
    ("ditl_gateway_handoff_shipped_total", "counter", "", "prefill->decode KV handoffs shipped to the decode replica"),
    ("ditl_gateway_hedges_total", "counter", "", "hedged duplicate requests fired"),
    ("ditl_gateway_loop_accept_backlog_drops_total", "counter", "", "client connects refused at accept because gateway.evloop_max_connections was reached (evloop data plane)"),
    ("ditl_gateway_loop_offload_busy_workers", "gauge", "", "offload-pool workers currently running a handler - pinned at pool size while queue wait grows = pool starvation, not a blocked loop"),
    ("ditl_gateway_loop_offload_queue_seconds", "histogram", "", "handler offload queue wait (loop submit -> worker pickup) - grows when the pool, not the loop, is the bottleneck"),
    ("ditl_gateway_loop_offload_workers", "gauge", "", "configured offload-pool size (gateway.evloop_offload_workers; occupancy denominator)"),
    ("ditl_gateway_loop_open_connections", "gauge", "", "client connections currently owned by the evloop data plane (any state)"),
    ("ditl_gateway_loop_open_sse_streams", "gauge", "", "detached SSE relays the event loop is currently pumping (no thread parked per stream)"),
    ("ditl_gateway_loop_ready_queue_depth", "gauge", "", "fds the last selector wakeup reported ready - sustained depth means the loop is the bottleneck"),
    ("ditl_gateway_loop_tick_p95_s", "gauge", "", "p95 event-loop tick over the last 512 ticks - the loop-stall early-warning signal (troubleshooting 35)"),
    ("ditl_gateway_loop_tick_seconds", "histogram", "", "one selector wakeup: dispatch every ready fd + drain the worker mailbox"),
    ("ditl_gateway_no_replica_total", "counter", "", "requests failed with no live replica"),
    ("ditl_gateway_pool_discards", "gauge", "", "pooled upstream connections discarded (stale socket, age/idle cap, mid-request error, or fleet-mutation invalidation; lifetime, stats mirror)"),
    ("ditl_gateway_pool_hits", "gauge", "", "pooled upstream connections reused across relays/polls/probes (lifetime, stats mirror)"),
    ("ditl_gateway_pool_idle", "gauge", "", "idle kept-alive upstream connections currently parked in the pool"),
    ("ditl_gateway_pool_misses", "gauge", "", "upstream hops that had to open a fresh connection (lifetime, stats mirror)"),
    ("ditl_gateway_recovery_adopted_total", "counter", "", "still-alive replica subprocesses adopted (pid + /health vetted) by a --recover incarnation instead of being restarted (ISSUE 20)"),
    ("ditl_gateway_recovery_relaunched_total", "counter", "", "manifest replicas a --recover incarnation could NOT adopt (dead pid or no /health answer) and left for a fresh-port relaunch (ISSUE 20; nonzero on an up-to-date manifest means replicas died with the gateway)"),
    ("ditl_gateway_recovery_runs_total", "counter", "", "gateway crash-recovery passes executed at startup (--recover with a readable manifest, ISSUE 20)"),
    ("ditl_gateway_relayed_by_class_batch_total", "counter", "", "requests relayed carrying SLO class batch"),
    ("ditl_gateway_relayed_by_class_best_effort_total", "counter", "", "requests relayed carrying SLO class best_effort"),
    ("ditl_gateway_relayed_by_class_default_total", "counter", "", "requests relayed carrying SLO class default"),
    ("ditl_gateway_relayed_by_class_interactive_total", "counter", "", "requests relayed carrying SLO class interactive"),
    ("ditl_gateway_replica_<id>_cold_start_seconds", "gauge", "replica id", "measured time-to-first-ready the replica stamped on /health - the scale-to-zero wake-budget input", True),
    ("ditl_gateway_replica_<id>_prefix_cache_hit_ratio", "gauge", "replica id", "measured engine prefix-cache hit ratio of replica r0 (lifetime, from its last health poll)"),
    ("ditl_gateway_replica_<id>_recent_prefix_cache_hit_ratio", "gauge", "replica id", "windowed (last few health polls) prefix-cache hit ratio of replica r0 - the spill-steering input"),
    ("ditl_gateway_replica_<id>_retried_total", "counter", "replica id", "requests retried for replica r0"),
    ("ditl_gateway_replica_<id>_routed_total", "counter", "replica id", "requests routed for replica r0"),
    ("ditl_gateway_replica_deaths_total", "counter", "", "replica died->drain->relaunch cycles the supervisor ran (the anomaly plane's death-rate input, ISSUE 10)"),
    ("ditl_gateway_replicas_active", "gauge", "", "replicas participating in serving (not parked by a scale-down, not quarantined)"),
    ("ditl_gateway_replicas_draining", "gauge", "", "replicas currently draining"),
    ("ditl_gateway_replicas_live", "gauge", "", "replicas currently routable"),
    ("ditl_gateway_replicas_quarantined", "gauge", "", "replicas quarantined by death-storm remediation"),
    ("ditl_gateway_request_e2e_seconds", "histogram", "", "gateway receive -> response relayed"),
    ("ditl_gateway_requests_completed_total", "counter", "", "requests relayed to completion"),
    ("ditl_gateway_requests_total", "counter", "", "requests received by the gateway"),
    ("ditl_gateway_retries_total", "counter", "", "proxy attempts retried on another replica (replica death/busy)"),
    ("ditl_gateway_role_decode_heavy_routed_total", "counter", "", "requests routed on decode_heavy-role replicas"),
    ("ditl_gateway_role_decode_heavy_spilled_total", "counter", "", "requests spilled on decode_heavy-role replicas"),
    ("ditl_gateway_role_hybrid_replicas_live", "gauge", "", "live hybrid-role replicas"),
    ("ditl_gateway_role_hybrid_routed_total", "counter", "", "requests routed on hybrid-role replicas"),
    ("ditl_gateway_role_hybrid_slot_pressure", "gauge", "", "max active_slots/capacity across hybrid-role replicas"),
    ("ditl_gateway_role_hybrid_spilled_total", "counter", "", "requests spilled on hybrid-role replicas"),
    ("ditl_gateway_role_hybrid_tpot_p95_s", "gauge", "", "worst per-replica tpot p95 across hybrid-role replicas (lifetime histograms, health-polled)"),
    ("ditl_gateway_role_hybrid_ttft_p95_s", "gauge", "", "worst per-replica ttft p95 across hybrid-role replicas (lifetime histograms, health-polled)"),
    ("ditl_gateway_role_prefill_heavy_routed_total", "counter", "", "requests routed on prefill_heavy-role replicas"),
    ("ditl_gateway_role_prefill_heavy_spilled_total", "counter", "", "requests spilled on prefill_heavy-role replicas"),
    ("ditl_gateway_routed_by_class_batch_total", "counter", "", "requests routed carrying SLO class batch"),
    ("ditl_gateway_routed_by_class_best_effort_total", "counter", "", "requests routed carrying SLO class best_effort"),
    ("ditl_gateway_routed_by_class_default_total", "counter", "", "requests routed carrying SLO class default"),
    ("ditl_gateway_routed_by_class_interactive_total", "counter", "", "requests routed carrying SLO class interactive"),
    ("ditl_gateway_stream_aborts_total", "counter", "", "streams cut mid-flight by a dying replica (not retryable)"),
    ("ditl_gateway_tenant_<tenant>_admitted_total", "counter", "tenant label", "requests admitted for tenant t0"),
    ("ditl_gateway_tenant_<tenant>_throttled_total", "counter", "tenant label", "requests throttled for tenant t0"),
    ("ditl_gateway_tenant_other_admitted_total", "counter", "overflow label", "admissions for tenants beyond the per-family cap", True),
    ("ditl_gateway_tenant_other_throttled_total", "counter", "overflow label", "throttles for tenants beyond the per-family cap", True),
    ("ditl_gateway_throttled_total", "counter", "", "requests rejected by tenant admission"),
    ("ditl_gateway_up", "gauge", "", "1 when the gateway is scraping"),
    ("ditl_incidents_suppressed_total", "counter", "", "anomaly triggers deduped/cooled down without a bundle"),
    ("ditl_incidents_total", "counter", "", "incident bundles assembled"),
    ("ditl_incidents_trigger_<kind>_total", "counter", "anomaly kind", "incident bundles triggered by serving.deadline_storm"),
    ("ditl_loop_lag_seconds", "histogram", "", "event-loop heartbeat age while busy, watchdog-sampled - how long the loop has been stuck inside one iteration (armed by telemetry.loop_stall_threshold_s)", True),
    ("ditl_loop_stalls_total", "counter", "", "loop stalls the watchdog convicted (lag crossed telemetry.loop_stall_threshold_s; each journals loop.stall with the convicting stack)", True),
    ("ditl_memory_<replica>_device<i>_bytes_in_use", "gauge", "replica id + device index", "replica HBM in use, re-namespaced on the gateway scrape", True),
    ("ditl_memory_<replica>_device<i>_bytes_limit", "gauge", "replica id + device index", "replica HBM limit, re-namespaced on the gateway scrape", True),
    ("ditl_memory_<replica>_device<i>_largest_alloc_size", "gauge", "replica id + device index", "replica largest allocation, re-namespaced on the gateway scrape", True),
    ("ditl_memory_<replica>_device<i>_peak_bytes_in_use", "gauge", "replica id + device index", "replica HBM high-watermark, re-namespaced on the gateway scrape", True),
    ("ditl_memory_device<i>_bytes_in_use", "gauge", "device index", "device 0 allocator bytes_in_use (absent on statless backends)", True),
    ("ditl_memory_device<i>_bytes_limit", "gauge", "device index", "device 0 allocator bytes_limit (absent on statless backends)", True),
    ("ditl_memory_device<i>_largest_alloc_size", "gauge", "device index", "device 0 allocator largest_alloc_size (absent on statless backends)", True),
    ("ditl_memory_device<i>_peak_bytes_in_use", "gauge", "device index", "device 0 allocator peak_bytes_in_use (absent on statless backends)", True),
    ("ditl_prof_samples_total", "counter", "", "wall-clock stack samples the sampling profiler took across all threads (armed by telemetry.prof_hz or /profile)", True),
    ("ditl_prof_stacks", "gauge", "", "distinct collapsed stacks currently held by the sampling profiler (bounded by telemetry.prof_max_stacks)", True),
    ("ditl_prof_stacks_evicted_total", "counter", "", "collapsed stacks evicted oldest-first at the telemetry.prof_max_stacks cap - non-zero means the flame graph has a truncated tail", True),
    ("ditl_serving_adapters", "gauge", "", "LoRA adapters resident (multi-LoRA serving)", True),
    ("ditl_serving_admission_degrade_windows_total", "counter", "", "tick windows that engaged the anti-thrash admission degrade"),
    ("ditl_serving_admission_degraded", "gauge", "", "1 while the optimistic-admission anti-thrash degrade is engaged"),
    ("ditl_serving_admission_degrades", "gauge", "", "lifetime anti-thrash degrade windows (stats mirror)"),
    ("ditl_serving_attn_page_steps_total", "gauge", "", "page steps the plain paged decode ticks' attention work lists held (a tick's list is built once; every layer of every step walks it); attn_pages_listed_total over attn_pages_a_step times this is how full the steps were (lifetime count from /v1/stats)"),
    ("ditl_serving_attn_pages_a_step", "gauge", "", "pages one step of the K/V decode attention kernel's walk takes, read off the page pool's shape (the fewest whose keys and values make 1 MiB, at most 4; 1 where a page is that large and for the latent kernels)"),
    ("ditl_serving_attn_pages_listed_total", "gauge", "", "pages the rows of the plain paged decode ticks' attention work lists held, counted from the rows' positions (lifetime count from /v1/stats)"),
    ("ditl_serving_client_disconnects_total", "counter", "", "in-flight generations cancelled because the client vanished mid-stream"),
    ("ditl_serving_deadline_expired_total", "counter", "", "requests evicted from the queue/slots at their deadline (expired work stops consuming engine ticks)"),
    ("ditl_serving_dead_chunk_rows_total", "gauge", "", "rows of harvested decode ticks whose request had already finished or been cancelled: the one dead chunk a slot decodes before a double-buffered tick's lagged harvest frees it (lifetime count from /v1/stats)"),
    ("ditl_serving_decode_chunk", "gauge", "", "decode tokens per scheduler tick"),
    ("ditl_serving_decode_token_seconds", "histogram", "", "per-token decode latency (harvest interval / chunk tokens)"),
    ("ditl_serving_draining", "gauge", "", "1 while the server is draining (SIGTERM / rolling restart)"),
    ("ditl_serving_first_tokens_early_total", "gauge", "", "requests whose first token was sent by the tick that ran their prefill, ahead of that tick's decode fetch (lifetime count from /v1/stats)"),
    ("ditl_serving_grammar_masked_tokens_total", "counter", "", "generated tokens decoded under an FSM grammar mask"),
    ("ditl_serving_guided_fsm_capacity", "gauge", "", "grammar FSM table rows available"),
    ("ditl_serving_guided_fsm_rows_used", "gauge", "", "grammar FSM table rows in use"),
    ("ditl_serving_guided_grammars_registered", "gauge", "", "distinct grammars registered"),
    ("ditl_serving_host_tier_bytes_used", "gauge", "", "host-RAM tier KV bytes resident", True),
    ("ditl_serving_host_tier_capacity_bytes", "gauge", "", "host-RAM tier size cap (kvtier.host_tier_mb)", True),
    ("ditl_serving_host_tier_corrupt_dropped", "gauge", "", "host-tier entries dropped on crc mismatch (stats mirror)", True),
    ("ditl_serving_host_tier_corrupt_entries_total", "counter", "", "host-tier entries whose crc32 failed at swap-in — detected, dropped, and re-prefilled; never served"),
    ("ditl_serving_host_tier_dropped", "gauge", "", "host-tier spill pages refused at the cap (stats mirror)", True),
    ("ditl_serving_host_tier_dropped_pages_total", "counter", "", "spill pages dropped (tier cap, oversized entry, or an injected kvtier.spill fault)"),
    ("ditl_serving_host_tier_entries", "gauge", "", "host-RAM tier entries resident", True),
    ("ditl_serving_host_tier_evictions_total", "counter", "", "host-tier entries LRU-evicted under the size cap"),
    ("ditl_serving_host_tier_nodes", "gauge", "", "host-tier chain nodes interned (the never-recycled key space)", True),
    ("ditl_serving_host_tier_spilled", "gauge", "", "lifetime pages spilled into the host tier (stats mirror)", True),
    ("ditl_serving_host_tier_spilled_pages_total", "counter", "", "LRU-evicted published pages spilled into the host-RAM tier"),
    ("ditl_serving_host_tier_swap_in_seconds", "histogram", "", "host-tier swap-in latency per admission (crc verify + device_put + republish of the matched run)"),
    ("ditl_serving_host_tier_swapped_in", "gauge", "", "lifetime pages swapped back in from the host tier (stats mirror)", True),
    ("ditl_serving_host_tier_swapped_pages_total", "counter", "", "host-tier pages swapped back into the device pool on an admission miss"),
    ("ditl_serving_inflight", "gauge", "", "HTTP requests currently in flight"),
    ("ditl_serving_interference_max_by_class_batch", "gauge", "", "worst interference stall absorbed by a batch victim (s)", True),
    ("ditl_serving_interference_max_by_class_best_effort", "gauge", "", "worst interference stall absorbed by a best_effort victim (s)", True),
    ("ditl_serving_interference_max_by_class_interactive", "gauge", "", "worst interference stall absorbed by an interactive victim (s)", True),
    ("ditl_serving_interference_max_s", "gauge", "", "largest single prefill-interference stall observed (s)"),
    ("ditl_serving_kv_bytes_per_token", "gauge", "", "KV bytes one token occupies in the page pools - the handoff cost model's size input", True),
    ("ditl_serving_kv_handoff_imports_total", "counter", "", "prefill->decode KV blobs imported by this replica"),
    ("ditl_serving_kv_handoff_rejected_total", "counter", "", "KV handoff blobs rejected (torn/short read, crc mismatch, or geometry mismatch) — reject-don't-install"),
    ("ditl_serving_kv_handoff_tokens_total", "counter", "", "prompt tokens installed from shipped prefill-handoff pages"),
    ("ditl_serving_kv_transfer_imported_bytes", "gauge", "", "lifetime KV handoff bytes imported", True),
    ("ditl_serving_kv_transfer_put_mbps", "gauge", "", "measured device_put bandwidth over KV imports - the handoff cost model's transfer input", True),
    ("ditl_serving_lockstep_speculative", "gauge", "", "1 when lock-step speculative serving is armed"),
    ("ditl_serving_lockstep_speculative_acceptance", "gauge", "", "lock-step speculative acceptance EMA"),
    ("ditl_serving_max_context", "gauge", "", "per-slot KV context cap (tokens)"),
    ("ditl_serving_max_tick_prefill_tokens", "gauge", "", "largest prefill token spend any single tick made"),
    ("ditl_serving_n_slots", "gauge", "", "decode slots"),
    ("ditl_serving_page_size", "gauge", "", "KV page size (tokens)"),
    ("ditl_serving_pages_cached_evictable", "gauge", "", "published prefix pages reclaimable by LRU"),
    ("ditl_serving_pages_free", "gauge", "", "free KV pages"),
    ("ditl_serving_pages_total", "gauge", "", "KV pages in the pool (sentinel excluded)"),
    ("ditl_serving_pod", "gauge", "", "1 on a pod-serving coordinator (tick-broadcast driver)", True),
    ("ditl_serving_preemptions_total", "counter", "", "optimistic-admission preemptions (pages reclaimed mid-flight)"),
    ("ditl_serving_prefill_tok_per_s", "gauge", "", "measured lifetime prefill throughput - the re-prefill side of the handoff cost model", True),
    ("ditl_serving_prefix_cache_evictions_total", "counter", "", "published prefix pages reclaimed by LRU eviction under pool pressure"),
    ("ditl_serving_prefix_cache_hit_ratio", "gauge", "", "measured hit tokens / (hit + miss) tokens — the number the gateway affinity router's score is validated against"),
    ("ditl_serving_prefix_cache_hit_tokens_handoff_total", "counter", "", "prompt tokens reused via the handoff tier (pages shipped by a prefill->decode handoff)"),
    ("ditl_serving_prefix_cache_hit_tokens_hbm_total", "counter", "", "prompt tokens reused via the hbm tier (published pages resident in the device pool)"),
    ("ditl_serving_prefix_cache_hit_tokens_host_total", "counter", "", "prompt tokens reused via the host tier (pages swapped back in from the host-RAM tier)"),
    ("ditl_serving_prefix_cache_hit_tokens_total", "counter", "", "prompt tokens whose KV was reused from the prefix cache at slot admission (paged content-hash match or registered prefix)"),
    ("ditl_serving_prefix_cache_miss_tokens_total", "counter", "", "prompt tokens the engine prefilled because no cached KV covered them"),
    ("ditl_serving_prefix_hits_refused", "gauge", "", "prefix matches of the full layers' page pool that were granted at NO length because the window layers' pool no longer covered a last window below any of it (a model with window attention layers: two page pools; lifetime count from /v1/stats)", True),
    ("ditl_serving_prefix_hits_short", "gauge", "", "prefix matches granted at a SHORTER length than the full layers' pool could give: the longest whose last window the window layers' pool still covers (lifetime count from /v1/stats)", True),
    ("ditl_serving_prefix_hits_whole", "gauge", "", "prefix matches granted at the whole matched length, the full layers' pages and the window layers' last window both present (lifetime count from /v1/stats)", True),
    ("ditl_serving_queue_by_class_batch", "gauge", "", "queued batch-class requests"),
    ("ditl_serving_queue_by_class_best_effort", "gauge", "", "queued best_effort-class requests"),
    ("ditl_serving_queue_by_class_interactive", "gauge", "", "queued interactive-class requests"),
    ("ditl_serving_queue_depth", "gauge", "", "requests waiting for a slot"),
    ("ditl_serving_queue_full_total", "counter", "", "submissions rejected QueueFull (HTTP 429)"),
    ("ditl_serving_request_e2e_seconds", "histogram", "", "submit -> request finished"),
    ("ditl_serving_request_queue_wait_seconds", "histogram", "", "submit -> slot admission"),
    ("ditl_serving_request_ttft_batch_seconds", "histogram", "", "TTFT of batch-class requests"),
    ("ditl_serving_request_ttft_best_effort_seconds", "histogram", "", "TTFT of best_effort-class requests"),
    ("ditl_serving_request_ttft_cache_hit_seconds", "histogram", "", "TTFT of requests whose prompt hit the prefix cache (>= 1 reused token)"),
    ("ditl_serving_request_ttft_cache_miss_seconds", "histogram", "", "TTFT of requests whose prompt missed the prefix cache entirely"),
    ("ditl_serving_request_ttft_interactive_seconds", "histogram", "", "TTFT of interactive-class requests"),
    ("ditl_serving_request_ttft_seconds", "histogram", "", "submit -> first generated token sent (by the tick that ran the prefill)"),
    ("ditl_serving_requests_admitted_total", "counter", "", "requests admitted into a slot"),
    ("ditl_serving_requests_completed_total", "counter", "", "requests finished"),
    ("ditl_serving_requests_total", "counter", "", "requests accepted by submit"),
    ("ditl_serving_ret_row_folds_total", "gauge", "", "live rows whose state a decode tick of a stack of retention layers WROTE: a tick's last step folds the tick's held tokens into the state of each row live at that step, once a layer; beside ditl_serving_ssm_row_steps_total, the rows whose state a step read, the ratio is one over the tick's steps but for rows that end inside a tick (lifetime count from /v1/stats)", True),
    ("ditl_serving_resume_prefill_tokens", "gauge", "", "tokens re-prefilled resuming preempted requests"),
    ("ditl_serving_slots_busy", "gauge", "", "occupied slots"),
    ("ditl_serving_slots_prefilling", "gauge", "", "slots running chunked prefill"),
    ("ditl_serving_spec_accepted_tokens_total", "counter", "", "speculative drafted tokens accepted by verify forwards"),
    ("ditl_serving_spec_rejected_tokens_total", "counter", "", "speculative drafted tokens rejected by verify forwards"),
    ("ditl_serving_speculative_acceptance_ema", "gauge", "", "measured speculative acceptance EMA (absent until measured)", True),
    ("ditl_serving_speculative_k", "gauge", "", "drafted tokens per speculative round"),
    ("ditl_serving_speculative_plain_step_ms", "gauge", "", "measured plain decode tick cost (absent until measured)", True),
    ("ditl_serving_speculative_rounds_per_tick", "gauge", "", "verify rounds per speculative tick"),
    ("ditl_serving_speculative_spec_round_ms", "gauge", "", "measured speculative round cost (absent until measured)", True),
    ("ditl_serving_speculative_spec_ticks", "gauge", "", "ticks that ran speculatively"),
    ("ditl_serving_speculative_threshold", "gauge", "", "predicted-acceptance threshold for speculating"),
    ("ditl_serving_speculative_ticks", "gauge", "", "ticks counted by the speculation decision path"),
    ("ditl_serving_ssm_row_steps_total", "gauge", "", "live rows summed over the decode ticks' steps of a model with state-space or retention layers: each read its recurrent state once a mixer, and a state-space mixer's rewrote it (lifetime count from /v1/stats)", True),
    ("ditl_serving_ssm_slots_seated", "gauge", "", "slots whose recurrent state belongs to a request in flight (a model with state-space or retention layers; a freed slot's state is overwritten by the next seat, never cleared)", True),
    ("ditl_serving_ssm_state_bytes_per_slot", "gauge", "", "recurrent state one slot holds over all state-space mixers (the float32 state and the convolution window) or retention layers (the float32 state and sum of keys)", True),
    ("ditl_serving_ssm_state_bytes_resident", "gauge", "", "recurrent state resident on the device: bytes a slot times the slots, allocated once beside the page pool (a stack of retention layers has no pool beside it)", True),
    ("ditl_serving_staged", "gauge", "", "requests staged for the next pod tick broadcast", True),
    ("ditl_serving_ticks_overlapped_total", "gauge", "", "scheduler steps that fetched and harvested one decode tick while the next tick's program was already enqueued on the device (double-buffered ticks; lifetime count from /v1/stats)"),
    ("ditl_serving_token_budget", "gauge", "", "per-tick token budget (0 = unbudgeted)"),
    ("ditl_serving_tokens_generated_total", "counter", "", "tokens generated (all requests)"),
    ("ditl_serving_tpot_interference_batch_seconds", "histogram", "", "per-tick decode delay absorbed by batch-class victims because the tick also ran another request's prefill"),
    ("ditl_serving_tpot_interference_best_effort_seconds", "histogram", "", "per-tick decode delay absorbed by best_effort-class victims because the tick also ran another request's prefill"),
    ("ditl_serving_tpot_interference_interactive_seconds", "histogram", "", "per-tick decode delay absorbed by interactive-class victims because the tick also ran another request's prefill"),
    ("ditl_serving_tpot_interference_seconds", "histogram", "", "per-tick decode delay a victim request absorbed because the tick also ran another request's prefill chunk(s) — the scheduler-interference signal behind chunked-prefill tuning (ISSUE 6)"),
    ("ditl_serving_up", "gauge", "", "1 when the replica server is scraping"),
    ("ditl_serving_window_kv_bytes_per_token", "gauge", "", "bytes one token's keys and values take in the window layers' page pool (a model with window attention layers; the full layers' are kv_bytes_per_token)", True),
    ("ditl_serving_window_pages_cached_evictable", "gauge", "", "pages of the window layers' pool that only the content cache holds: the last windows of published prefixes, reclaimable", True),
    ("ditl_serving_window_pages_free", "gauge", "", "free pages of the window layers' pool", True),
    ("ditl_serving_window_pages_freed_total", "gauge", "", "window pages that went back to the free list because the row that held them moved past them (lifetime count from /v1/stats)", True),
    ("ditl_serving_window_pages_released_total", "gauge", "", "references to window pages that rows gave up behind their window, in chunked prefill and in decode (a shared page stays while another row or the cache holds it; lifetime count from /v1/stats)", True),
    ("ditl_serving_window_pages_total", "gauge", "", "size of the window layers' page pool (--window-pages less the sentinel)", True),
    ("ditl_serving_window_pages_walked_total", "gauge", "", "pages the rows of the window layers' decode work list held, counted from their positions (a step of the list may take several) and summed over the decode ticks' steps (every window layer walks the list once a step; full_pages_walked_total is the full layers')", True),
    ("ditl_serving_full_pages_walked_total", "gauge", "", "pages the rows of the full layers' decode work list held, counted from their positions and summed over the decode ticks' steps, in a model that also has window attention layers", True),
    ("ditl_serving_window_pool_evictions", "gauge", "", "companions the window layers' pool reclaimed from the content cache under pressure (the full page stays published; a later hit over it is shortened or refused)", True),
    ("ditl_slo_availability_alerting", "gauge", "", "1 when every window burns availability's budget faster than 1.0x"),
    ("ditl_slo_availability_burn_rate_w<window>", "gauge", "window seconds", "availability burn rate over 300s (error rate / error budget)"),
    ("ditl_slo_e2e_alerting", "gauge", "", "1 when every window burns e2e's budget faster than 1.0x"),
    ("ditl_slo_e2e_burn_rate_w<window>", "gauge", "window seconds", "e2e burn rate over 300s (error rate / error budget)"),
    ("ditl_slo_tpot_alerting", "gauge", "", "1 when every window burns tpot's budget faster than 1.0x"),
    ("ditl_slo_tpot_burn_rate_w<window>", "gauge", "window seconds", "tpot burn rate over 300s (error rate / error budget)"),
    ("ditl_slo_ttft_alerting", "gauge", "", "1 when every window burns ttft's budget faster than 1.0x"),
    ("ditl_slo_ttft_burn_rate_w<window>", "gauge", "window seconds", "ttft burn rate over 300s (error rate / error budget)"),
    ("ditl_usage_requests_200_total", "counter", "", "terminal requests metered with outcome 200", True),
    ("ditl_usage_requests_429_total", "counter", "", "terminal requests metered with outcome 429", True),
    ("ditl_usage_requests_503_total", "counter", "", "terminal requests metered with outcome 503", True),
    ("ditl_usage_requests_504_total", "counter", "", "terminal requests metered with outcome 504", True),
    ("ditl_usage_requests_adapter_total", "counter", "", "adapter-plane owner-billing flush rows (HBM residency + gather attribution; no client request behind them)", True),
    ("ditl_usage_requests_cancel_total", "counter", "", "terminal requests metered with outcome cancel", True),
    ("ditl_usage_requests_other_total", "counter", "", "terminal requests metered with an out-of-vocabulary outcome", True),
    ("ditl_usage_requests_total", "counter", "", "terminal requests metered by the per-tenant usage meter (ISSUE 15)", True),
    ("ditl_usage_tenant_<tenant>_cached_tokens_saved_total", "counter", "tenant label (overflow folds into `other`)", "prompt tokens served from cached KV (all tiers) attributed to the tenant", True),
    ("ditl_usage_tenant_<tenant>_device_seconds_total", "counter", "tenant label (overflow folds into `other`)", "estimated device-seconds (prefill wall + decode-tick share) attributed to the tenant", True),
    ("ditl_usage_tenant_<tenant>_generated_tokens_total", "counter", "tenant label (overflow folds into `other`)", "generated tokens attributed to the tenant", True),
    ("ditl_usage_tenant_<tenant>_prompt_tokens_total", "counter", "tenant label (overflow folds into `other`)", "prompt tokens attributed to the tenant", True),
)

CATALOG: tuple[CatalogEntry, ...] = tuple(
    CatalogEntry(*row) for row in _ROWS
)


def catalog_families() -> dict[str, CatalogEntry]:
    return {e.family: e for e in CATALOG}


def required_families() -> set[str]:
    """Families the drift guard requires a live run to actually register
    (everything not marked optional)."""
    return {e.family for e in CATALOG if not e.optional}


def render_markdown() -> str:
    """docs/metrics.md, generated whole. Regenerate with
    ``python -m ditl_tpu.telemetry.catalog --write docs/metrics.md``."""
    lines = [
        "# Metrics catalog",
        "",
        "<!-- GENERATED by `python -m ditl_tpu.telemetry.catalog --write "
        "docs/metrics.md` — edit telemetry/catalog.py, not this file. -->",
        "",
        "Every `ditl_*` Prometheus family the system exposes, across the "
        "replica server's `/metrics`, the gateway's `/metrics`, and the "
        "training leg's instruments. `<placeholders>` mark dynamic label "
        "segments sanitized into the family name (the registry is "
        "label-free by design). Families marked *optional* are absent on "
        "some backends or configurations — absent, never zero-valued "
        "lies. The drift-guard test "
        "(tests/test_metrics_catalog.py) pins this table against what a "
        "live run actually registers, in both directions.",
        "",
        "| family | type | dynamic labels | meaning |",
        "|---|---|---|---|",
    ]
    for e in CATALOG:
        meaning = e.meaning + (" *(optional)*" if e.optional else "")
        lines.append(
            f"| `{e.family}` | {e.type} | {e.labels or '—'} | {meaning} |"
        )
    lines.append("")
    lines.append(f"{len(CATALOG)} families "
                 f"({sum(1 for e in CATALOG if not e.optional)} required, "
                 f"{sum(1 for e in CATALOG if e.optional)} optional).")
    lines += [
        "",
        "## Span attributes",
        "",
        "No `/metrics` family carries a span's attributes: an armed tracer "
        "(`--trace-dir`) writes them to the span journal. Those of a "
        "request's first token (`first_write_s`, `bucket`, `ahead_tokens`, "
        "`behind_tokens`, `fetch_wait_s` and the rest) are defined in "
        "`docs/design.md`, \"A request's first token\", with the recipe "
        "that reads them: `python benchmarks/layer_metrics/_ttft.py RUN_DIR`.",
        "",
        "A process's start is journaled the same way (`docs/design.md`, "
        "\"A program's start\"): one span `startup` from the program's "
        "entry, its legs `startup.<leg>` (the server's `imports`, `runtime`, "
        "`tokenizer`, `params`, `engine`, `listen`; the trainer's `config`, "
        "`runtime`, `data`, `state`, `restore`, `loop_prep`, `first_flush`) "
        "with `loader`, `synced`, `param_bytes`, `restored`, `pool_bytes`, `port`, "
        "`examples`, `n_params`, `resumed`, `step`, `steps`, and on every "
        "`jit.compile` event `cache` (`hit` with `retrieval_s`, `miss`, "
        "`off`). No `/metrics` family carries them either: the legs' seconds "
        "are `startup` (`entry_wall`, `legs`, `tokenizer_loader`) in the server's `/v1/stats` "
        "(their sum is `/health`'s `cold_start_s`) and in the FIRST "
        "`train.metrics_file` row a process writes; the programs the "
        "persistent cache did not hold are `compile_miss_count_cum`, beside "
        "`compile_count_cum` / `compile_s_cum`, in `/v1/stats` and in every "
        "row. The recipe that reads them: `python "
        "benchmarks/layer_metrics/_setup.py RUN_DIR`.",
    ]
    return "\n".join(lines) + "\n"


def main(argv: list[str] | None = None) -> int:
    import argparse

    parser = argparse.ArgumentParser(
        prog="python -m ditl_tpu.telemetry.catalog",
        description="render / check the generated metrics catalog",
    )
    parser.add_argument("--write", default="",
                        help="write the generated markdown to PATH")
    parser.add_argument("--check", default="",
                        help="exit 1 unless PATH matches the generated "
                        "markdown (the drift guard's doc half)")
    args = parser.parse_args(argv)
    body = render_markdown()
    if args.write:
        with open(args.write, "w") as f:
            f.write(body)
        print(f"wrote {len(CATALOG)} families to {args.write}")
        return 0
    if args.check:
        try:
            with open(args.check) as f:
                current = f.read()
        except OSError as e:
            print(f"error: cannot read {args.check}: {e}")
            return 1
        if current != body:
            print(f"{args.check} is stale — regenerate with "
                  "python -m ditl_tpu.telemetry.catalog --write "
                  f"{args.check}")
            return 1
        print(f"{args.check} matches the catalog")
        return 0
    print(body, end="")
    return 0


if __name__ == "__main__":
    import sys

    sys.exit(main())
