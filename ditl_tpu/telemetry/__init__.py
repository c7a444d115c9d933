"""Unified telemetry subsystem (ISSUE 3 + 6 + 7 + 10): process-local
metrics registry (registry.py), serving instrument bundle (serving.py),
goodput/badput accounting (goodput.py), the cross-process JSONL event
journal (journal.py), end-to-end request tracing (tracing.py), Chrome-trace
export (trace_export.py), SLO burn-rate monitoring (slo.py), the training
performance observatory (perf.py: step-time anatomy, roofline cost
analysis, versioned sweep records; perf_compare.py: the regression gate),
HBM accounting (memwatch.py), and the flight-recorder/anomaly/incident
plane (flight.py: always-on black-box rings; anomaly.py: signal-driven
detectors; incident.py: fingerprint-deduped self-contained bundles;
catalog.py: the generated metrics catalog), and per-tenant usage
metering/cost attribution (usage.py: the crash-consistent usage ledger,
bounded per-tenant meters, and the noisy-neighbor conviction the
serving anomaly monitor applies — ISSUE 15). Host-only by design —
importing this package never touches jax (memwatch imports it lazily
inside functions), and no instrument accepts a device value."""

from ditl_tpu.telemetry.anomaly import (
    Anomaly,
    AnomalyPlane,
    GatewayDetector,
    ServingAnomalyMonitor,
    ServingDetector,
    TrainingDetector,
)
from ditl_tpu.telemetry.flight import (
    LIVENESS_RING,
    ROUTING_RING,
    STEP_RING,
    TICK_RING,
    FlightRecorder,
    FlightRing,
)
from ditl_tpu.telemetry.goodput import (
    BADPUT_BUCKETS,
    GoodputTracker,
    lost_work_from_journal,
)
from ditl_tpu.telemetry.incident import (
    IncidentManager,
    incidents_total,
    list_bundles,
    read_bundle,
)
from ditl_tpu.telemetry.memwatch import MemoryWatcher, live_buffer_topk
from ditl_tpu.telemetry.perf import (
    ANATOMY_BUCKETS,
    SWEEP_SCHEMA,
    StepAnatomy,
    compiled_cost,
    load_sweep_record,
    new_sweep_record,
    record_sweep_cell,
    roofline,
)
from ditl_tpu.telemetry.journal import (
    EventJournal,
    controller_journal_path,
    merge_journals,
    read_journal,
    worker_journal_path,
    write_pod_timeline,
)
from ditl_tpu.telemetry.registry import (
    LATENCY_BUCKETS_S,
    TOKEN_LATENCY_BUCKETS_S,
    Counter,
    Gauge,
    Histogram,
    MetricsRegistry,
)
from ditl_tpu.telemetry.serving import ServingMetrics
from ditl_tpu.telemetry.usage import (
    UsageLedger,
    UsageMeter,
    convict_noisy_neighbor,
    load_usage,
    rollup,
    usage_ledger_path,
)
from ditl_tpu.telemetry.slo import (
    BurnRateMonitor,
    Objective,
    gateway_slo,
    serving_slo,
)
from ditl_tpu.telemetry.tracing import (
    NULL_TRACER,
    Span,
    SpanContext,
    StartupRecorder,
    Tracer,
    format_traceparent,
    new_request_id,
    parse_traceparent,
)

__all__ = [
    "ANATOMY_BUCKETS",
    "Anomaly",
    "AnomalyPlane",
    "BADPUT_BUCKETS",
    "BurnRateMonitor",
    "Counter",
    "EventJournal",
    "FlightRecorder",
    "FlightRing",
    "Gauge",
    "GatewayDetector",
    "GoodputTracker",
    "Histogram",
    "IncidentManager",
    "LATENCY_BUCKETS_S",
    "LIVENESS_RING",
    "MemoryWatcher",
    "MetricsRegistry",
    "NULL_TRACER",
    "Objective",
    "ROUTING_RING",
    "STEP_RING",
    "SWEEP_SCHEMA",
    "ServingAnomalyMonitor",
    "ServingDetector",
    "ServingMetrics",
    "Span",
    "SpanContext",
    "StartupRecorder",
    "StepAnatomy",
    "TICK_RING",
    "TOKEN_LATENCY_BUCKETS_S",
    "Tracer",
    "TrainingDetector",
    "UsageLedger",
    "UsageMeter",
    "compiled_cost",
    "controller_journal_path",
    "convict_noisy_neighbor",
    "format_traceparent",
    "gateway_slo",
    "incidents_total",
    "list_bundles",
    "live_buffer_topk",
    "load_sweep_record",
    "load_usage",
    "lost_work_from_journal",
    "merge_journals",
    "new_request_id",
    "new_sweep_record",
    "parse_traceparent",
    "read_bundle",
    "read_journal",
    "record_sweep_cell",
    "rollup",
    "roofline",
    "serving_slo",
    "usage_ledger_path",
    "worker_journal_path",
    "write_pod_timeline",
]
