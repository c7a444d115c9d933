"""The p95 sibling of ``queue_wait_p50_ms``, over the same spans: the
engine's ``engine.queue`` spans (``--trace-dir``) that began inside the
window, submit to slot admission, without those that touch the device
profiler's capture or its length behind it (``_ttft.quiet``: a p95 over all
of a traced window is partly the capture's stop). 0.0 where the window holds
none; None only without a traced run."""
from harness import percentile
from layer_metrics import _ttft

LAYER = "Scheduler"
UNIT = "ms"
MOVES = "tpot_p50_ms"
SOURCE = "program_span"


def read(run):
    spans = _ttft.run_queue_spans(run)
    if spans is None:
        return None
    return percentile([1e3 * s["dur_s"] for s in spans], 95) if spans else 0.0
