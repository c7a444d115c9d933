"""The p95 over the window's requests of ``first_wait`` (``_ttft.py``): from
the instant a request's prefill program was enqueued (the end of its
``engine.prefill`` span) to the delivery of its first token (the end of its
first ``engine.decode``): what is left of the decode program enqueued a step
earlier, the step's prefills, the shared fetch. Over the requests clear of
the device profiler's capture (``_ttft.quiet``: a p95 over all of a traced
window is partly the capture's stop). Needs no attribute newer than the
spans themselves. 0.0 where the window holds no such request; None only
without a traced run."""
from layer_metrics import _ttft

LAYER = "Scheduler"
UNIT = "ms"
MOVES = "tpot_p50_ms"
SOURCE = "program_span"


def read(run):
    return _ttft.quantile_ms(run, lambda rec: rec["first_wait"], 95)
