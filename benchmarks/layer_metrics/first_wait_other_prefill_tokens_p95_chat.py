"""Padded prefill tokens of OTHER requests that ran between a request's
enqueue and its first token: the p95 over the window's requests of
``ahead_tokens`` (its ``engine.prefill`` span: the prefill programs enqueued
earlier in the same step) + ``behind_tokens`` (its first ``engine.decode``:
those enqueued after its own, which the step's shared fetch made it wait
for). The program's own count of which prefills met in a step. Over the
requests clear of the device profiler's capture (``_ttft.quiet``: the burst
that enters behind the capture's stop meets in one step). 0.0 on a journal
whose spans lack the attributes; None only without a traced run."""
from harness import percentile
from layer_metrics import _ttft

LAYER = "Scheduler"
UNIT = "tokens"
MOVES = "tpot_p50_ms"
SOURCE = "program_counter"


def read(run):
    records = _ttft.run_requests(run)
    if records is None:
        return None
    xs = [r["ahead_tokens"] + r["behind_tokens"] for r in records
          if r["ahead_tokens"] is not None and r["behind_tokens"] is not None]
    return float(percentile(xs, 95)) if xs else 0.0
