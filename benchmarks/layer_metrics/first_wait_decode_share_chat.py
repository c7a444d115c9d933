"""What a first token pays for the decode program that was enqueued a step
before its prefill: over the requests whose whole ``first_wait`` (prefill
enqueued to first token delivered, ``_ttft.py``) lies inside the traced
interval, the time the first chip's ``XLA Modules`` line shows
``jit_paged_decode`` running inside that interval, summed, over the
intervals' summed length; the remainder is prefill programs and idle. Spans
are laid on the trace's clock through its ``benchmarks.clock`` marks. No run
is matched to a request. 0.0 where the traced interval holds no whole
``first_wait``; None without a traced run, and where the trace has no clock
mark or no device event (nothing was measured; the benchmark's own runs
always have both)."""
from layer_metrics import _ttft

LAYER = "Scheduler"
UNIT = "%"
MOVES = "tpot_p50_ms"
SOURCE = "device_trace"


def read(run):
    return _ttft.run_decode_share(run)
