"""Non-padding tokens (the metrics_file rows' ``n_tokens``) of the steps
between the first flush after warm-up and the last flush inside the window,
over the time between those two flushes on the harness's clock and the chip
count. In a traced run the intervals the profiler sits in are left out."""
from layer_metrics import _lib

UNIT = "tokens/s/chip"


def read(run):
    return _lib.steady_tokens_per_s_per_chip(run)
