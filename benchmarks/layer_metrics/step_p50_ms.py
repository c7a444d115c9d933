"""Median over flush-to-flush intervals of the interval over its steps, on the
harness's clock (a flush ends in the trainer's only device sync)."""
from harness import percentile
from layer_metrics import _lib

LAYER = "Model step"
UNIT = "ms"
MOVES = "train_tokens_per_s_per_chip"
SOURCE = "host_clock"


def read(run):
    xs = [1e3 * (i["t1"] - i["t0"]) / i["steps"]
          for i in _lib.steady_intervals(run) if i["steps"]]
    return percentile(xs, 50) if xs else None
