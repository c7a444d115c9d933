"""Median time from a request's due instant to its first streamed token (client clock)."""
from layer_metrics import _lib

LAYER = "Server front"
UNIT = "ms"
MOVES = "ttft_p95_ms"
SOURCE = "host_clock"


def read(run):
    return _lib.ttft_ms(run, 50)
