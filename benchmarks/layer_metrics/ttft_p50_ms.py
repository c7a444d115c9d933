"""Median time from a request's due instant to its first streamed token (client clock)."""
from layer_metrics import _lib

LAYER = "Server front"
UNIT = "ms"
MOVES = "tpot_p50_ms"
SOURCE = "host_clock"


def read(run):
    return _lib.ttft_ms(run, 50)
