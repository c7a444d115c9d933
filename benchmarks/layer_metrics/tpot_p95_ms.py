"""95th percentile over requests of the mean gap between streamed tokens
after the first event (client clock): the tail of ``tpot_p50_ms``, too noisy
at 150 requests a window to carry a bound."""
from layer_metrics import _lib

LAYER = "Server front"
UNIT = "ms"
MOVES = "tpot_p50_ms"
SOURCE = "host_clock"


def read(run):
    return _lib.tpot_ms(run, 95)
