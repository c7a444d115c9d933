"""Per request, the time from its first streamed event to its last over the
tokens that arrived after the first event; the MEDIAN over requests. Not the
raw gap between events: the engine emits a chunk of tokens per tick, so raw
gaps are zeros and a tick. The median and not the 95th percentile, because at
150 requests a window the p95 of two sets of runs of the same code spread by
up to 9.6% (51 s windows: 5.1%), more than any bound may allow; the p95 is the
per-layer metric ``tpot_p95_ms``, which moves this one."""
from layer_metrics import _lib

UNIT = "ms"


def read(run):
    return _lib.tpot_ms(run, 50)
