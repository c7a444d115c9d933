"""Device self time under the scope ``dsa_select`` (the top-k of a query's
index scores over its whole context, and the page-table arithmetic that
turns the chosen positions into pool rows) over the traced window's busy
time; decode and prefill. Nothing to read where the program has no indexer."""
from layer_metrics import _dsa

LAYER = "Kernels"
UNIT = "%"
MOVES = "tpot_p50_ms"
SOURCE = "device_trace"


def read(run):
    return _dsa.time_share(run, ("dsa_select",))
