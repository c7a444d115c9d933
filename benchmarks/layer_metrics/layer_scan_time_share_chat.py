"""Device self time under the scope ``layer_scan`` inside ``jit_paged_decode``
over the traced window's busy time: what the decode program's loop over the
layers does itself, nearly all of it the slice of each layer's WHOLE K and V
page pool out of the stacked pools (PERF.md section 5)."""
from layer_metrics import _scopes

LAYER = "Cache manager"
UNIT = "%"
MOVES = "tpot_p50_ms"
SOURCE = "device_trace"


def read(run):
    return _scopes.time_share(run, ("layer_scan",), program="paged_decode")
