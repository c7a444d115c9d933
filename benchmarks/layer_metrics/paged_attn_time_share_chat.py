"""Device self time inside the ``paged_attention`` kernel over the traced
window's busy time."""
from layer_metrics import _scopes

LAYER = "Kernels"
UNIT = "%"
MOVES = "tpot_p50_ms"
SOURCE = "device_trace"


def read(run):
    return _scopes.time_share(run, ("paged_attention",))
