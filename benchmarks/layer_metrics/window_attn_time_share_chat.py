"""Device self time under ``attn_window`` over the traced window's busy time:
the window layers' attention, the decode kernel over the window layers' pool,
a prefill chunk's masked scores over its last 2,048 cached tokens, and the
output gate's product; decode and prefill. Nothing to read where the program
has no window layer."""
from layer_metrics import _swa

LAYER = "Kernels"
UNIT = "%"
MOVES = "tpot_p50_ms"
SOURCE = "device_trace"


def read(run):
    return _swa.time_share(run, "attn_window")
