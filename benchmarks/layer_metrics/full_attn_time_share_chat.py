"""Device self time under ``attn_full`` over the traced window's busy time:
the full layers' attention in a stack that also has window layers, the decode
kernel over every cached page of a row, a prefill chunk's scores over the
whole cached context, and the output gate's product; decode and prefill.
Nothing to read where the program has no window layer."""
from layer_metrics import _swa

LAYER = "Kernels"
UNIT = "%"
MOVES = "tpot_p50_ms"
SOURCE = "device_trace"


def read(run):
    return _swa.time_share(run, "attn_full")
