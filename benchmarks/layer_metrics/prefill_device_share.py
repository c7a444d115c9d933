"""Device time of the ``jit_paged_prefill`` runs (the trace's ``XLA Modules``
line) over the traced window's busy time: what admission's prefills take of
the device that decode ticks would otherwise have."""
from layer_metrics import _scopes

LAYER = "Model step"
UNIT = "%"
MOVES = "tpot_p50_ms"
SOURCE = "device_trace"


def read(run):
    return _scopes.program_share(run, "jit_paged_prefill")
