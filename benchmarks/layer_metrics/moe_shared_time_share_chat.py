"""Device self time under the scope ``moe_shared`` (the shared expert: a
dense SwiGLU FFN every token passes through, inside ``mlp``) over the traced
window's busy time; decode and prefill. Nothing to read where the program has
no shared expert."""
from layer_metrics import _dsa

LAYER = "Model step"
UNIT = "%"
MOVES = "tpot_p50_ms"
SOURCE = "device_trace"


def read(run):
    return _dsa.time_share(run, _dsa.MOE_SHARED_SCOPES)
