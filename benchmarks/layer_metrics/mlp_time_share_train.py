"""Device self time under the ``mlp`` scope (the norm before it, the gate, up
and down matmuls, their backward and recomputation) over the traced window's
busy time; mean over the chips."""
from layer_metrics import _scopes

LAYER = "Model step"
UNIT = "%"
MOVES = "train_tokens_per_s_per_chip"
SOURCE = "device_trace"


def read(run):
    return _scopes.time_share(run, ("mlp",))
