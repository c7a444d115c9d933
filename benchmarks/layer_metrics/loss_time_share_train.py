"""Device self time under the ``loss`` scope (the head matmul of the fused
loss, its softmax, their backward, and whatever collectives they issue) over
the traced window's busy time; mean over the chips."""
from layer_metrics import _scopes

LAYER = "Kernels"
UNIT = "%"
MOVES = "train_tokens_per_s_per_chip"
SOURCE = "device_trace"


def read(run):
    return _scopes.time_share(run, ("loss",))
