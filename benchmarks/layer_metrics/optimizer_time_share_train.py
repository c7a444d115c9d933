"""Device self time under the ``optimizer`` scope (gradient norm, clipping,
the optax update, applying it) over the traced window's busy time; mean over
the chips."""
from layer_metrics import _scopes

LAYER = "Model step"
UNIT = "%"
MOVES = "train_tokens_per_s_per_chip"
SOURCE = "device_trace"


def read(run):
    return _scopes.time_share(run, ("optimizer",))
