"""Device self time under any name of the table over the traced window's
busy time; mean over the chips. The measurement's own health: what is left
runs under no name, so no other ``*_time_share_*`` can see it."""
from layer_metrics import _scopes

LAYER = "Device"
UNIT = "%"
MOVES = "train_tokens_per_s_per_chip"
SOURCE = "device_trace"


def read(run):
    return _scopes.time_share(run, _scopes.TABLE)
