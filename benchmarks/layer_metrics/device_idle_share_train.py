"""Share of the traced window in which no operation ran on the device (mean over chips), trainer cells."""
from layer_metrics import _lib

LAYER = "Device"
UNIT = "%"
MOVES = "train_tokens_per_s_per_chip"
SOURCE = "device_trace"


def read(run):
    return _lib.idle_share(run)
