"""Share of the traced window in which no operation ran on the device, chat-steady."""
from layer_metrics import _lib

LAYER = "Device"
UNIT = "%"
MOVES = "tpot_p50_ms"
SOURCE = "device_trace"


def read(run):
    return _lib.idle_share(run)
