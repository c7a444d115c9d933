"""Mean of /v1/stats slots_busy, polled twice a second over the window, chat-steady."""
from layer_metrics import _lib

LAYER = "Scheduler"
UNIT = "slots"
MOVES = "tpot_p50_ms"
SOURCE = "program_counter"


def read(run):
    return _lib.slots_busy_mean(run)
