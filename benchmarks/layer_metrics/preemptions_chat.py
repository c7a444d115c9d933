"""Requests preempted for pages inside the window, chat-steady."""
from layer_metrics import _lib

LAYER = "Cache manager"
UNIT = "count"
MOVES = "tpot_p50_ms"
SOURCE = "program_counter"


def read(run):
    return _lib.preemptions(run)
