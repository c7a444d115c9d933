"""Prompt tokens served from cached pages over all prompt tokens admitted in the window (predicted near 0: nothing is shared)."""
from layer_metrics import _lib

LAYER = "Cache manager"
UNIT = "%"
MOVES = "tpot_p50_ms"
SOURCE = "program_counter"


def read(run):
    return _lib.prefix_hit_share(run)
