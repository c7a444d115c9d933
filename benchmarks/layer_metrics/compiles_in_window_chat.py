"""Programs added to the persistent compile cache inside the window; must read 0."""
from layer_metrics import _lib

LAYER = "Runtime"
UNIT = "count"
MOVES = "tpot_p50_ms"
SOURCE = "program_counter"


def read(run):
    return _lib.compiles(run)
