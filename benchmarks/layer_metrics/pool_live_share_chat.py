"""Pages that hold a running request's K and V (pool - free - cached), mean of /v1/stats polled twice a second over the window, as a share of the pool: how much of what the server reserves this traffic works on."""
from layer_metrics import _lib

LAYER = "Cache manager"
UNIT = "%"
MOVES = "tpot_p50_ms"
SOURCE = "program_counter"


def read(run):
    return _lib.pool_share(run, lambda p, total: total - p["pages_free"] - p["pages_cached"])
