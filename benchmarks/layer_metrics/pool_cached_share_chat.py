"""Pages kept only as prefix cache (pages_cached_evictable), mean over the window's polls, as a share of the pool; nothing is shared in chat-steady, so these are finished prompts nobody asks for again."""
from layer_metrics import _lib

LAYER = "Cache manager"
UNIT = "%"
MOVES = "tpot_p50_ms"
SOURCE = "program_counter"


def read(run):
    return _lib.pool_share(run, lambda p, total: p["pages_cached"])
