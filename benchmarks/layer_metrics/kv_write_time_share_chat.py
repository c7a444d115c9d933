"""Device self time under the scope ``kv_write`` inside ``jit_paged_decode``
over the traced window's busy time: each step's write of the new K and V
into the tick's tail and, once a tick, the flush of that tail into the page
pools (``_flush_tail_into_pools``). Layout copies the compiler puts around a
write carry no scope: they are what ``scoped_time_share_chat`` leaves over."""
from layer_metrics import _scopes

LAYER = "Cache manager"
UNIT = "%"
MOVES = "tpot_p50_ms"
SOURCE = "device_trace"


def read(run):
    return _scopes.time_share(run, ("kv_write",), program="paged_decode")
