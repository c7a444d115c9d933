"""Device self time under any name of the table over the traced window's
busy time, as ``scoped_time_share_train``: the names' own health in a
serving trace. What is left runs under no name (until PR 29 the flush's
copies of the whole page pool, a fifth of busy time), so no other
``*_time_share_*`` can see it."""
from layer_metrics import _scopes

LAYER = "Device"
UNIT = "%"
MOVES = "tpot_p50_ms"
SOURCE = "device_trace"


def read(run):
    return _scopes.time_share(run, _scopes.TABLE)
