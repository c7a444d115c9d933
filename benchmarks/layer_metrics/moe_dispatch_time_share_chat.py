"""Device self time of what an expert layer does that is not its matmuls
(``moe_router``: the router and its softmax; ``moe_dispatch``: top-k, the sort
by expert, the gather of token rows; ``moe_combine``: rows back in token order,
weighted and summed) over the traced window's busy time."""
from layer_metrics import _moe

LAYER = "Model step"
UNIT = "%"
MOVES = "tpot_p50_ms"
SOURCE = "device_trace"


def read(run):
    return _moe.time_share(run, ("moe_router", "moe_dispatch", "moe_combine"))
