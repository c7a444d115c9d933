"""Device self time under the four scopes of an expert layer (``moe_router``,
``moe_dispatch``, ``moe_experts``, ``moe_combine``: all of ``models/moe.py``,
in decode and in prefill) over the traced window's busy time."""
from layer_metrics import _moe

LAYER = "Model step"
UNIT = "%"
MOVES = "tpot_p50_ms"
SOURCE = "device_trace"


def read(run):
    return _moe.time_share(run, _moe.MOE_SCOPES)
