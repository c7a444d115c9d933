"""Device self time under the scope ``ret_state`` alone (a prefill's chunked
form; a decode step's feature maps, the sum of keys, the read of the live
rows' states, their update, the read-out and the write back: the kernel
``ret_step`` and what surrounds it) over the traced window's busy time. 0.0
where the trace has no such scope."""
from layer_metrics import _ret

LAYER = "Kernels"
UNIT = "%"
MOVES = "tpot_p50_ms"
SOURCE = "device_trace"


def read(run):
    return _ret.time_share(run, ("ret_state",))
