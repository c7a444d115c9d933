"""Device self time under the scope ``ssm_scan`` alone (a prefill's chunked
scan; a decode step's read of the live rows' states, their update, the
read-out and the write back: the kernel ``ssd_step`` and what surrounds it)
over the traced window's busy time. 0.0 where the trace has no such scope."""
from layer_metrics import _ssm

LAYER = "Kernels"
UNIT = "%"
MOVES = "tpot_p50_ms"
SOURCE = "device_trace"


def read(run):
    return _ssm.time_share(run, ("ssm_scan",))
