"""Device self time under the three scopes of a state-space mixer (``ssm_in``:
the input projection, the convolution and its activation; ``ssm_scan``: a
prefill's chunked scan or a decode step's state update and read-out;
``ssm_out``: gate, norm and output projection) over the traced window's busy
time, prefill and decode programs together. 0.0 where the trace has no such
scope."""
from layer_metrics import _ssm

LAYER = "Model step"
UNIT = "%"
MOVES = "tpot_p50_ms"
SOURCE = "device_trace"


def read(run):
    return _ssm.time_share(run, _ssm.SSM_SCOPES)
