"""Device self time under the three scopes of a retention mixer (``ret_in``:
projections, head norms, rotation, gate; ``ret_state``: a prefill's chunked
form or a decode step's feature maps, state update and read-out; ``ret_out``:
the output projection) over the traced window's busy time, prefill and decode
programs together. 0.0 where the trace has no such scope."""
from layer_metrics import _ret

LAYER = "Model step"
UNIT = "%"
MOVES = "tpot_p50_ms"
SOURCE = "device_trace"


def read(run):
    return _ret.time_share(run, _ret.RET_SCOPES)
