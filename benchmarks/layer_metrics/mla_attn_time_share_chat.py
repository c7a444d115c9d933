"""Device self time under the scope ``mla_attn`` inside ``jit_paged_decode``
(a decode step's attention over the latent page pool: the kernel
``mla_paged_attention`` and whatever surrounds it inside the scope) over the
traced window's busy time. 0.0 where the trace has no such scope."""
from layer_metrics import _mla

LAYER = "Kernels"
UNIT = "%"
MOVES = "tpot_p50_ms"
SOURCE = "device_trace"


def read(run):
    return _mla.time_share(run, ("mla_attn",), program="paged_decode")
