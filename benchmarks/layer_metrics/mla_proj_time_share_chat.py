"""Device self time under the scopes ``mla_q`` and ``mla_kv`` (latent
attention's projections: down-projection, norm, scale, up-projection or
absorption, rotation; decode and prefill) over the traced window's busy
time. 0.0 where the trace has neither."""
from layer_metrics import _mla

LAYER = "Model step"
UNIT = "%"
MOVES = "tpot_p50_ms"
SOURCE = "device_trace"


def read(run):
    return _mla.time_share(run, ("mla_q", "mla_kv"))
