"""Share of the live rows' expert choices that fell on zero-compute
(identity) experts, over the decode ticks of the measured window:
``moe_assign_zero`` over ``moe_assignments`` of the server's ``engine.tick``
spans. 256 of 768 router outputs: a third on random weights. 0.0 where no
tick counted any."""
from layer_metrics import _mla

LAYER = "Model step"
UNIT = "%"
MOVES = "tpot_p50_ms"
SOURCE = "program_counter"


def read(run):
    return _mla.assign_share(run, "moe_assign_zero")
