"""Share of the live rows' expert choices that fell on experts HELD here,
over the decode ticks of the measured window: ``moe_assign_held`` over
``moe_assignments`` of the server's ``engine.tick`` spans. 16 of 768 router
outputs: 2.1% on random weights, a thirty-second of what the deployment's 32
ranks would bring these experts at equal batch. 0.0 where no tick counted any."""
from layer_metrics import _mla

LAYER = "Model step"
UNIT = "%"
MOVES = "tpot_p50_ms"
SOURCE = "program_counter"


def read(run):
    return _mla.assign_share(run, "moe_assign_held")
