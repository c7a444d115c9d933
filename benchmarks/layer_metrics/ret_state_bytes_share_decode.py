"""How much of a decode step's bytes the mechanism is: the live rows' states
(the server's own ``ssm_row_steps``, each row-step reading and rewriting its
state in every layer) over that plus the weights the steps read
(``retention_counts.state_bytes_share``), from the ``engine.tick`` spans of
the whole window. None without a trace; 0.0 where the journal has no such
tick (a program without a state a slot)."""
import retention_counts
from layer_metrics import _ret

LAYER = "Model step"
UNIT = "%"
MOVES = "tpot_p50_ms"
SOURCE = "program_counter"


def read(run):
    if run.get("trace") is None:
        return None
    ticks = _ret.window_ticks(run)
    return retention_counts.state_bytes_share(
        run["config"], sum(t.get("ssm_row_steps", 0) for t in ticks),
        sum(t["ssm_steps"] for t in ticks))
