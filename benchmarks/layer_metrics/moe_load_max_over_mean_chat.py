"""The busiest expert's load over the mean load (1 when balanced, 64 when one
expert takes all), per decode tick and layer over the live rows, mean over the
window's ticks: ``moe_load_max_over_mean`` of the server's ``engine.tick``
spans. The grouped matmul's longest group, and what a trained router's skew
would move."""
from layer_metrics import _moe

LAYER = "Model step"
UNIT = "ratio"
MOVES = "tpot_p50_ms"
SOURCE = "program_counter"


def read(run):
    xs = [r["moe_load_max_over_mean"] for r in _moe.tick_rows(run)
          if r.get("moe_assignments")]
    return sum(xs) / len(xs) if xs else None
