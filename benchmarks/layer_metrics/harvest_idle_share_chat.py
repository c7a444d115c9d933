"""Idle device time under the engine's ``engine.tick.harvest`` span (the
host reads a finished tick's tokens and streams them) over the traced window."""
from layer_metrics import _scopes

LAYER = "Scheduler"
UNIT = "%"
MOVES = "tpot_p50_ms"
SOURCE = "device_trace"


def read(run):
    return _scopes.gap_share(run, "engine.tick.harvest")
