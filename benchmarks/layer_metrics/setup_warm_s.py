"""Ready to the window's first instant (``_setup.py``). Serving: the end of
the ``startup`` span (the port is bound) to ``window_wall[0]``: the harness's
``/health`` poll, the warm-up requests, a closed loop's document prefills, the
pre-roll. Trainer: the ``first_flush`` leg (the step's lowering, its compile
or cache load, the steps of the first flush) and the flush-to-flush wall of
the later warm-up flushes. None where the program wrote no start-up record,
or without a traced run."""
from layer_metrics import _setup

LAYER = "Runtime"
UNIT = "s"
MOVES = "setup_s"
SOURCE = "program_span"


def read(run):
    return _setup.stretch(run, "warm")
