"""Programs built before the window that the persistent compile cache did not
hold (``_setup.py``). Serving: the journal's ``jit.compile`` events before
``window_wall[0]`` whose ``cache`` is ``miss``. Trainer:
``compile_miss_count_cum`` of the ``metrics_file`` row of the flush the window
opens at. 0 on a warm run; what explains a ``setup_s`` several times the
median. None where the program counts no misses."""
from layer_metrics import _setup

LAYER = "Runtime"
UNIT = "count"
MOVES = "setup_s"
SOURCE = "program_counter"


def read(run):
    n = _setup.stretch(run, "cache_misses")
    return None if n is None else float(n)
