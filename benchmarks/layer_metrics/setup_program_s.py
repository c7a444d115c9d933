"""The program's entry to ready (``_setup.py``). Serving: the ``startup``
span, ``serve()``'s entry to the bound port, which is ``/health``'s
``cold_start_s``. Trainer: the legs of the first ``metrics_file`` row's
``startup`` up to ``loop_prep``, ``launch.main``'s entry to the loop's first
iteration. None where the program wrote no start-up record, or without a
traced run."""
from layer_metrics import _setup

LAYER = "Runtime"
UNIT = "s"
MOVES = "setup_s"
SOURCE = "program_span"


def read(run):
    return _setup.stretch(run, "program")
