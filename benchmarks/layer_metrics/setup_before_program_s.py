"""Process start to the program's entry (``_setup.py``): the interpreter,
``chip_child.py``'s jax import and device check, the reference check
(``setup_reference_check_s``), the program's module imports. Serving: the
``startup`` span's start on the harness's clock (``window_wall[0] - setup_s``).
Trainer: ``setup_s`` less the legs of the first ``metrics_file`` row's
``startup`` less the flush-to-flush wall of the later warm-up flushes. With
``setup_program_s`` and ``setup_warm_s`` it telescopes to ``setup_s``. None
where the program wrote no start-up record, or without a traced run."""
from layer_metrics import _setup

LAYER = "Runtime"
UNIT = "s"
MOVES = "setup_s"
SOURCE = "program_span"


def read(run):
    return _setup.stretch(run, "before_program")
