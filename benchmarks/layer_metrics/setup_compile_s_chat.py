"""Seconds the server spent in the backend's compile (or the persistent
cache's load) of the programs it built before the window: the summed
``compile_s`` of its journal's ``jit.compile`` events stamped before
``window_wall[0]`` (``_setup.py``; the trainer's sibling is
``setup_compile_s_train``, which also counts lowering). None where the program
wrote no start-up record, or without a traced run."""
from layer_metrics import _setup

LAYER = "Runtime"
UNIT = "s"
MOVES = "setup_s"
SOURCE = "program_span"


def read(run):
    return _setup.stretch(run, "compile_s")
