"""The legs of a start that import and parse (``_setup.py``). Serving:
``startup.imports`` (entry to parsed arguments) + ``startup.tokenizer``
(``get_tokenizer``, which imports ``transformers``). Trainer: ``config``
(``build_config``) + ``runtime`` (the trainer's own imports, ``init_runtime``,
the mesh). None where the program wrote no such leg."""
from layer_metrics import _setup

LAYER = "Runtime"
UNIT = "s"
MOVES = "setup_s"
SOURCE = "program_span"


def read(run):
    if run.get("kind") == "train":
        return _setup.legs_sum(run, "config", "runtime")
    return _setup.legs_sum(run, "imports", "tokenizer")
