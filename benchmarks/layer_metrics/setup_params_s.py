"""The leg of a start that makes the weights (``_setup.py``). Serving:
``startup.params`` (``init_params``, a restore, adapters, quantisation,
placement; it ends in a ``block_until_ready`` where the journal is armed, so
the device's share of the draw is in it: ``synced`` 1). Trainer: ``state``
(``init_fn`` built, compiled or loaded, and called; not synced on the
``metrics_file`` channel, so the device's share may fall into ``restore`` or
``loop_prep``) + ``restore``. None where the program wrote no such leg."""
from layer_metrics import _setup

LAYER = "Runtime"
UNIT = "s"
MOVES = "setup_s"
SOURCE = "program_span"


def read(run):
    if run.get("kind") == "train":
        return _setup.legs_sum(run, "state", "restore")
    return _setup.legs_sum(run, "params")
