"""Host time the step loop spent blocked on the data pipeline
(``data_wait_s`` of the metrics_file rows) over the window."""
from layer_metrics import _lib

LAYER = "Host data path"
UNIT = "%"
MOVES = "train_tokens_per_s_per_chip"
SOURCE = "program_counter"


def read(run):
    steady = _lib.steady_intervals(run)
    seconds = sum(i["t1"] - i["t0"] for i in steady)
    if not seconds:
        return None
    return 100.0 * sum(i["data_wait_s"] for i in steady) / seconds
