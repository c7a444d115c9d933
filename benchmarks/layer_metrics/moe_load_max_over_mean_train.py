"""The busiest held expert's load over the held experts' mean load (1 when
balanced), mean over the expert layers: the median of
``moe_load_max_over_mean`` over the window's ``metrics_file`` rows. The
grouped matmul's longest group."""
from layer_metrics import _mla_train

LAYER = "Model step"
UNIT = "ratio"
MOVES = "train_tokens_per_s_per_chip"
SOURCE = "program_counter"


def read(run):
    return _mla_train.row_median(run, "moe_load_max_over_mean")
