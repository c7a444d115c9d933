"""Of the live tokens' ``T x k`` choices, the share that fell on an expert
held here (12.5 where the router is even: 16 of 128), all expert layers
together: the median of ``moe_held_assign_share`` over the window's
``metrics_file`` rows. What the held experts' grouped matmuls follow."""
from layer_metrics import _mla_train

LAYER = "Model step"
UNIT = "%"
MOVES = "train_tokens_per_s_per_chip"
SOURCE = "program_counter"


def read(run):
    share = _mla_train.row_median(run, "moe_held_assign_share")
    return None if share is None else 100.0 * share
