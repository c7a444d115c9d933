"""Device self time under the scopes of an expert layer's share
(``moe_router``, ``moe_dispatch``, ``moe_experts``, ``moe_combine`` and the
shared expert's ``moe_shared``: all of ``models/moe.py``; forward, the
rematerialised forward and backward) over the self time of the traced whole
train steps."""
from layer_metrics import _mla_train

LAYER = "Model step"
UNIT = "%"
MOVES = "train_tokens_per_s_per_chip"
SOURCE = "device_trace"


def read(run):
    return _mla_train.time_share(run, _mla_train.EXPERT_LAYER)
