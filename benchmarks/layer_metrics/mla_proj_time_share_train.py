"""Device self time under ``mla_q`` and ``mla_kv`` (latent attention's
projections in the decompressed training form: the query's one matrix and
rotation, the latent's projection, norm and rotation, the keys' and values'
decompression through ``Wkvb`` and the rotary key's broadcast over the heads;
forward, rematerialised forward and backward) over the self time of the traced
whole train steps."""
from layer_metrics import _mla_train

LAYER = "Model step"
UNIT = "%"
MOVES = "train_tokens_per_s_per_chip"
SOURCE = "device_trace"


def read(run):
    return _mla_train.time_share(run, _mla_train.PROJECTIONS)
