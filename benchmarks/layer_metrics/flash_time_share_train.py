"""Device self time inside the three flash attention kernels (``flash_fwd``,
the rematerialised forward included, ``flash_bwd_dq``, ``flash_bwd_dkv``) over
the traced window's busy time; mean over the chips."""
from layer_metrics import _scopes

LAYER = "Kernels"
UNIT = "%"
MOVES = "train_tokens_per_s_per_chip"
SOURCE = "device_trace"


def read(run):
    return _scopes.time_share(run, ("flash_fwd", "flash_bwd_dq", "flash_bwd_dkv"))
