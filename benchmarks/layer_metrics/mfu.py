"""Model FLOP/s utilization: tokens per second per chip times the operations
a token's forward and backward passes require (``flops.py``; recomputation
not counted) over the chip's bf16 peak (``peaks.json``). A fair end-to-end
utilization; not a kernel's roofline share, and blind to idle time."""
import flops
from layer_metrics import _lib

LAYER = "Model step"
UNIT = "%"
MOVES = "train_tokens_per_s_per_chip"
SOURCE = "host_clock"


def read(run):
    per_chip = _lib.steady_tokens_per_s_per_chip(run)
    if per_chip is None:
        return None
    per_token = flops.train_flops_per_token(
        run["config"], run["traffic"]["attention_context_mean"])
    return 100.0 * per_chip * per_token / run["peaks"]["bf16_flops_per_s"]
