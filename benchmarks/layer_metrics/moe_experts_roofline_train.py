"""The held experts' grouped matmuls against the chip's bfloat16 peak: the
operations the ``gmm`` and ``tgmm`` calls of the traced steps executed
(``mla_train_counts``: the steps' own held pairs x three matrices x four
products, the rematerialised forward among them) over the device self time
under ``moe_experts`` inside the traced whole train steps (the kernels, their
group metadata and the activation between them). Compute-bound: a held
expert's 9.4 MB of weights meet some 1,500 rows a step."""
import mla_train_counts
from layer_metrics import _mla, _mla_train

LAYER = "Kernels"
UNIT = "%"
MOVES = "train_tokens_per_s_per_chip"
SOURCE = "device_trace"


def read(run):
    by = _mla_train.run_seconds(run)
    share = _mla_train.row_median(run, "moe_held_assign_share")
    if by is None or share is None or not by.get("moe_experts"):
        return None
    pairs = mla_train_counts.held_pairs_per_step(
        run["config"], _mla_train.tokens_per_step(run), share)
    flops = by["steps"] * pairs * mla_train_counts.expert_flops_per_held_pair(run["config"])
    return _mla.roofline_share(flops / run["peaks"]["bf16_flops_per_s"], by["moe_experts"])
