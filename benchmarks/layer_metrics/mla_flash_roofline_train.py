"""The three flash kernels at 192 / 128 against the chip's bfloat16 peak: the
operations they executed on the blocks their predicate keeps
(``mla_train_counts``: the steps' own ``flash_blocks_needed`` x heads x layers
x a block's products, two forwards and both backward kernels) over their
device self time inside the traced whole train steps. The kernels multiply in
float32, so the bfloat16 peak is a ceiling they cannot reach."""
import mla_train_counts
from layer_metrics import _mla, _mla_train

LAYER = "Kernels"
UNIT = "%"
MOVES = "train_tokens_per_s_per_chip"
SOURCE = "device_trace"


def read(run):
    by = _mla_train.run_seconds(run)
    blocks = _mla_train.row_median(run, "flash_blocks_needed")
    seconds = 0.0 if by is None else sum(by.get(k, 0.0) for k in _mla_train.FLASH)
    if by is None or blocks is None or not seconds:
        return None
    flops = by["steps"] * mla_train_counts.flash_flops_per_step(run["config"], blocks)
    return _mla.roofline_share(flops / run["peaks"]["bf16_flops_per_s"], seconds)
