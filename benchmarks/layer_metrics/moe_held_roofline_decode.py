"""The decode program's grouped matmuls over the HELD experts against the
memory roofline: the least time HBM needs to deliver the held experts the
live rows touched (``moe_touched`` of the matched ticks, summed over steps and
layers, x ``mla_counts.held_expert_bytes``) over the device self time under
``moe_experts``, both for the SAME ticks (``_mla.traced_ticks``: the whole
recorded runs of ``jit_paged_decode``). Memory-bound: a held expert sees a
row or two a step. 0.0 where no tick could be matched."""
import mla_counts
from layer_metrics import _mla

LAYER = "Kernels"
UNIT = "%"
MOVES = "tpot_p50_ms"
SOURCE = "device_trace"


def read(run):
    by = _mla.run_seconds(run, program="paged_decode", inside_whole_runs=True)
    if by is None:
        return None
    touched = sum(r.get("moe_touched", 0) for r in _mla.traced_ticks(run))
    least_s = (touched * mla_counts.held_expert_bytes(run["config"])
               / run["peaks"]["hbm_bytes_per_s"])
    return _mla.roofline_share(least_s, by.get("moe_experts", 0.0))
