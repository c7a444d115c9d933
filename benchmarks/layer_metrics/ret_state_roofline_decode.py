"""A decode step's state update against its roofline: the least time the chip
needs to READ the live rows' states once (``retention_counts.
decode_state_floor_s``: the larger of bytes over the HBM peak and operations
over the bf16 peak) over the device self time under ``ret_state``, both for
the SAME ticks: the whole recorded runs of ``jit_paged_decode`` and the
``engine.tick`` spans that hold them (``_ssm.traced_ticks``). The write is
not owed every step (an exact step may fold several tokens into the state
together), so a kernel that rewrites the state every step reads at most 50%.
Live rows only: a floor of bytes, so the share cannot pass 100% by
over-counting. 0.0 where no tick could be matched."""
import retention_counts
from layer_metrics import _mla, _ret, _ssm

LAYER = "Kernels"
UNIT = "%"
MOVES = "tpot_p50_ms"
SOURCE = "device_trace"


def read(run):
    by = _ret.run_seconds(run, program="paged_decode", inside_whole_runs=True)
    if by is None:
        return None
    row_steps = sum(r.get("ssm_row_steps", 0) for r in _ssm.traced_ticks(run))
    return _mla.roofline_share(
        retention_counts.decode_state_floor_s(run["config"], row_steps, run["peaks"]),
        by.get("ret_state", 0.0))
