"""A decode step's sparse latent attention against its roofline: the least
time the chip needs for the entries the live rows SELECTED
(``dsa_counts.attn_floor_s``: the larger of bytes over the HBM peak and
operations over the bf16 peak) over the device self time under ``dsa_gather``
and ``mla_attn`` (the read of the selected entries out of the pool and the
attention over them), both for the SAME ticks (``_mla.traced_ticks``). What
the gather writes and the attention reads again is not counted: a floor. 0.0
where no tick could be matched; nothing to read where the program has no
indexer."""
import dsa_counts
from layer_metrics import _dsa, _mla

LAYER = "Kernels"
UNIT = "%"
MOVES = "tpot_p50_ms"
SOURCE = "device_trace"


def read(run):
    by = _dsa.run_seconds(run, program="paged_decode", inside_whole_runs=True)
    if by is None:
        return None
    selected = _dsa.tick_sum(run, "dsa_selected_tokens", traced_only=True)
    return _mla.roofline_share(
        dsa_counts.attn_floor_s(run["config"], selected, run["peaks"]),
        sum(by.get(name, 0.0) for name in _dsa.SPARSE_ATTENTION))
