"""A decode step's index scores against their roofline: the least time the
chip needs for the index keys the live rows' contexts hold
(``dsa_counts.index_floor_s``: the larger of bytes over the HBM peak and
operations over the bf16 peak) over the device self time under ``dsa_index``
inside ``attn_core``, both for the SAME ticks: the whole recorded runs of
``jit_paged_decode`` and the ``engine.tick`` spans that hold them
(``_mla.traced_ticks``). Live rows only, page padding not counted. 0.0 where
no tick could be matched; nothing to read where the program has no indexer."""
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
    ctx = _dsa.tick_sum(run, "dsa_ctx_tokens", traced_only=True)
    return _mla.roofline_share(dsa_counts.index_floor_s(run["config"], ctx, run["peaks"]),
                               by.get("dsa_index.scores", 0.0))
