"""Decode attention over the latent page pool against its roofline: the least
time the chip needs for the entries the live rows' contexts hold
(``mla_counts.decode_attn_floor_s``: the larger of bytes over the HBM peak
and operations over the bf16 peak) over the device self time under
``mla_attn``, both for the SAME ticks: the whole recorded runs of
``jit_paged_decode`` and the ``engine.tick`` spans that hold them
(``_mla.traced_ticks``). Live rows only, page padding not counted: a floor of
bytes, so the share cannot pass 100% by over-counting. 0.0 where no tick could
be matched."""
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
    ctx = sum(r.get("decode_ctx_tokens", 0) for r in _mla.traced_ticks(run))
    return _mla.roofline_share(
        mla_counts.decode_attn_floor_s(run["config"], ctx, run["peaks"]),
        by.get("mla_attn", 0.0))
