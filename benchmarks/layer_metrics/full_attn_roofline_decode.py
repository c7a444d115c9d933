"""A decode step's full attention against its roofline, in a stack that also
has window layers: the least time the chip needs for the keys and values of
the page steps the FULL layers' work list walked (``window_counts.
attn_floor_s``; ``full_pages_walked`` of the ``engine.tick`` spans) over the
device self time of the kernel ``paged_attention`` under ``attn_full``, both
for the SAME ticks (``_mla.traced_ticks``). 0.0 where no tick could be
matched; nothing to read where the program has no window layer."""
from layer_metrics import _swa

LAYER = "Kernels"
UNIT = "%"
MOVES = "tpot_p50_ms"
SOURCE = "device_trace"


def read(run):
    return _swa.roofline(run, "full")
