"""Entries the live rows selected over the context tokens they had, summed
over the window's decode ticks and the layers (``dsa_selected_tokens`` over
``dsa_ctx_tokens`` of the ``engine.tick`` spans), in percent: ``index_topk``
over the mean context, ~6% at 2,048 of ~33,100; 100% would mean the contexts
are no longer than ``index_topk`` and the mechanism idles. Nothing to read
where no tick carries the counts."""
from layer_metrics import _dsa

LAYER = "Model step"
UNIT = "%"
MOVES = "tpot_p50_ms"
SOURCE = "program_counter"


def read(run):
    if run.get("trace") is None:
        return None
    ctx = _dsa.tick_sum(run, "dsa_ctx_tokens")
    selected = _dsa.tick_sum(run, "dsa_selected_tokens")
    if ctx is None or selected is None:
        return None
    return 100.0 * selected / ctx if ctx else 0.0
