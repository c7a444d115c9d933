"""Page steps a window layer's decode kernel walks over what a full layer's
walks for the same rows, summed over the window's decode ticks
(``window_pages_walked`` over ``full_pages_walked`` of the ``engine.tick``
spans), in percent: 8 or 9 pages of ~130 at 33,000 tokens of context and a
window of 2,048, ~7%; 100% would mean no context is longer than the window and
the mechanism idles. Nothing to read where no tick carries the counts."""
from layer_metrics import _swa

LAYER = "Model step"
UNIT = "%"
MOVES = "tpot_p50_ms"
SOURCE = "program_counter"


def read(run):
    rows = _swa.ticks(run)
    if rows is None:
        return None
    full = sum(r["full_pages_walked"] for r in rows)
    return 100.0 * sum(r["window_pages_walked"] for r in rows) / full if full else 0.0
