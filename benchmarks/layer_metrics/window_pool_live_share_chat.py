"""Pages of the WINDOW layers' pool that a row or the content cache holds
(``window_pages_live`` over ``window_pages_total`` of the ``engine.tick``
spans), mean over the window's decode ticks, in percent: what the rows'
windows and the cached last windows of the documents keep of a pool sized
apart from the full layers'. Nothing to read where no tick carries the
counts."""
from layer_metrics import _swa

LAYER = "Cache manager"
UNIT = "%"
MOVES = "tpot_p50_ms"
SOURCE = "program_counter"


def read(run):
    rows = _swa.ticks(run)
    if rows is None:
        return None
    shares = [r["window_pages_live"] / r["window_pages_total"] for r in rows
              if r.get("window_pages_total")]
    return 100.0 * sum(shares) / len(shares) if shares else 0.0
