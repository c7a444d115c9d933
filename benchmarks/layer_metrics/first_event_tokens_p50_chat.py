"""Median number of tokens in a request's first streamed event, over the
window's requests that got a token (the client's record, ``n_first``): 1
when the scheduler sends the prefill's token by itself, a decode tick's
``decode_chunk`` when the token waits for the tick's harvest."""
from harness import percentile

LAYER = "Scheduler"
UNIT = "tokens"
MOVES = "tpot_p50_ms"
SOURCE = "host_clock"


def read(run):
    xs = [float(r["n_first"]) for r in run["requests"] if r["first"] is not None]
    return percentile(xs, 50) if xs else None
