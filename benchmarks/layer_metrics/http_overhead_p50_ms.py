"""What the HTTP front adds around the engine: the median over the window's
requests of ``http_in + http_out`` (``_ttft.py``): from the handler's entry
to the submit, which is the start of the request's ``engine.queue`` span
(body, JSON, template, tokeniser), and from the first token's delivery to
its SSE event's flush (stream hand-off, detokenise, the write), on the
server's own clock. Over the requests clear of the device profiler's capture
(``_ttft.quiet``). 0.0 on a journal whose ``server.request`` spans lack
``first_write_s`` (a program from before it); None only without a traced
run."""
from layer_metrics import _ttft

LAYER = "Server front"
UNIT = "ms"
MOVES = "tpot_p50_ms"
SOURCE = "program_span"


def overhead_s(rec):
    return None if rec["http_out"] is None else rec["http_in"] + rec["http_out"]


def read(run):
    return _ttft.quantile_ms(run, overhead_s, 50)
