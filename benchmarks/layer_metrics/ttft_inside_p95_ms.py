"""Time to first token on the server's own clock: the p95 over the window's
requests of ``first_write_s``, from the handler's entry to the flush of the
first SSE event that carried a token (``_ttft.py``). Over the requests clear
of the device profiler's capture (``_ttft.quiet``), so it is the tail of the
undisturbed server: the same run's ``ttft_client_p95_ms`` counts from the
instant a request was DUE and over the whole window, and the difference is
the client, the connect, the accept queue and the capture's stop. 0.0 on a
journal whose ``server.request`` spans lack ``first_write_s``; None only
without a traced run."""
from layer_metrics import _ttft

LAYER = "Server front"
UNIT = "ms"
MOVES = "tpot_p50_ms"
SOURCE = "program_span"


def read(run):
    return _ttft.quantile_ms(run, lambda rec: rec["first_write_s"], 95)
