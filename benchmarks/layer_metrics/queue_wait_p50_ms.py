"""Median of the engine's ``engine.queue`` spans (``--trace-dir``) that began
inside the window: submit to slot admission, on the server's host clock."""
from harness import percentile

LAYER = "Scheduler"
UNIT = "ms"
MOVES = "tpot_p50_ms"
SOURCE = "program_span"


def read(run):
    lo, hi = run["window_wall"]
    xs = [1e3 * s["dur_s"] for s in run["host_spans"]
          if s["name"] == "engine.queue" and lo <= s["t0"] < hi]
    return percentile(xs, 50) if xs else None
