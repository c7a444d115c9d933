"""Client clock, from the instant a request was DUE (open loop) to its first
streamed token, p95 over ALL the window's requests; a failed or refused
request counts as the window's length. Until PR 39 this was the end-to-end
metric ``ttft_p95_ms``: the statistic is what it was, only its place changed.
A p95 over the 230-306 requests of a 51 s window spreads by 3.4-5.5% of
itself from the draw alone (PERF.md section 2), more than half the widest
bound the contract allows, so it is reported and held to no bound."""
from harness import percentile

LAYER = "Server front"
UNIT = "ms"
MOVES = "tpot_p50_ms"
SOURCE = "host_clock"


def read(run):
    xs = [1e3 * (r["first"] - r["due"]) if r["first"] is not None
          else 1e3 * run["window_s"] for r in run["requests"]
          if r["first"] is not None or not r["ok"]]  # a correct empty answer has none
    return percentile(xs, 95) if xs else None
