"""Client clock, from the instant a request was DUE (open loop) to its first
streamed token, p95 over the window's requests; a failed or refused request
counts as the window's length."""
from harness import percentile

UNIT = "ms"


def read(run):
    xs = [1e3 * (r["first"] - r["due"]) if r["first"] is not None
          else 1e3 * run["window_s"] for r in run["requests"]
          if r["first"] is not None or not r["ok"]]  # a correct empty answer has none
    return percentile(xs, 95)
