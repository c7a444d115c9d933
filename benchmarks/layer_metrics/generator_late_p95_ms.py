"""How late the load generator sent: send instant minus due instant, p95.
It moves nothing; it says whether the run is valid: above a tenth of
``ttft_p50_ms`` the generator starved and the tails are its own."""
from harness import percentile

LAYER = "Server front"
UNIT = "ms"
MOVES = "tpot_p50_ms"
SOURCE = "host_clock"


def read(run):
    xs = [1e3 * (r["sent"] - r["due"]) for r in run["requests"]
          if r["sent"] is not None and r["due"] is not None]
    return percentile(xs, 95) if xs else None
