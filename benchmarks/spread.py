#!/usr/bin/env python3
"""How widely a serving cell's end-to-end metrics spread, the way the driver
reads it, from run directories that are already there:

    python benchmarks/spread.py [--cut 30,51] benchmarks/out/runs/<cell>.s*.t0

Each ``run.json`` keeps every counted request's ``due`` and ``first``. With
``--cut`` a run is cut by ``due`` into consecutive whole sub-windows of each
length (one long run gives several windows of one server's state: an
estimate; whole runs are the proof); without it each run is one window. Per
window the metrics are read by the benchmark's own readers. Over sets of six
windows in the order given (a last set of three or more counts), as a share
of the set's median:

    iqr    third less first quartile (``statistics.quantiles(n=4)``): a bound
           over 8 x the widest of these is refused as too loose
    iqr-1  the same with the window farthest from the median left out: a
           bound under 2 x the mean of these is refused as too tight
    rng-1  the range of what is left: what a PR that changes nothing is held
           to, ``rng-1 <= bound`` (the ledger's "spread" over "bound")
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

from harness import load_module  # noqa: E402

METRICS = (("layer_metrics", "ttft_client_p95_ms"), ("layer_metrics", "ttft_p50_ms"),
           ("end_to_end", "tpot_p50_ms"))


def windows(run: dict, length: float | None) -> list[dict]:
    """The run as one window, or its whole sub-windows of ``length`` seconds
    by the instant each request was due."""
    reqs = run["requests"]
    if length is None:
        return [{"window_s": run["window_s"], "requests": reqs}]
    t0 = run.get("window_t0")
    if t0 is None:  # a record from before PR 39: the first request was due within ~0.2 s of it
        t0 = min(r["due"] for r in reqs)
    return [{"window_s": float(length),
             "requests": [r for r in reqs if i * length <= r["due"] - t0 < (i + 1) * length]}
            for i in range(int(run["window_s"] // length))]


def nearest(values: list[float]) -> list[float]:
    """The values without the one farthest from their median."""
    mid = statistics.median(values)
    far = max(range(len(values)), key=lambda i: abs(values[i] - mid))
    return [v for i, v in enumerate(values) if i != far]


def iqr_share(values: list[float]) -> float:
    q1, _, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / statistics.median(values)


def spreads(values: list[float]) -> dict:
    kept = nearest(values)
    return {"n": len(values), "median": statistics.median(values), "iqr": iqr_share(values),
            "iqr-1": iqr_share(kept),
            "rng-1": (max(kept) - min(kept)) / statistics.median(values)}


def sets_of_six(values: list[float]) -> list[list[float]]:
    sets = [values[i:i + 6] for i in range(0, len(values), 6)]
    return [s for s in sets if len(s) >= 3]


def table(runs: list[dict], lengths: list[float | None]) -> list[dict]:
    rows = []
    readers = {name: load_module(os.path.join(HERE, folder, f"{name}.py"))
               for folder, name in METRICS}
    for length in lengths:
        wins = [w for run in runs for w in windows(run, length)]
        requests = statistics.median(len(w["requests"]) for w in wins)
        for name, reader in readers.items():
            values = [reader.read(w) for w in wins]
            for k, s in enumerate(sets_of_six(values)):
                rows.append({"seconds": length or runs[0]["window_s"], "metric": name, "set": k,
                             "requests": requests, **spreads(s), "values": s})
    return rows


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--cut", default="", help="comma-separated sub-window lengths in seconds")
    ap.add_argument("run_dirs", nargs="+")
    args = ap.parse_args()
    runs = []
    for d in args.run_dirs:
        with open(os.path.join(d, "run.json")) as f:
            runs.append(json.load(f))
    lengths = [float(x) for x in args.cut.split(",") if x] or [None]
    print("seconds metric              set  n requests    median    iqr  iqr-1  rng-1  values")
    for r in table(runs, lengths):
        print(f"{r['seconds']:7.0f} {r['metric']:<18} {r['set']:>4} {r['n']:>2} {r['requests']:>8.0f} "
              f"{r['median']:>9.3f} {100 * r['iqr']:5.1f}% {100 * r['iqr-1']:5.1f}% "
              f"{100 * r['rng-1']:5.1f}%  {' '.join(f'{v:.2f}' for v in r['values'])}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
