#!/usr/bin/env python3
"""Find the knee of an open-loop cell: the highest of a few fixed rates at
which the traffic file's share of requests meets both of its limits and no
backlog grows. Run once when a cell is defined (or by a later benchmark PR
that has to find a moved knee again); the cell then runs at four fifths of
the knee, fixed in its traffic file.

    python benchmarks/sweep.py --workload qwen2-0.5b.chat-steady --seed 101 \
        --seconds 30 --rates 4,8,12,16,20,24

One run of ``run.py`` per rate (``--traffic-set rate_per_s=R``), each a new
server process; one table row per rate on stdout.
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

import manifest as manifest_mod  # noqa: E402
from harness import OUT, percentile  # noqa: E402


def attainment(run: dict, limits: dict) -> dict:
    reqs = run["requests"]
    window = run["window_s"]
    ttft = [1e3 * (r["first"] - r["due"]) if r["first"] is not None else 1e3 * window
            for r in reqs]
    tpot = [1e3 * (r["last"] - r["first"]) / (r["n_out"] - r["n_first"])
            if r["first"] is not None and r["n_out"] > r["n_first"] else 0.0 for r in reqs]
    ok = sum(1 for a, b, r in zip(ttft, tpot, reqs)
             if a <= limits["ttft_ms"] and b <= limits["tpot_ms"]
             and r["status"] == 200 and r["finished"])
    t0 = min(r["due"] for r in reqs)
    early = [a for a, r in zip(ttft, reqs) if r["due"] - t0 < window / 3]
    late = [a for a, r in zip(ttft, reqs) if r["due"] - t0 >= 2 * window / 3]
    return {"requests": len(reqs), "attained": ok / len(reqs),
            "ttft_p50": percentile(ttft, 50), "ttft_p95": percentile(ttft, 95),
            "tpot_p50": percentile(tpot, 50), "tpot_p95": percentile(tpot, 95),
            "ttft_p50_first_third": percentile(early, 50),
            "ttft_p50_last_third": percentile(late, 50),
            "out_tokens_per_s": sum(r["n_out"] for r in reqs) / window}


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", required=True, help="comma-separated requests per second")
    args = ap.parse_args()
    cell = manifest_mod.cell(manifest_mod.load(), args.workload)
    with open(manifest_mod.traffic_path(cell["traffic"])) as f:
        limits = json.load(f)["limits"]
    print(f"limits {limits}", flush=True)
    for i, rate in enumerate(float(r) for r in args.rates.split(",")):
        seed = args.seed + i
        p = subprocess.run(
            [sys.executable, os.path.join(HERE, "run.py"), "--workload", args.workload,
             "--seed", str(seed), "--seconds", str(args.seconds), "--trace", "0",
             "--traffic-set", f"rate_per_s={rate}"],
            stdout=subprocess.PIPE, stderr=subprocess.DEVNULL, text=True)
        if p.returncode != 0:
            print(json.dumps({"rate_per_s": rate, "rc": p.returncode}), flush=True)
            continue
        with open(os.path.join(OUT, "runs", f"{args.workload}.s{seed}.t0", "run.json")) as f:
            run = json.load(f)
        row = {"rate_per_s": rate, "seed": seed, **attainment(run, limits),
               "failed": run["failed"], "setup_s": run["setup_s"],
               # a run that reads far off its neighbours: did it compile?
               "compiles_in_window": run["compiles_in_window"]}
        print(json.dumps({k: round(v, 3) if isinstance(v, float) else v
                          for k, v in row.items()}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
