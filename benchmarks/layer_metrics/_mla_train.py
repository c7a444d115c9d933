"""What the readers of the Kanana-2 trainer cell share: device time of the
TRAIN step by the scopes of latent attention and of an expert layer's share
(the innermost name of ``_dsa.py``'s table, which holds every table), taken
inside the WHOLE recorded runs of ``jit_train_step`` so that time and the
count of steps cover the same work; and the medians of the window's
``metrics_file`` rows.

A program without these scopes or counters (the parent commit cannot run the
configuration at all) gives every reader here None, which leaves the metric
out of the line.
"""

from __future__ import annotations

import bisect
import functools

import reduce_trace
from harness import percentile
from layer_metrics import _dsa, _mla, _scopes

TRAIN = "jit_train_step"
EXPERT_LAYER = ("moe_router", "moe_dispatch", "moe_experts", "moe_combine", "moe_shared")
PROJECTIONS = ("mla_q", "mla_kv")
FLASH = ("flash_fwd", "flash_bwd_dq", "flash_bwd_dkv")


def seconds_by_scope(trace: dict) -> dict:
    """{innermost name: seconds of self time inside whole runs of the train
    step, mean over the chips}; ``"steps"``: those runs, mean over the chips."""
    n = len(trace["devices"])
    out: dict = {"steps": 0.0}
    for dev, events in trace["devices"].items():
        meta = trace["meta"][dev]
        runs = _mla.whole_runs(trace, dev, TRAIN)
        out["steps"] += len(runs) / n
        order = sorted(events, key=lambda e: (e[1], -e[2]))  # self_times' own order
        for (mid, start, _dur), (_, self_ps, _leaf) in zip(order, reduce_trace.self_times(events)):
            i = bisect.bisect_right(runs, (start, float("inf"))) - 1
            if i < 0 or start >= runs[i][1]:
                continue
            name = _dsa.innermost(meta.get(str(mid), ["", ""])[1])
            out[name] = out.get(name, 0.0) + self_ps / 1e12 / n
    return out


@functools.lru_cache(maxsize=2)
def _seconds_of(path: str) -> dict:
    return seconds_by_scope(_scopes._loaded(path))


def run_seconds(run: dict) -> dict | None:
    """``seconds_by_scope`` of the run's own trace; None without a trace, a
    whole step in it, or any of this file's scopes."""
    path = _scopes.trace_file(run) if run.get("trace") is not None else None
    if path is None:
        return None
    by = _seconds_of(path)
    if not by["steps"] or not any(s in by for s in EXPERT_LAYER + PROJECTIONS):
        return None
    return by


def time_share(run: dict, names) -> float | None:
    """Self time under ``names`` over ALL self time inside the whole steps,
    in percent: a share of the step."""
    by = run_seconds(run)
    if by is None:
        return None
    total = sum(v for k, v in by.items() if k != "steps")
    return 100.0 * sum(by.get(n, 0.0) for n in names) / total if total else None


def row_median(run: dict, key: str) -> float | None:
    xs = [r[key] for r in run["rows"] if r.get(key) is not None]
    return percentile(xs, 50) if xs else None


def tokens_per_step(run: dict) -> int:
    """Rows x row length of the job, from the traffic file's launch arguments."""
    args = dict(a.split("=", 1) for a in run["traffic"]["launch_args"])
    return int(args["data.batch_size"]) * int(args["data.seq_len"])
