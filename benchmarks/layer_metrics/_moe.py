"""What the readers of an expert layer share: device time by the parts of
``ditl_tpu/models/moe.py`` and the experts' load as the server counted it.

The parts are scopes INSIDE ``mlp`` (``MOE_SCOPES`` of ``ditl_tpu/ops/
names.py``; this file's copy is the yardstick, ``tests/test_moe_readers.py``
holds them equal). ``_scopes.py`` knows only the table of PR 23 and charges
an expert layer's time to ``mlp`` whole; here the innermost of either table
wins, so ``mlp`` keeps only its norm.

The load comes from the server's ``--trace-dir`` journal of the traced run:
each ``engine.tick`` span whose decode tick ran a model with experts carries
``moe_steps`` (decode steps of the tick), ``moe_assignments`` (live rows x k
x layers over those steps), ``moe_touched`` (experts with at least one live
row, summed over steps and layers) and ``moe_load_max_over_mean`` (busiest
expert over the mean, mean over layers, of the tick's counts). A program
without experts writes none of them and every reader here returns None.
"""

from __future__ import annotations

import bisect
import functools
import glob
import json
import os

import reduce_trace
from layer_metrics import _scopes

MOE_SCOPES = ("moe_router", "moe_dispatch", "moe_experts", "moe_combine")
_BOTH = _scopes.TABLE | frozenset(MOE_SCOPES)


def innermost(tf_op: str) -> str | None:
    """The innermost name of either table in an operation's scope path."""
    for segment in reversed(_scopes._SEPARATORS.split(_scopes._JIT_SEGMENT.sub("", tf_op))):
        if segment in _BOTH:
            return segment
    return None


def seconds_by_scope(trace: dict, program: str | None = None,
                     inside_runs_of: str | None = None) -> dict:
    """{name of either table: seconds of self time, mean over the chips}.
    ``program``: only operations whose path begins ``jit(<program>)/``.
    ``inside_runs_of``: only operations that start inside a WHOLE recorded run
    of that program on the ``XLA Modules`` line (``jit_paged_decode``), so
    that time and the count of runs cover the same work; the key ``"runs"``
    then holds that count."""
    prefix = f"jit({program})/" if program else ""
    n = len(trace["devices"])
    out: dict = {}
    for dev, events in trace["devices"].items():
        meta = trace["meta"][dev]
        runs = None
        if inside_runs_of:
            # A run that began before the trace or ended after it is on the
            # line clipped to the trace (seen on the chip: 0.28 s and 0.04 s
            # beside whole runs of 0.90 s), and its operations are only partly
            # there: whole runs only, which touch neither end of the events.
            lo = min(e[1] for e in events) + 1_000_000  # 1 us, in ps
            hi = max(e[1] + e[2] for e in events) - 1_000_000
            runs = sorted((s, s + d) for name, s, d in trace.get("modules", {}).get(dev, [])
                          if name == inside_runs_of and s > lo and s + d < hi)
            out["runs"] = out.get("runs", 0.0) + len(runs) / n
        order = sorted(events, key=lambda e: (e[1], -e[2]))  # self_times' own order
        for (mid, start, _dur), (_, self_ps, _leaf) in zip(order, reduce_trace.self_times(events)):
            tf_op = meta.get(str(mid), ["", ""])[1]
            if not tf_op.startswith(prefix):
                continue
            if runs is not None:
                i = bisect.bisect_right(runs, (start, float("inf"))) - 1
                if i < 0 or start >= runs[i][1]:
                    continue
            name = innermost(tf_op)
            out[name] = out.get(name, 0.0) + self_ps / 1e12 / n
    return out


@functools.lru_cache(maxsize=4)
def _seconds_of(path: str, program: str | None, inside_runs_of: str | None) -> dict:
    return seconds_by_scope(_scopes._loaded(path), program, inside_runs_of)


def run_seconds(run: dict, program: str | None = None,
                inside_runs_of: str | None = None) -> dict | None:
    """``seconds_by_scope`` of the run's own trace; None without a trace or
    without any expert scope in it."""
    path = _scopes.trace_file(run) if run.get("trace") is not None else None
    if path is None:
        return None
    by = _seconds_of(path, program, inside_runs_of)
    return by if any(s in by for s in MOE_SCOPES) else None


def time_share(run: dict, names) -> float | None:
    """Self time under ``names`` over the trace's busy time, in percent."""
    by = run_seconds(run)
    if by is None:
        return None
    return 100.0 * sum(by.get(n, 0.0) for n in names) / run["trace"]["busy_s"]


def tick_rows(run: dict) -> list[dict]:
    """The ``engine.tick`` spans of the run's measured window that carry the
    experts' counts, from the journal beside the run's trace."""
    path = _scopes.trace_file(run) if run.get("trace") is not None else None
    if path is None:
        return []
    run_dir = path
    for _ in range(5):  # <run>/trace/plugins/profile/<time>/<host>.xplane.pb
        run_dir = os.path.dirname(run_dir)
    return read_ticks(glob.glob(os.path.join(run_dir, "spans", "events-server-*.jsonl*")),
                      *run["window_wall"])


def read_ticks(paths, wall0: float, wall1: float) -> list[dict]:
    rows = []
    for p in paths:
        with open(p) as f:
            for line in f:
                try:
                    rec = json.loads(line)
                except ValueError:
                    continue
                if (rec.get("event") == "trace.span" and rec.get("name") == "engine.tick"
                        and "moe_steps" in rec and wall0 <= rec["ts"] < wall1):
                    rows.append(rec)
    return rows


def touched_mean(rows, layers: int) -> float | None:
    """Experts with at least one live row, per decode step and layer."""
    steps = sum(r["moe_steps"] for r in rows)
    return sum(r["moe_touched"] for r in rows) / (steps * layers) if steps else None
