"""What the readers of Brumby's cell share: device time by the scopes of a
power-retention mixer, and the server's own count of the rows whose state a
decode tick rewrote.

The scopes (``RET_SCOPES`` of ``ditl_tpu/ops/names.py``; this file's copy is
the yardstick, ``tests/test_retention_readers.py`` holds them equal) each sit
INSIDE a scope of ``_scopes.py``'s table: to that file the time is
``attn_qkv``'s, ``attn_core``'s, ``attn_out``'s; here the innermost of both
tables wins.

The count is the one a state-space stack gives (``_ssm.traced_ticks``: the
``engine.tick`` spans that carry ``ssm_steps`` and ``ssm_row_steps`` and hold
the middle of a whole recorded run of ``jit_paged_decode``), so that bytes and
time cover the SAME ticks.

A program without these scopes (the parent commit, every other family) gives
0.0 to every reader here that has a trace, None without one.
"""

from __future__ import annotations

import bisect
import functools
import glob
import os

import reduce_trace
from layer_metrics import _mla, _scopes, _ssm

RET_SCOPES = ("ret_in", "ret_state", "ret_out")
_ALL = _scopes.TABLE | frozenset(RET_SCOPES)


def innermost(tf_op: str) -> str | None:
    """The innermost name of either table in a scope path."""
    for segment in reversed(_scopes._SEPARATORS.split(_scopes._JIT_SEGMENT.sub("", tf_op))):
        if segment in _ALL:
            return segment
    return None


def seconds_by_scope(trace: dict, program: str | None = None,
                     inside_whole_runs: bool = False) -> dict:
    """``_mla.seconds_by_scope`` with this file's table."""
    prefix = f"jit({program})/" if program else ""
    n = len(trace["devices"])
    out: dict = {}
    for dev, events in trace["devices"].items():
        meta = trace["meta"][dev]
        runs = _mla.whole_runs(trace, dev) if inside_whole_runs else None
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
def _seconds_of(path: str, program: str | None, inside_whole_runs: bool) -> dict:
    return seconds_by_scope(_scopes._loaded(path), program, inside_whole_runs)


def run_seconds(run: dict, program: str | None = None,
                inside_whole_runs: bool = False) -> dict | None:
    """``seconds_by_scope`` of the run's own trace (possibly empty); None only
    where the run has no trace."""
    path = _scopes.trace_file(run) if run.get("trace") is not None else None
    return None if path is None else _seconds_of(path, program, inside_whole_runs)


def time_share(run: dict, names, program: str | None = None) -> float | None:
    """Self time under ``names`` over the trace's busy time, in percent; 0.0
    where the trace has none of them, None only without a trace."""
    by = run_seconds(run, program)
    if by is None:
        return None
    busy = run["trace"]["busy_s"]
    return 100.0 * sum(by.get(n, 0.0) for n in names) / busy if busy else 0.0


def window_ticks(run: dict) -> list[dict]:
    """The decode ticks of the run's whole measured window (``engine.tick``
    spans that carry ``ssm_steps``), from the journal beside the run's trace."""
    path = _scopes.trace_file(run) if run.get("trace") is not None else None
    if path is None:
        return []
    run_dir = path
    for _ in range(5):  # <run>/trace/plugins/profile/<time>/<host>.xplane.pb
        run_dir = os.path.dirname(run_dir)
    return _ssm.read_ticks(glob.glob(os.path.join(run_dir, "spans", "events-server-*.jsonl*")),
                           *run["window_wall"])
