"""What the readers of DeepSeek-V3.2's cell share: device time by the scopes
of its sparse attention and of its shared expert.

The scopes (``DSA_SCOPES`` and ``MOE_SHARED_SCOPES`` of ``ditl_tpu/ops/
names.py``; this file's copy is the yardstick, ``tests/test_deepseek_readers.py``
holds them equal) each sit INSIDE a scope of the tables ``_scopes.py``,
``_moe.py`` and ``_mla.py`` know: to those files the time is ``attn_qkv``'s,
``attn_core``'s, ``mlp``'s; here the innermost of all four tables wins.
``dsa_index`` is two things, told apart by the scope around it: the
indexer's projections (inside ``attn_qkv``: ``dsa_index.proj``) and its
scores of a query against the cached index keys (inside ``attn_core``:
``dsa_index.scores``). The sparse attention itself is ``dsa_gather`` (the
selected entries read out of the pool) and ``mla_attn`` (the attention over
them), as ``_mla.py`` names it.

The counts come from the ``engine.tick`` spans of the server's journal
(``dsa_ctx_tokens``, ``dsa_selected_tokens``), for the SAME ticks as the time:
``_mla.traced_ticks`` / ``_mla.whole_runs``. A program without an indexer (the
parent commit) writes neither scope nor count and every reader here returns
None, which leaves the metric out of the line.
"""

from __future__ import annotations

import bisect
import functools

import reduce_trace
from layer_metrics import _mla, _scopes

DSA_SCOPES = ("dsa_index", "dsa_select", "dsa_gather")
MOE_SHARED_SCOPES = ("moe_shared",)
_ALL = _mla._ALL | frozenset(DSA_SCOPES + MOE_SHARED_SCOPES)
SELECTION = ("dsa_index.proj", "dsa_index.scores", "dsa_select", "dsa_gather")
SPARSE_ATTENTION = ("dsa_gather", "mla_attn")


def innermost(tf_op: str) -> str | None:
    """The innermost name of any table in a scope path; ``dsa_index`` with
    what it is inside of."""
    segments = _scopes._SEPARATORS.split(_scopes._JIT_SEGMENT.sub("", tf_op))
    for i in range(len(segments) - 1, -1, -1):
        if segments[i] == "dsa_index":
            return "dsa_index.scores" if "attn_core" in segments[:i] else "dsa_index.proj"
        if segments[i] in _ALL:
            return segments[i]
    return None


def seconds_by_scope(trace: dict, program: str | None = None,
                     inside_whole_runs: bool = False) -> dict:
    """``_mla.seconds_by_scope`` under this file's table."""
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
    """Seconds by scope of the run's own trace; None without a trace, or
    where the trace has none of this file's scopes (a program without them)."""
    path = _scopes.trace_file(run) if run.get("trace") is not None else None
    if path is None:
        return None
    by = _seconds_of(path, program, inside_whole_runs)
    mine = SELECTION + MOE_SHARED_SCOPES
    return by if any(name in by for name in mine) else None


def time_share(run: dict, names, program: str | None = None) -> float | None:
    """Self time under ``names`` over the trace's busy time, in percent."""
    by = run_seconds(run, program)
    if by is None:
        return None
    busy = run["trace"]["busy_s"]
    return 100.0 * sum(by.get(n, 0.0) for n in names) / busy if busy else 0.0


def tick_sum(run: dict, key: str, traced_only: bool = False) -> float | None:
    """``key`` summed over the window's decode ticks (``traced_only``: over
    the ticks whose whole run the device trace holds); None where no tick
    carries it."""
    rows = _mla.traced_ticks(run) if traced_only else _mla._moe.tick_rows(run)
    if not traced_only and not any(key in r for r in rows):
        return None
    return float(sum(r.get(key, 0) for r in rows))
