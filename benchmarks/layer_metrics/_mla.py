"""What the readers of LongCat-Flash's cell share: device time by the scopes
of a latent attention sublayer and of an expert layer's share, and the
server's own counts of what a decode tick had to read.

The scopes (``MLA_SCOPES`` and ``MOE_ZERO_SCOPES`` of ``ditl_tpu/ops/
names.py``; this file's copy is the yardstick, ``tests/test_longcat_readers.py``
holds them equal) each sit INSIDE a scope of ``_scopes.py``'s table or of
``_moe.py``'s: to those files the time is ``attn_qkv``'s, ``attn_core``'s,
``attn_out``'s, ``moe_combine``'s; here the innermost of all three tables wins.

The counts come from the server's ``--trace-dir`` journal: each ``engine.tick``
span of a decode tick carries ``decode_ctx_tokens`` (the live rows' context
lengths summed over the tick's steps), ``moe_touched`` (held experts with at
least one live row, summed over steps and layers) and ``moe_assign_held`` /
``_zero`` / ``_absent``. A roofline share divides bytes by time, so both have
to cover the SAME ticks: time is taken inside the WHOLE recorded runs of
``jit_paged_decode`` and counts from the ticks whose span holds such a run's
middle, found through the wall-clock marks ``chip_child.py`` stamps into the
trace (``moe_experts_roofline_decode`` took its counts from the whole window
and swings 70-98%: PERF.md section 7 (6), the defect not to copy).

Every reader built on this file returns a number whenever the run has a
trace, 0.0 where nothing matched, so that a traced line never lacks it (a
missing metric refuses a new cell).
"""

from __future__ import annotations

import bisect
import functools

import reduce_trace
from layer_metrics import _moe, _scopes

MLA_SCOPES = ("mla_q", "mla_kv", "mla_attn")
MOE_ZERO_SCOPES = ("moe_zero",)
_ALL = _scopes.TABLE | frozenset(_moe.MOE_SCOPES + MLA_SCOPES + MOE_ZERO_SCOPES)
DECODE = "jit_paged_decode"


def innermost(tf_op: str) -> str | None:
    """The innermost name of any of the three tables in a scope path."""
    for segment in reversed(_scopes._SEPARATORS.split(_scopes._JIT_SEGMENT.sub("", tf_op))):
        if segment in _ALL:
            return segment
    return None


def whole_runs(trace: dict, dev: str, program: str = DECODE) -> list[tuple[int, int]]:
    """(start ps, end ps) of the runs of ``program`` on the ``XLA Modules``
    line that touch neither end of the device's events (a run cut by the
    trace is there clipped, and its operations only partly)."""
    events = trace["devices"][dev]
    lo = min(e[1] for e in events) + 1_000_000  # 1 us, in ps
    hi = max(e[1] + e[2] for e in events) - 1_000_000
    return sorted((s, s + d) for name, s, d in trace.get("modules", {}).get(dev, [])
                  if name == program and s > lo and s + d < hi)


def seconds_by_scope(trace: dict, program: str | None = None,
                     inside_whole_runs: bool = False) -> dict:
    """{name of any table: seconds of self time, mean over the chips}.
    ``program``: only operations whose path begins ``jit(<program>)/``.
    ``inside_whole_runs``: only operations that start inside a whole recorded
    run of ``jit_paged_decode``."""
    prefix = f"jit({program})/" if program else ""
    n = len(trace["devices"])
    out: dict = {}
    for dev, events in trace["devices"].items():
        meta = trace["meta"][dev]
        runs = whole_runs(trace, dev) if inside_whole_runs else None
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


@functools.lru_cache(maxsize=2)
def _clock_offset_s(path: str) -> float | None:
    """wall seconds - trace seconds, from the trace's own clock marks."""
    try:
        offset = reduce_trace.clock_offset_ns(reduce_trace.load(path)["clock"])
    except Exception:  # noqa: BLE001 - a trace no mark can be read from has no offset
        return None
    return None if offset is None else offset / 1e9


def traced_ticks(run: dict) -> list[dict]:
    """The ``engine.tick`` spans (with the experts' counts) whose span holds
    the middle of a whole recorded run of ``jit_paged_decode`` on the first
    chip: the ticks whose device time ``inside_whole_runs`` measures. Empty
    where the trace has no clock mark or no whole run."""
    path = _scopes.trace_file(run) if run.get("trace") is not None else None
    if path is None:
        return []
    return match_ticks(_scopes._loaded(path), _clock_offset_s(path), _moe.tick_rows(run))


def match_ticks(trace: dict, offset_s: float | None, rows: list[dict]) -> list[dict]:
    if offset_s is None or not trace["devices"]:
        return []
    dev = sorted(trace["devices"])[0]
    spans = sorted((r["ts"], r["ts"] + r["dur_s"], i) for i, r in enumerate(rows))
    starts = [s[0] for s in spans]
    out = []
    for s_ps, e_ps in whole_runs(trace, dev):
        mid = (s_ps + e_ps) / 2e12 + offset_s
        i = bisect.bisect_right(starts, mid) - 1
        if i >= 0 and mid < spans[i][1]:
            out.append(rows[spans[i][2]])
    return out


def roofline_share(least_s: float, seconds: float) -> float:
    """Percent; 0.0 where there is no time to divide by."""
    return 100.0 * least_s / seconds if seconds > 0 else 0.0


def assign_share(run: dict, key: str) -> float | None:
    """``key`` (``moe_assign_zero``, ``moe_assign_held``) over all the live
    rows' choices, in percent, over the window's decode ticks."""
    if run.get("trace") is None:
        return None
    rows = _moe.tick_rows(run)
    total = sum(r.get("moe_assignments", 0) for r in rows)
    return 100.0 * sum(r.get(key, 0) for r in rows) / total if total else 0.0
