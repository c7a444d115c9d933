"""What the readers of Trinity-Mini's cell share: device time by the kind of
an attention layer, and the server's own counts of the pages each kind's
decode kernel walked and of the window layers' page pool.

The scopes (``SWA_SCOPES`` of ``ditl_tpu/ops/names.py``; this file's copy is
the yardstick, ``tests/test_trinity_readers.py`` holds them equal) sit INSIDE
``attn_core``: to ``_scopes.py`` the time is ``attn_core``'s or the kernel
``paged_attention``'s. Here an operation whose path passes ``attn_window`` or
``attn_full`` belongs to that kind, and the kernel inside it is told from what
surrounds it (the gate's product, a prefill's masked scores):
``attn_window.kernel`` / ``attn_full.kernel``.

The counts come from the ``engine.tick`` spans of the server's journal
(``window_pages_walked``, ``full_pages_walked``, ``window_pages_live``, ...),
for the SAME ticks as the time where a roofline divides one by the other:
``_mla.traced_ticks`` / ``_mla.whole_runs``. A program without window layers
(the parent commit, every other family) writes neither scope nor count and
every reader here returns None, which leaves the metric out of the line.
"""

from __future__ import annotations

import bisect
import functools

import reduce_trace
from layer_metrics import _mla, _scopes

SWA_SCOPES = ("attn_window", "attn_full")
KERNEL = "paged_attention"


def kind_of(tf_op: str) -> str | None:
    """``attn_window`` / ``attn_full`` (``.kernel`` for the decode kernel
    inside) for an operation under one of the two scopes, else None."""
    segments = _scopes._SEPARATORS.split(_scopes._JIT_SEGMENT.sub("", tf_op))
    for scope in SWA_SCOPES:
        if scope in segments:
            return scope + (".kernel" if KERNEL in segments else "")
    return None


def seconds_by_kind(trace: dict, program: str | None = None,
                    inside_whole_runs: bool = False) -> dict:
    """{kind: seconds of self time, mean over the chips}, as
    ``_mla.seconds_by_scope`` under this file's two scopes."""
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
            kind = kind_of(tf_op)
            if kind is None:
                continue
            if runs is not None:
                i = bisect.bisect_right(runs, (start, float("inf"))) - 1
                if i < 0 or start >= runs[i][1]:
                    continue
            out[kind] = out.get(kind, 0.0) + self_ps / 1e12 / n
    return out


@functools.lru_cache(maxsize=4)
def _seconds_of(path: str, program: str | None, inside_whole_runs: bool) -> dict:
    return seconds_by_kind(_scopes._loaded(path), program, inside_whole_runs)


def run_seconds(run: dict, program: str | None = None,
                inside_whole_runs: bool = False) -> dict | None:
    """Seconds by kind of the run's own trace; None without a trace, or where
    the trace has neither scope (a program without window layers)."""
    path = _scopes.trace_file(run) if run.get("trace") is not None else None
    if path is None:
        return None
    return _seconds_of(path, program, inside_whole_runs) or None


def time_share(run: dict, scope: str) -> float | None:
    """Self time under ``scope`` (the kernel and what surrounds it, decode and
    prefill) over the trace's busy time, in percent."""
    by = run_seconds(run)
    if by is None:
        return None
    busy = run["trace"]["busy_s"]
    mine = by.get(scope, 0.0) + by.get(scope + ".kernel", 0.0)
    return 100.0 * mine / busy if busy else 0.0


def ticks(run: dict, traced_only: bool = False) -> list[dict] | None:
    """The window's decode ticks that carry this family's counts
    (``traced_only``: those whose whole run the device trace holds); None where
    no tick of the window carries them."""
    if run.get("trace") is None:
        return None
    rows = [r for r in _mla._moe.tick_rows(run) if "window_pages_walked" in r]
    if not rows:
        return None
    if traced_only:
        return [r for r in _mla.traced_ticks(run) if "window_pages_walked" in r]
    return rows


def page_size_of(run: dict) -> int:
    args = run["traffic"]["server_args"]
    return int(args[args.index("--page-size") + 1])


def roofline(run: dict, kind: str) -> float | None:
    """The least time for the page steps the traced ticks walked in ``kind``'s
    layers over the decode kernel's time in those ticks, in percent; 0.0
    where no tick could be matched."""
    import window_counts

    by = run_seconds(run, program="paged_decode", inside_whole_runs=True)
    rows = ticks(run, traced_only=True)
    if by is None or rows is None:
        return None
    steps = float(sum(r[f"{kind}_pages_walked"] for r in rows))
    return _mla.roofline_share(
        window_counts.attn_floor_s(run["config"], kind, steps, page_size_of(run), run["peaks"]),
        by.get(f"attn_{kind}.kernel", 0.0))
