"""Arithmetic the per-layer readers share. A reader is one file
``layer_metrics/<name>.py`` with ``LAYER``, ``UNIT``, ``MOVES``, ``SOURCE``
and ``read(run) -> float | None``; ``run`` is the generator's run record
(see ``run.py``). A reader that finds nothing to read returns None and the
harness leaves the metric out of the line.
"""

from __future__ import annotations

from harness import percentile


def idle_share(run):
    return None if run["trace"] is None else 100.0 * run["trace"]["idle_share"]


def compiles(run):
    return float(run["compiles_in_window"])


def steady_intervals(run):
    """Flush intervals outside the profiler's window."""
    return [i for i in run["intervals"] if not i["traced"]]


def steady_tokens_per_s_per_chip(run):
    steady = steady_intervals(run)
    seconds = sum(i["t1"] - i["t0"] for i in steady)
    if not seconds:
        return None
    return sum(i["tokens"] for i in steady) / seconds / run["chips"]


def ttft_ms(run, q):
    xs = [1e3 * (r["first"] - r["due"]) for r in run["requests"]
          if r["first"] is not None]
    return percentile(xs, q) if xs else None


def tpot_samples_ms(run):
    """Per request: the time from its first event to its last, over the
    tokens that arrived after the first event."""
    return [1e3 * (r["last"] - r["first"]) / (r["n_out"] - r["n_first"])
            for r in run["requests"]
            if r["first"] is not None and r["n_out"] > r["n_first"]]


def tpot_ms(run, q):
    xs = tpot_samples_ms(run)
    return percentile(xs, q) if xs else None


def slots_busy_mean(run):
    xs = [p["slots_busy"] for p in run["polls"] if p["slots_busy"] is not None]
    return sum(xs) / len(xs) if xs else None


def pool_share(run, pages):
    """Mean over the window's polls of ``pages(poll, pages_total)`` as a
    share of the pool, in percent."""
    total = run["counters"][0].get("pages_total")
    xs = [pages(p, total) for p in run["polls"]
          if p.get("pages_free") is not None and p.get("pages_cached") is not None]
    return 100.0 * sum(xs) / len(xs) / total if xs and total else None


def prefix_hit_share(run):
    a, b = run["counters"]
    hit = b["hit_tokens"] - a["hit_tokens"]
    miss = b["miss_tokens"] - a["miss_tokens"]
    return 100.0 * hit / (hit + miss) if hit + miss else None


def preemptions(run):
    a, b = run["counters"]
    return float(b["preemptions"] - a["preemptions"])
