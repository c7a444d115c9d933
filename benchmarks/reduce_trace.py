#!/usr/bin/env python3
"""From a profiler trace to numbers: device busy intervals, idle share, time
by operation, collective time and its exposed part, the longest idle gaps.

    python benchmarks/reduce_trace.py FILE              # the reduction, as JSON
    python benchmarks/reduce_trace.py FILE --inspect    # planes, lines, names
    python benchmarks/reduce_trace.py FILE --dump OUT.json.gz [--from-s A --to-s B]

FILE is the ``.xplane.pb`` that ``jax.profiler`` writes (read with
``jax.profiler.ProfileData``, nothing else), or a dump made by ``--dump``:
the same events as plain JSON, which is what ``tests/data/`` keeps, because
a whole trace is tens of megabytes.

What a v5e trace looks like (seen in this PR's first traced runs, PERF.md
section 5): one plane per chip named ``/device:TPU:<n>``; its line ``XLA
Ops`` holds one event per executed HLO instruction, nested where an
instruction contains others (a ``while`` holds its body's); ``XLA Modules``
holds one event per program run, ``Async XLA Ops`` the copy-start/done pairs,
and ``Steps`` is empty. An operation's name is its whole instruction text
(``%fusion.1 = bf16[...] fusion(...)``); the part before `` = `` is kept as
its name and the text beside it for readers that look for a kernel. Mosaic
calls are ``custom-call``s named ``closed_call.N`` or ``checkpoint.N``: no
name of their own yet. Host threads are lines of the plane ``/host:CPU``.
Times are nanoseconds on the trace's own clock, which starts near zero;
``chip_child.py`` stamps ``benchmarks.clock`` annotations carrying the wall
clock into the host plane, and they place spans stamped with ``time.time()``
on the trace's clock.

Definitions (the yardstick; a PR that claims a gain cannot change them):

- an operation event: an event of a device plane's ``XLA Ops`` line;
- busy: the union of the operation events' intervals, per device;
- window: from the first operation's start to the last operation's end over
  all devices (so the profiler's own start and stop are outside it);
- ``busy_s``: the mean over devices of busy; idle share: 1 - busy_s/window_s;
- an operation's self time: its duration minus that of the events nested
  directly inside it; time by name sums self time over devices and divides
  by the number of devices;
- collective: an operation whose name contains all-gather, all-reduce,
  reduce-scatter, all-to-all or collective-permute; its exposed part is the
  time inside collective events during which no other leaf operation runs on
  that device;
- a gap: a maximal idle interval of device 0 inside the window, labelled
  with the shortest host span that covers its middle, else ``unattributed``.
"""

from __future__ import annotations

import gzip
import json
import re
import sys

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
COLLECTIVE = re.compile(r"all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute")
CLOCK_MARK = "benchmarks.clock"


# --------------------------------------------------------------------------
# Reading
# --------------------------------------------------------------------------


def short_name(name: str) -> str:
    """``%fusion.1 = bf16[...] fusion(...)`` -> ``fusion.1``: the trace names
    an operation by its whole HLO instruction; the part before `` = `` is its
    name in the program."""
    return name.split(" = ", 1)[0].lstrip("%")


def load_xplane(path: str, cpu_rehearsal: bool = False) -> dict:
    """{"devices": {n: [[name, start_ns, dur_ns], ...]}, "clock": [[trace_ns,
    wall_ns], ...], "long": {name: instruction text}} from an ``.xplane.pb``. ``cpu_rehearsal``: a CPU trace has no device plane; take
    the XLA CPU client's host threads as device 0, so that the rehearsal
    walks the same code. Never a measurement."""
    import jax

    data = jax.profiler.ProfileData.from_file(path)
    out = {"devices": {}, "clock": [], "long": {}}
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            for line in plane.lines:
                if line.name == OPS_LINE:
                    evs = out["devices"][m.group(1)] = []
                    for e in line.events:
                        short = short_name(e.name)
                        if short not in out["long"]:
                            # the instruction's text and the framework's
                            # name for it: where a kernel's own name shows
                            stats = " ".join(str(v) for k, v in e.stats
                                             if k in ("tf_op", "hlo_op", "name", "long_name"))
                            out["long"][short] = (e.name + " " + stats)[:400]
                        evs.append([short, e.start_ns, e.duration_ns])
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name == CLOCK_MARK:
                        wall = dict(e.stats).get("wall_ns")
                        if wall is not None:
                            out["clock"].append([e.start_ns, int(wall)])
                if cpu_rehearsal and line.name.startswith("tf_XLAPjRtCpuClient"):
                    out["devices"].setdefault("0", []).extend(
                        [e.name, e.start_ns, e.duration_ns] for e in line.events
                        if e.duration_ns > 0)
    return out


def load(path: str, cpu_rehearsal: bool = False) -> dict:
    if path.endswith(".json.gz"):
        with gzip.open(path, "rt") as f:
            return json.load(f)
    return load_xplane(path, cpu_rehearsal)


def dump(events: dict, path: str, from_s: float | None, to_s: float | None) -> None:
    """Write the events (cut to [from_s, to_s) of the trace's clock) as a
    gzipped JSON dump that ``load`` reads back."""
    lo = -1e30 if from_s is None else from_s * 1e9
    hi = 1e30 if to_s is None else to_s * 1e9
    cut = {
        "devices": {d: [e for e in evs if lo <= e[1] < hi]
                    for d, evs in events["devices"].items()},
        "clock": [c for c in events["clock"] if lo <= c[0] < hi],
    }
    kept = {e[0] for evs in cut["devices"].values() for e in evs}
    cut["long"] = {k: v for k, v in events.get("long", {}).items() if k in kept}
    with gzip.open(path, "wt") as f:
        json.dump(cut, f, separators=(",", ":"))


def inspect(path: str, top: int = 25) -> str:
    """Planes, lines, event counts and the most frequent names: what to look
    at by hand before trusting the reduction."""
    import collections

    import jax

    data = jax.profiler.ProfileData.from_file(path)
    rows = []
    for plane in data.planes:
        rows.append(f"PLANE {plane.name!r}")
        for line in plane.lines:
            names: collections.Counter = collections.Counter()
            dur: collections.Counter = collections.Counter()
            n, lo, hi = 0, None, None
            first_stats = None
            for e in line.events:
                n += 1
                names[e.name] += 1
                dur[e.name] += e.duration_ns
                lo = e.start_ns if lo is None else min(lo, e.start_ns)
                hi = max(hi or 0, e.start_ns + e.duration_ns)
                if first_stats is None:
                    first_stats = [(k, str(v)[:40]) for k, v in list(e.stats)[:8]]
                if "custom-call" in e.name.split(" = ")[0] and names[e.name] == 1:
                    rows.append(f"      CUSTOM-CALL {e.name[:500]} STATS "
                                f"{[(k, str(v)[:300]) for k, v in e.stats]}")
            span = f"{(hi - lo) / 1e9:.3f}s from {lo / 1e9:.3f}s" if n else "-"
            rows.append(f"  LINE {line.name!r}: {n} events, {span}; "
                        f"first event's stats {first_stats}")
            for name, ns in dur.most_common(top):
                rows.append(f"      {ns / 1e6:10.3f} ms {names[name]:7d} x  {name[:110]}")
    return "\n".join(rows)


# --------------------------------------------------------------------------
# Interval arithmetic
# --------------------------------------------------------------------------


def union(intervals) -> list[list[float]]:
    """Sorted, merged copy of [start, end] intervals."""
    out: list[list[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def measure(merged) -> float:
    return sum(e - s for s, e in merged)


def subtract(a, b) -> list[list[float]]:
    """The part of merged intervals ``a`` that no interval of merged ``b``
    covers."""
    out, j = [], 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append([cur, b[k][0]])
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append([cur, e])
    return out


def self_times(events) -> list[tuple[str, float, bool]]:
    """(name, self ns, is leaf) per event of one line, where events nest:
    an event's self time is its duration less its direct children's."""
    order = sorted(events, key=lambda e: (e[1], -e[2]))
    out: list[list] = []
    stack: list[tuple[int, float]] = []  # (index in out, end)
    for name, start, durn in order:
        end = start + durn
        while stack and stack[-1][1] <= start:
            stack.pop()
        if stack:
            parent = out[stack[-1][0]]
            parent[1] -= durn
            parent[2] = False
        out.append([name, float(durn), True])
        stack.append((len(out) - 1, end))
    return [(n, max(0.0, s), leaf) for n, s, leaf in out]


# --------------------------------------------------------------------------
# The reduction
# --------------------------------------------------------------------------


def reduce(events: dict, host_spans: list[dict] | None = None) -> dict:
    """The numbers the metric readers pick from. ``host_spans``: records with
    ``name``, ``t0`` (wall seconds) and ``dur_s``, e.g. the server's
    ``--trace-dir`` spans."""
    devices = {d: evs for d, evs in events["devices"].items() if evs}
    if not devices:
        raise ValueError("the trace holds no operation that ran on a device")
    lo = min(e[1] for evs in devices.values() for e in evs)
    hi = max(e[1] + e[2] for evs in devices.values() for e in evs)
    n = len(devices)
    busy = {d: union([e[1], e[1] + e[2]] for e in evs) for d, evs in devices.items()}
    by_name: dict[str, float] = {}
    coll_s = exposed_s = 0.0
    for d, evs in devices.items():
        timed = self_times(evs)
        for name, ns, _leaf in timed:
            by_name[name] = by_name.get(name, 0.0) + ns / 1e9 / n
        order = sorted(evs, key=lambda e: (e[1], -e[2]))
        leaves = [(e, t[2]) for e, t in zip(order, timed)]
        coll = union([e[1], e[1] + e[2]] for e, leaf in leaves if COLLECTIVE.search(e[0]))
        other = union([e[1], e[1] + e[2]] for e, leaf in leaves
                      if leaf and not COLLECTIVE.search(e[0]))
        coll_s += measure(coll) / 1e9 / n
        exposed_s += measure(subtract(coll, other)) / 1e9 / n
    first = sorted(devices)[0]
    gaps = subtract([[lo, hi]], busy[first])
    offset = clock_offset_ns(events["clock"])
    spans = []
    if offset is not None:
        for s in host_spans or []:
            t0 = s["t0"] * 1e9 - offset
            spans.append((s["name"], t0, t0 + s["dur_s"] * 1e9))
    labelled: dict[str, float] = {}
    longest = []
    for s, e in gaps:
        label = label_of((s + e) / 2, spans)
        labelled[label] = labelled.get(label, 0.0) + (e - s) / 1e9
        longest.append([label, (e - s) / 1e9])
    longest.sort(key=lambda g: -g[1])
    busy_s = sum(measure(b) for b in busy.values()) / 1e9 / n
    window_s = (hi - lo) / 1e9
    return {
        "devices": n,
        "window_s": window_s,
        "busy_s": busy_s,
        "idle_share": 1.0 - busy_s / window_s,
        "op_self_s": dict(sorted(by_name.items(), key=lambda kv: -kv[1])),
        "op_long": {k: v for k, v in events.get("long", {}).items() if k in by_name},
        "collective_s": coll_s,
        "collective_exposed_s": exposed_s,
        "gaps_longest": longest[:10],
        "gap_s_by_label": dict(sorted(labelled.items(), key=lambda kv: -kv[1])),
        "gap_count": len(gaps),
        "clock_marks": len(events["clock"]),
    }


def clock_offset_ns(marks) -> float | None:
    """wall_ns - trace_ns, the median over the marks (each is exact to the
    few microseconds between reading the clock and opening the annotation)."""
    if not marks:
        return None
    diffs = sorted(w - t for t, w in marks)
    return float(diffs[len(diffs) // 2])


def label_of(t: float, spans) -> str:
    best = None
    for name, s, e in spans:
        if s <= t < e and (best is None or e - s < best[1]):
            best = (name, e - s)
    return best[0] if best else "unattributed"


def breakdown(reduced: dict) -> dict:
    """The result line's ``breakdown``: the ten operations with the most
    device time and the ten longest idle gaps."""
    return {
        "device_ops": [[k, v] for k, v in list(reduced["op_self_s"].items())[:10]],
        "idle_gaps": reduced["gaps_longest"][:10],
    }


def main(argv: list[str]) -> int:
    import argparse

    ap = argparse.ArgumentParser()
    ap.add_argument("file")
    ap.add_argument("--inspect", action="store_true")
    ap.add_argument("--dump")
    ap.add_argument("--from-s", type=float)
    ap.add_argument("--to-s", type=float)
    args = ap.parse_args(argv)
    if args.inspect:
        print(inspect(args.file))
        return 0
    events = load(args.file)
    if args.dump:
        dump(events, args.dump, args.from_s, args.to_s)
        return 0
    print(json.dumps(reduce(events), indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
