"""A request's time to first token, leg by leg, from the server's own spans.

    python benchmarks/layer_metrics/_ttft.py RUN_DIR

prints p50 / p95 of every leg over the journal under ``RUN_DIR/spans`` (the
measured window's requests where ``RUN_DIR/run.json`` names one, those clear
of the device profiler where ``RUN_DIR/trace`` holds a capture) and, for the
slowest twentieth of requests by ``first_write_s``, each one's legs with what
its prefill queued behind and what its first token's fetch waited for.

One pass over the server's ``--trace-dir`` journal (``spans/events-server-*``)
gives one record a request: its ``server.request`` span, the ``engine.request``
whose ``parent`` that span is, and that request's ``engine.queue``, its
``engine.prefill`` of ``kind`` ``prompt`` and its first ``engine.decode``
(``first`` true), joined by ``req``. Seven instants on the server's wall clock
bound six legs:

    b0 handler entered            server.request ts
    b1 submitted to the engine    engine.queue ts
    b2 admitted to a slot         engine.queue ts + dur_s
    b3 prefill dispatch began     engine.prefill ts
    b4 prefill enqueued           engine.prefill ts + dur_s
    b5 first token delivered      first engine.decode ts + dur_s
    b6 first SSE event flushed    b0 + first_write_s

``http_in`` b0-b1 (body, JSON, template, tokeniser), ``queue`` b1-b2,
``admit`` b2-b3 (pages, the prefix match, the prefills in front of it in the
step), ``dispatch`` b3-b4 (the host's enqueue of the prefill program),
``first_wait`` b4-b5 (the device: what is left of the decode program enqueued
a step earlier, the step's prefills, the shared fetch), ``http_out`` b5-b6
(stream hand-off, detokenise, SSE write). They telescope to ``first_write_s``
by construction; a leg whose boundary the journal lacks is None.

Requests that get NO record, counted in the CLI's first line: one whose prompt
was prefilled in chunks (``kind`` ``chunk``, under ``--prefill-chunk``: the
longest prompts, so a deployment that chunks reads its p95 without them), one
resumed before its first token, one that never got a first token. Of a request
with ``n`` > 1 only the child whose ``engine.request`` the journal holds last
is read.

The device profiler of a ``--trace 1`` run delays requests (PERF.md section 6,
PR 36): ``stop_trace`` holds the server's threads while it writes what the
capture gathered (1.7-2.3 s for 3 s on a v5e host), and the burst that enters
behind it queues on itself. More than a twentieth of a window, so a p95 over
all of it is partly the capture's. ``quiet`` therefore leaves out every record
and ``engine.queue`` span that touches the capture (from the first to the last
``benchmarks.clock`` mark of the trace) or the capture's own length behind it.
``first_wait_decode_share_chat`` reads the requests INSIDE the capture, there
being no device line elsewhere; every other reader reads the quiet ones.

A program from before these attributes (the parent a new metric is first read
on) writes ``server.request`` behind the body's read and without
``first_write_s``, and ``engine.prefill`` / ``engine.decode`` without
``ahead_tokens`` / ``behind_tokens``: ``queue``, ``admit``, ``dispatch`` and
``first_wait`` read true values there, ``http_in`` what is left of it behind
the body's read, and the rest None. A reader built on this file returns None
without a traced run, and 0.0 where no request carries what it reads.
"""

from __future__ import annotations

import functools
import glob
import json
import os
import sys

if __name__ == "__main__":  # run by hand: benchmarks/ is not on the path yet
    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import reduce_trace
from harness import percentile
from layer_metrics import _scopes

LEGS = ("http_in", "queue", "admit", "dispatch", "first_wait", "http_out")
# What a slow request's row says besides its legs: of its ``server.request``,
# of its ``engine.prefill`` and of its first ``engine.decode``.
OF_SERVER = ("status", "events")
OF_PREFILL = ("tick", "bucket", "ahead", "ahead_tokens", "decode_queued")
OF_FIRST = ("behind_tokens", "shared", "fetch_wait_s")
DECODE = "jit_paged_decode"


def journal_paths(run_dir: str) -> tuple[str, ...]:
    return tuple(sorted(glob.glob(os.path.join(run_dir, "spans", "events-server-*.jsonl*"))))


@functools.lru_cache(maxsize=2)  # every reader asks for the same run's journal
def _walk(paths: tuple[str, ...]) -> tuple[list[dict], list[dict], list[float]]:
    """(one record a request, every ``engine.queue`` span, the starts of the
    ``server.request`` spans that got no record) of the journal."""
    server, request_of, queues = {}, {}, []
    by_req: dict = {}
    for p in paths:
        with open(p) as f:
            for line in f:
                try:
                    rec = json.loads(line)
                except ValueError:
                    continue
                if rec.get("event") != "trace.span":
                    continue
                name = rec.get("name")
                if name == "server.request":
                    server[rec["span"]] = rec
                elif name == "engine.request":
                    request_of[rec.get("parent")] = rec.get("req")
                elif name == "engine.queue":
                    queues.append(rec)
                    by_req.setdefault(rec.get("req"), {}).setdefault("queue", rec)
                elif name == "engine.prefill" and rec.get("kind") == "prompt":
                    by_req.setdefault(rec.get("req"), {}).setdefault("prefill", rec)
                elif name == "engine.decode" and rec.get("first"):
                    by_req.setdefault(rec.get("req"), {}).setdefault("decode", rec)
    records, dropped = [], []
    for span_id, srv in server.items():
        spans = by_req.get(request_of.get(span_id), {})
        if {"queue", "prefill", "decode"} <= spans.keys():
            records.append(_record(srv, **spans))
        else:
            dropped.append(srv["ts"])
    records.sort(key=lambda r: r["t0"])
    return records, queues, dropped


def _record(srv: dict, queue: dict, prefill: dict, decode: dict) -> dict:
    end = lambda s: s["ts"] + s["dur_s"]  # noqa: E731
    b0 = srv["ts"]
    b6 = b0 + srv["first_write_s"] if "first_write_s" in srv else None
    edges = (b0, queue["ts"], end(queue), prefill["ts"], end(prefill), end(decode), b6)
    rec = {"t0": b0, "t1": edges[5] if b6 is None else b6, "req": queue.get("req"),
           "first_write_s": srv.get("first_write_s"), "first_wait_wall": edges[4:6]}
    for leg, a, b in zip(LEGS, edges, edges[1:]):
        rec[leg] = None if b is None else b - a
    for keys, span in ((OF_SERVER, srv), (OF_PREFILL, prefill), (OF_FIRST, decode)):
        rec.update((key, span.get(key)) for key in keys)
    return rec


def requests(paths, wall0: float, wall1: float) -> list[dict]:
    """The records of the requests whose ``server.request`` began in
    [wall0, wall1), oldest first."""
    return [r for r in _walk(tuple(paths))[0] if wall0 <= r["t0"] < wall1]


def queue_spans(paths, wall0: float, wall1: float) -> list[dict]:
    """Every ``engine.queue`` span that began in [wall0, wall1): the spans
    ``queue_wait_p50_ms`` reads."""
    return [q for q in _walk(tuple(paths))[1] if wall0 <= q["ts"] < wall1]


def dropped(paths, wall0: float, wall1: float) -> int:
    """How many ``server.request`` spans that began in [wall0, wall1) got no
    record (the module's docstring says which)."""
    return sum(wall0 <= t < wall1 for t in _walk(tuple(paths))[2])


@functools.lru_cache(maxsize=2)
def _marks(path: str) -> tuple:
    """((trace ns, wall ns), ...): the trace's ``benchmarks.clock`` marks."""
    try:
        return tuple(map(tuple, reduce_trace.load(path)["clock"]))
    except Exception:  # noqa: BLE001 - a trace no mark can be read from has none
        return ()


def capture_wall(path: str | None) -> tuple[float, float] | None:
    """(start, end) of the device profiler's capture on the wall clock: the
    trace's first and last mark (a tenth of a second apart); None without."""
    walls = [w / 1e9 for _, w in _marks(path)] if path else []
    return (min(walls), max(walls)) if walls else None


def quiet(items, capture, start=lambda r: r["t0"], end=lambda r: r["t1"]) -> list:
    """``items`` (records; ``engine.queue`` spans with their own ``start`` and
    ``end``) without those that touch the capture or its length behind it."""
    if capture is None:
        return list(items)
    lo, hi = capture[0], 2 * capture[1] - capture[0]
    return [x for x in items if end(x) < lo or start(x) > hi]


def _trace_file(run: dict) -> str | None:
    return _scopes.trace_file(run) if run.get("trace") is not None else None


def run_dir_of(run: dict) -> str | None:
    """The directory of the traced run that is being read (its ``spans/`` and
    ``trace/``); None without a traced run."""
    path = _trace_file(run)
    if path is None:
        return None
    for _ in range(5):  # <run>/trace/plugins/profile/<time>/<host>.xplane.pb
        path = os.path.dirname(path)
    return path


def run_requests(run: dict, clear_of_capture: bool = True) -> list[dict] | None:
    """The window's records, the quiet ones unless told otherwise; None
    without a traced run."""
    run_dir = run_dir_of(run)
    if run_dir is None:
        return None
    records = requests(journal_paths(run_dir), *run["window_wall"])
    return quiet(records, capture_wall(_trace_file(run))) if clear_of_capture else records


def run_queue_spans(run: dict) -> list[dict] | None:
    run_dir = run_dir_of(run)
    if run_dir is None:
        return None
    return quiet(queue_spans(journal_paths(run_dir), *run["window_wall"]),
                 capture_wall(_trace_file(run)),
                 start=lambda q: q["ts"], end=lambda q: q["ts"] + q["dur_s"])


def quantile_ms(run: dict, value, q: float) -> float | None:
    """The q-th percentile, in ms, of ``value(record)`` (seconds) over the
    window's quiet requests for which it is not None; 0.0 where there is none."""
    records = run_requests(run)
    if records is None:
        return None
    xs = [1e3 * x for x in map(value, records) if x is not None]
    return percentile(xs, q) if xs else 0.0


def decode_share(records, decode_runs, lo_s: float, hi_s: float, offset_s: float) -> float:
    """Percent of the requests' ``first_wait`` intervals during which a run of
    the decode program held the device: over the records whose whole interval
    lies inside [lo_s, hi_s] of the trace's clock (wall - ``offset_s``), the
    overlap with ``decode_runs`` ((start s, end s) on that clock), summed,
    over the intervals' summed length. 0.0 where none lies inside."""
    inside = overlap = 0.0
    for r in records:
        a, b = (t - offset_s for t in r["first_wait_wall"])
        if not lo_s <= a <= b <= hi_s:
            continue
        inside += b - a
        overlap += sum(max(0.0, min(b, e) - max(a, s)) for s, e in decode_runs)
    return 100.0 * overlap / inside if inside > 0 else 0.0


def run_decode_share(run: dict) -> float | None:
    """``decode_share`` of the run's own journal and trace (the first chip's
    ``XLA Modules`` line, laid on the wall clock through the trace's
    ``benchmarks.clock`` marks, as ``_mla.traced_ticks`` does). None without
    a traced run, and where the trace has no mark or no device event: nothing
    was measured then, and with ``better: lower`` a 0.0 would read as the best."""
    records = run_requests(run, clear_of_capture=False)
    if records is None:
        return None
    path = _trace_file(run)
    trace, offset_ns = _scopes._loaded(path), reduce_trace.clock_offset_ns(_marks(path))
    if offset_ns is None or not trace["devices"]:
        return None
    dev = sorted(trace["devices"])[0]
    events = trace["devices"][dev]
    lo = min(e[1] for e in events) / 1e12
    hi = max(e[1] + e[2] for e in events) / 1e12
    runs = [(s / 1e12, (s + d) / 1e12)
            for name, s, d in trace.get("modules", {}).get(dev, []) if name == DECODE]
    return decode_share(records, runs, lo, hi, offset_ns / 1e9)


def table(records: list[dict], left_out: str = "") -> str:
    """p50 / p95 of every leg, then the slowest twentieth by ``first_write_s``:
    beside the total the response's ``status`` and ``events``, beside the
    legs what ``first_wait`` was spent behind."""
    ms = lambda x: "      -" if x is None else f"{1e3 * x:7.1f}"  # noqa: E731
    rows = [f"{len(records)} requests with a prompt prefill and a first token{left_out}",
            f"{'leg':14s} {'p50 ms':>8s} {'p95 ms':>8s} {'n':>5s}"]
    for leg in LEGS + ("first_write_s",):
        xs = [r[leg] for r in records if r[leg] is not None]
        rows.append(f"{leg:14s} {ms(percentile(xs, 50) if xs else None):>8s} "
                    f"{ms(percentile(xs, 95) if xs else None):>8s} {len(xs):5d}")
    stamped = sorted((r for r in records if r["first_write_s"] is not None),
                     key=lambda r: -r["first_write_s"])
    slow = stamped[:max(1, len(stamped) // 20)] if stamped else []
    if slow:
        rows.append("slowest twentieth by first_write_s (ms; tokens; fetch_wait in ms):")
        rows.append(f"{'req':>6s} {'total':>7s} {'status':>6s} {'events':>6s} "
                    + " ".join(f"{leg:>10s}" for leg in LEGS) + "  "
                    + " ".join(f"{key.removesuffix('_s'):>{max(6, len(key))}s}"
                               for key in OF_PREFILL + OF_FIRST))
    for r in slow:
        queued = [r[key] for key in OF_PREFILL + OF_FIRST[:-1]] + [
            None if r["fetch_wait_s"] is None else round(1e3 * r["fetch_wait_s"], 1)]
        rows.append(f"{r['req']!s:>6s} {ms(r['first_write_s'])} {r['status']!s:>6s} "
                    f"{r['events']!s:>6s} " + " ".join(f"{ms(r[leg]):>10s}" for leg in LEGS)
                    + "  " + " ".join(f"{v!s:>{max(6, len(key))}s}"
                                      for key, v in zip(OF_PREFILL + OF_FIRST, queued)))
    return "\n".join(rows)


def main(argv: list[str]) -> int:
    if len(argv) != 1:
        print(__doc__.split("\n\n")[1], file=sys.stderr)
        return 2
    window = (float("-inf"), float("inf"))
    try:  # a run of the benchmark: its measured window, so not the warm-up
        with open(os.path.join(argv[0], "run.json")) as f:
            window = tuple(json.load(f)["window_wall"])
    except (OSError, ValueError, KeyError):
        pass
    paths = journal_paths(argv[0])
    found = glob.glob(os.path.join(argv[0], "trace", "plugins", "profile", "*", "*.xplane.pb"))
    records = requests(paths, *window)
    kept = quiet(records, capture_wall(max(found, key=os.path.getmtime) if found else None))
    print(table(kept, f"; left out: {dropped(paths, *window)} without one, "
                      f"{len(records) - len(kept)} at the device profiler's capture"))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
