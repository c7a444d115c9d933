"""Share of the window's scheduler steps that harvested one decode tick
under the next one's program: of the server's ``engine.tick`` spans
(``--trace-dir``) that began inside the window and dispatched a decode
program (a child span ``engine.tick.dispatch`` carries their ``tick``),
those whose ``overlapped`` is 1, i.e. whose own program was already enqueued
on the device while the step fetched and harvested the tick before it. The
steps it leaves out of the share are the first of a busy stretch and a
speculative engine's serial probe ticks. 0.0 where no span has the attribute
(a program from before double-buffered ticks) or none dispatched; None only
without a traced run."""
import glob
import json
import os

from layer_metrics import _scopes

LAYER = "Scheduler"
UNIT = "%"
MOVES = "tpot_p50_ms"
SOURCE = "program_span"


def overlap_share(paths, wall0: float, wall1: float) -> float:
    dispatched, overlapped = set(), {}
    for p in paths:
        with open(p) as f:
            for line in f:
                try:
                    rec = json.loads(line)
                except ValueError:
                    continue
                if rec.get("event") != "trace.span":
                    continue
                if rec.get("name") == "engine.tick.dispatch":
                    dispatched.add(rec.get("tick"))
                elif rec.get("name") == "engine.tick" and wall0 <= rec["ts"] < wall1:
                    overlapped[rec.get("tick")] = rec.get("overlapped", 0)
    ticks = [t for t in overlapped if t in dispatched]
    return 100.0 * sum(overlapped[t] == 1 for t in ticks) / len(ticks) if ticks else 0.0


def read(run):
    path = _scopes.trace_file(run) if run.get("trace") is not None else None
    if path is None:
        return None
    run_dir = path
    for _ in range(5):  # <run>/trace/plugins/profile/<time>/<host>.xplane.pb
        run_dir = os.path.dirname(run_dir)
    return overlap_share(glob.glob(os.path.join(run_dir, "spans", "events-server-*.jsonl*")),
                         *run["window_wall"])
