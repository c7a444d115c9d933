"""``setup_s`` leg by leg, from the programs' own start-up records.

    python benchmarks/layer_metrics/_setup.py RUN_DIR

prints, for the run whose ``run.json`` lies in RUN_DIR, every leg of the
program's start beside ``setup_s``, the programs built before the window with
their seconds and whether the persistent cache held them, and what is left.

Both programs keep one start-up clock (``telemetry/tracing.py``
``StartupRecorder``): contiguous legs from the program's entry on.

The server writes them to its ``--trace-dir`` journal
(``RUN_DIR/spans/events-server-*``): a span ``startup`` from ``serve()``'s entry
to the bound port (``/health``'s ``cold_start_s``), its children
``startup.imports`` ``.runtime`` ``.tokenizer`` ``.params`` ``.engine``
``.listen``, and one ``jit.compile`` event a program with ``compile_s`` and
``cache`` (``hit`` with ``retrieval_s``, ``miss``, ``off``). They lie on the
wall clock; the harness's clock starts at ``window_wall[0] - setup_s`` of it.

The trainer writes them to the ``metrics_file`` the benchmark passes
(``RUN_DIR/metrics.jsonl``): the first row's ``startup`` (``entry_wall``,
``legs``: ``config`` ``runtime`` ``data`` ``state`` ``restore`` ``loop_prep``
``first_flush``), and on every row ``compile_count_cum`` / ``compile_s_cum`` /
``compile_miss_count_cum``. ``first_flush`` ends in the first flush's sync; the
window opens at flush ``warmup_flushes``, so the flush-to-flush wall of the
flushes between (``flush_step_s`` times the steps flushed) is warm-up too.

Three stretches telescope to ``setup_s``:

    before_program  process start to the program's entry: the interpreter,
                    ``chip_child.py``'s jax import and device check, the
                    reference check (``reference.seconds``), the program's
                    module imports
    program         entry to ready: the server listens; the trainer enters
                    its loop
    warm            ready to the window's first instant: warm-up requests,
                    the documents' prefills, pre-roll; the trainer's first
                    flush (the step's compile or cache load) and the later
                    warm-up flushes

For the trainer ``before_program`` is what ``setup_s`` leaves over the other
two; for the server all three are placed by ``window_wall``. ``of(run)``
gives them with the legs; None where the program wrote no start-up record (a
parent from before them) or the run was not traced.
"""

from __future__ import annotations

import functools
import json
import os
import sys

if __name__ == "__main__":  # run by hand: benchmarks/ is not on the path yet
    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from layer_metrics import _ttft

SERVE_LEGS = ("imports", "runtime", "tokenizer", "params", "engine", "listen")
TRAIN_LEGS = ("config", "runtime", "data", "state", "restore", "loop_prep", "first_flush")
TOLERANCE_S = 0.5  # before_program + program + warm against setup_s


@functools.lru_cache(maxsize=2)  # every reader asks for the same run's journal
def _journal(paths: tuple[str, ...]) -> tuple[dict | None, dict, list[dict]]:
    """(the ``startup`` span, seconds by leg, the ``jit.compile`` events) of
    the first process of the journal that wrote a ``startup`` span."""
    parent, children, compiles = None, [], []
    for p in paths:
        with open(p) as f:
            for line in f:
                try:
                    rec = json.loads(line)
                except ValueError:
                    continue
                name = rec.get("name", "")
                if rec.get("event") == "jit.compile":
                    compiles.append(rec)
                elif rec.get("event") != "trace.span":
                    continue
                elif name == "startup":
                    parent = parent or rec
                elif name.startswith("startup."):
                    children.append(rec)
    if parent is None:
        return None, {}, []
    legs = {c["name"][len("startup."):]: c["dur_s"] for c in children
            if c.get("parent") == parent["span"]}
    own = sorted((c for c in compiles if c.get("pid") == parent.get("pid")),
                 key=lambda c: c["ts"])
    return parent, legs, own


def serve_start(run: dict, run_dir: str) -> dict | None:
    parent, legs, compiles = _journal(_ttft.journal_paths(run_dir))
    if parent is None:
        return None
    window0 = run["window_wall"][0]
    origin = window0 - run["setup_s"]  # the harness's start on the wall clock
    ready = parent["ts"] + parent["dur_s"]
    built = [c for c in compiles if c["ts"] < window0]
    return {
        "kind": "serve", "setup_s": run["setup_s"], "legs": legs,
        "before_program": parent["ts"] - origin,
        "program": parent["dur_s"],
        "warm": window0 - ready,
        "compile_s": sum(c["compile_s"] for c in built),
        "cache_misses": sum(c.get("cache") == "miss" for c in built),
        "programs": [(c["program"], c["compile_s"], c.get("cache"), c.get("retrieval_s"))
                     for c in built],
        "reference_s": reference_s(run),
        # how long after the port was bound the harness's /health poll answered
        "health_lag_s": (run["health_s"] - (ready - origin)) if "health_s" in run else None,
    }


def metrics_rows(run_dir: str) -> list[dict]:
    try:
        with open(os.path.join(run_dir, "metrics.jsonl")) as f:
            return [json.loads(ln) for ln in f if ln.strip()]
    except (OSError, ValueError):
        return []


def train_start(run: dict, run_dir: str) -> dict | None:
    rows = metrics_rows(run_dir)
    first = next((r for r in rows if "startup" in r), None)
    if first is None:
        return None
    legs = dict(first["startup"]["legs"])
    # The flushes after the first, up to the one the window opens at.
    flushes = [r for r in rows if "flush_step_s" in r][:run["traffic"]["warmup_flushes"]]
    later = sum(b["flush_step_s"] * (b["step"] - a["step"])
                for a, b in zip(flushes, flushes[1:]))
    program = sum(v for k, v in legs.items() if k != "first_flush")
    warm = legs.get("first_flush", 0.0) + later
    opened = flushes[-1] if flushes else first
    return {
        "kind": "train", "setup_s": run["setup_s"], "legs": legs,
        "before_program": run["setup_s"] - program - warm,
        "program": program,
        "warm": warm,
        "later_flushes_s": later,
        "compile_s": opened.get("compile_s_cum"),
        "cache_misses": opened.get("compile_miss_count_cum"),
        "programs": opened.get("compile_count_cum"),
        "reference_s": reference_s(run),
        "health_lag_s": None,
    }


def reference_s(run: dict) -> float | None:
    return (run.get("reference") or {}).get("seconds")


def start(run: dict, run_dir: str) -> dict | None:
    if run.get("kind") == "train":
        return train_start(run, run_dir)
    return serve_start(run, run_dir)


def of(run: dict) -> dict | None:
    """``start`` of the traced run that is being read; None without one."""
    run_dir = _ttft.run_dir_of(run)
    return None if run_dir is None else start(run, run_dir)


def stretch(run: dict, key: str) -> float | None:
    s = of(run)
    return None if s is None else s[key]


def legs_sum(run: dict, *names: str) -> float | None:
    """The summed seconds of ``names`` among the start's legs; None where the
    program wrote none of them."""
    s = of(run)
    if s is None or not any(n in s["legs"] for n in names):
        return None
    return sum(s["legs"].get(n, 0.0) for n in names)


def difference(s: dict) -> float:
    return s["before_program"] + s["program"] + s["warm"] - s["setup_s"]


def table(s: dict) -> str:
    rows = [f"{'setup_s':24s} {s['setup_s']:10.3f}",
            f"{'  before_program':24s} {s['before_program']:10.3f}"]
    if s["reference_s"] is not None:
        rows += [f"{'    reference check':24s} {s['reference_s']:10.3f}",
                 f"{'    interpreter, imports':24s} "
                 f"{s['before_program'] - s['reference_s']:10.3f}"]
    rows.append(f"{'  program':24s} {s['program']:10.3f}")
    order = SERVE_LEGS if s["kind"] == "serve" else TRAIN_LEGS
    names = [n for n in order if n in s["legs"]] + sorted(set(s["legs"]) - set(order))
    for n in names:
        if n == "first_flush":
            continue
        rows.append(f"{'    ' + n:24s} {s['legs'][n]:10.3f}")
    rows.append(f"{'  warm':24s} {s['warm']:10.3f}")
    if s["kind"] == "train":
        rows += [f"{'    first_flush':24s} {s['legs'].get('first_flush', 0.0):10.3f}",
                 f"{'    later warm-up flushes':24s} {s['later_flushes_s']:10.3f}"]
    diff = difference(s)
    rows.append(f"{'  difference':24s} {diff:10.3f}"
                + ("" if abs(diff) <= TOLERANCE_S else f"  OVER {TOLERANCE_S} s"))
    if s["health_lag_s"] is not None:
        rows.append(f"{'  /health seen after':24s} {s['health_lag_s']:10.3f}")
    if s["kind"] == "train":
        rows.append(f"programs built before the window: {s['programs']}, "
                    f"{s['compile_s']} s, {s['cache_misses']} not in the cache")
    else:
        rows.append(f"programs built before the window: {len(s['programs'])}, "
                    f"{s['compile_s']:.3f} s, {s['cache_misses']} not in the cache")
        for program, seconds, cache, retrieval in s["programs"]:
            rows.append(f"  {seconds:9.3f} s  {cache or '-':4s} "
                        + (f"(read in {retrieval:.3f} s)  " if retrieval is not None else "")
                        + program)
    return "\n".join(rows)


def main(argv: list[str]) -> int:
    if len(argv) != 1:
        print(__doc__.split("\n\n")[1], file=sys.stderr)
        return 2
    try:
        with open(os.path.join(argv[0], "run.json")) as f:
            run = json.load(f)
    except (OSError, ValueError) as e:
        print(f"no run record in {argv[0]}: {e}", file=sys.stderr)
        return 2
    s = start(run, argv[0])
    if s is None:
        print("the program wrote no start-up record (a --trace 0 serving run, or a "
              "program from before the recorder)", file=sys.stderr)
        return 1
    print(table(s))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
