#!/usr/bin/env python3
"""The benchmark's one command.

    python benchmarks/run.py --workload NAME --seed N --seconds S --trace 0|1

runs one cell of ``BENCHMARK.json`` and prints, as the last line of its
standard output, one JSON object with ``correct``, ``attempted``, ``failed``,
``metrics`` and ``device`` (and ``breakdown`` with ``--trace 1``). Everything
else goes to stderr or under ``benchmarks/out/``.

Driven by data: the cell names a configuration (its file is in the manifest)
and a traffic mix (``traffic/<name>.json``); the traffic file names its
generator (``generators/<name>.py``, ``run(ctx) -> run record``); each metric
is one reader (``end_to_end/<name>.py`` or ``layer_metrics/<name>.py``,
``read(run)``); each plain reference is ``reference/<name>.py``. A later PR
adds files and manifest entries and edits nothing that is there.

This process never initialises a JAX backend: the chip belongs to the child
(``chip_child.py``), and the device in the result line is what the child
reported. Without the cell's chips it exits non-zero and prints no result.

``--rehearse`` runs the same commands on the CPU at the tiny sizes of
``rehearsal.json`` to find wrong paths and control flow here, and can never
print a result line.
"""

from __future__ import annotations

import argparse
import importlib
import json
import os
import shutil
import sys
import time
import types

T_START = time.monotonic()
HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, HERE)

import harness  # noqa: E402
import manifest as manifest_mod  # noqa: E402
from harness import BenchFailure, NoDevice, log  # noqa: E402

SETUP_TIMEOUT_S = 1100.0


def peaks_for(kind: str, rehearse: bool) -> dict:
    with open(os.path.join(HERE, "peaks.json")) as f:
        peaks = json.load(f)
    row = peaks.get(kind.lower().strip())
    if row is None:
        if rehearse:
            return {"bf16_flops_per_s": float("nan"), "hbm_bytes_per_s": float("nan")}
        raise BenchFailure(f"device_kind {kind!r} is missing from benchmarks/peaks.json")
    return row


def reduce_trace_file(path: str, device: dict, host_spans=None) -> dict:
    import reduce_trace

    events = reduce_trace.load(path, cpu_rehearsal=device["platform"] == "cpu")
    try:
        reduced = reduce_trace.reduce(events, host_spans)
    except ValueError as e:
        raise BenchFailure(str(e)) from None
    if device["platform"] != "cpu" and reduced["devices"] != device["count"]:
        raise BenchFailure(f"the trace holds {reduced['devices']} device planes, "
                           f"the run had {device['count']} chips")
    return reduced


def main(argv: list[str]) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), required=True)
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--traffic-set", action="append", default=[], metavar="KEY=NUMBER",
                    help="override a number of the traffic file: only for the sweep "
                    "that finds a knee when a cell is defined (sweep.py); the driver "
                    "never passes it, and a cell's rate is the one in its file")
    args = ap.parse_args(argv)

    if not os.path.isdir(os.path.join(ROOT, "ditl_tpu")):
        log("the ditl_tpu package is not beside benchmarks/: the benchmark "
            "drives the repository, it is not a program of its own")
        return 2
    manifest = manifest_mod.load()
    problems = manifest_mod.validate(manifest)
    if problems:
        for p in problems:
            log(f"BENCHMARK.json: {p}")
        return 2
    cell = manifest_mod.cell(manifest, args.workload)
    config_path = os.path.join(ROOT, manifest_mod.config_entry(manifest, cell["config"])["file"])
    with open(config_path) as f:
        config = json.load(f)
    with open(manifest_mod.traffic_path(cell["traffic"])) as f:
        traffic = json.load(f)
    for item in args.traffic_set:
        key, _, value = item.partition("=")
        traffic[key] = float(value)
    rehearsal = None
    if args.rehearse:
        with open(os.path.join(HERE, "rehearsal.json")) as f:
            rehearsal = json.load(f)
        if "rate_per_s" in traffic:
            traffic["rate_per_s"] = rehearsal["rate_per_s"]

    run_dir = os.path.join(harness.OUT, "runs",
                           f"{args.workload}.s{args.seed}.t{args.trace}"
                           + (".rehearsal" if args.rehearse else ""))
    shutil.rmtree(run_dir, ignore_errors=True)
    os.makedirs(run_dir)
    ctx = types.SimpleNamespace(
        workload=args.workload, config=config, config_path=config_path,
        traffic=traffic, seed=args.seed, seconds=args.seconds,
        trace=bool(args.trace), chips=cell["chips"], run_dir=run_dir,
        out_dir=harness.OUT, rehearsal=rehearsal, t_start=T_START,
        setup_timeout_s=SETUP_TIMEOUT_S,
        cache_entries=harness.compile_cache_entries,
        reduce_trace=reduce_trace_file,
    )
    generator = importlib.import_module(f"generators.{traffic['generator']}")
    try:
        run = generator.run(ctx)
        run.update(config=config, traffic=traffic, workload=args.workload,
                   peaks=peaks_for(run["device"]["kind"], args.rehearse))
        with open(os.path.join(run_dir, "run.json"), "w") as f:
            json.dump(run, f, default=str)
        section = "per_layer" if args.trace else "end_to_end"
        metrics = {}
        for m in manifest_mod.metrics_for(manifest, section, args.workload):
            value = harness.load_module(
                manifest_mod.reader_path(section, m["name"])).read(run)
            if value is None:
                if section == "end_to_end":
                    raise BenchFailure(f"end-to-end metric {m['name']} has no value")
                log(f"{m['name']}: nothing to read, left out")
                continue
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    except NoDevice as e:
        log(f"NO DEVICE: {e}")
        return harness.NO_DEVICE_RC
    except BenchFailure as e:
        log(f"FAILED: {e}")
        return 1
    device = dict(run["device"])
    result = {"correct": run["correct"], "attempted": run["attempted"],
              "failed": run["failed"], "metrics": metrics, "device": device}
    if args.trace:
        import reduce_trace

        device["busy_s"] = run["trace"]["busy_s"]
        device["window_s"] = run["trace"]["window_s"]
        result["breakdown"] = reduce_trace.breakdown(run["trace"])
    if args.rehearse:
        log(f"rehearsal complete, no result line. It would have held: "
            f"{json.dumps(result)[:1500]}")
        return 0
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
