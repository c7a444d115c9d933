#!/usr/bin/env python3
"""The benchmark's launcher inside the process that holds the chip.

    python benchmarks/chip_child.py --role train|serve --out DIR --chips N \
        --workload NAME --config FILE --spec JSON --verdicts DIR [--allow-cpu] \
        -- <program argv>

``run.py`` (which never touches JAX) starts one of these per run. It

1. checks the device: platform ``tpu`` (unless ``--allow-cpu``, the
   rehearsal), exactly ``--chips`` devices, a ``device_kind`` that
   ``peaks.json`` knows; writes ``DIR/device.json`` or exits 3;
2. runs the reference check (``reference_check.py``) before the program has
   allocated anything, and writes ``DIR/reference.json``;
3. starts a daemon thread that serves the parent's commands on stdin
   (``memory``, ``trace_start``, ``trace_stop``) and stamps a wall-clock mark
   into the profiler's host trace ten times a second, so that a device trace
   taken from this process can be laid on the host's clock;
4. calls the program's own entry point unchanged, in the main thread (both
   install signal handlers): ``ditl_tpu.launch.main(argv)`` or
   ``ditl_tpu.infer.server.serve(argv)``.

It imports nothing from the program but those two functions, the logging
set-up that the server's own ``__main__`` block calls, and what the reference
check compares.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import threading
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)

NO_DEVICE_RC = 3


def log(msg: str) -> None:
    print(f"chip_child: {msg}", file=sys.stderr, flush=True)


def write_json(path: str, obj) -> None:
    tmp = f"{path}.tmp"
    with open(tmp, "w") as f:
        json.dump(obj, f)
    os.replace(tmp, path)


def check_device(chips: int, allow_cpu: bool) -> dict | None:
    """The device as JAX reports it, or None (after logging why) when it is
    not what the cell asks for."""
    import jax

    try:
        devices = jax.devices()
    except RuntimeError as e:
        log(f"no accelerator could be initialised: {e}")
        return None
    dev = {"platform": devices[0].platform, "kind": devices[0].device_kind,
           "count": len(devices)}
    if dev["platform"] != "tpu" and not allow_cpu:
        log(f"jax landed on platform {dev['platform']!r}, not tpu")
        return None
    if dev["count"] != chips:
        log(f"the cell asks for {chips} chip(s), jax sees {dev['count']}")
        return None
    if not allow_cpu:
        with open(os.path.join(HERE, "peaks.json")) as f:
            peaks = json.load(f)
        if dev["kind"].lower().strip() not in peaks:
            log(f"device_kind {dev['kind']!r} is missing from benchmarks/peaks.json")
            return None
    return dev


def memory_peak_bytes() -> int:
    """Peak bytes in use on the fullest chip (0 where the backend does not
    report it, as on the CPU)."""
    import jax

    peaks = []
    for d in jax.devices():
        stats = d.memory_stats() or {}
        peaks.append(int(stats.get("peak_bytes_in_use", 0)))
    return max(peaks, default=0)


def control_loop(out_dir: str) -> None:
    """Serve the parent's commands: one JSON object per stdin line, answered
    in ``reply-<id>.json``. Ends when stdin closes."""
    import jax

    for line in sys.stdin:
        try:
            cmd = json.loads(line)
        except ValueError:
            continue
        reply = {"id": cmd.get("id"), "ok": True, "wall": time.time()}
        try:
            if cmd["op"] == "memory":
                reply["memory_peak_bytes"] = memory_peak_bytes()
            elif cmd["op"] == "trace_start":
                jax.profiler.start_trace(cmd["dir"])
            elif cmd["op"] == "trace_stop":
                jax.profiler.stop_trace()
            else:
                reply.update(ok=False, error=f"unknown op {cmd['op']!r}")
        except Exception as e:  # noqa: BLE001 - reported to the parent, which decides
            reply.update(ok=False, error=f"{type(e).__name__}: {e}")
        reply["wall_done"] = time.time()
        write_json(os.path.join(out_dir, f"reply-{cmd.get('id')}.json"), reply)


def clock_marks() -> None:
    """A host-trace annotation carrying the wall clock, ten times a second.
    Outside a trace it costs a few microseconds; inside one it is what lets
    ``reduce_trace.py`` put host spans stamped with ``time.time()`` on the
    trace's own clock."""
    import jax

    while True:
        with jax.profiler.TraceAnnotation("benchmarks.clock", wall_ns=time.time_ns()):
            pass
        time.sleep(0.1)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--role", choices=("train", "serve"), required=True)
    ap.add_argument("--out", required=True)
    ap.add_argument("--chips", type=int, required=True)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--config", required=True)
    ap.add_argument("--spec", required=True, help="JSON: the reference check's settings")
    ap.add_argument("--verdicts", required=True, help="directory of cached verdicts")
    ap.add_argument("--allow-cpu", action="store_true")
    ap.add_argument("argv", nargs=argparse.REMAINDER)
    args = ap.parse_args()
    argv = args.argv[1:] if args.argv[:1] == ["--"] else args.argv
    sys.path.insert(0, ROOT)
    sys.path.insert(0, HERE)

    dev = check_device(args.chips, args.allow_cpu)
    if dev is None:
        return NO_DEVICE_RC
    write_json(os.path.join(args.out, "device.json"), dev)

    import reference_check

    t0 = time.monotonic()
    try:
        verdict = reference_check.cached_or_run(
            args.verdicts, args.workload, args.config, json.loads(args.spec))
    except Exception as e:  # noqa: BLE001 - a check that cannot run is a failed check
        import traceback

        traceback.print_exc()
        verdict = {"ok": False, "error": f"{type(e).__name__}: {e}"}
    verdict["seconds"] = round(time.monotonic() - t0, 3)
    log(f"reference check: {verdict}")
    write_json(os.path.join(args.out, "reference.json"), verdict)

    threading.Thread(target=control_loop, args=(args.out,), daemon=True).start()
    threading.Thread(target=clock_marks, daemon=True).start()

    if args.role == "train":
        from ditl_tpu.launch import main as program
    else:
        from ditl_tpu.infer.server import serve as program
        from ditl_tpu.utils.logging import setup_logging

        setup_logging()
    return int(program(argv) or 0)


if __name__ == "__main__":
    sys.exit(main())
