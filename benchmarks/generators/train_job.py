"""Generator ``train_job``: one fine-tuning job through ``ditl_tpu.launch``.

The traffic file gives the job (``launch_args``, ``log_every``, how many
flushes warm up, which seconds of the window a traced run profiles); ``--seed`` seeds the
model initialisation and the data order.

The clock is the harness's. The trainer's only device sync is its metrics
flush every ``log_every`` steps (``train/metrics.py``: one ``jax.device_get``
over the pending steps, then the ``step N: loss=...`` log line); the child's
stderr is unbuffered and each flush line is stamped as it arrives. Throughput
is tokens (the rows' ``n_tokens``, which count non-padding targets) between
two flushes over the time between them. The rows' own ``step_time_s`` is
taken on the host before the flush, so it times the enqueue: never used.
"""

from __future__ import annotations

import glob
import json
import math
import os
import re
import time

from harness import BenchFailure, Child, device_block, log, model_override_args

FLUSH_LINE = re.compile(r"\bstep (\d+): loss=")


def build_argv(ctx) -> tuple[list[str], list[str]]:
    """(program argv, the ``X=Y`` ModelConfig overrides among them)."""
    traffic, config = ctx.traffic, ctx.config
    launch = list(traffic["launch_args"])
    if ctx.rehearsal:
        launch += ctx.rehearsal["train_launch_args"]
        launch += [f"model.{o}" for o in config.get("rehearsal_overrides", [])]
    own = model_override_args(config, "train")
    model = own + [a[len("model."):] for a in launch if a.startswith("model.")]
    argv = ["--preset", config["preset"]] + [f"model.{o}" for o in own]
    argv += launch + [
        f"train.log_every={traffic['log_every']}",
        f"train.seed={ctx.seed}", f"data.seed={ctx.seed}",
        f"train.metrics_file={os.path.join(ctx.run_dir, 'metrics.jsonl')}",
    ]
    if ctx.chips > 1:
        argv += list(traffic.get("mesh_args", []))
    return argv, model


def run(ctx) -> dict:
    traffic = ctx.traffic
    argv, model_overrides = build_argv(ctx)
    flushes: list[dict] = []

    def on_line(t: float, line: str) -> None:
        m = FLUSH_LINE.search(line)
        if m:
            flushes.append({"t": t, "step": int(m.group(1))})

    child = Child(role="train", run_dir=ctx.run_dir, workload=ctx.workload,
                  chips=ctx.chips, config_path=ctx.config_path,
                  spec={"role": "train", "model_overrides": model_overrides,
                        "rehearsal": bool(ctx.rehearsal)},
                  argv=argv, allow_cpu=bool(ctx.rehearsal), on_line=on_line)
    try:
        warm = traffic["warmup_flushes"]
        deadline = time.monotonic() + ctx.setup_timeout_s
        while len(flushes) < warm:
            child.check_alive("during warm-up")
            if time.monotonic() > deadline:
                raise BenchFailure(f"warm-up: {len(flushes)} of {warm} flushes "
                                   f"within {ctx.setup_timeout_s:.0f}s")
            time.sleep(0.01)
        t0 = flushes[warm - 1]["t"]
        cache0 = ctx.cache_entries()
        log(f"window opens at flush {warm} (step {flushes[warm - 1]['step']}), "
            f"{t0 - ctx.t_start:.1f}s after start")
        traced = None
        if ctx.trace:
            # The launcher's thread takes the device trace (see the traffic
            # file's trace_note for why not train.profile_dir).
            spec = traffic["trace"]
            time.sleep(max(0.0, t0 + min(spec["start_s"], ctx.seconds / 3)
                           - time.monotonic()))
            a = time.monotonic()
            child.command("trace_start", dir=os.path.join(ctx.run_dir, "trace"))
            time.sleep(min(spec["seconds"], ctx.seconds / 3))
            child.command("trace_stop", timeout_s=300)
            traced = (a, time.monotonic())
        while time.monotonic() < t0 + ctx.seconds:
            child.check_alive("inside the window")
            time.sleep(0.01)
        cache1 = ctx.cache_entries()
        reference = child.wait_file("reference.json", 5, "reference check")
        trace_file = None
        if ctx.trace:
            found = glob.glob(os.path.join(ctx.run_dir, "trace", "plugins",
                                           "profile", "*", "*.xplane.pb"))
            if not found:
                raise BenchFailure("the traced run left no .xplane.pb")
            trace_file = found[0]
        device = device_block(child)
    finally:
        child.stop()
    reduced = ctx.reduce_trace(trace_file, device) if trace_file else None

    with open(os.path.join(ctx.run_dir, "metrics.jsonl")) as f:
        rows = [json.loads(ln) for ln in f if ln.strip()]
    window = [fl for fl in flushes[warm - 1:] if fl["t"] <= t0 + ctx.seconds]
    if len(window) < 2:
        raise BenchFailure(f"{len(window) - 1} flush intervals inside the "
                           f"{ctx.seconds}s window: the job is too slow for it")
    intervals = []
    for a, b in zip(window, window[1:]):
        steps = [r for r in rows if a["step"] < r["step"] <= b["step"]]
        intervals.append({
            "t0": a["t"], "t1": b["t"], "steps": len(steps),
            "tokens": sum(r["n_tokens"] for r in steps),
            "data_wait_s": sum(r["data_wait_s"] for r in steps),
            # The profiler's start, its overhead and its stop (writing the
            # file) sit inside these intervals: not steady state.
            "traced": bool(traced and a["t"] < traced[1] and b["t"] > traced[0]),
        })
    in_window = [r for r in rows
                 if window[0]["step"] < r["step"] <= window[-1]["step"]]
    first_flush = [r["loss"] for r in rows if r["step"] <= flushes[0]["step"]]
    last_flush = [r["loss"] for r in rows
                  if window[-2]["step"] < r["step"] <= window[-1]["step"]]
    bad = [r["step"] for r in in_window if not math.isfinite(r["loss"])]
    loss_fell = (bool(first_flush) and bool(last_flush)
                 and sum(last_flush) / len(last_flush)
                 < sum(first_flush) / len(first_flush))
    log(f"{len(intervals)} flush intervals, {len(in_window)} steps, loss "
        f"{first_flush[:1]} -> {last_flush[-1:]}, reference {reference}")
    return {
        "kind": "train",
        "correct": bool(not bad and loss_fell and reference.get("ok")),
        "attempted": len(in_window),
        "failed": len(bad),
        "setup_s": t0 - ctx.t_start,
        "window_s": window[-1]["t"] - window[0]["t"],
        "chips": ctx.chips,
        "intervals": intervals,
        "rows": in_window,
        "reference": reference,
        "compiles_in_window": cache1 - cache0,
        "device": device,
        "trace": reduced,
        "host_spans": [],
        "model_overrides": model_overrides,
    }
