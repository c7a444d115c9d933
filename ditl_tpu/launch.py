"""Single launcher for all hosts (L6).

The reference needs one hand-edited Bash script per node, differing only in
``--node_rank`` (ref ``scripts/run_node0.sh:13`` vs ``run_node1.sh:13``), plus
NCCL env tuning. On TPU VMs every host runs *the same command* —
``jax.distributed.initialize`` discovers rank/world topology from the TPU
metadata — so the launcher collapses to one CLI (BASELINE.json north star:
'run_node0.sh + run_node1.sh collapse into a single TPU-VM launcher'):

    python -m ditl_tpu.launch --preset tiny-llama mesh.fsdp=8 train.total_steps=50

CPU simulation of an N-device pod (SURVEY.md §4's repaired test strategy):

    python -m ditl_tpu.launch --simulate 8 data.synthetic=true

The persistent XLA compilation cache is on by default
(``runtime.compile_cache``, wired through ``init_runtime``): restarts,
elastic relaunches, and repeat runs of an unchanged config skip the first
compile. It lives where ``JAX_COMPILATION_CACHE_DIR`` says, else in one
fixed git-ignored directory inside the checkout;
``runtime.compile_cache=false`` disables it; docs/troubleshooting.md §20
covers staleness.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time

from ditl_tpu.config import Config, parse_overrides
from ditl_tpu.models.presets import get_preset


def build_config(argv: list[str] | None = None) -> Config:
    parser = argparse.ArgumentParser(
        prog="ditl_tpu.launch",
        description="TPU-native distributed fine-tuning launcher (one command, every host)",
        # No prefix abbreviation: every host (and the pod controller's
        # rendezvous-clash guard) must see the same literal flag tokens —
        # an abbreviated --coord would bypass the --pod ownership check.
        allow_abbrev=False,
    )
    parser.add_argument("--preset", default=None, help="model preset name")
    parser.add_argument(
        "--simulate", type=int, default=0, help="simulate N CPU devices (no TPU needed)"
    )
    parser.add_argument(
        "--distributed", action="store_true", help="multi-host: call jax.distributed.initialize"
    )
    parser.add_argument("--coordinator", default=None, help="host:port of process 0")
    parser.add_argument("--num-processes", type=int, default=None)
    parser.add_argument("--process-id", type=int, default=None)
    parser.add_argument("--print-config", action="store_true")
    parser.add_argument(
        "--supervise", action="store_true",
        help="run training in a child PROCESS and restart it (up to "
        "train.max_restarts) on any death — including SIGKILL/host-crash "
        "class failures the in-process supervisor cannot catch; each "
        "restart resumes from the latest Orbax checkpoint",
    )
    parser.add_argument(
        "--pod", type=int, default=0,
        help="with --supervise: run an elastic POD of N distributed worker "
        "processes on this host (runtime/elastic.py) — any worker death "
        "tears down the survivors and relaunches the whole pod on a fresh "
        "coordinator port, resuming from the multi-host Orbax checkpoint",
    )
    parser.add_argument(
        "overrides", nargs="*", help="config overrides like train.total_steps=50"
    )
    args = parser.parse_args(argv)
    if args.pod and not args.supervise:
        parser.error("--pod requires --supervise (the elastic pod controller)")

    config = Config()
    if args.preset:
        config = dataclasses.replace(config, model=get_preset(args.preset))
    # `model.name=<preset>` in overrides swaps in the preset shapes FIRST, so
    # later model.* overrides layer on top of it rather than being silently
    # ignored or applied to the tiny default shapes.
    from ditl_tpu.models.presets import PRESETS

    for item in args.overrides:
        if item.startswith("model.name=") and item.split("=", 1)[1] in PRESETS:
            config = dataclasses.replace(
                config, model=get_preset(item.split("=", 1)[1])
            )
    config = dataclasses.replace(
        config,
        runtime=dataclasses.replace(
            config.runtime,
            simulate_devices=args.simulate,
            distributed=args.distributed,
            coordinator_address=args.coordinator,
            num_processes=args.num_processes,
            process_id=args.process_id,
        ),
    )
    config = parse_overrides(config, args.overrides)
    if args.print_config:
        print(config.to_json())
        sys.exit(0)
    return config


def run_supervised(config: Config, startup=None) -> dict:
    """Restart supervisor — the analog of torchrun's elastic ``--max_restarts``
    (which the reference launches through but never configures, ref
    ``scripts/run_node0.sh:10``, SURVEY.md §5 'failure detection'). On an
    unhandled training exception, re-enters ``train()`` up to
    ``train.max_restarts`` times; each retry resumes from the latest Orbax
    checkpoint (``init_runtime`` is idempotent, so re-entry is in-process).
    Recovery requires somewhere to recover FROM: without ``checkpoint_dir`` +
    ``resume`` the exception propagates immediately. ``startup``: the
    process's start-up clock (``main``'s), handed to the first ``train()``;
    a restart in this process counts its own start."""
    import logging

    from ditl_tpu.train.trainer import train

    restarts = 0
    while True:
        try:
            summary = train(config, startup)
            summary["restarts"] = restarts
            return summary
        except Exception:
            if (
                config.runtime.distributed
                or restarts >= config.train.max_restarts
                or not config.train.checkpoint_dir
                or not config.train.resume
            ):
                # Distributed: NEVER retry solo — re-entering train() while
                # the peers sit mid-collective at a later step desyncs the
                # pod into a permanent wedge. Die loudly instead; pod-level
                # recovery (the controller relaunching ALL workers,
                # runtime/elastic.py) is the only sound restart.
                raise
            restarts += 1
            startup = None
            logging.getLogger(__name__).exception(
                "training failed; restart %d/%d from latest checkpoint",
                restarts,
                config.train.max_restarts,
            )


def _strip_supervisor_args(argv: list[str]) -> list[str]:
    """Remove --supervise and --pod N/--pod=N from an argv: workers must not
    recursively supervise."""
    out, skip = [], False
    for a in argv:
        if skip:
            skip = False
            continue
        if a == "--supervise" or a.startswith("--pod="):
            continue
        if a == "--pod":
            skip = True
            continue
        out.append(a)
    return out


def run_process_supervised(argv: list[str], num_workers: int = 1) -> int:
    """Process-level restart supervisor over the elastic pod controller
    (runtime/elastic.py) — the recovery story for SIGKILL/OOM/host-crash
    failures that never reach a Python except block (``run_supervised``
    handles only in-process exceptions).

    ``num_workers == 1`` (plain ``--supervise``) runs one non-distributed
    child and restarts it on abnormal death. ``num_workers > 1``
    (``--supervise --pod N``) runs N distributed workers rendezvousing on a
    controller-owned coordinator port; ANY worker death tears down the
    survivors (wedged in collectives with a dead peer) and relaunches the
    whole pod on a fresh port. Resumption correctness comes from the same
    multi-host Orbax checkpoint + data-iterator position in both modes."""
    import logging

    from ditl_tpu.runtime.elastic import PodController

    logger = logging.getLogger(__name__)
    child_argv = _strip_supervisor_args(argv)
    if num_workers > 1:
        # Reject-don't-drop: the controller OWNS rendezvous in pod mode — it
        # assigns a fresh coordinator port per generation and a distinct
        # process id per worker. User-supplied rendezvous flags would
        # argparse-last-win over the controller's (duplicate process ids,
        # fixed ports across relaunches), so refuse them loudly.
        owned = ("--distributed", "--coordinator", "--num-processes",
                 "--process-id",
                 # ...and the override spellings of the same fields, which
                 # parse_overrides applies AFTER the flag-derived config.
                 "runtime.distributed", "runtime.coordinator_address",
                 "runtime.num_processes", "runtime.process_id")
        clashes = [
            a for a in child_argv
            if a in owned or any(a.startswith(f"{o}=") for o in owned)
        ]
        if clashes:
            raise SystemExit(
                "ditl_tpu.launch: error: --pod manages rendezvous itself; "
                f"remove {' '.join(sorted(set(clashes)))}"
            )
    config = build_config(child_argv)
    can_resume = bool(config.train.checkpoint_dir and config.train.resume)
    if num_workers == 1 and config.runtime.distributed:
        # A single supervised child that is one member of a LARGER pod must
        # never be solo-restarted: relaunching it against peers sitting
        # mid-collective at a later step wedges the whole pod (the same
        # desync run_supervised's in-process guard forbids). Let the failure
        # propagate; pod-level recovery (--pod on one host, or an external
        # controller restarting EVERY host) is the only sound restart.
        can_resume = False

    def build_argv(proc_id: int, nproc: int, port: int, attempt: int):
        worker = [sys.executable, "-m", "ditl_tpu.launch"]
        if nproc > 1:
            worker += [
                "--distributed", "--coordinator", f"127.0.0.1:{port}",
                "--num-processes", str(nproc), "--process-id", str(proc_id),
            ]
        return worker + child_argv

    def on_restart(failure_rc, restarts, max_restarts):
        logger.error(
            "training process exited rc=%d; restart %d/%d from latest "
            "checkpoint", failure_rc, restarts, max_restarts,
        )

    controller = PodController(
        num_workers,
        build_argv,
        max_pod_restarts=config.train.max_restarts if can_resume else 0,
        heartbeat_dir=config.train.heartbeat_dir,
        heartbeat_timeout_s=config.train.heartbeat_timeout_s,
        # Slow-not-dead escalation (ISSUE 5): heartbeat STEP lag vs. the
        # pod median, journaled `pod.straggler`, optionally relaunching.
        straggler_lag_steps=config.train.straggler_lag_steps,
        straggler_relaunch=config.train.straggler_relaunch,
        # The trainer emits heartbeats under its jax.process_index(): the
        # worker slot for a controller-owned pod, but the configured (or,
        # when rank is autodetected, unknowable — None = wildcard) process
        # id for a single supervised member of a larger pod.
        heartbeat_ids=(
            None if num_workers > 1
            else [config.runtime.process_id if config.runtime.distributed else 0]
        ),
        # State transitions on stderr for debuggability (the child's summary
        # JSON owns stdout).
        log=lambda msg: print(msg, file=sys.stderr, flush=True),
        on_restart=on_restart,
        # The workers journal under the same dir (train.telemetry_dir), so
        # the controller's end-of-run merge yields one ordered pod timeline.
        journal_dir=config.train.telemetry_dir,
        # Size control (ISSUE 6 satellite): telemetry.journal_max_mb caps
        # every per-process journal via segment rotation.
        journal_max_bytes=config.telemetry.journal_max_bytes(),
        # Anomaly/incident plane (ISSUE 10): worker deaths, heartbeat
        # stalls, and straggler escalations assemble liveness-ring bundles
        # under a controller-owned subdirectory (the workers' trainer-side
        # managers write their own).
        incident_dir=(
            os.path.join(config.telemetry.incident_dir, "controller")
            if config.telemetry.incident_dir else ""
        ),
        incident_kwargs=config.telemetry.incident_kwargs(),
    )
    result = controller.run()
    if not result.ok:
        rc = result.returncode
        logger.error(
            "training process exited rc=%d; giving up (%d restarts used, "
            "resume %s)", rc, result.restarts, "on" if can_resume else "off",
        )
        return rc
    return 0


def _pod_size(argv: list[str]) -> int:
    """Parse --pod N / --pod=N without argparse (main must decide the
    supervisor mode before any config parsing)."""
    for i, a in enumerate(argv):
        value = None
        if a == "--pod":
            if i + 1 >= len(argv):
                raise SystemExit(
                    "ditl_tpu.launch: error: --pod expects a worker count"
                )
            value = argv[i + 1]
        elif a.startswith("--pod="):
            value = a.split("=", 1)[1]
        if value is not None:
            try:
                n = int(value)
            except ValueError:
                n = -1
            if n < 0:
                raise SystemExit(
                    f"ditl_tpu.launch: error: --pod expects a worker count "
                    f">= 0, got {value!r}"
                )
            # 0 is the documented default: "no pod" — plain single-child
            # supervision, so templated `--pod $N` invocations degrade
            # gracefully.
            return n
    return 0


def main(argv: list[str] | None = None) -> int:
    t_entry = time.time()
    if argv is None:
        argv = sys.argv[1:]
    if argv and argv[0] == "gateway":
        # Serving-gateway subcommand (ditl_tpu/gateway/, ISSUE 4): spawn N
        # subprocess replicas of infer/server.py and front them with one
        # OpenAI-compatible endpoint. Deliberately dispatched before the
        # training argparse — the gateway has its own CLI surface.
        from ditl_tpu.gateway.gateway import main as gateway_main
        from ditl_tpu.utils.logging import setup_logging

        setup_logging()
        return gateway_main(argv[1:])
    if "--supervise" in argv:
        return run_process_supervised(argv, max(1, _pod_size(argv)))
    # The training process's start-up clock (the supervisor's parent above
    # records nothing): train() adds its legs and hands them to the first
    # metrics_file row.
    from ditl_tpu.telemetry.tracing import StartupRecorder

    startup = StartupRecorder(t_entry)
    config = build_config(argv)
    startup.mark("config")
    try:
        summary = run_supervised(config, startup)
    except Exception:
        import logging

        logging.getLogger(__name__).exception("training failed")
        return 1
    # Only the coordinator answers on stdout — in a pod every worker runs
    # this identical program and N copies of the summary would interleave.
    from ditl_tpu.runtime.distributed import is_coordinator

    if is_coordinator():
        print(json.dumps(summary, sort_keys=True))
    return 0


if __name__ == "__main__":
    sys.exit(main())
