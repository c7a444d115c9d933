"""Distributed runtime bring-up / teardown (L2).

TPU-native replacement for the reference's NCCL process-group lifecycle:
``setup(rank, world_size)`` — env mutation + ``dist.init_process_group("nccl")``
+ ``dist.barrier()`` (ref ``src/distributed_inference.py:14-18``) — and
``cleanup()`` — ``dist.destroy_process_group()`` (ref ``:20-21``).

Design differences (TPU-first, SURVEY.md §5 'Distributed communication
backend'):

- Rendezvous is ``jax.distributed.initialize``: coordinator = process 0
  (the analog of ``MASTER_ADDR:MASTER_PORT``); on TPU pods all arguments are
  autodetected from the TPU metadata, so a single launcher serves every host
  (collapsing ``run_node0.sh``/``run_node1.sh``).
- Collectives are emitted by GSPMD/XLA over ICI/DCN; user code never issues
  them. The startup-health ``barrier()`` analog is
  ``multihost_utils.sync_global_devices``.
- CPU simulation: ``simulate_devices=N`` forces N virtual host devices via
  ``xla_force_host_platform_device_count``, which is how multi-node behavior
  is tested without a cluster (repairs the reference's deadlocking distributed
  test fixture, SURVEY.md §3.5).
"""

from __future__ import annotations

import os

from ditl_tpu.config import RuntimeConfig
from ditl_tpu.utils.logging import get_logger, setup_logging

logger = get_logger(__name__)

_initialized = False
_active_coordinator: str | None = None


def simulate_devices(n: int) -> None:
    """Request ``n`` virtual CPU devices. Must run before the first JAX
    *backend* touch (first ``jax.devices()``/array op). Env vars alone are not
    enough if something imported jax before us (jax snapshots env into its
    config at import time), so the config is also set directly."""
    # REPLACE any inherited device-count flag rather than keeping it: an
    # explicit simulate request must win over a parent process's env (e.g. a
    # supervisor child launched from the 8-device test harness).
    parts = [
        p
        for p in os.environ.get("XLA_FLAGS", "").split()
        if not p.startswith("--xla_force_host_platform_device_count")
    ]
    parts.append(f"--xla_force_host_platform_device_count={n}")
    os.environ["XLA_FLAGS"] = " ".join(parts)
    os.environ["JAX_NUM_CPU_DEVICES"] = str(n)
    os.environ["JAX_PLATFORMS"] = "cpu"
    import jax

    jax.config.update("jax_platforms", "cpu")
    jax.config.update("jax_num_cpu_devices", n)


# The in-checkout default: found from the package's own location (never the
# working directory), git-ignored, and fixed — a cache directory that moves
# never hits, and one outside the checkout dies with a sealed machine.
DEFAULT_COMPILE_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
    ".jax_compile_cache",
)


def enable_compile_cache() -> str | None:
    """Turn on JAX's persistent compilation cache (idempotent) and return
    its directory, or None when refused. The ONE place every program —
    trainer, server, chip_smoke.py's children — enables it.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, jax already reads it and
    this helper sets no directory in code: the operator (or the machine the
    program was copied onto) places the cache. Otherwise the cache lives at
    :data:`DEFAULT_COMPILE_CACHE_DIR`. Either way the thresholds drop to
    zero so every program is cached — the big training step is one entry;
    the small host-side programs cost nothing.

    Touches the backend (to ask whether it is a multi-device CPU), so it
    must only run in the process that owns the device — never in a
    supervisor parent. On CPU the cache is only honored for single-device,
    single-process runs: XLA:CPU intermittently aborts (SIGABRT)
    deserializing cached executables under the multi-device host platform
    (the 8-device test sim — see tests/conftest.py and
    docs/troubleshooting.md §20)."""
    import jax

    if (jax.default_backend() == "cpu"
            and (jax.local_device_count() > 1 or jax.process_count() > 1)):
        logger.debug(
            "compile cache skipped: multi-device/multi-process CPU host "
            "platform (known-bad executable deserialization — worker "
            "SIGSEGV/SIGABRT in the pod drills)"
        )
        return None
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        os.makedirs(DEFAULT_COMPILE_CACHE_DIR, exist_ok=True)
        jax.config.update("jax_compilation_cache_dir", DEFAULT_COMPILE_CACHE_DIR)
    # Cache everything: the default thresholds skip exactly the small
    # programs whose re-compiles add up across drills and restarts.
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    path = jax.config.jax_compilation_cache_dir
    logger.info("persistent compilation cache at %s", path)
    return path


def device_summary() -> dict:
    """What this process actually runs on, as jax reports it — stamped into
    the trainer's summary JSON and the server's /v1/stats so a caller (and
    chip_smoke.py, whose parent never touches jax) reads the device from
    the program's own output instead of trusting a log line."""
    from importlib.metadata import PackageNotFoundError, version

    import jax
    import jaxlib

    try:
        libtpu = version("libtpu")
    except PackageNotFoundError:  # a CPU-only installation
        libtpu = None
    devices = jax.devices()
    return {
        "platform": devices[0].platform,
        "device_kind": devices[0].device_kind,
        "device_count": len(devices),
        "jax": jax.__version__,
        "jaxlib": jaxlib.__version__,
        "libtpu": libtpu,
    }


def init_runtime(config: RuntimeConfig | None = None) -> None:
    """Bring up the distributed runtime (idempotent).

    Order matters: simulation flags must be set before JAX initializes its
    backends, and ``jax.distributed.initialize`` must run before any
    device access on multi-host.
    """
    global _initialized, _active_coordinator
    config = config or RuntimeConfig()
    if _initialized:
        if (
            config.distributed
            and config.coordinator_address
            and _active_coordinator is not None
            and config.coordinator_address != _active_coordinator
        ):
            # Elastic relaunch in-process: the pod came back on a bumped
            # coordinator port (runtime/elastic.py restarts a generation
            # against a fresh port), so the old distributed client — whose
            # rendezvous state is generation-scoped — must be replaced, not
            # reused.
            reinit_distributed(config)
        return
    if config.simulate_devices > 0:
        simulate_devices(config.simulate_devices)

    import jax

    if config.distributed:
        _enable_cpu_cross_process_collectives()
        # Explicit args for CPU/GPU clusters; all-None autodetects on TPU pods.
        jax.distributed.initialize(
            coordinator_address=config.coordinator_address,
            num_processes=config.num_processes,
            process_id=config.process_id,
        )
        _active_coordinator = config.coordinator_address
    # This process owns its devices: bring the backend up now, so that
    # setup_logging's process-0 gate reads the real index (the logger itself
    # never initialises a backend — utils/logging.py).
    jax.devices()
    setup_logging(config.log_level)
    if config.compile_cache:
        enable_compile_cache()
    from ditl_tpu.utils.profiling import compile_counter, start_profiler_server

    compile_counter()  # counts every program this process builds from here on
    start_profiler_server(config.profiler_port)
    logger.info(
        "runtime up: process %d/%d, %d local / %d global devices (%s)",
        jax.process_index(),
        jax.process_count(),
        jax.local_device_count(),
        jax.device_count(),
        device_summary(),
    )
    _initialized = True


def _enable_cpu_cross_process_collectives() -> None:
    """Select the Gloo transport for CPU cross-process collectives. The
    default in-process CPU backend refuses multiprocess computations
    ("Multiprocess computations aren't implemented on the CPU backend"), so
    any distributed CPU pod — the multi-process drills, or a CPU cluster —
    needs this set BEFORE the backend initializes. No-ops on TPU/GPU
    platforms."""
    import jax

    platforms = jax.config.jax_platforms or ""
    # Unset platforms means auto-detection, which on a plain CPU host picks
    # the very backend that needs this flag — only skip when the operator
    # explicitly selected a non-CPU platform.
    if platforms and "cpu" not in platforms.split(","):
        return
    jax.config.update("jax_cpu_collectives_implementation", "gloo")


def reinit_distributed(config: RuntimeConfig) -> None:
    """Replace the distributed client for a new pod generation (elastic
    relaunch on a bumped coordinator port).

    Only possible BEFORE this process has executed any JAX computation —
    jax refuses to re-initialize an already-computed process (drilled in
    tests/elastic_drill.py, both polarities), because the backend's
    collective channels were created against the old generation's store. A
    process that has already computed must be RELAUNCHED to rejoin — which
    is exactly what the pod controller does; this path serves workers that
    brought the client up but died/rewired before touching a device. The
    refusal is translated into an actionable error instead of jax's
    generic one."""
    global _active_coordinator
    import jax

    logger.info(
        "re-initializing distributed runtime: coordinator %s -> %s",
        _active_coordinator,
        config.coordinator_address,
    )
    try:
        jax.distributed.shutdown()
    except RuntimeError:
        pass  # old client already gone (e.g. coordinator died with the pod)
    # The rejoin can only succeed when the backend has NOT initialized yet —
    # which means the CPU collectives transport can (and must) still be
    # selected for the new generation's first computation.
    _enable_cpu_cross_process_collectives()
    try:
        jax.distributed.initialize(
            coordinator_address=config.coordinator_address,
            num_processes=config.num_processes,
            process_id=config.process_id,
        )
    except RuntimeError as e:
        raise RuntimeError(
            "cannot rejoin a new pod generation in-process: this process "
            "already executed JAX computations against the old generation's "
            "collective channels. Relaunch the process to rejoin (the pod "
            "controller in runtime/elastic.py does this automatically)."
        ) from e
    _active_coordinator = config.coordinator_address


def barrier(name: str = "startup") -> None:
    """Block until all processes reach this point — the health-check analog of
    the reference's lone ``dist.barrier()`` (ref ``src/distributed_inference.py:18``).
    Implemented as an all-reduce over every global device, so it also verifies
    that cross-host collectives actually work."""
    from jax.experimental import multihost_utils

    multihost_utils.sync_global_devices(name)


def is_coordinator() -> bool:
    """True on process 0 — the reference's ``rank == 0`` gate (ref ``:71``)."""
    import jax

    return jax.process_index() == 0


def shutdown_runtime() -> None:
    """Tear down cleanly (analog of ``cleanup()``, ref ``:20-21``): final
    barrier so no host exits while peers are mid-collective, then release the
    distributed client."""
    global _initialized, _active_coordinator
    if not _initialized:
        return
    import jax

    try:
        if jax.process_count() > 1:
            barrier("shutdown")
            jax.distributed.shutdown()
    finally:
        _initialized = False
        _active_coordinator = None
    logger.info("runtime shut down")
