"""Reusable real-OS-process cluster harness.

Generalized from the launcher logic that used to live inline in
``tests/test_multiprocess.py``: spawn N copies of a worker script that
rendezvous through ``jax.distributed.initialize`` against a local
coordinator, collect every process's (returncode, stdout+stderr), and
guarantee teardown. Worker scripts follow the ``multiproc_drill.py``
convention: ``python <script> <proc_id> <nproc> <port> [extra args...]``.

Every drill built on this harness is hard-bounded: the per-process
``timeout`` is the suite's protection against a wedged collective (there is
no pytest-timeout plugin in this image — the harness IS the timeout).
"""

from __future__ import annotations

import os
import subprocess
import sys
import time

from ditl_tpu.runtime.elastic import free_port  # noqa: F401  (re-export)


def hermetic_env(repo_root: str, **overrides: str) -> dict[str, str]:
    """Hermetic subprocess env for cross-process drills: CPU platform, ONE
    real device per process (cross-PROCESS coordination is the point; the
    8-device sim covers virtual-device SPMD — and the parent test process's
    8-device XLA_FLAGS must NOT leak in), repo root on PYTHONPATH."""
    return {
        **os.environ,
        "PYTHONPATH": repo_root + os.pathsep + os.environ.get("PYTHONPATH", ""),
        "JAX_PLATFORMS": "cpu",
        "JAX_NUM_CPU_DEVICES": "1",
        "XLA_FLAGS": "",
        **overrides,
    }


class ClusterHarness:
    """Launch ``nproc`` copies of ``script`` as real OS processes.

    ``env_overrides`` layer on top of :func:`hermetic_env`.
    """

    def __init__(
        self,
        nproc: int,
        script: str,
        *,
        env_overrides: dict[str, str] | None = None,
        timeout: int = 420,
    ):
        self.nproc = nproc
        self.script = os.path.abspath(script)
        self.timeout = timeout
        repo_root = os.path.dirname(os.path.dirname(self.script))
        self.env = hermetic_env(repo_root, **(env_overrides or {}))

    def run(self, *extra: str) -> list[tuple[int, str]]:
        """One pod generation on a fresh coordinator port; returns each
        worker's (returncode, combined output) in process-id order."""
        port = free_port()
        procs = [
            subprocess.Popen(
                [
                    sys.executable,
                    self.script,
                    str(i),
                    str(self.nproc),
                    str(port),
                    *extra,
                ],
                stdout=subprocess.PIPE,
                stderr=subprocess.STDOUT,
                text=True,
                env=self.env,
            )
            for i in range(self.nproc)
        ]
        outs = []
        # One SHARED deadline: sequential per-process timeouts would bound
        # the drill at nproc * timeout, not timeout.
        deadline = time.monotonic() + self.timeout
        try:
            for p in procs:
                out, _ = p.communicate(
                    timeout=max(0.0, deadline - time.monotonic())
                )
                outs.append((p.returncode, out))
        finally:
            for p in procs:
                if p.poll() is None:
                    p.kill()
            for p in procs:
                # Reap: without this a timed-out drill leaks zombies and
                # open pipe fds into the long-lived pytest process.
                try:
                    p.wait(timeout=10)
                except subprocess.TimeoutExpired:
                    pass
        return outs
