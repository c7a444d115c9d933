"""chip_smoke.py has no CPU mode: on a machine without a chip it must fail
within seconds, before compiling anything, and say why — whatever
``JAX_PLATFORMS`` it inherited."""

from __future__ import annotations

import os
import shutil
import subprocess
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SMOKE = os.path.join(REPO, "chip_smoke.py")


def _run(script: str, cwd: str) -> tuple[subprocess.CompletedProcess, float]:
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    t0 = time.monotonic()
    out = subprocess.run([sys.executable, script], env=env, cwd=cwd,
                         capture_output=True, text=True, timeout=120)
    return out, time.monotonic() - t0


def test_fails_fast_without_a_tpu():
    out, wall = _run(SMOKE, REPO)
    assert out.returncode != 0
    assert "no TPU could be initialised" in out.stderr
    assert '"ok"' not in out.stdout  # no result line
    assert wall < 60, f"took {wall:.0f}s to notice there is no chip"


def test_fails_alone_without_the_repository(tmp_path):
    alone = shutil.copy(SMOKE, tmp_path)
    out, _ = _run(alone, str(tmp_path))
    assert out.returncode != 0
    assert out.stdout.strip() == ""
    assert "ditl_tpu package is not next to this script" in out.stderr


_SUPERVISOR_PROBE = r"""
import sys
sys.path.insert(0, {repo!r})
from ditl_tpu import launch
from ditl_tpu.runtime import elastic

class Done:  # what PodController.run returns
    ok, returncode, restarts = True, 0, 0

elastic.PodController.run = lambda self: Done()
rc = launch.main(["--supervise", "--preset", "qwen2-0.5b", "data.synthetic=true"])
# What `launch gateway`'s parent does before it spawns replicas: configure
# logging and log (the [pN] tag once asked jax for the process index).
from ditl_tpu.utils.logging import get_logger, setup_logging
setup_logging()
get_logger("probe").info("a line from a supervisor")
from jax._src import xla_bridge
print("RC", rc, "BACKENDS", xla_bridge.backends_are_initialized())
"""


def test_supervisor_parents_never_initialise_a_backend():
    """One process per chip: the ``launch --supervise`` parent parses the
    config (which imports jax), and the gateway's parent sets up logging
    and logs, but both must leave the backend to their children — a parent
    that touched it would hold the chip the child needs."""
    out = subprocess.run(
        [sys.executable, "-c", _SUPERVISOR_PROBE.format(repo=REPO)],
        capture_output=True, text=True, timeout=120, cwd=REPO,
    )
    assert out.returncode == 0, out.stderr
    assert out.stdout.strip().splitlines()[-1] == "RC 0 BACKENDS False"
