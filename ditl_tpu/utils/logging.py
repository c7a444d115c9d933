"""Process-0-gated logging (L0).

Parity surface: ``setup_logging()`` (ref ``src/utils.py:5-10``) configured
INFO-level timestamped logging, and the driver gated per-example output on
``rank == 0`` (ref ``src/distributed_inference.py:71-76``). Here the gating is
built into the logger itself so every module gets it for free: non-zero
processes log only WARNING and above unless ``all_processes=True``.
"""

from __future__ import annotations

import logging
import sys

_FORMAT = "%(asctime)s - %(levelname)s - [p%(process_index)s] %(name)s - %(message)s"
_handler: logging.Handler | None = None


class _ProcessIndexFilter(logging.Filter):
    """Injects the JAX process index into every record (lazily — jax may not be
    initialized when logging is configured, and logging never initializes it)."""

    def filter(self, record: logging.LogRecord) -> bool:
        record.process_index = _process_index()
        return True


def _process_index() -> int:
    """This process's index in the pod, WITHOUT ever initialising a JAX
    backend. ``jax.process_index()`` asked cold initialises one, and a
    process that has initialised a backend holds every chip it can see:
    that is how ``launch gateway``'s parent once took the chips its own
    replicas needed (found on the v5e, PR 21). Until a backend is up — and
    supervisors and the gateway never bring one up — the index is 0."""
    jax = sys.modules.get("jax")
    if jax is None:
        return 0
    from jax._src import xla_bridge

    if not xla_bridge.backends_are_initialized():
        return 0
    return jax.process_index()


def setup_logging(level: str = "INFO", all_processes: bool = False) -> None:
    """Configure root logging. On processes != 0, raise the threshold to
    WARNING (the reference's ``if rank == 0`` gate, made structural).

    Re-entrant and embedding-safe: we track OUR OWN handler and replace only
    it on reconfiguration. The old behavior cleared root handlers only when
    we had already configured once, so under pytest (which installs its own
    capture handler first) or any embedding app, the first setup_logging
    added a second root handler and every record was emitted twice — and a
    re-setup would wipe the HOST's handlers (ISSUE 3 satellite)."""
    global _handler
    effective = level.upper()
    if not all_processes and _process_index() != 0:
        effective = "WARNING"
    handler = logging.StreamHandler(sys.stderr)
    handler.setFormatter(logging.Formatter(_FORMAT))
    handler.addFilter(_ProcessIndexFilter())
    root = logging.getLogger()
    if _handler is not None and _handler in root.handlers:
        root.removeHandler(_handler)
    root.addHandler(handler)
    root.setLevel(effective)
    _handler = handler


def get_logger(name: str) -> logging.Logger:
    return logging.getLogger(name)
