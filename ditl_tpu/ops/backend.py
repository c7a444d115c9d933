"""Which backend the Pallas kernels are about to run on, asked in one place.

Off the TPU the kernels run in Pallas interpret mode so the same numerics
tests run on CPU, and a shape a kernel cannot tile may give way to the XLA
spelling (tiny test shapes). On the TPU neither is acceptable in silence: a
config that says ``flash`` and runs XLA attention hides a large slowdown
behind a true-looking setting, so there a requested kernel either runs or
raises, naming the shape.
"""

from __future__ import annotations

import jax

__all__ = ["interpret_default", "refuse_on_tpu"]


def interpret_default() -> bool:
    """Default for the kernels' ``interpret`` argument: interpret everywhere
    but on the TPU backend."""
    return jax.default_backend() != "tpu"


def refuse_on_tpu(kernel: str, why: str) -> None:
    """Called where a requested Pallas kernel cannot run and the caller is
    about to substitute the XLA path: raises on the TPU backend, returns
    (the caller gives way) in interpret mode."""
    if jax.default_backend() == "tpu":
        raise ValueError(
            f"{kernel} was requested but cannot run on this TPU: {why}"
        )
