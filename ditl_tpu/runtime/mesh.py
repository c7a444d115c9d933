"""Device-mesh construction (L2).

The reference's only notion of topology is ``(rank, world_size)`` handed to a
sampler (ref ``src/distributed_inference.py:46-47,58``). The TPU-native
equivalent is an explicit N-d ``jax.sharding.Mesh`` whose axes name the
parallelism strategies; GSPMD lowers shardings over it to XLA collectives that
ride ICI within a slice and DCN across slices.

Axis order is chosen so that the *innermost* (fastest-varying, most
ICI-adjacent under default device order) axes carry the highest-bandwidth
traffic: tensor parallelism needs per-layer all-reduces every microsecond,
FSDP needs per-layer all-gathers, data parallelism needs one gradient
reduction per step, so the mesh is laid out data-outermost / tensor-innermost.
"""

from __future__ import annotations

import numpy as np

from ditl_tpu.config import MeshConfig
from ditl_tpu.utils.logging import get_logger

logger = get_logger(__name__)

# Outer -> inner. DCN-friendly axes first, ICI-hungry axes last. Pipeline
# stages exchange one activation per microbatch per stage (point-to-point,
# modest bandwidth) so "stage" sits on the DCN-friendly side.
AXIS_ORDER = ("data", "stage", "fsdp", "sequence", "expert", "tensor")


def build_mesh(config: MeshConfig | None = None, devices=None) -> "jax.sharding.Mesh":
    """Build the global mesh from a MeshConfig (resolving any -1 axis)."""
    import jax

    config = config or MeshConfig()
    if devices is None:
        devices = jax.devices()
    n = len(devices)
    by_name = dict(zip(config.axis_names, config.resolve(n)))
    shape = tuple(by_name[a] for a in AXIS_ORDER)
    # Auto axis types: GSPMD infers intermediate shardings from the constraints
    # we annotate (with_sharding_constraint / in_shardings), which is the
    # propagation model this framework is designed around. make_mesh lays
    # the axes out topology-aware on real TPU slices; a shape the topology
    # cannot carry raises here rather than being reshaped into a mesh whose
    # inner axes no longer ride adjacent chips.
    mesh = jax.make_mesh(
        shape, AXIS_ORDER, devices=devices,
        axis_types=(jax.sharding.AxisType.Auto,) * len(AXIS_ORDER),
    )
    logger.info("mesh: %s", dict(zip(AXIS_ORDER, shape)))
    return mesh


def batch_axes() -> tuple[str, ...]:
    """Mesh axes over which the global batch is split. FSDP shards both params
    and batch (it is data parallelism with sharded state)."""
    return ("data", "fsdp")


def data_parallel_size(mesh) -> int:
    """Number of distinct data shards (product of batch axes)."""
    return int(np.prod([mesh.shape[a] for a in batch_axes()]))
