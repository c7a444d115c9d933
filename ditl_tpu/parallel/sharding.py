"""GSPMD sharding rules: logical axes -> mesh axes -> PartitionSpecs.

The reference's single parallelism strategy is data parallelism by sampler
sharding (ref ``src/distributed_inference.py:58-59``); it has no weight,
activation, sequence, or expert sharding (SURVEY.md §2 checklist). Here all of
them are expressed through one mechanism — every parameter and activation
declares *logical* axes (``"embed"``, ``"heads"``, ``"batch"``...), and a rule
table maps logical axes onto mesh axes. Changing parallelism strategy
(DP -> FSDP -> TP/SP -> MoE) is a rule/mesh change, not a model rewrite —
SURVEY.md §7 'hard part (b)'.

Rules (MaxText-style conventions):
- ``batch``   -> ``("data", "fsdp")``: both axes split the batch; FSDP is data
  parallelism with sharded parameters/optimizer state.
- ``embed``   -> ``fsdp``: ZeRO-3-style parameter sharding along the embedding
  dim; XLA all-gathers weights per layer and reduce-scatters grads.
- ``heads`` / ``mlp`` / ``vocab`` -> ``tensor``: Megatron-style intra-layer
  tensor parallelism (all-reduce on the row-parallel matmul output).
- ``seq``     -> ``sequence``: context parallelism for long sequences (ring
  attention partner axis).
- ``expert``  -> ``expert``: MoE expert parallelism (all-to-all dispatch).
- ``layers``  -> ``None``: the scanned layer dim is never sharded (pipeline
  parallelism would shard it; see parallel/pipeline.py).
"""

from __future__ import annotations

from typing import Any, Sequence

import jax
from jax.sharding import NamedSharding, PartitionSpec as P

__all__ = [
    "DEFAULT_RULES", "logical_to_spec", "spec_tree", "named_sharding_tree",
    "mesh_axes_size", "seq_shards", "pallas_batch_shards",
    "pallas_bwd_effective",
]


def mesh_axes_size(mesh, axes) -> int:
    """Product of mesh-axis sizes for a rules value (str, tuple, or None)."""
    if axes is None:
        return 1
    if isinstance(axes, str):
        axes = (axes,)
    size = 1
    for a in axes:
        size *= mesh.shape[a]
    return size


def seq_shards(mesh, rules=None) -> int:
    """Shard count of the KV-cache context dim ("cache_seq" rule) on this
    mesh — 1 means context is unsharded. One definition for the engines
    and the attention routing, so they can never disagree."""
    if mesh is None:
        return 1
    r = rules if rules is not None else DEFAULT_RULES
    return mesh_axes_size(mesh, r.get("cache_seq"))


def pallas_batch_shards(mesh, rules, batch: int) -> int | None:
    """Shard count over the batch axes for shard_map'ing a Pallas op whose
    weights stay replicated, or None when this mesh cannot host it:
    sequence-sharded activations, batch not divisible by the batch axes,
    or TENSOR parallelism in use — TP shards the very weights the wrapper
    would replicate, so running the kernel would silently de-shard TP's
    compute (tensor-x redundant FLOPs) while looking like a kernel A/B.
    (FSDP-sharded weights are fine: FSDP all-gathers weights per use
    anyway, so replication inside the island matches its cost model.)
    ONE definition shared by the backward-kernel seams (ops/mlp.py,
    ops/projection.py) and whatever records which backward ran, so the
    dispatch and its attribution can never drift apart."""
    if mesh is None:
        return 1
    r = rules if rules is not None else DEFAULT_RULES
    if mesh_axes_size(mesh, r.get("seq")) > 1:
        return None
    if max(mesh_axes_size(mesh, r.get("heads")),
           mesh_axes_size(mesh, r.get("mlp"))) > 1:
        return None
    dp = mesh_axes_size(mesh, r.get("batch"))
    return None if batch % dp else dp


def pallas_bwd_effective(bwd_impl: str, batch: int, seq: int, d: int, f: int,
                         blocks, mesh, rules, supports_fn) -> str:
    """The backward implementation a Pallas-seamed op will ACTUALLY run —
    the mesh gate above plus the op's own shape predicate on the per-shard
    token count. Shared by ops/mlp.py and ops/projection.py so the two
    seams cannot diverge.
    Giving way to "xla" is for interpret mode only: on the TPU backend a
    requested kernel that cannot run raises, naming the shape."""
    if bwd_impl != "pallas":
        return bwd_impl
    from ditl_tpu.ops.backend import refuse_on_tpu

    blocks = tuple(blocks or ())
    shape = f"batch={batch} seq={seq} d={d} f={f} blocks={blocks}"
    shard = pallas_batch_shards(mesh, rules, batch)
    if shard is None:
        refuse_on_tpu(
            "the Pallas backward kernel",
            f"mesh {dict(mesh.shape)} cannot host it (sequence/tensor "
            f"parallelism, or batch not divisible by the batch axes) at "
            f"{shape}",
        )
        return "xla"
    if not supports_fn((batch // shard) * seq, d, f, blocks):
        refuse_on_tpu(
            "the Pallas backward kernel",
            f"cannot tile {shape} over {shard} batch shard(s)",
        )
        return "xla"
    return "pallas"

# logical axis -> mesh axis (or tuple of mesh axes, or None = replicated)
DEFAULT_RULES: dict[str, Any] = {
    # parameter axes
    "embed": "fsdp",
    "heads": "tensor",
    "kv_heads": "tensor",
    "mlp": "tensor",
    "vocab": "tensor",
    "expert": "expert",
    "head_dim": None,
    "layers": None,
    "norm": None,
    "lora_rank": None,
    # activation axes (distinct from parameter axes: an activation's embed dim
    # is NOT fsdp-sharded — fsdp shards weights and splits batch)
    "batch": ("data", "fsdp"),
    "seq": "sequence",
    "act_embed": None,
    "act_heads": "tensor",
    "act_kv_heads": "tensor",
    "act_mlp": "tensor",
    "act_vocab": "tensor",
    # KV-cache CONTEXT dim for sequence-sharded serving: the contiguous
    # cache's token axis splits over the sequence mesh axis and decode
    # attention merges per-shard partial softmax over ICI
    # (ops/attention._seq_sharded_decode) — context capacity scales with
    # the mesh instead of one chip's HBM.
    "cache_seq": "sequence",
}


def logical_to_spec(
    logical_axes: Sequence[str | None], rules: dict[str, Any] | None = None
) -> P:
    """Map a tuple of logical axis names to a PartitionSpec."""
    rules = rules if rules is not None else DEFAULT_RULES
    spec = []
    for ax in logical_axes:
        if ax is None:
            spec.append(None)
        else:
            if ax not in rules:
                raise KeyError(f"no sharding rule for logical axis {ax!r}")
            spec.append(rules[ax])
    return P(*spec)


def is_axes_leaf(x: Any) -> bool:
    """A logical-axes leaf is a *plain* tuple of axis names / None. Namedtuples
    (optax states) and tuples holding subtrees (optax.chain state) are pytree
    containers, not leaves."""
    return type(x) is tuple and all(e is None or isinstance(e, str) for e in x)


def spec_tree(logical_tree: Any, rules: dict[str, Any] | None = None) -> Any:
    """Map a pytree of logical-axis tuples to a pytree of PartitionSpecs."""
    return jax.tree.map(
        lambda axes: logical_to_spec(axes, rules), logical_tree, is_leaf=is_axes_leaf
    )


def placement(tree: Any) -> dict:
    """Where a pytree of arrays actually lives, read from the arrays' own
    shardings rather than from the mesh that was asked for: total bytes, and
    the bytes each device holds (a replicated leaf counts once per device).
    The trainer's summary and the server's /v1/stats carry it, so a caller
    can tell a state spread over four chips from one parked on the first."""
    per_device: dict[int, int] = {}
    total = 0
    for leaf in jax.tree.leaves(tree):
        if not isinstance(leaf, jax.Array):
            continue
        total += leaf.nbytes
        for shard in leaf.addressable_shards:
            per_device[shard.device.id] = (
                per_device.get(shard.device.id, 0) + shard.data.nbytes
            )
    return {
        "bytes": total,
        "per_device_bytes": {str(d): n for d, n in sorted(per_device.items())},
    }


def named_sharding_tree(mesh, logical_tree: Any, rules: dict[str, Any] | None = None):
    """Pytree of NamedShardings for ``jax.jit``'s in/out_shardings or
    ``jax.device_put``."""
    return jax.tree.map(
        lambda axes: NamedSharding(mesh, logical_to_spec(axes, rules)),
        logical_tree,
        is_leaf=is_axes_leaf,
    )
