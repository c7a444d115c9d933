"""What a training step's flash kernels and held experts EXECUTE: the count
functions behind ``mla_flash_roofline_train`` and
``moe_experts_roofline_train`` (Kanana-2, ``ditl_tpu/models/dsa.py``'s
decompressed form, ``ditl_tpu/ops/flash_attention.py`` at two widths,
``ditl_tpu/models/moe.py``'s share with a backward pass). ``config`` is the
configuration file. Executed, not required: a rematerialised forward counts,
a block on the diagonal counts whole though half of it is masked, so the
shares say how busy the kernels keep the MXU and never what a step needs
(``mfu`` says that).

A flash block is ``block x block`` scores of one head (512 x 512 here, the
trainer's default tiles; the step's own counter ``flash_blocks_needed`` counts
the blocks the kernels' predicate keeps, over the batch, a head). With ``D``
the queries' and keys' width (192) and ``Dv`` the values' (128), a block's
products are, at 2 operations a multiply-add:

- ``flash_fwd``: ``q k^T`` (D) and ``p v`` (Dv), run TWICE a layer (the
  forward, and the layer's rematerialised forward in the backward pass);
- ``flash_bwd_dq``: ``q k^T`` (D), ``dO v^T`` (Dv), ``dS k`` (D);
- ``flash_bwd_dkv``: ``q k^T`` (D), ``p^T dO`` (Dv), ``dO v^T`` (Dv),
  ``dS^T q`` (D).

A held pair (a token's choice that fell on an expert held here) passes three
grouped matmuls of ``hidden x moe_intermediate`` (gate, up, down), each run
as ``gmm`` in the forward, ``gmm`` again in the layer's rematerialised
forward (the buffer's own checkpoint recomputes the same products, which the
compiler merges with the layer's: 9 ``gmm`` and 3 ``tgmm`` a buffer in the
compiled step, ``tests/test_tpu_compile_train.py``), and in the backward pass
``gmm`` against the transposed weight (the rows' cotangent) and ``tgmm`` (the
weight's): four products a matrix.
"""

from __future__ import annotations

BLOCK = 512  # the trainer's default flash tiles, forward and backward


def expert_layers(config: dict) -> int:
    return config["cut"]["num_hidden_layers"] - config["first_k_dense_replace"]


def flash_flops_per_block(config: dict) -> int:
    """All three kernels' operations on one needed block of one head of one
    layer, over a whole step (two forwards, one of each backward kernel)."""
    d = config["qk_nope_head_dim"] + config["qk_rope_head_dim"]
    dv = config["v_head_dim"]
    products = 2 * (d + dv) + (2 * d + dv) + (2 * d + 2 * dv)
    return 2 * BLOCK * BLOCK * products


def flash_flops_per_step(config: dict, blocks_needed: float) -> float:
    """``blocks_needed``: the step's ``flash_blocks_needed`` (over its batch,
    a head)."""
    return (blocks_needed * config["num_attention_heads"]
            * config["cut"]["num_hidden_layers"] * flash_flops_per_block(config))


def expert_flops_per_held_pair(config: dict) -> int:
    """Three matrices, four products each (forward, rematerialised forward,
    the two backward products)."""
    return 3 * 4 * 2 * config["hidden_size"] * config["moe_intermediate_size"]


def held_pairs_per_step(config: dict, tokens: int, held_share: float) -> float:
    """``held_share``: the step's ``moe_held_assign_share`` (held pairs over
    ``T x k``, all expert layers together)."""
    return held_share * tokens * config["num_experts_per_tok"] * expert_layers(config)
