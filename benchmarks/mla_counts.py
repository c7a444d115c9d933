"""What a decode step's latent attention and held experts have to move: the
count functions behind ``mla_attn_roofline_decode`` and
``moe_held_roofline_decode`` (LongCat-Flash, ``ditl_tpu/models/mla.py``,
``ditl_tpu/ops/mla_attention.py``). ``config`` is the configuration file.

Latent attention in its absorbed form reads, for every live row and every
attention sublayer, ONE stored entry a context token, ``[c | rope(kr)]``
padded to whole lanes of 128 (576 -> 640 values, 1,280 B in bf16: the bytes
STORED, padding included), and does per entry and head a score over ``c`` and
``kr`` (2 x 576 operations) and a value sum over ``c`` (2 x 512): 64 x 2 x
1,088 = 139,264 operations. 109 operations a byte against the v5e's 240, so
the bytes bound it, with little to spare: the floor is the larger of the two
times. Context tokens are the server's own count over LIVE rows
(``decode_ctx_tokens`` of an ``engine.tick`` span: the rows' lengths summed
over the tick's steps); a page is fetched whole and a dead row's sentinel
page is fetched too, none of which is counted, so the count is a floor and
the share of the roofline it gives cannot pass 100% by over-counting.

A held expert that at least one live row chose is read whole once a step and
layer: its three matrices, ``hidden x expert_ffn_hidden_size`` each.
Activations are thousands of times smaller and are not counted.
"""

from __future__ import annotations

LANES = 128


def sublayers(config: dict) -> int:
    """Attention sublayers of the cut: two a double layer."""
    return 2 * config["cut"]["num_layers"]


def entry_bytes(config: dict, bytes_per_value: int = 2) -> int:
    """One stored cache entry of one sublayer, lane padding included."""
    width = config["kv_lora_rank"] + config["qk_rope_head_dim"]
    return -(-width // LANES) * LANES * bytes_per_value


def attn_flops_per_entry(config: dict) -> int:
    """Operations all heads do on one context entry of one sublayer."""
    r, rope = config["kv_lora_rank"], config["qk_rope_head_dim"]
    return config["num_attention_heads"] * 2 * ((r + rope) + r)


def decode_attn_floor_s(config: dict, ctx_tokens: float, peaks: dict) -> float:
    """Least seconds the chip needs for the latent attention of decode steps
    whose live rows' contexts sum to ``ctx_tokens``: the larger of the time
    HBM needs for the entries and the time the MXU needs for the operations."""
    n = ctx_tokens * sublayers(config)
    return max(n * entry_bytes(config) / peaks["hbm_bytes_per_s"],
               n * attn_flops_per_entry(config) / peaks["bf16_flops_per_s"])


def held_expert_bytes(config: dict, bytes_per_weight: int = 2) -> int:
    """One routed expert's three matrices."""
    return 3 * config["hidden_size"] * config["expert_ffn_hidden_size"] * bytes_per_weight
