"""What a decode step's lightning indexer and sparse latent attention have to
move: the count functions behind ``dsa_index_roofline_decode`` and
``dsa_attn_roofline_decode`` (DeepSeek-V3.2, ``ditl_tpu/models/dsa.py``).
``config`` is the configuration file.

The indexer reads, for every live row and layer, ONE index key a context
token (``index_head_dim`` values, 256 B in bf16) and does per key and index
head a dot product of ``index_head_dim`` (2 x 128 operations; the ReLU and
the weighted sum over the heads, 2 a head, are counted too): 64 x 258 =
16,512 operations on 256 bytes, 64 operations a byte against the v5e's 240,
so the bytes bound it. Context tokens are the server's own count over LIVE
rows and layers (``dsa_ctx_tokens`` of an ``engine.tick`` span: the rows'
lengths summed over the tick's steps, times the layers).

Attention in the absorbed form reads ONE stored latent entry a SELECTED token
(``[c | rope(kr)]`` padded to whole lanes: 1,280 B, the bytes stored) and
does per entry and head a score over ``c`` and ``kr`` and a value sum over
``c``: 128 x 2 x 1,088 = 278,528 operations, 218 a byte, so the bytes bound
it too, barely. Selected entries are the program's own count
(``dsa_selected_tokens``: the entries ``top_indices`` marked valid, live rows
only, summed over steps and layers). Each floor is the larger of the two
times; what the gather writes and the attention reads again is not counted,
nor padding, so neither share can pass 100% by over-counting.
"""

from __future__ import annotations

LANES = 128


def index_key_bytes(config: dict, bytes_per_value: int = 2) -> int:
    return config["index_head_dim"] * bytes_per_value


def index_flops_per_key(config: dict) -> int:
    """Operations all index heads do on one context token's key."""
    return config["index_n_heads"] * (2 * config["index_head_dim"] + 2)


def index_floor_s(config: dict, ctx_tokens: float, peaks: dict) -> float:
    """Least seconds the chip needs to score ``ctx_tokens`` (context tokens of
    live rows, summed over steps AND layers)."""
    return max(ctx_tokens * index_key_bytes(config) / peaks["hbm_bytes_per_s"],
               ctx_tokens * index_flops_per_key(config) / peaks["bf16_flops_per_s"])


def entry_bytes(config: dict, bytes_per_value: int = 2) -> int:
    """One stored latent entry, lane padding included."""
    width = config["kv_lora_rank"] + config["qk_rope_head_dim"]
    return -(-width // LANES) * LANES * bytes_per_value


def attn_flops_per_entry(config: dict) -> int:
    r, rope = config["kv_lora_rank"], config["qk_rope_head_dim"]
    return config["num_attention_heads"] * 2 * ((r + rope) + r)


def attn_floor_s(config: dict, selected_tokens: float, peaks: dict) -> float:
    """Least seconds the chip needs to attend to ``selected_tokens`` entries
    (selected by live rows, summed over steps and layers)."""
    return max(selected_tokens * entry_bytes(config) / peaks["hbm_bytes_per_s"],
               selected_tokens * attn_flops_per_entry(config) / peaks["bf16_flops_per_s"])
