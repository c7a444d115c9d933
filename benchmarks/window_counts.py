"""What a decode step's attention has to move in a stack with window and full
attention layers (Trinity-Mini, ``ditl_tpu/models/swa.py``): the count
functions behind ``window_attn_roofline_decode`` and
``full_attn_roofline_decode``. ``config`` is the configuration file,
``page_size`` the server's.

A page step of the decode kernel reads ONE page of ONE layer's pool: the keys
and the values of ``page_size`` tokens for every kv head, ``2 x
num_key_value_heads x page_size x head_dim`` values (524,288 B at 4 heads of
128, pages of 256, bf16), and does for every query head a score over and a
value sum of those tokens: ``num_attention_heads x 2 x 2 x head_dim`` operations a
token, 8 a byte against the v5e's 240, so the bytes bound it. The page steps
are the program's own count (``window_pages_walked`` / ``full_pages_walked``
of an ``engine.tick`` span: the page steps of that kind's work list, summed
over the tick's steps; every layer of the kind walks the list once a step), a
function of the rows' positions alone, so the same work reads the same
whatever implements it. A first page masked in part, a last page filled in
part and the page of a row that ended inside the tick are counted whole: the
kernel fetches whole pages. The tail's few columns, the queries and the
outputs are not counted: a floor.
"""

from __future__ import annotations

KINDS = {"window": "sliding_attention", "full": "full_attention"}


def layers_of(config: dict, kind: str) -> int:
    """Layers of ``kind`` ("window" | "full") in the share that runs."""
    n = config["cut"]["num_hidden_layers"]
    return sum(1 for t in config["layer_types"][:n] if t == KINDS[kind])


def page_bytes(config: dict, page_size: int, bytes_per_value: int = 2) -> int:
    """Keys and values of one page of one layer."""
    return (2 * config["num_key_value_heads"] * page_size * config["head_dim"]
            * bytes_per_value)


def page_flops(config: dict, page_size: int) -> int:
    return config["num_attention_heads"] * 4 * config["head_dim"] * page_size


def attn_floor_s(config: dict, kind: str, page_steps: float, page_size: int,
                 peaks: dict) -> float:
    """Least seconds the chip needs for ``page_steps`` page steps of a call
    (summed over the ticks' steps) in every layer of ``kind``."""
    steps = page_steps * layers_of(config, kind)
    return max(steps * page_bytes(config, page_size) / peaks["hbm_bytes_per_s"],
               steps * page_flops(config, page_size) / peaks["bf16_flops_per_s"])
