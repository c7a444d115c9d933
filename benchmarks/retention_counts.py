"""What a decode step's retention layers have to move: the count functions
behind ``ret_state_roofline_decode`` and ``ret_state_bytes_share_decode``
(Brumby, ``ditl_tpu/models/retention.py``, ``ditl_tpu/ops/retention.py``).
``config`` is the configuration file.

A layer keeps, for every row and kv head, the state ``S`` (features x head
float32 values) and the sum of keys ``z`` (features float32 values); the
features of a head are ``assumed_values["features_a_head"]`` (9,216: the
blocked second power of a 128-wide head; 8,256 would be the distinct products
alone: ``state_bytes(config, features=8256)`` is 32.5 MiB a layer a row).

The floor counts a live row's state ONCE a step, the READ: ``y_t`` needs all
of ``S_(t-1)``, but an exact step may hold a few tokens' ``k, v`` back and
fold them into ``S`` together, so the write is not owed every step. A kernel
that reads and rewrites the state every step, as ``ret_step`` does, reads at
most 50% of this roofline. On each state value a step does a decay, an update
and one read-out a query head of the group: two operations each; at 5 query
heads a kv head that is 14 operations on 4 bytes read, 3.5 a byte against the
v5e's 240, so the bytes bound it: the floor is the larger of the two times all
the same. Live rows are the server's own count (``ssm_row_steps`` of an
``engine.tick`` span: the live rows summed over the tick's steps); what a dead
row costs is not counted, so the count is a floor and the share of the
roofline it gives cannot pass 100% by over-counting.
"""

from __future__ import annotations


def features(config: dict) -> int:
    return config["assumed_values"]["features_a_head"]


def state_bytes(config: dict, features: int | None = None) -> int:
    """One layer's state for one row: ``S`` and ``z`` of every kv head, float32."""
    d = features or config["assumed_values"]["features_a_head"]
    return config["num_key_value_heads"] * d * (config["head_dim"] + 1) * 4


def row_step_bytes(config: dict) -> int:
    """Bytes one live row's step has to READ over all layers."""
    return config["num_hidden_layers"] * state_bytes(config)


def row_step_flops(config: dict) -> int:
    group = config["num_attention_heads"] // config["num_key_value_heads"]
    values = config["num_key_value_heads"] * features(config) * config["head_dim"]
    return config["num_hidden_layers"] * values * (4 + 2 * group)


def decode_state_floor_s(config: dict, row_steps: float, peaks: dict) -> float:
    """Least seconds the chip needs for the states of ``row_steps`` live
    rows' steps: the larger of the time HBM needs for the bytes and the time
    the MXU would need for the operations."""
    return max(row_steps * row_step_bytes(config) / peaks["hbm_bytes_per_s"],
               row_steps * row_step_flops(config) / peaks["bf16_flops_per_s"])


def step_weight_bytes(config: dict, bytes_per_value: int = 2) -> int:
    """Bytes of weights a decode step reads whatever its rows: every layer's
    matrices and the head (the embedding is a gather of a row a token)."""
    d, f = config["hidden_size"], config["intermediate_size"]
    heads, kv, hd = (config["num_attention_heads"], config["num_key_value_heads"],
                     config["head_dim"])
    layer = 2 * d * heads * hd + 2 * d * kv * hd + d * kv + 3 * d * f
    return (config["num_hidden_layers"] * layer + d * config["vocab_size"]) * bytes_per_value


def state_bytes_share(config: dict, row_steps: float, steps: float) -> float:
    """What the mechanism is of a step's bytes, in percent: the live rows'
    states, read and rewritten as the kernel does, over that plus the weights
    ``steps`` steps read."""
    state = 2.0 * row_steps * row_step_bytes(config)
    total = state + steps * step_weight_bytes(config)
    return 100.0 * state / total if total else 0.0
