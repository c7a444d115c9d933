"""What a decode step's state-space mixers have to move: the count function
behind ``ssm_state_roofline_decode`` (Granite-4.0-H, ``ditl_tpu/models/
ssm.py``, ``ditl_tpu/ops/ssd.py``). ``config`` is the configuration file.

A decode step reads and rewrites, for every LIVE row and every mixer, the
row's state ``S`` (heads x head x state float32 values: 2,097,152 B at the
published widths, once in and once out) and its convolution window (the last
``d_conv - 1`` pre-activation ``xBC`` columns, bf16, in and out), and does on
each state value a decay, an update and a read-out: two operations each, 6 a
value. 0.75 operations a byte against the v5e's 240, so the bytes bound it by
a factor of 300: the floor is the larger of the two times all the same. Live
rows are the server's own count (``ssm_row_steps`` of an ``engine.tick`` span:
the live rows summed over the tick's steps); what a dead row costs is not
counted, so the count is a floor and the share of the roofline it gives
cannot pass 100% by over-counting.
"""

from __future__ import annotations


def mixers(config: dict) -> int:
    return sum(t == "mamba" for t in config["layer_types"])


def state_values(config: dict) -> int:
    """Values of one mixer's state ``S`` for one row."""
    return config["mamba_n_heads"] * config["mamba_d_head"] * config["mamba_d_state"]


def conv_window_bytes(config: dict, bytes_per_value: int = 2) -> int:
    """One mixer's convolution window for one row."""
    width = (config["mamba_n_heads"] * config["mamba_d_head"]
             + 2 * config["mamba_n_groups"] * config["mamba_d_state"])
    return (config["mamba_d_conv"] - 1) * width * bytes_per_value


def row_step_bytes(config: dict) -> int:
    """Bytes one live row's step moves over all mixers: state and window,
    read and written."""
    return mixers(config) * 2 * (4 * state_values(config) + conv_window_bytes(config))


def row_step_flops(config: dict) -> int:
    return mixers(config) * 6 * state_values(config)


def decode_state_floor_s(config: dict, row_steps: float, peaks: dict) -> float:
    """Least seconds the chip needs for the states of ``row_steps`` live
    rows' steps: the larger of the time HBM needs for the bytes and the time
    the MXU would need for the operations."""
    return max(row_steps * row_step_bytes(config) / peaks["hbm_bytes_per_s"],
               row_steps * row_step_flops(config) / peaks["bf16_flops_per_s"])
