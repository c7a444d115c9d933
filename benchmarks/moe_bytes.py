"""Bytes an expert layer's grouped matmuls have to read in a decode step: the
count function behind ``moe_experts_roofline_decode``.

In a decode step every live row brings ``k`` experts, and each expert that
has at least one row has to be read whole from HBM once: its gate, up and
down matrices. Activations (a few rows of ``hidden`` and ``intermediate``
values) are thousands of times smaller and are not counted, nor are experts
that only a dead slot's padding row chose: the count is a floor, so the share
of the roofline it gives cannot pass 100% by over-counting.
"""

from __future__ import annotations


def expert_weight_bytes(config: dict, bytes_per_weight: int = 2) -> int:
    """One expert's three matrices (``hidden x intermediate`` each)."""
    return 3 * config["hidden_size"] * config["intermediate_size"] * bytes_per_weight


def decode_expert_bytes(config: dict, steps: float, touched_mean: float,
                        bytes_per_weight: int = 2) -> float:
    """``steps`` decode steps of ``num_hidden_layers`` layers, each reading
    ``touched_mean`` experts."""
    return (steps * config["num_hidden_layers"] * touched_mean
            * expert_weight_bytes(config, bytes_per_weight))
