"""Bytes a Mamba-1 state update has to move, from the configuration's
published shapes and never from the program.

One token of one sequence in one Mamba layer reads the layer's SSM state
``[d_inner, d_state]`` in float32 and writes it back; its convolution
reads the tail of ``d_conv - 1`` earlier inputs ``[d_inner]`` in the
activations' precision and writes it back.  Nothing less can do the
update: the state is the sequence's whole past.  What else the step
touches (projections' weights, activations) is the model's and is left
out, so the share of the roofline computed from this is a floor of the
traffic over the scan's time, not an account of it.
"""

from __future__ import annotations

from typing import Any, Dict

ACTIVATION_BYTES = {"bfloat16": 2, "float16": 2, "float32": 4}


def mamba_layers(config: Dict[str, Any]) -> int:
    period, offset = config["attn_layer_period"], config["attn_layer_offset"]
    return sum(1 for i in range(config["num_hidden_layers"])
               if i % period != offset)


def state_bytes_per_row_layer(config: Dict[str, Any]) -> int:
    """SSM state and convolution tail of one sequence in one layer."""
    d_inner = config["mamba_expand"] * config["hidden_size"]
    act = ACTIVATION_BYTES[config.get("torch_dtype", "bfloat16")]
    return (d_inner * config["mamba_d_state"] * 4
            + d_inner * (config["mamba_d_conv"] - 1) * act)


def state_update_bytes(config: Dict[str, Any], rows: int) -> int:
    """One decode step over ``rows`` sequences: every Mamba layer reads
    and writes each row's state once."""
    return rows * mamba_layers(config) * 2 * state_bytes_per_row_layer(config)
