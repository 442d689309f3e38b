"""Bytes a step NEEDS of a configuration with sparse experts and window
layers, from its widths (``harness/opsbytes.py`` is dense-only: every
layer's whole FFN and the whole context in every layer). Needed, not
executed: an expert is streamed once however many rows chose it, a window
layer needs the last ``sliding_window`` tokens and no more. Waste (a
weight fetched twice, a page read and masked) can only understate a share
computed from these, never push it past 100%.

Inputs are keys of the configuration file: the source's ``config.json``
names at its top level and the ``serving`` group.
"""
from __future__ import annotations


def expert_bytes(config: dict) -> int:
    """One expert's three matrices (gate, up, down) at the stored width."""
    return (3 * config["hidden_size"] * config["moe_intermediate_size"]
            * config["serving"]["weight_bytes_per_param"])


def expert_layer_bytes(config: dict, experts_hit: float) -> float:
    """Expert weights one layer's grouped products have to read when
    ``experts_hit`` of its experts were given at least one row."""
    if not 0 <= experts_hit <= config["num_experts"]:
        raise ValueError(f"experts_hit {experts_hit} of {config['num_experts']}")
    return experts_hit * expert_bytes(config)


def window_tokens(config: dict, context: int) -> int:
    """Tokens of K/V one decode step at ``context`` tokens has to read,
    summed over the layers: ``context`` on a full layer, the last
    ``sliding_window`` of them (the query's own among them) on a sliding
    layer."""
    w = config["sliding_window"]
    return sum(min(context, w) if kind == "sliding_attention" else context
               for kind in config["layer_types"])


def kv_bytes_needed(config: dict, context: int) -> int:
    """K and V bytes of one decode step of one row at ``context`` tokens:
    one layer's bytes per token (2 x kv heads x head_dim x element) times
    ``window_tokens``."""
    per_layer_token = (2 * config["num_key_value_heads"] * config["head_dim"]
                       * config["serving"]["kv_bytes_per_element"])
    return per_layer_token * window_tokens(config, context)
